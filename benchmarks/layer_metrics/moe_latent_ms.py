"""Device ms a traced round in a latent expert layer's two projections
(``fedml.model.moe.latent``: hidden to latent before the experts, back
after them; shared by all experts)."""

from lib import decoder_kernels, state_space


def read(ctx):
    return decoder_kernels.scope_ms(ctx, state_space.LATENT)
