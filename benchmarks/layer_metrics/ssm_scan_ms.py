"""Device ms a traced round in the chunked state-space scan alone
(``fedml.model.ssm.scan``: its four products a chunk, the decay masks,
the recurrence between chunks; forward and backward)."""

from lib import decoder_kernels, state_space


def read(ctx):
    return decoder_kernels.scope_ms(ctx, state_space.SCAN)
