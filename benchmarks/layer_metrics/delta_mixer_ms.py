"""Device ms a traced round under ``fedml.model.delta`` (norm, the six
projections in and the one out), the convolutions, norms and gates
inside it (``fedml.model.delta.mix``) and the chunked recurrence
(``fedml.model.delta.scan``), forward, recomputation and backward
together, mean over chips."""

from lib import decoder_kernels, delta_rule


def read(ctx):
    return decoder_kernels.scope_ms(
        ctx, delta_rule.DELTA, delta_rule.MIX, delta_rule.SCAN)
