"""Device ms a traced round in the gated delta rule's recurrence alone
(``fedml.model.delta.scan``: the decays' running sums, the two decayed
Gram products and the solve a chunk, the recurrence between chunks, the
reads of the entering states; forward and backward)."""

from lib import decoder_kernels, delta_rule


def read(ctx):
    return decoder_kernels.scope_ms(ctx, delta_rule.SCAN)
