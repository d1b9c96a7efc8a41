"""Device ms a traced round under ``fedml.model.ssm`` (norm, input and
output projections, convolution, gate and group norm) and the chunked
scan inside it (``fedml.model.ssm.scan``), forward, recomputation and
backward together, mean over chips."""

from lib import decoder_kernels, state_space


def read(ctx):
    return decoder_kernels.scope_ms(ctx, state_space.SSM, state_space.SCAN)
