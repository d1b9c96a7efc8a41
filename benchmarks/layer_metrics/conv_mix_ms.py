"""Device ms a traced round under ``fedml.model.conv.mix`` alone: a
gated short-convolution layer's ``B * u``, its causal depthwise taps
and ``C * z`` — the part of the mixer that no matrix product does —
forward, recomputation and backward together. An innermost scope: this
time is in ``conv_mixer_ms`` and in nothing else."""

from lib import decoder_kernels, short_conv


def read(ctx):
    return decoder_kernels.scope_ms(ctx, short_conv.MIX_SCOPE)
