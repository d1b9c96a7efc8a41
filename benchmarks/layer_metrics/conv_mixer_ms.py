"""Device ms a traced round under ``fedml.model.conv`` and its
sub-scope ``fedml.model.conv.mix``: a gated short-convolution layer's
whole mixer — norm, ``in_proj``, the two gates, the taps, ``out_proj``
— forward, recomputation and backward together. Nothing to read on a
program without the scope."""

from lib import decoder_kernels, short_conv


def read(ctx):
    return decoder_kernels.scope_ms(ctx, *short_conv.MIXER_SCOPES)
