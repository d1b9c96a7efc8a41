"""The blockwise attention kernel's share of its roofline in the
``full_attention`` layers of a stack with short-convolution layers
(heads of 64 in ``lfm2-8b-a1b-share4``): the least time the chip could
take for the kernel calls of the traced rounds
(``lib/short_conv.attention_work``: the published head size, the key
blocks the causal mask leaves, ONE forward call and ONE backward walk a
layer and step) over the device time under
``fedml.model.attn.kernel``."""

from lib import decoder_kernels as K
from lib import short_conv


def read(ctx):
    block = K.attention_block()
    if block is None:
        return None
    return K.roofline_pct(
        ctx, short_conv.KERNEL_SCOPE, short_conv.step_work(
            ctx, lambda *sizes: short_conv.attention_work(*sizes, block)))
