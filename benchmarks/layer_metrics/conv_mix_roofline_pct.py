"""How near the gates and taps of the short-convolution layers run to
ONE fused forward pass: the least time the chip's memory could take for
one forward pass a layer and step of the traced rounds
(``lib/short_conv.mix_work``: reads of ``B``, ``C``, ``u``, a write of
the result) over the device time under ``fedml.model.conv.mix``. The
recomputed forward pass is not charged and shows as share lost; the
backward gates are not charged either, because the compiled program
books them inside the products' fusions, under ``fedml.model.conv``
(``lib/short_conv.py``'s docstring)."""

from lib import decoder_kernels as K
from lib import short_conv


def read(ctx):
    return K.roofline_pct(
        ctx, short_conv.MIX_SCOPE, short_conv.step_work(
            ctx, short_conv.mix_work))
