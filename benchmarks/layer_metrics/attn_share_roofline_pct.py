"""The blockwise attention kernel's share of its roofline in a
configuration that holds a share of its heads: the least time the chip
could take for the kernel calls of the traced rounds
(``lib/attention_share.attention_share_work``: the heads HELD, the key
blocks each layer visits under its mask, one forward and one backward
call a step) over the device time under ``fedml.model.attn.kernel``."""

from lib import attention_share
from lib import decoder_kernels as K


def read(ctx):
    block, sizes = K.attention_block(), K.decoder_sizes(ctx)
    if block is None or sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq = sizes
    batch = int(ctx["cell"]["config"]["batch_size"])
    work = attention_share.attention_share_work(extra, seq, batch, block)
    if work is None:
        return None
    steps = ctx["client_steps"]
    return K.roofline_pct(ctx, "fedml.model.attn.kernel",
                          (steps * work[0], steps * work[1]))
