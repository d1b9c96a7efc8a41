"""The blockwise attention kernel's share of its roofline: the least
time the chip could take for the kernel calls of the traced rounds
(``lib/decoder_kernels.attention_work``: by the key blocks each layer
really visits) over the device time under
``fedml.model.attn.kernel``."""

from lib import decoder_kernels as K


def read(ctx):
    block, sizes = K.attention_block(), K.decoder_sizes(ctx)
    if block is None or sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq = sizes
    batch = int(ctx["cell"]["config"]["batch_size"])
    flops, nbytes = K.attention_work(extra, seq, batch, block)
    steps = ctx["client_steps"]
    return K.roofline_pct(ctx, "fedml.model.attn.kernel",
                          (steps * flops, steps * nbytes))
