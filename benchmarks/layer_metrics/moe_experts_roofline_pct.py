"""The grouped expert products' share of their roofline: the least time
the chip could take for the rows the traced rounds really routed here
(their own ``moe_rows_held``; ``lib/decoder_kernels.experts_work``) over
the device time under ``fedml.model.moe.experts``."""

from lib import decoder_kernels as K


def read(ctx):
    c, sizes = K.round_counters(ctx, "moe_rows_held"), K.decoder_sizes(ctx)
    if not c or sizes is None or not ctx.get("client_steps"):
        return None
    extra, _ = sizes
    calls = ctx["client_steps"] * K.sparse_layers(extra)
    return K.roofline_pct(
        ctx, "fedml.model.moe.experts",
        K.experts_work(extra, c["moe_rows_held"], calls))
