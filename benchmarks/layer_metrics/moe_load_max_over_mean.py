"""The fullest held expert's rows over the mean held expert's, over the
traced rounds' sparse layers and steps: ``moe_rows_max_expert`` x held
experts / ``moe_rows_held`` (1 when the held experts share their rows
evenly)."""

from lib import decoder_kernels


def read(ctx):
    c = decoder_kernels.round_counters(
        ctx, "moe_rows_held", "moe_rows_max_expert")
    sizes = decoder_kernels.decoder_sizes(ctx)
    if not c or sizes is None or not c["moe_rows_held"]:
        return None
    held = sizes[0]["experts_held"][1]
    return c["moe_rows_max_expert"] * held / c["moe_rows_held"]
