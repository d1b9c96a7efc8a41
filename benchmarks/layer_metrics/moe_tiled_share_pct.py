"""Of the assignments that landed on the experts held here in the
traced rounds, the share whose grouped products ran in the row-tiled
kernels (``fedml_tpu/ops/grouped.py``) and not in the compiler's
``ragged_dot``: 100 x ``moe_rows_tiled`` / ``moe_rows_held`` (100 where
the shape rule sends every sparse-layer call to the tiled kernels, 0
where it sends none)."""

from lib import decoder_kernels


def read(ctx):
    c = decoder_kernels.round_counters(
        ctx, "moe_rows_tiled", "moe_rows_held")
    if not c or not c["moe_rows_held"]:
        return None
    return 100.0 * c["moe_rows_tiled"] / c["moe_rows_held"]
