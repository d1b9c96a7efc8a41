"""Of the assignments the routers made in the traced rounds, the rows
the sparse layers' combine read back into the tokens: 100 x
``moe_rows_combined`` / ``moe_rows_routed``. A token reads one row for
each of its slots, ``min(top_k, held experts)``: 100 where a token's
ways are the fewer (8 ways over 32 or 16 held), 36.36 at 22 ways over 8
held. A program without the counter gives nothing to read."""

from lib import decoder_kernels


def read(ctx):
    c = decoder_kernels.round_counters(
        ctx, "moe_rows_combined", "moe_rows_routed")
    if not c or not c["moe_rows_routed"]:
        return None
    return 100.0 * c["moe_rows_combined"] / c["moe_rows_routed"]
