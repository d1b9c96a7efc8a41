"""Of the assignments the routers made in the traced rounds, the share
made in sparse-layer calls that went through the bounded row buffer
(twice the held experts' uniform share) and not through the worst-case
one: 100 x ``moe_rows_compact`` / ``moe_rows_routed`` (100 when every
call's held rows fit)."""

from lib import decoder_kernels


def read(ctx):
    c = decoder_kernels.round_counters(
        ctx, "moe_rows_compact", "moe_rows_routed")
    if not c or not c["moe_rows_routed"]:
        return None
    return 100.0 * c["moe_rows_compact"] / c["moe_rows_routed"]
