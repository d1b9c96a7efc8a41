"""Of the assignments the routers made in the traced rounds (tokens x
experts a token x sparse layers x steps), the share that landed on the
experts held here: 100 x ``moe_rows_held`` / ``moe_rows_routed``
(100 x held / all experts if routing were uniform)."""

from lib import decoder_kernels


def read(ctx):
    c = decoder_kernels.round_counters(
        ctx, "moe_rows_held", "moe_rows_routed")
    if not c or not c["moe_rows_routed"]:
        return None
    return 100.0 * c["moe_rows_held"] / c["moe_rows_routed"]
