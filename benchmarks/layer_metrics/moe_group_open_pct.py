"""Of the tokens the routers of the traced rounds scored (tokens x
sparse layers x steps), the share for which a group that holds an
expert held here was open: 100 x ``moe_tokens_group_open`` x experts a
token / ``moe_rows_routed`` (100 x open groups / groups if the groups'
scores were uniform, 100 where the router has one group)."""

from lib import decoder_kernels


def read(ctx):
    c = decoder_kernels.round_counters(
        ctx, "moe_tokens_group_open", "moe_rows_routed")
    sizes = decoder_kernels.decoder_sizes(ctx)
    if not c or not c["moe_rows_routed"] or sizes is None:
        return None
    return (100.0 * c["moe_tokens_group_open"]
            * sizes[0]["num_experts_per_tok"] / c["moe_rows_routed"])
