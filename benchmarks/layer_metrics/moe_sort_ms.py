"""Device ms a traced round in the round program's ``sort`` operations
(chip 0's; every scope's). Until PR 44 the sparse layers' routers ranked
by one: ``jax.lax.top_k`` of ``[N, E]`` probabilities is a full sort on
the chip. A router that takes its ``top_k`` largest without one leaves
the sorts of the ``N x top_k`` expert ids (``ops/moe.py:route``) and of
a step's token ids (the embedding's rule). None on a program without the
sparse layer's scope (``fedml.model.moe.route``), or without a trace."""

from lib import program_spans

ROUTE = "fedml.model.moe.route"
SORT = "sort"


def read(ctx):
    t = program_spans.analyse(ctx)
    if t is None or not t["scopes"] or ROUTE not in t["scope_busy_s"]:
        return None
    busy = sum(s for (family, _), s in t["family_scope_s"].items()
               if family == SORT)
    return 1e3 * busy / t["rounds"]
