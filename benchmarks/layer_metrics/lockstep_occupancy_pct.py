"""Live client steps over the slot-steps the lockstep schedule executed
in the traced rounds: a lockstep group steps every member to its largest
member's count, so its padded steps are executed and thrown away.

Numerator: the benchmark's own count (``ctx["client_steps"]``,
``ceil(n_k / batch)`` a sampled client an epoch). Denominator: the
program's ``slot_steps`` counter — per group, trip count x width, summed
over shards — read off the ``slot_steps`` attr of the traced rounds'
``fedml.log`` spans. The profiler is switched off from inside the last
traced round's ``log()``, so the trace never holds that round's span:
its count is taken from the same round's record. A program without the
counter (the parent of the PR that added it) gives nothing to read."""

from lib import program_spans

LOG = program_spans.PREFIX + "log"


def read(ctx):
    t = program_spans.analyse(ctx)
    if t is None or not ctx.get("client_steps"):
        return None
    slots = {int(st["round"]): st["slot_steps"]
             for _, _, name, st in t["spans"]
             if name == LOG and "slot_steps" in st and "round" in st}
    if not slots:
        return None
    for rec in ctx.get("records", ()):
        if "slot_steps" in rec:
            slots.setdefault(int(rec["round"]), rec["slot_steps"])
    traced = ctx["traced_rounds"]
    if any(r not in slots for r in traced):
        return None
    return 100.0 * ctx["client_steps"] / sum(slots[r] for r in traced)
