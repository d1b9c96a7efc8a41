"""Self time of ``fedml.round`` a traced round: the span's length minus
its ``fedml.dispatch``, ``.fetch``, ``.eval`` and ``.log`` children — what
the round loop itself costs (record building, the profiler's and the
monitor's calls), mean over the whole rounds of the traced part."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "loop_self_ms")
