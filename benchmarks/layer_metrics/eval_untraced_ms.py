"""Median ``fedml.eval`` span over the evaluations of the window that
the profiler did not touch: the inside twin of ``eval_ms.*``, one name
for all cells. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "eval_untraced_ms")
