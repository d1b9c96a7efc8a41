"""Median gap from the end of one ``fedml.round`` span to the start
of the next, over the non-evaluating rounds the profiler did not touch:
the loop outside every span. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "between_untraced_ms")
