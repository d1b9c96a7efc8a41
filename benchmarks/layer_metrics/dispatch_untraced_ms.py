"""Median ``fedml.dispatch`` span over the non-evaluating rounds the
profiler did not touch: the untraced twin of ``dispatch_ms``. Host
ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "dispatch_untraced_ms")
