"""Median ``fedml.round`` span over the rounds the profiler did not
touch (not the traced rounds, not their two neighbours), evaluating
rounds left out. With ``between_untraced_ms`` it is a non-evaluating
round's interval. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "round_untraced_ms")
