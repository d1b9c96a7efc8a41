"""Chip 0's idle time a traced round that lies inside a ``fedml.eval``
span (an evaluation's dispatches and scalar fetches), cut at the span's
edges; 0 where it holds none."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "idle_eval_ms")
