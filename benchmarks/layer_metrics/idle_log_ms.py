"""Chip 0's idle time a traced round that lies inside a ``fedml.log``
span (the metrics sink's ``log()``), cut at the span's edges; 0 where
it holds none."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "idle_log_ms")
