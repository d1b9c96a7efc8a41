"""Chip 0's idle time a traced round that lies inside a ``fedml.dispatch``
span (the chip waiting for the round program to be enqueued), cut at
the span's edges; 0 where it holds none."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "idle_dispatch_ms")
