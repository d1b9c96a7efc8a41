"""Chip 0's idle time a traced round that lies inside a ``fedml.fetch``
span (the host blocked on the device: gaps between the round program's
ops), cut at the span's edges; 0 where it holds none. With the other
three ``idle_<span>_ms`` and ``idle_unnamed_ms`` it partitions chip 0's
idle time in the traced part."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "idle_fetch_ms")
