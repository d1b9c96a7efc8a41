"""Median ``fedml.fetch`` span over the non-evaluating rounds the
profiler did not touch: the device round seen from the host, on every
cohort of the window and not the traced rounds' alone — the untraced
twin of ``fetch_wait_ms``. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "fetch_untraced_ms")
