"""Seconds of set-up inside a ``fedml.compile`` span: every
``ProgramSite`` compile before the window (the round program's: tracing
+ lowering, the backend's compile or cache read, the accounting and the
kept module text), each nested event once — so it does not double-count
as ``trace_lower_s`` does. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "round_compile_s")
