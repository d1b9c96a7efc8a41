"""The 95th percentile of round intervals as a per-layer number, for a
cell whose tail cannot carry a bound (a window with too few rounds, a
tail set by a host transfer). In a traced run it is taken over the
rounds the profiler did not touch."""


def read(ctx):
    return ctx["round_p95_ms"]
