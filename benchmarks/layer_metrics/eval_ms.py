"""Median ``evaluate_global`` call (benchmark span, host clock: the call
ends in the floats ``run`` reads), over the evaluations of the window
that the profiler did not touch: under it a call that moves data from
the host reads many times its length."""

import statistics


def read(ctx):
    spans = [1e3 * (b - a) for n, a, b in ctx["spans"]
             if n == "evaluate_global"]
    return statistics.median(spans) if spans else None
