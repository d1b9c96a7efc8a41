"""Median ``fedml.dispatch`` span of the traced part: the
``self.run_round(state)`` call — enqueueing the round program, and any
retrace."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "dispatch_ms")
