"""Chip 0's idle time a traced round that lies in NO ``fedml.*`` child span
(dispatch, fetch, eval, log, compile): the coverage check of the host
spans — idle the program's own spans cannot name."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "idle_unnamed_ms")
