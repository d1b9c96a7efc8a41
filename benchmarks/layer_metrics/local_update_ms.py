"""Round-program busy time a traced round under ``fedml.local.update``
(``opt.update``, ``apply_updates`` and the where-gate of the stacked
per-client weights), mean over chips."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "local_update_ms")
