"""Round-program busy time a traced round under ``fedml.local.gather`` (the
batch's ``dynamic_slice`` / ``take_along_axis`` / ``take``), mean over chips."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "local_gather_ms")
