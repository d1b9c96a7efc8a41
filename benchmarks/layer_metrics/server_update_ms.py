"""Round-program busy time a traced round under ``fedml.server_update`` (the
server step with its reducer — the ``psum`` on the mesh, collectives
included — and the metric sums), mean over chips."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "server_update_ms")
