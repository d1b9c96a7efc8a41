"""Share of the round program's busy time under no ``fedml.*`` scope
(compiler-made copies and the like), mean over chips: the coverage check
of the device scopes."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "unscoped_pct")
