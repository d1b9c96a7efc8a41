"""``setup_s`` minus the union of every ``fedml.*`` span of set-up:
imports, the harness's population generator, seed state, the check
rounds' execution and host comparisons — the coverage check of set-up,
as ``idle_unnamed_ms`` is of the traced part. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "setup_unnamed_s")
