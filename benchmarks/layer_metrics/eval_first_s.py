"""The first ``fedml.eval`` span before the window: the warm-up's
evaluation. The evaluator is a plain ``jax.jit``, so its tracing,
lowering, compile or cache read and first run all lie inside this one
span; ``eval_untraced_ms`` is what a later call costs. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "eval_first_s")
