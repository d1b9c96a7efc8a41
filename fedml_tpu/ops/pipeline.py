"""Pipeline parallelism: GPipe-style microbatch schedule over a "pp" axis.

Beyond-reference capability (SURVEY.md §2.7: the reference's closest
analog is SplitNN's round-robin ring). Each shard of the ``pp`` mesh axis
owns ONE stage's parameters; microbatches stream through the pipeline
with activations hopping stage->stage via ``jax.lax.ppermute`` each tick.
The schedule runs ``M + p - 1`` ticks for ``M`` microbatches over ``p``
stages (fill + drain); every tensor shape is static.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_pipeline(stage_fn, mesh, axis_name: str = "pp"):
    """Build ``pipeline(stage_params, x) -> y``:

    - ``stage_params``: pytree with a leading stage axis [p, ...], sharded
      over ``axis_name`` (each shard holds its own stage's params).
    - ``x``: [M, mb, ...] microbatches (replicated).
    - ``stage_fn(params, x_mb) -> y_mb``: one stage's computation (shapes
      preserved across stages).

    Returns y [M, mb, ...] (replicated; produced on the last stage and
    psum-broadcast)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis_name]
    # tuple, not list: `run` below closes over this and is compiled by
    # shard_map — a mutable closure is invisible to jit's cache key
    # (fedlint recompile-hazard)
    perm = tuple((i, (i + 1) % p) for i in range(p))

    def run(stage_params, x):
        # stage_params arrives [1, ...] on each shard; drop the stage axis
        local_params = jax.tree.map(lambda l: l[0], stage_params)
        shard = jax.lax.axis_index(axis_name)
        m = x.shape[0]
        mb_shape = x.shape[1:]
        ticks = m + p - 1

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (zeros once drained)
            inject = jnp.where(
                t < m,
                jax.lax.dynamic_index_in_dim(
                    x, jnp.clip(t, 0, m - 1), keepdims=False
                ),
                jnp.zeros(mb_shape, x.dtype),
            )
            inp = jnp.where(shard == 0, inject, state)
            out = stage_fn(local_params, inp)
            # last stage emits microbatch t-(p-1) at tick t
            m_idx = t - (p - 1)
            emit = (shard == p - 1) & (m_idx >= 0)
            safe = jnp.clip(m_idx, 0, m - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, safe,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(emit, out, cur), safe, axis=0
            )
            state = jax.lax.ppermute(out, axis_name, perm)
            return (state, outputs), None

        init = (
            jnp.zeros(mb_shape, x.dtype),
            jnp.zeros((m,) + mb_shape, x.dtype),
        )
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
        # outputs are only populated on the last stage; broadcast them
        outputs = jnp.where(shard == p - 1, outputs, 0.0)
        return jax.lax.psum(outputs, axis_name)

    return shard_map(
        run,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
