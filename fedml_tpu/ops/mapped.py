"""``vmap`` rules for operations the chip runs badly once batched."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def once_a_client(fn):
    """``fn`` (arrays or pytrees of them in and out) with a ``vmap``
    rule that runs it UNBATCHED, once for each place of the mapped axis,
    and stacks what it returns.

    For row gathers: the cohort's ``vmap`` turns ``x[index]`` into a
    gather with a batch dimension, another program of the TPU compiler's
    than the row gather it makes of the same indexing alone, and a slower
    one a row: 0.93 ms against 0.25 for 24,576 rows of 2,560 at a batch
    of one, and three to four times at the embedding's shapes
    (``PERF.md`` section 6, PRs 47 and 43). Free at a block of one
    client; a block of ``b`` holds ``b`` copies of ``fn`` in its
    program."""
    wrapped = jax.custom_batching.custom_vmap(fn)

    @wrapped.def_vmap
    def each(axis_size, in_batched, *operands):
        def one(i):
            return wrapped(*jax.tree.map(
                lambda x, mapped: x[i] if mapped else x,
                operands, tuple(in_batched)))

        out = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *[one(i) for i in range(axis_size)])
        return out, jax.tree.map(lambda _: True, out)

    return wrapped
