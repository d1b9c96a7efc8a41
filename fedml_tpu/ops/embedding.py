"""An embedding lookup whose gradient is products over a step's DISTINCT
token ids and one write a table row, not a row-by-row scatter-add.

Forward, :func:`embedding_lookup` is ``jnp.take(table, ids, axis=0)``:
the gather ``flax.linen.Embed`` makes. Differentiated by JAX, that
gather's transpose is a scatter-add of one cotangent row a token into a
zero table, with indices that repeat (text draws its tokens by Zipf's
law). The TPU compiler sorts the ids, gathers the rows into that order
and scatters them sorted but not unique; at 8,192 rows into 37,984 x
2,560 that one operation takes 15 ms where the bytes ask for under 1
(``PERF.md`` section 6, PR 43). The backward rule here
(:func:`distinct_row_sums`) reaches the same sums with no scatter of a
table row at all:

1. rank the DISTINCT ids: sort the ``N`` ids and flag the first of each
   run; a running count numbers the runs;
2. sum each run's cotangent rows on the matrix units, :data:`BLOCK`
   sorted tokens at a time: inside a block the runs are numbered from
   0, so a block's sums are ``onehot [BLOCK, BLOCK]`` transposed times
   its rows ``[BLOCK, D]`` — the 0/1 factor exact in any float type,
   accumulated in float32, ``2 N BLOCK D`` operations in all. A run
   that goes on past its block's end leaves one partial sum in each
   later block it enters, always in slot 0: those few rows (one a block
   at most) are added to the run's first slot by a second, tiny product;
3. write each table row ONCE, by a gather from the table's side: row
   ``r`` reads the slot where the run of id ``r`` starts, or zeros where
   no token names it. Which slot that is comes from a scatter of ``N``
   integers whose indices are unique and say so.

An id receives the sum of the same rows as by the scatter-add, taken in
float32 and rounded once to the table's dtype. Ids outside the table
(after ``jnp.take``'s one wrap of negative ones) read NaN forward, as
``jnp.take`` has it, and receive nothing backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fedml_tpu.ops.mapped import once_a_client

#: sorted tokens a product (``PERF.md`` section 6, PR 43, has the chip's
#: readings of 256, 512 and 1,024 at the cells' shapes)
BLOCK = 256


def distinct_row_sums(g, ids, rows: int, dtype=jnp.float32,
                      block: int = BLOCK):
    """``out[r] = sum of g[n] over the tokens n with ids[n] == r``:
    ``g`` ``[N, D]``, ``ids`` ``[N]`` of any integer type -> ``[rows,
    D]`` of ``dtype``, every sum taken in float32 (module docstring;
    ``block`` sorted tokens a product)."""
    n, d = g.shape
    ids = ids.astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + rows, ids)
    # every id outside the table is the one id past its last row
    ids = jnp.where((ids < 0) | (ids >= rows), rows, ids)
    b = min(block, n)
    if n % b:  # whole blocks: more tokens of that id, with nothing to add
        more = b - n % b
        ids = jnp.concatenate([ids, jnp.full((more,), rows, jnp.int32)])
        g = jnp.concatenate([g, jnp.zeros((more, d), g.dtype)])
        n += more
    blocks = n // b
    position = jnp.arange(n, dtype=jnp.int32)
    sorted_ids, order = jax.lax.sort((ids, position), num_keys=1)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    run = jnp.cumsum(first.astype(jnp.int32)) - 1
    # a token's slot: its run's number among the runs of its block
    slot = run.reshape(blocks, b) - run[::b, None]
    onehot = slot[:, :, None] == jnp.arange(b, dtype=jnp.int32)
    sums = jnp.einsum(
        "kts,ktd->ksd", onehot.astype(g.dtype),
        g.at[order].get(mode="promise_in_bounds").reshape(blocks, b, d),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # a block that opens inside a run begun earlier holds that run's
    # further rows in its slot 0: add them where the run starts, which
    # is the last slot of its home block
    begun = jax.lax.cummax(jnp.where(first, position, 0))
    goes_on = ~first[::b]
    home = begun[::b] // b
    carried = jnp.where(goes_on[:, None], sums[:, 0], 0)
    to_home = (home[None, :] == jnp.arange(blocks)[:, None]) & goes_on[None]
    sums = sums.at[jnp.arange(blocks), slot[:, -1]].add(
        jnp.dot(to_home.astype(jnp.float32), carried,
                precision=jax.lax.Precision.HIGHEST),
        unique_indices=True, indices_are_sorted=True)
    # a table row's slot: written from the FIRST token of its run alone
    # (the others, and the ids outside the table, fall past the end);
    # a row no token names keeps a slot past the sums and reads zeros
    where = jnp.full((rows,), n, jnp.int32).at[
        jnp.where(first, sorted_ids, rows + position)].set(
            (position // b) * b + slot.reshape(n), mode="drop",
            unique_indices=True)
    return jnp.take(sums.reshape(n, d).astype(dtype), where, axis=0,
                    mode="fill", fill_value=0)


@jax.custom_vjp
def embedding_lookup(table, ids):
    """``table`` ``[rows, D]``, ``ids`` integers of any shape -> ``ids.shape
    + [D]``: ``jnp.take(table, ids, axis=0)`` with the backward rule of
    the module docstring."""
    return jnp.take(table, ids, axis=0)


def _lookup_fwd(table, ids):
    # an empty slice keeps the table's row count and dtype for the rule
    return embedding_lookup(table, ids), (ids, table[:, :0])


@once_a_client
def _table_gradient(g, ids, like):
    """Mapped over a block of clients, the rule runs once a client: the
    batched gathers a plain ``vmap`` makes of it take the chip three to
    four times as long as the same gathers alone (``PERF.md`` section 6,
    PR 43)."""
    return distinct_row_sums(
        g.reshape(-1, g.shape[-1]), ids.reshape(-1), like.shape[0],
        like.dtype)


def _lookup_bwd(kept, g):
    ids, like = kept
    return _table_gradient(g, ids, like), None


embedding_lookup.defvjp(_lookup_fwd, _lookup_bwd)
