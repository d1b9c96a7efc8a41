"""Grouped matrix products in row tiles: the TPU kernels behind
``ops/moe.py:grouped_product`` and its two transposes.

Rows ``[M, K]`` sorted by group, ``sizes`` ``[G]`` rows a group, one
matrix a group. A product walks the VISITS of its row tiles
(:func:`visits`): a tile of ``tm`` rows is visited once by every group
that has rows in it, in row order, so a tile that holds a group's edge
is visited by both groups one after the other and a tile past
``sum(sizes)`` not at all — the work follows the rows present, never
``M``. The rows of a visited tile that are another group's are masked
out of what is stored (:func:`rows_product`) or of what is summed
(:func:`matrices_product`).

* :func:`rows_product`: ``[M, K] x [G, K, N] -> [M, N]`` and, with
  ``transposed``, ``[M, N] x [G, K, N] -> [M, K]`` (the rows'
  cotangent). A group's matrix stays in fast memory for all its visits,
  so rows, matrices and result each cross the memory bus once; a tile
  is worked in parts of ``sub`` rows, each skipped where the visiting
  group has none of them.
* :func:`matrices_product`: ``[M, K], [M, N] -> [G, K, N]``, ``x^T g``
  over a group's rows, summed in float32 in fast memory over the
  group's visits and rounded once (the matrices' cotangent).

Operands as they come (bfloat16 where :func:`tiles`, the shape rule,
sends a call here), float32 accumulation over the whole contraction, one
rounding to the result's dtype. Rows of the result past ``sum(sizes)``
are never written. What the compiler's rewrite of ``jax.lax.ragged_dot``
takes for the same products on a v5e, and the library's ``megablox``
kernels, is in :func:`tiles`' table.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: columns of a memory tile, and the rows and columns of a matrix unit
LANES = 128
#: fast memory a kernel may be given, of the 128 MiB a v5e core has
VMEM_LIMIT = 100 * 2 ** 20


class Tiles(NamedTuple):
    """Rows a visit of :func:`rows_product` (``tm``), rows a part of
    one (``sub``), rows a visit of :func:`matrices_product`
    (``matrices``)."""
    tm: int
    sub: int
    matrices: int


#: the fewest rows a group that was measured: ``m // (2 groups)``, a row
#: buffer being twice its groups' uniform share
ROWS_A_GROUP = 128


def tiles(m: int, k: int, n: int, groups: int, dtype) -> Tiles | None:
    """The shape rule: the row tiles these kernels run a grouped product
    of ``[m, k]`` rows by ``groups`` matrices ``[k, n]`` of ``dtype``
    in, or None where ``jax.lax.ragged_dot`` keeps it. The same answer
    for ``(k, n)`` and ``(n, k)``: a layer's products go one way.

    Set by one v5e chip's readings of each kind of product alone at the
    benchmark's six decoder shapes, held rows and load as the cells'
    counters have them (``PERF.md`` section 6, PR 48; ms a call,
    ``ragged_dot`` -> these kernels at ``(512, 128, 256)``):

    ====================================  =============  =============  =============
    shape ``m, k, n, groups`` (held)      forward        by the rows    by the matrices
    ====================================  =============  =============  =============
    LFM2 16384, 2048, 1792, 8 (8,260)     0.923 -> 0.416  0.827 -> 0.419  1.012 -> 0.484
    SmallThinker 24576, 2560, 768, 16     0.797 -> 0.379  0.738 -> 0.418  0.935 -> 0.455
    Keye 16384, 2048, 768, 16 (7,095)     0.478 -> 0.221  0.459 -> 0.245  0.562 -> 0.266
    Nemotron 22528, 1024, 2688, 8         0.558 -> 0.206  0.395 -> 0.224  0.624 -> 0.211
    JoyAI 8192, 2048, 768, 16 (4,382)     0.391 -> 0.213  0.383 -> 0.213  0.461 -> 0.228
    Laguna 8192, 2048, 512, 32 (4,100)    0.303 -> 0.219  0.373 -> 0.221  0.366 -> 0.235
    ====================================  =============  =============  =============

    (146 TFLOP/s of the chip's 197 at LFM2's shape against 66; the
    library's ``megablox`` ``gmm`` / ``tgmm`` read 0.80 to 1.24 times
    ``ragged_dot`` at their best tiling.) So:

    * bfloat16 only. float32 operands (the evaluator's stack) read 1.9
      to 2.9 times faster here too, but their arithmetic is the
      configuration's stated one and stays ``ragged_dot``'s until it is
      shown at least as precise against an exact product;
    * ``k`` and ``n`` in whole lanes, ``m`` in whole tiles of 128, and a
      group's whole matrix within the fast memory the kernels ask for
      (:data:`VMEM_LIMIT`: a float32 sum, a product and two roundings of
      ``k x n`` for the matrices' kind);
    * at least :data:`ROWS_A_GROUP` rows a group by the buffer's shape:
      the fewest measured (Laguna's, 1.4 to 1.7 times faster; LFM2's
      1,024 read 2.0 to 2.2);
    * ``tm`` the largest of 512, 256, 128 that divides ``m`` (a tile is
      what one step brings in: 128 to 1,024 read within 8 % of each
      other), worked in parts of ``sub`` 128 rows (an edge tile costs
      its groups the parts they have rows in: 0.416 ms against 0.519
      with the whole tile a part; parts of 256 read 0.447);
    * the matrices' kind in visits of at most 256 rows (0.484 ms against
      0.541 at 512 and 1.105 at 1,024: an edge tile is summed whole,
      masked, by each of its groups).
    """
    if dtype != jnp.bfloat16 or k % LANES or n % LANES or m % LANES:
        return None
    tm = next(t for t in (512, 256, 128) if m % t == 0)
    if 12 * k * n + 8 * tm * (k + n) > VMEM_LIMIT:
        return None
    if m // (2 * groups) < ROWS_A_GROUP:
        return None
    return Tiles(tm, LANES, min(tm, 256))


def visits(sizes, m: int, tm: int, empty: bool = False):
    """The walk over ``m // tm`` row tiles for groups of ``sizes`` rows:
    ``(starts [G], ends [G], group [V], tile [V], count)``: a group's
    first row and the row past its last, and visit ``v < count`` being
    group ``group[v]``'s of row tile ``tile[v]``; ``V = m // tm + G - 1``
    is the most there can be (every boundary inside a tile) and the
    entries from ``count`` on repeat the last group and a tile in range.
    With ``empty`` a group of no rows gets one visit all the same (of a
    tile in which it then finds nothing), for a kernel that has to write
    its result."""
    groups, tiles = sizes.shape[0], m // tm
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if empty else 0)
    upto = jnp.cumsum(count)
    slot = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    # (all the comparisons at once: ``groups`` is small, and the default
    # bisection is a loop on the device)
    group = jnp.minimum(jnp.searchsorted(
        upto, slot, side="right", method="compare_all"), groups - 1)
    tile = first[group] + slot - (upto - count)[group]
    as_int32 = lambda a: a.astype(jnp.int32)
    return (as_int32(starts), as_int32(ends), as_int32(group),
            as_int32(jnp.clip(tile, 0, tiles - 1)), as_int32(upto[-1]))


def _own_rows(starts, ends, group, tile, v, first, rows: int):
    """Of the ``rows`` rows from ``first`` on of visit ``v``'s tile:
    ``(any, all, own)`` — whether any and whether all of them are the
    visiting group's, and the ``[rows, 1]`` mask of those that are."""
    lo, hi = starts[group[v]], ends[group[v]]
    at = first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return ((first < hi) & (first + rows > lo),
            (lo <= first) & (first + rows <= hi), (at >= lo) & (at < hi))


def _rows_kernel(tm, sub, transposed, starts, ends, group, tile, x_ref,
                 w_ref, o_ref):
    v = pl.program_id(1)
    over = (((1,), (1 if transposed else 0,)), ((), ()))

    def part(i, _):
        # a tile is worked in parts of ``sub`` rows, each skipped where
        # none of its rows is the visiting group's: a tile that holds a
        # group's edge costs its groups what they have in it
        rows = pl.ds(pl.multiple_of(i * sub, sub), sub)
        some, whole, own = _own_rows(
            starts, ends, group, tile, v, tile[v] * tm + i * sub, sub)

        @pl.when(some)
        def _():
            product = jax.lax.dot_general(
                x_ref[rows, :], w_ref[...], over,
                preferred_element_type=jnp.float32)

            @pl.when(whole)
            def _():
                o_ref[rows, :] = product.astype(o_ref.dtype)

            @pl.when(jnp.logical_not(whole))
            def _():
                # the block stays in fast memory from one visit of a
                # tile to the next: what another group wrote is kept
                o_ref[rows, :] = jnp.where(
                    own, product,
                    o_ref[rows, :].astype(jnp.float32)).astype(o_ref.dtype)

    jax.lax.fori_loop(0, tm // sub, part, None)


@functools.partial(jax.jit, static_argnames=(
    "tm", "tn", "sub", "transposed", "interpret"))
def rows_product(x, w, sizes, *, tm: int, tn: int, sub: int | None = None,
                 transposed: bool = False, interpret: bool = False):
    """``x`` ``[M, K]`` by each group's ``w[g]`` ``[K, N]`` -> ``[M, N]``
    in ``x``'s dtype, in tiles of ``tm`` rows by ``tn`` columns over the
    whole of ``K``; ``transposed``: ``x`` ``[M, N]`` by ``w[g]^T`` ->
    ``[M, K]`` (``tn`` then tiles ``K``). ``M % tm == 0``; ``tn`` divides
    the result's width or is all of it."""
    m, inner = x.shape
    width = w.shape[1] if transposed else w.shape[2]
    *walk, count = visits(sizes, m, tm)
    matrix = ((None, tn, inner) if transposed else (None, inner, tn))
    at = ((lambda j, v, s, e, g, t: (g[v], j, 0)) if transposed
          else (lambda j, v, s, e, g, t: (g[v], 0, j)))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm, sub or tm, transposed),
        out_shape=jax.ShapeDtypeStruct((m, width), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, inner), lambda j, v, s, e, g, t: (t[v], 0)),
                pl.BlockSpec(matrix, at)],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, s, e, g, t: (t[v], j)),
            grid=(width // tn, count)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * inner * width, transcendentals=0,
            bytes_accessed=x.dtype.itemsize * (
                m * inner * (width // tn) + w.size + m * width)),
        interpret=interpret, name="moe_rows_product",
    )(*walk, x, w)


def _matrices_kernel(tm, starts, ends, group, tile, x_ref, g_ref, o_ref,
                     acc_ref):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    before, after = jnp.maximum(v - 1, 0), jnp.minimum(v + 1, last)
    some, whole, own = _own_rows(
        starts, ends, group, tile, v, tile[v] * tm, tm)

    @pl.when((v == 0) | (group[before] != group[v]))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(x, g):
        acc_ref[...] += jax.lax.dot_general(
            x, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _():
        add(x_ref[...], g_ref[...])

    @pl.when(some & jnp.logical_not(whole))
    def _():
        # both sides: a row that is not this group's may hold anything
        # (past ``sum(sizes)`` nothing wrote it), and 0 x NaN is NaN
        keep = lambda ref: jnp.where(
            own, ref[...].astype(jnp.float32), 0).astype(ref.dtype)
        add(keep(x_ref), keep(g_ref))

    @pl.when((v == last) | (group[after] != group[v]))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "interpret"))
def matrices_product(x, g, sizes, *, tm: int, tk: int, tn: int,
                     interpret: bool = False):
    """``x`` ``[M, K]``, ``g`` ``[M, N]`` -> ``[G, K, N]`` in ``x``'s
    dtype: ``x^T g`` over each group's rows (zeros for a group of none),
    ``tm`` rows a visit into a float32 block of ``tk x tn`` held in fast
    memory through a group's visits. ``M % tm == 0``; ``tk`` and ``tn``
    divide ``K`` and ``N`` or are all of them."""
    (m, k), n = x.shape, g.shape[1]
    *walk, count = visits(sizes, m, tm, empty=True)
    return pl.pallas_call(
        functools.partial(_matrices_kernel, tm),
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, v, s, e, g, t: (t[v], i)),
                pl.BlockSpec((tm, tn), lambda i, j, v, s, e, g, t: (t[v], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda i, j, v, s, e, g, t: (g[v], i, j)),
            grid=(k // tk, n // tn, count),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=x.dtype.itemsize * (
                m * k * (n // tn) + m * n * (k // tk)
                + sizes.shape[0] * k * n)),
        interpret=interpret, name="moe_matrices_product",
    )(*walk, x, g)
