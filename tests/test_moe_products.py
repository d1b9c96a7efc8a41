"""The sparse layer's grouped products in row tiles
(``fedml_tpu/ops/grouped.py`` behind ``ops/moe.py:grouped_product``):
the two kernels in the Pallas interpreter against ``jax.lax.ragged_dot``
and its two ``linear_transpose``s, reading only rows below
``sum(sizes)`` (the rows past them hold NaN here); the walk over the row
tiles; the shape rule that sends a call to the tiled kernels or leaves it
to ``ragged_dot``, as a table over the benchmark's six decoder cells; and
the counter of how often it engages, ``moe_rows_tiled``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import attention, grouped
from fedml_tpu.ops import moe as MOE

M, K, N, TM = 1024, 128, 256, 256
#: rows a group of ``M`` in tiles of ``TM``
SIZES = {
    "even": [256, 256, 256, 256],
    "skewed_3x": [600, 100, 200, 124],
    "an_empty_group": [300, 0, 424, 300],
    "a_group_smaller_than_a_tile": [500, 40, 300, 184],
    "a_boundary_inside_a_tile": [384, 384, 128, 128],
    "fewer_rows_than_the_buffer": [130, 260, 1, 120],
    "no_rows_at_all": [0, 0, 0, 0],
}
#: (kind of product, rows a part of a tile): the rows' two kinds with a
#: tile worked whole and in parts of 128 rows
KINDS = [("forward", TM), ("forward", 128), ("by_the_rows", TM),
         ("by_the_rows", 128), ("by_the_matrices", None)]


def _operands(sizes, dtype=jnp.bfloat16):
    kx, kw, kg = jax.random.split(jax.random.key(sum(sizes)), 3)
    return (jax.random.normal(kx, (M, K), dtype),
            jax.random.normal(kw, (len(sizes), K, N), dtype),
            jax.random.normal(kg, (M, N), dtype))


def _past_held(a, held, fill):
    return a.at[held:].set(fill)


def _reference(kind, x, w, g, sizes):
    """The product in float32 from the same operands."""
    x, w, g = (a.astype(jnp.float32) for a in (x, w, g))
    product = lambda x, w: jax.lax.ragged_dot(
        x, w, sizes, precision=jax.lax.Precision.HIGHEST)
    if kind == "forward":
        return product(x, w)
    if kind == "by_the_rows":
        return jax.linear_transpose(lambda x: product(x, w), x)(g)[0]
    return jax.linear_transpose(lambda w: product(x, w), w)(g)[0]


def _tiled(kind, x, w, g, sizes, sub):
    if kind == "forward":
        return grouped.rows_product(x, w, sizes, tm=TM, tn=N, sub=sub,
                                    interpret=True)
    if kind == "by_the_rows":
        return grouped.rows_product(g, w, sizes, tm=TM, tn=K, sub=sub,
                                    transposed=True, interpret=True)
    return grouped.matrices_product(x, g, sizes, tm=TM, tk=K, tn=N,
                                    interpret=True)


@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("kind, sub", KINDS)
def test_tiled_product_is_ragged_dots(kind, sub, case):
    """Each kind of product, in the interpreter, is ``ragged_dot``'s (or
    its transpose's) of the same bfloat16 operands summed in float32 and
    rounded once — on the rows below ``sum(sizes)``; the rows past them
    hold NaN in every operand and reach nothing."""
    sizes = jnp.array(SIZES[case], jnp.int32)
    held = sum(SIZES[case])
    x, w, g = _operands(SIZES[case])
    want = np.asarray(_reference(
        kind, _past_held(x, held, 0), w, _past_held(g, held, 0), sizes))
    got = np.asarray(_tiled(
        kind, _past_held(x, held, jnp.nan), w, _past_held(g, held, jnp.nan),
        sizes, sub).astype(jnp.float32))
    if kind != "by_the_matrices":
        got, want = got[:held], want[:held]
    # one rounding to bfloat16 (8 bits) of a float32 sum
    np.testing.assert_allclose(got, want, rtol=2 ** -8, atol=1e-3)


@pytest.mark.parametrize("empty", [False, True])
@pytest.mark.parametrize("case", list(SIZES))
def test_the_walk_visits_each_tile_once_a_group_that_has_rows_in_it(
        case, empty):
    sizes = np.array(SIZES[case])
    starts, stops, group, tile, count = (np.asarray(a) for a in grouped.visits(
        jnp.array(sizes, jnp.int32), M, TM, empty=empty))
    ends = np.cumsum(sizes)
    want = [(g, t) for g in range(len(sizes))
            for t in (range((ends[g] - sizes[g]) // TM,
                            (ends[g] - 1) // TM + 1) if sizes[g]
                      else [min((ends[g] - sizes[g]) // TM, M // TM - 1)]
                      if empty else [])]
    assert list(stops) == list(ends) and list(starts) == list(ends - sizes)
    assert list(zip(group[:count], tile[:count])) == want
    assert len(group) == len(tile) == M // TM + len(sizes) - 1 >= count
    assert tile.max(initial=0) < M // TM and group.max() < len(sizes)


#: the benchmark's decoder cells: rows of the buffer, the experts' two
#: widths, experts held (``benchmarks/configs/*.json``, ``row_buffer``)
CELLS = {
    "lfm2": (16384, 2048, 1792, 8),
    "smallthinker": (24576, 2560, 768, 16),
    "keye": (16384, 2048, 768, 16),
    "nemotron": (22528, 1024, 2688, 8),
    "joyai": (8192, 2048, 768, 16),
    "laguna": (8192, 2048, 512, 32),
}
#: rows a group under the fewest measured (64 experts on Laguna's rows)
FEW_ROWS_A_GROUP = (8192, 2048, 512, 64)


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_shape_rule_over_the_six_cells(cell):
    """Every cell's products, both ways round and on the worst-case side
    of its row buffer, go to the tiled kernels in tiles of 512 rows
    worked in parts of 128 (256 a visit for the matrices' kind), as the
    chip's table has them fastest; float32 operands, a width off the
    lanes, fewer rows a group than was measured and a matrix too large
    for the kernels' fast memory stay ``ragged_dot``'s."""
    m, k, n, groups = CELLS[cell]
    want = grouped.Tiles(tm=512, sub=128, matrices=256)
    for rows in (m, 4 * m):
        assert grouped.tiles(rows, k, n, groups, jnp.bfloat16) == want
        assert grouped.tiles(rows, n, k, groups, jnp.bfloat16) == want
    assert grouped.tiles(m, k, n, groups, jnp.float32) is None
    assert grouped.tiles(m, k, n, groups, None) is None
    assert grouped.tiles(m, k + 64, n, groups, jnp.bfloat16) is None
    assert grouped.tiles(m + 64, k, n, groups, jnp.bfloat16) is None
    assert grouped.tiles(m, 8 * k, n, groups, jnp.bfloat16) is None
    assert grouped.tiles(m, k, n, m // 128, jnp.bfloat16) is None
    # a buffer in tiles of 128 alone still runs, in those
    assert grouped.tiles(m + 128, k, n, groups, jnp.bfloat16) == (
        grouped.Tiles(tm=128, sub=128, matrices=128))


def test_off_the_chip_the_rule_is_not_asked():
    assert MOE.product_tiles(*CELLS["lfm2"], jnp.bfloat16) is None


def _primitives(fn, *args):
    """The names of every primitive ``fn``'s jaxpr runs, branches and
    inner programs included (traced anew each time: ``make_jaxpr``
    remembers a function's trace, and the rule is patched between)."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(lambda *args: fn(*args))(*args).jaxpr)
    return names


@pytest.fixture
def on_tpu(monkeypatch):
    """The chip's branch of the rule, with no chip: programs are traced
    here, never run."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def _traced_operands(cell="lfm2", dtype=jnp.bfloat16):
    m, k, n, groups = {**CELLS, "few_rows": FEW_ROWS_A_GROUP}[cell]
    sds = jax.ShapeDtypeStruct
    return (sds((m, k), dtype), sds((groups, k, n), dtype),
            sds((groups,), jnp.int32))


def _value_and_cotangents(x, w, sizes):
    y, back = jax.vjp(lambda x, w: MOE.grouped_product(x, w, sizes), x, w)
    return y, back(y)


def test_on_the_chip_a_call_of_its_own_runs_the_tiled_kernels(on_tpu):
    """Forward one kernel call, in the rule two (by the rows, by the
    matrices), and no ``ragged_dot``."""
    names = _primitives(_value_and_cotangents, *_traced_operands())
    assert names.count("pallas_call") == 3
    assert "ragged_dot_general" not in names


@pytest.mark.parametrize("why", [
    "off_the_chip", "float32", "mixed_dtypes", "mapped", "few_rows"])
def test_where_the_tiled_kernels_do_not_engage(why, monkeypatch):
    """``ragged_dot`` and its two transposes as before: off the TPU; for
    float32 operands (the evaluator's stack); where rows and matrices
    differ in dtype; under ``vmap``; and at a shape the rule leaves to
    the compiler's kernel (fewer rows a group than any measured)."""
    if why != "off_the_chip":
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    x, w, sizes = _traced_operands(
        "few_rows" if why == "few_rows" else "lfm2",
        jnp.float32 if why == "float32" else jnp.bfloat16)
    fn = _value_and_cotangents
    if why == "mixed_dtypes":
        w = jax.ShapeDtypeStruct(w.shape, jnp.float32)
    if why == "mapped":
        fn = jax.vmap(fn)
        x, w, sizes = (jax.ShapeDtypeStruct((2,) + a.shape, a.dtype)
                       for a in (x, w, sizes))
    names = _primitives(fn, x, w, sizes)
    assert names.count("ragged_dot_general") == 3
    assert "pallas_call" not in names


def _interpreted(monkeypatch):
    """The rule as on the chip, its kernels in the interpreter (and the
    router's ranking left as off the chip)."""
    monkeypatch.setattr(MOE, "product_tiles", grouped.tiles)
    for name in ("rows_product", "matrices_product"):
        monkeypatch.setattr(grouped, name, functools.partial(
            getattr(grouped, name), interpret=True))


def _layer(dtype, key=0):
    """One sparse layer at a shape the rule tiles: 1,024 tokens 2 ways
    over 4 experts, 2 held: a buffer of 2,048 rows."""
    kr, k1, k3, k2, kh = jax.random.split(jax.random.key(key), 5)
    normal = lambda key, *shape: (
        jax.random.normal(key, shape) / np.sqrt(shape[-2])).astype(dtype)
    params = {"router": normal(kr, 128, 4), "w1": normal(k1, 2, 128, 256),
              "w3": normal(k3, 2, 128, 256), "w2": normal(k2, 2, 256, 128)}
    return params, jax.random.normal(kh, (1024, 128)).astype(dtype)


def _counted(counters):
    return dict(zip(MOE.MOE_COUNTERS, np.asarray(counters).T))


def test_rows_tiled_is_a_counter_and_follows_the_rule(monkeypatch):
    """``moe_rows_tiled`` is ``moe_rows_held`` where the layer's products
    ran in the tiled kernels and 0 where the rule left them to
    ``ragged_dot``: off the chip, for float32 operands, under ``vmap``."""
    assert MOE.MOE_COUNTERS[-2] == "moe_rows_tiled"
    layer = lambda params, h: MOE.moe_layer(params, h, (1, 2), 2, 1.0)[1]
    params, h = _layer(jnp.bfloat16)
    off = _counted(layer(params, h))
    assert off["moe_rows_held"] > 0 and off["moe_rows_tiled"] == 0
    _interpreted(monkeypatch)
    assert grouped.tiles(2048, 128, 256, 2, jnp.bfloat16) is not None
    on = _counted(layer(params, h))
    assert on["moe_rows_tiled"] == on["moe_rows_held"] == off["moe_rows_held"]
    assert _counted(layer(*_layer(jnp.float32)))["moe_rows_tiled"] == 0
    mapped = _counted(jax.vmap(layer)(*jax.tree.map(
        lambda a: jnp.stack([a, a]), (params, h))))
    assert (mapped["moe_rows_held"] == on["moe_rows_held"]).all()
    assert (mapped["moe_rows_tiled"] == 0).all()


def test_a_layer_through_the_tiled_kernels_is_the_layer(monkeypatch):
    """Value and every gradient of one sparse layer, its products in the
    tiled kernels (the interpreter), against the same layer through
    ``ragged_dot``: bfloat16 products summed in another order."""
    params, h = _layer(jnp.bfloat16, key=1)
    g = jax.random.normal(jax.random.key(2), h.shape).astype(h.dtype)

    def run(params, h):
        loss = lambda params, h: jnp.sum(
            (MOE.moe_layer(params, h, (1, 2), 2, 1.0)[0] * g).astype(
                jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1))(params, h)

    plain = run(params, h)
    _interpreted(monkeypatch)
    tiled = run(params, h)
    for got, want in zip(jax.tree.leaves(tiled), jax.tree.leaves(plain)):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.abs(got - want).max() <= 2 ** -6 * np.abs(want).max()
