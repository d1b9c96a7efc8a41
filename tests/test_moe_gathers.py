"""The sparse layer's row gathers (``ops/moe.py:_all_rows``,
``_read_weighed``, ``_read_back``) against the rule they re-express: the
row buffer filled by ONE ``x[index]`` over all its rows, masked past the
held rows and read back through a zero row appended to it. Which rows are read
changes, no sum does: a token's slots are added in the same order, the
exact zeros of the slots no held expert fills included — so the held
experts' ``y`` and the cotangents of ``h``, of every matrix and of the
routing weights are the plain form's TO THE BIT, alone and mapped, on
either side of the bounded buffer. Also: a mapped call lowers to no
gather with a batch dimension, and ``moe_rows_gathered`` counts the
rows a training step's four gathers move."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import moe as MOE
from fedml_tpu.ops.mapped import once_a_client

D, F = 16, 8

#: the routing's four shapes (``moe._by_slot``): name -> (tokens,
#: top_k, experts, (first held, held), slots' layout)
SHAPES = {
    "ways_in_whole_tiles": (64, 8, 32, (8, 8), "nk"),
    "ways_cut_by_way": (128, 4, 16, (4, 4), "kn"),
    "held_experts_cut_by_slot": (128, 6, 32, (4, 4), "kn"),
    "held_experts_in_whole_tiles": (64, 12, 64, (8, 8), "nk"),
}
#: held rows of a call: every shape's bounded buffer is 256 rows, two
#: row tiles -> (tokens on every slot they have, tokens on one held
#: expert) by the slots a token has
HELD_ROWS = {"none": 0, "some": 77, "a_tile": 128, "one_past_a_tile": 129,
             "the_buffer_exactly": 256, "over_the_buffer": 257}


def _routing(key, shape, held_rows):
    """``top_e`` ``[N, k]`` with exactly ``held_rows`` assignments on
    held experts, tokens and ways shuffled, and what ``moe.route`` makes
    of it -> (top_w, order, back, sizes, n_held), ``moe._held_experts``'
    operands."""
    n, k, experts, (first, count), _ = SHAPES[shape]
    slots = min(k, count)
    full, one = divmod(held_rows, slots)
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**30)))
    held = np.arange(first, first + count)
    absent = np.setdiff1d(np.arange(experts), held)
    top_e = np.empty((n, k), np.int64)
    for t in range(n):
        here = (rng.permutation(held)[:slots] if t < full
                else held[rng.integers(count, size=1)] if t < full + one
                else held[:0])
        rest = rng.permutation(absent)[:k - len(here)]
        top_e[t] = rng.permutation(np.concatenate([here, rest]))
    top_e = jnp.asarray(rng.permutation(top_e), jnp.int32)
    top_w = jax.random.uniform(jax.random.fold_in(key, 1), (n, k),
                               minval=0.1, maxval=1.0)
    local = top_e.reshape(-1) - first
    group = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(group, stable=True)
    if k <= count:
        back = jnp.argsort(order)
        sizes = jnp.bincount(group, length=count + 1)[:count].astype(
            jnp.int32)
    else:
        back, sizes = MOE._slot_rows(local.reshape(n, k), count, n * k)
    n_held = jnp.sum(sizes)
    assert int(n_held) == held_rows
    return top_w, order, back, sizes, n_held


def _operands(key, shape, held_rows, dtype=jnp.float32):
    n, _, _, (_, count), _ = SHAPES[shape]
    ks = jax.random.split(key, 6)
    h = jax.random.normal(ks[0], (n, D), dtype)
    g = jax.random.normal(ks[1], (n, D), dtype)
    w = tuple((jax.random.normal(k, s) * s[-2] ** -0.5).astype(dtype)
              for k, s in zip(ks[2:5], [(count, D, F), (count, D, F),
                                        (count, F, D)]))
    top_w, *routing = _routing(ks[5], shape, held_rows)
    return g, (h, w, top_w.astype(dtype), *routing)


# ---------------------------------------------------------------------------
# the plain form: every gather an ``x[index]`` over the whole buffer
# ---------------------------------------------------------------------------

def _plain_read_back(x, index, n_held):
    if index.ndim == 1 and x.shape[0] == index.shape[0]:
        return x[index]
    x = jnp.pad(x, ((0, 1),) + ((0, 0),) * (x.ndim - 1))
    return x[jnp.where(index < n_held, index, x.shape[0] - 1)]


def _plain_by_token(x, back, n_held, k, layout):
    index = back[0] if isinstance(back, tuple) else back.reshape(-1, k)
    if layout == "kn":
        index = index.T
    if isinstance(back, tuple):
        return _plain_read_back(x, index, n_held)
    return _plain_read_back(x, index.reshape(-1), n_held).reshape(
        *index.shape, *x.shape[1:])


def _slot_weights(top_w, back):
    if isinstance(back, tuple):
        return jnp.sum(jnp.where(back[1], top_w[:, :, None], 0), 1)
    return top_w


def _plain_forward(r, layout, activation, h, w, top_w, order, back, sizes,
                   n_held):
    k = top_w.shape[1]
    rows = h[order[:r] // k]
    into = tuple(MOE.grouped_product(rows, m, sizes) for m in w[:-1])
    out = MOE.grouped_product(MOE._middle(activation, *into), w[-1], sizes)
    out = jnp.where((jnp.arange(r) < n_held)[:, None], out, 0)
    theirs = _plain_by_token(out, back, n_held, k, layout)
    y = jnp.einsum(layout + "d,nk->nd", theirs,
                   _slot_weights(top_w, back).astype(out.dtype))
    return y, (rows, into, out)


def _plain_backward(r, layout, activation, kept, g, h, w, top_w, order,
                    back, sizes, n_held):
    rows, into, out = kept
    n, k = top_w.shape
    g_rows = g[order[:r] // k]
    weight = top_w.reshape(-1)[order[:r]].astype(out.dtype)
    d_out = g_rows * weight[:, None]
    d_weight = jnp.einsum("rd,rd->r", out, g_rows,
                          preferred_element_type=jnp.float32)
    if isinstance(back, tuple):
        by_slot = _plain_read_back(d_weight, back[0], n_held)
        d_top_w = jnp.sum(jnp.where(back[1], by_slot[:, None], 0), 2)
    else:
        d_top_w = _plain_read_back(d_weight, back, n_held).reshape(n, k)
    up, middle = jax.vjp(functools.partial(MOE._middle, activation), *into)
    d_up, d_out_w = MOE._grouped_transposed(up, w[-1], sizes, d_out)
    d_rows, d_w = zip(*(MOE._grouped_transposed(rows, m, sizes, d_into)
                        for m, d_into in zip(w[:-1], middle(d_up))))
    d_rows = jnp.where((jnp.arange(r) < n_held)[:, None],
                       sum(d_rows[1:], d_rows[0]), 0)
    theirs = _plain_by_token(d_rows, back, n_held, k, layout)
    d_h = theirs.sum(layout.index("k"))
    return d_h, (*d_w, d_out_w), d_top_w.astype(top_w.dtype)


def _plain(r, layout, activation, g, operands):
    """-> (y, d_h, d_w, d_top_w) of the plain form over ``r`` rows."""
    y, kept = _plain_forward(r, layout, activation, *operands)
    return (y, *_plain_backward(r, layout, activation, kept, g, *operands))


def _layer(c, activation, g, operands):
    """The same four of ``moe._held_experts`` (and which side ran)."""
    h, w, top_w, *routing = operands
    (y, taken), back = jax.vjp(
        lambda h, w, top_w: MOE._held_experts(
            c, activation, h, w, top_w, *routing), h, w, top_w)
    return (y, *back((g, jnp.zeros_like(taken)))), taken


def _assert_same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)).view(np.uint32),
            np.asarray(b.astype(jnp.float32)).view(np.uint32))


def _case(shape, held_rows, dtype=jnp.float32, seed=0):
    n, k, experts, (_, count), layout = SHAPES[shape]
    c = MOE.row_buffer(n, k, count, experts)
    assert c == 2 * MOE.ROW_TILE < n * k
    g, operands = _operands(jax.random.key(seed), shape,
                            HELD_ROWS[held_rows], dtype)
    return c, n * k, layout, g, operands


@pytest.fixture(params=[None, 128], ids=["buffer_whole", "leading_tile"])
def resident(request, monkeypatch):
    """How much of the buffer the combine gathers from: all of it, as
    at these sizes, and — as where the buffer outgrows the chip's fast
    memory — its leading row tile where the held rows are fewer than
    that (0 and 77 held), all of it where they are not."""
    if request.param:
        monkeypatch.setattr(MOE, "_resident_rows",
                            lambda x, *_: min(request.param, x.shape[0]))
    return request.param


@pytest.mark.parametrize("held_rows", list(HELD_ROWS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_alone_the_layer_is_the_plain_form_to_the_bit(shape, held_rows,
                                                      resident):
    c, nk, layout, g, operands = _case(shape, held_rows)
    fits = HELD_ROWS[held_rows] <= c
    *got, taken = jax.jit(functools.partial(_layer, c, MOE.SILU_GATED))(
        g, operands)
    want = jax.jit(functools.partial(
        _plain, c if fits else nk, layout, MOE.SILU_GATED))(g, operands)
    assert float(taken) == (nk if fits else 0)
    _assert_same_bits(got, want)
    if HELD_ROWS[held_rows] == 0:
        assert not any(np.asarray(x).any() for x in jax.tree.leaves(got))


@pytest.mark.parametrize("axis_size", [1, 2])
@pytest.mark.parametrize("held_rows", list(HELD_ROWS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_mapped_the_layer_is_the_plain_form_to_the_bit(
        shape, held_rows, axis_size, resident):
    """Under ``jax.vmap`` of axis 1 and 2 (the second instance holds 77
    rows, and the whole batch goes the worst-case side when the first is
    over the buffer)."""
    cases = [_case(shape, held_rows), _case(shape, "some", seed=1)][
        :axis_size]
    c, nk, layout = cases[0][:3]
    fits = HELD_ROWS[held_rows] <= c
    g, operands = jax.tree.map(lambda *x: jnp.stack(x),
                               *[case[3:] for case in cases])
    *got, taken = jax.jit(jax.vmap(functools.partial(
        _layer, c, MOE.SILU_GATED)))(g, operands)
    want = jax.jit(jax.vmap(functools.partial(
        _plain, c if fits else nk, layout, MOE.SILU_GATED)))(g, operands)
    assert [float(t) for t in taken] == [nk if fits else 0] * axis_size
    _assert_same_bits(got, want)


@pytest.mark.parametrize("activation", [MOE.RELU_GATED, MOE.RELU2])
@pytest.mark.parametrize("shape", ["ways_cut_by_way",
                                   "held_experts_cut_by_slot"])
def test_bfloat16_rows_and_the_other_activations_to_the_bit(
        shape, activation, resident):
    """The cells compute in bfloat16: the zero a slot without a held
    expert reads is exact there too."""
    c, nk, layout, g, operands = _case(shape, "one_past_a_tile",
                                       jnp.bfloat16)
    h, w, *rest = operands
    operands = (h, w[::2] if activation == MOE.RELU2 else w, *rest)
    *got, _ = jax.jit(functools.partial(_layer, c, activation))(g, operands)
    want = jax.jit(functools.partial(_plain, c, layout, activation))(
        g, operands)
    _assert_same_bits(got, want)


def test_adding_the_exact_zero_of_an_unfilled_slot_changes_no_bit():
    """What the re-expression rests on, said by itself: a slot no held
    expert fills reads ``+0``, as the zero row did, and a sum that takes
    ``+0`` in keeps every bit of what it had — ``-0`` too, which only a
    ``-0`` added to ``-0`` could make and which no matrix product's
    result is."""
    x = jnp.array([1.5, -2.25e-30, 3e38, -0.0, 0.0, jnp.inf], jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        v = x.astype(dtype)
        np.testing.assert_array_equal(
            np.asarray((v + jnp.zeros_like(v)).astype(jnp.float32)).view(
                np.uint32)[[0, 1, 2, 4, 5]],
            np.asarray(v.astype(jnp.float32)).view(np.uint32)[
                [0, 1, 2, 4, 5]])
    rows = jnp.full((4, 3), jnp.nan)
    read = MOE._read_back(rows.at[:2].set(7.0), jnp.array([0, 3, 9, 1]), 2)
    np.testing.assert_array_equal(
        np.asarray(read).view(np.uint32),
        np.asarray(jnp.array([[7.0] * 3, [0.0] * 3, [0.0] * 3, [7.0] * 3])
                   ).view(np.uint32))


@pytest.mark.parametrize("rows, width, dtype, leading", [
    (24576, 2560, jnp.bfloat16, 16384),  # SmallThinker's: 120 MiB
    (49152, 2560, jnp.bfloat16, 16384),  # its worst-case side's
    (16384, 2048, jnp.bfloat16, 16384),  # Keye's, LFM2's: 64 MiB, whole
    (22528, 1024, jnp.bfloat16, 22528),  # Nemotron's latent rows
    (24576, 2560, jnp.float32, 8192),
    (24576, None, jnp.float32, 24576)])  # a number a row
def test_resident_rows_are_whole_tiles_under_the_measured_size(
        rows, width, dtype, leading):
    x = jax.ShapeDtypeStruct((rows, width) if width else (rows,), dtype)
    assert MOE._resident_rows(x) == leading
    # of a kernel's result no more than three quarters, for the slice
    # to be a copy the compiler makes in fast memory (PR 48)
    part = min(leading, 3 * rows // 4 // MOE.ROW_TILE * MOE.ROW_TILE)
    assert MOE._resident_rows(x, True) == part < rows
    assert leading == rows or (
        leading % MOE.ROW_TILE == 0
        and leading * width * x.dtype.itemsize <= MOE.RESIDENT_BYTES)


def test_a_place_past_the_held_rows_reads_a_row_of_the_part_gathered():
    """Over a buffer of 512 rows whose leading 256 are the part a gather
    holds: with fewer held rows than that no read leaves the part (rows
    past it hold NaN here); with more, the whole buffer is read.
    :func:`MOE._read_weighed` reads row 0 for a place past the held
    rows, :func:`MOE._read_back` a zero."""
    rows = jnp.arange(512.0)[:, None] + jnp.ones((1, 4))
    index = jnp.array([0, 3, 400, 511, 1000, 255])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(MOE, "_resident_rows", lambda x, *_: 256)
        few = rows.at[256:].set(jnp.nan)
        np.testing.assert_array_equal(
            MOE._read_weighed(few, index, 4)[:, 0], [1, 4, 1, 1, 1, 1])
        np.testing.assert_array_equal(
            MOE._read_back(few, index, 4)[:, 0], [1, 4, 0, 0, 0, 0])
        np.testing.assert_array_equal(
            MOE._read_weighed(rows, index, 401)[:, 0], [1, 4, 401, 1, 1, 256])
        np.testing.assert_array_equal(
            MOE._read_back(rows, index, 512)[:, 0], [1, 4, 401, 512, 0, 256])


# ---------------------------------------------------------------------------
# what a mapped call lowers to
# ---------------------------------------------------------------------------

def _eqns(jaxpr, outer=""):
    """Every equation of ``jaxpr`` and of the programs its equations
    call, each with its whole name stack (an inner program's are
    relative to the equation that calls it)."""
    for eqn in jaxpr.eqns:
        scope = outer + "/" + str(eqn.source_info.name_stack)
        yield scope, eqn
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, scope)


def _batched_gathers(fn, *args):
    """The gathers of ``fn``'s program under the routing's scope that
    carry a batch dimension: batching dimensions, or rows picked (a
    collapsed dimension; a slice at a traced place collapses none) from
    an operand with the mapped axis in front of them."""
    found = []
    for scope, eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "gather" and MOE.ROUTE in scope:
            numbers = eqn.params["dimension_numbers"]
            if numbers.operand_batching_dims or (
                    numbers.collapsed_slice_dims
                    and eqn.invars[0].aval.ndim > 2):
                found.append(eqn)
    return found


def _training_step(p, h, held, top_k):
    def loss(p, h):
        y, counters = MOE.moe_layer(p, h, held, top_k, 1.0, "softmax")
        return jnp.sum(y), counters
    return jax.value_and_grad(loss, (0, 1), has_aux=True)(p, h)


@pytest.mark.parametrize("axis_size", [1, 2])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_mapped_call_holds_no_gather_with_a_batch_dimension(
        shape, axis_size, monkeypatch):
    n, top_k, experts, held, _ = SHAPES[shape]
    ks = jax.random.split(jax.random.key(3), 5)
    p = {"router": jax.random.normal(ks[0], (D, experts)),
         "w1": jax.random.normal(ks[1], (held[1], D, F)),
         "w3": jax.random.normal(ks[2], (held[1], D, F)),
         "w2": jax.random.normal(ks[3], (held[1], F, D))}
    h = jax.random.normal(ks[4], (axis_size, n, D))
    step = jax.vmap(lambda h: _training_step(p, h, held, top_k))
    assert _batched_gathers(step, h) == []
    evaluate = jax.vmap(lambda h: MOE.moe_layer(p, h, held, top_k, 1.0))
    assert _batched_gathers(evaluate, h) == []
    # the reading sees them where they are: the same layer whose
    # gathers run under the plain rule of ``vmap``
    # (``_read_weighed`` wraps its gather when called: by the name below)
    for name in ("_all_rows", "_read_back"):
        monkeypatch.setattr(MOE, name, getattr(MOE, name).fun)
    monkeypatch.setattr(MOE, "once_a_client", lambda fn: fn)
    jax.clear_caches()  # the rules' traces are kept by function
    assert len(_batched_gathers(step, h)) >= 4
    jax.clear_caches()


def test_once_a_client_runs_unbatched_and_stacks_trees():
    calls = []

    @once_a_client
    def fn(x, pair):
        calls.append(x.shape)
        return {"sum": x + pair[0], "both": (x * pair[1], pair[0])}

    x = jnp.arange(6.0).reshape(3, 2)
    shared, own = jnp.ones(2), jnp.arange(6.0).reshape(3, 2) + 1
    out = jax.vmap(fn, in_axes=(0, (None, 0)))(x, (shared, own))
    # every trace saw one client's operand, never the mapped [3, 2]
    assert len(calls) >= 3 and set(calls) == {(2,)}
    np.testing.assert_array_equal(out["sum"], x + 1)
    np.testing.assert_array_equal(out["both"][0], x * own)
    np.testing.assert_array_equal(out["both"][1], jnp.ones((3, 2)))
    # nested maps peel one axis at a time
    calls.clear()
    out = jax.vmap(jax.vmap(lambda x: fn(x, (shared, shared))))(
        x.reshape(3, 2, 1) * jnp.ones(2))
    assert len(calls) >= 6 and set(calls) == {(2,)}
    assert out["sum"].shape == (3, 2, 2)


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", list(SHAPES))
def test_rows_gathered_counts_the_four_gathers_of_a_training_step(shape):
    """``moe_rows_gathered`` = 2 x the rows of the buffer of the side
    taken — ``row_buffer(...)`` where the held rows fit it, else all ``N
    x top_k`` — + 2 x the rows the combine reads, ``N`` x a token's
    slots: the forward pass's two gathers and their transposes in the
    rule. Over ``4 x moe_rows_held`` it is the rows moved a held row."""
    n, top_k, experts, held, _ = SHAPES[shape]
    ks = jax.random.split(jax.random.key(7), 5)
    p = {"router": jax.random.normal(ks[0], (D, experts)),
         "w1": jax.random.normal(ks[1], (held[1], D, F)),
         "w3": jax.random.normal(ks[2], (held[1], D, F)),
         "w2": jax.random.normal(ks[3], (held[1], F, D))}
    at = MOE.MOE_COUNTERS.index
    slots = min(top_k, held[1])
    c = MOE.row_buffer(n, top_k, held[1], experts)
    for lean in (0.0, 6.0):  # as drawn; every token onto the held experts
        router = p["router"].at[:, held[0]:held[0] + held[1]].add(lean)
        _, counters = jax.jit(
            lambda h: MOE.moe_layer({**p, "router": router}, h, held, top_k,
                                    1.0, "softmax"))(
            jnp.abs(jax.random.normal(ks[4], (n, D))))
        rows_held = int(counters[at("moe_rows_held")])
        bounded = float(counters[at("moe_rows_compact")]) > 0
        assert bounded == (rows_held <= c) == (lean == 0.0)
        assert float(counters[at("moe_rows_gathered")]) == (
            2 * (c if bounded else n * top_k) + 2 * n * slots)
        assert float(counters[at("moe_rows_combined")]) == n * slots
    assert len(MOE.MOE_COUNTERS) == counters.shape[0] == 8
