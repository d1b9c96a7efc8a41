"""Learned sparse attention (``fedml_tpu/ops/attention.py``): the index
scores, the exact per-query selection and attention over the selected
keys — the written-out forms the CPU runs and the TPU kernel in the
Pallas interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import attention as A


def _ranked(scores, k):
    """The selection written out: every row sorted by (score down,
    position up), the first ``min(t + 1, k)`` of its causal keys kept."""
    scores = np.asarray(scores)
    b, t, _ = scores.shape
    want = np.zeros((b, t, t), bool)
    for i in range(b):
        for q in range(t):
            row = scores[i, q, :q + 1]
            order = sorted(range(q + 1), key=lambda s: (-row[s], s))
            want[i, q, order[:min(k, q + 1)]] = True
    return want


def _scores(ties: bool, seed=0, b=2, t=64):
    s = jax.random.normal(jax.random.key(seed), (b, t, t))
    return jnp.round(2 * s) / 2 if ties else s


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("k", [1, 16, 40, 63])
def test_selection_is_the_ranked_one_ties_to_the_lower_position(ties, k):
    scores = _scores(ties)
    got = np.asarray(jax.jit(lambda s: A.select_top_k(s, k))(scores))
    np.testing.assert_array_equal(got, _ranked(scores, k))
    assert (got.sum(-1) == np.minimum(np.arange(64) + 1, k)).all()


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("k", [40, 130, 200])
def test_selection_kernel_is_the_ranked_one(ties, k):
    """The TPU's selection kernel (Pallas interpreter; 256 rows in
    blocks of 128, so with ``k`` 130 and 200 the first block keeps
    every causal key unranked) against the ranking written out; what
    lies above the diagonal — the index kernel leaves it unwritten —
    is never read."""
    scores = _scores(ties, seed=k, t=256)
    above = ~np.tril(np.ones((256, 256), bool))
    got = A.select_top_k_kernel(
        jnp.where(above, jnp.nan, scores), k, interpret=True)
    assert got.dtype == jnp.bool_
    np.testing.assert_array_equal(got, _ranked(scores, k))
    np.testing.assert_array_equal(got, A.select_top_k_passes(scores, k))


def test_a_row_of_equal_scores_keeps_its_first_keys():
    """Every score equal (zeros of both signs among them): query ``t``
    keeps positions ``0 .. min(t, k - 1)``."""
    scores = jnp.zeros((1, 32, 32)).at[:, :, ::3].set(-0.0)
    got = np.asarray(A.select_top_k(scores, 5))
    want = np.tril(np.ones((32, 32), bool)) & (np.arange(32)[None, :] < 5)
    np.testing.assert_array_equal(got[0], want)


@pytest.mark.parametrize("k", [64, 2048])
def test_topk_at_or_over_the_length_is_dense_causal_attention(k):
    """Where ``t + 1 <= topk`` every causal key is kept, and the layer
    equals dense causal attention to the bit of the written-out form."""
    ks = jax.random.split(jax.random.key(2), 4)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    kk = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    selection = A.select_top_k(jax.random.normal(ks[3], (2, 64, 64)), k)
    np.testing.assert_array_equal(
        selection, np.broadcast_to(A.attention_mask(64, None), (2, 64, 64)))
    np.testing.assert_array_equal(
        A.causal_attention(q, kk, v, selection=selection),
        A.masked_attention(q, kk, v))
    with pytest.raises(ValueError):
        A.select_top_k(jnp.zeros((1, 8, 8)), 0)


@pytest.mark.parametrize("block", [16, 64])
def test_index_scores_against_the_sum_written_out(block, monkeypatch):
    monkeypatch.setattr(A, "BLOCK", block)
    ks = jax.random.split(jax.random.key(3), 3)
    qi = jax.random.normal(ks[0], (2, 64, 4, 8))
    ki = jax.random.normal(ks[1], (2, 64, 8))
    w = jax.random.normal(ks[2], (2, 64, 4))
    want = np.zeros((2, 64, 64))
    for j in range(4):
        dots = np.einsum("bqe,bke->bqk", np.asarray(qi[:, :, j], np.float64),
                         np.asarray(ki, np.float64))
        want += np.asarray(w[:, :, j], np.float64)[..., None] * np.maximum(
            dots, 0)
    want = want / np.sqrt(4 * 8)
    got = A.index_scores(qi, ki, w)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the TPU's kernel (Pallas interpreter): the tiles at or under the
    # diagonal; a tile above it is left unwritten
    tiles = np.kron(np.tril(np.ones((64 // block,) * 2)),
                    np.ones((block, block))).astype(bool)
    got = A.index_scores_kernel(qi, ki, w, interpret=True)
    np.testing.assert_allclose(np.where(tiles, got, 0),
                               np.where(tiles, want, 0), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t, on_tpu, refused", [
    (40, False, "index_scores"),   # longer than a block, not whole blocks
    (40, True, "select_top_k"),    # the chip would run the slow form
    (8, True, None),               # under one block: the written-out forms
    (32, False, None),             # whole blocks
])
def test_a_sequence_the_kernels_cannot_tile_is_refused(
        t, on_tpu, refused, monkeypatch):
    monkeypatch.setattr(A, "BLOCK", 16)
    monkeypatch.setattr(A, "SELECT_ROWS", 16)
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    ks = jax.random.split(jax.random.key(5), 4)
    qi = jax.random.normal(ks[0], (1, t, 2, 8))
    ki = jax.random.normal(ks[1], (1, t, 8))
    w = jax.random.normal(ks[2], (1, t, 2))
    scores = jax.random.normal(ks[3], (1, t, t))
    if refused == "index_scores":
        with pytest.raises(ValueError, match="whole blocks of 16"):
            A.index_scores(qi, ki, w)
    elif refused == "select_top_k":
        with pytest.raises(ValueError, match="select_top_k"):
            A.select_top_k(scores, 4)
    else:
        assert A.index_scores(qi, ki, w).shape == (1, t, t)
        np.testing.assert_array_equal(
            A.select_top_k(scores, 4), _ranked(scores, 4))


@pytest.mark.parametrize("heads", [4, 8])
def test_selected_attention_kernel_against_the_written_out_product(
        heads, monkeypatch):
    """The TPU kernel (splash attention over the selection as a dynamic
    mask; Pallas interpreter here, 256 tokens in blocks of 128, so the
    one backward kernel's key block holds two compute blocks) and the
    masked product the CPU runs, against scores written out a query
    head at a time: forward and the cotangents of q, k and v."""
    monkeypatch.setattr(A, "BLOCK", 128)
    t, d, kv = 256, 128, 2
    ks = jax.random.split(jax.random.key(heads), 5)
    q = jax.random.normal(ks[0], (2, t, heads, d))
    k = jax.random.normal(ks[1], (2, t, kv, d))
    v = jax.random.normal(ks[2], (2, t, kv, d))
    g = jax.random.normal(ks[3], (2, t, heads, d))
    selection = A.select_top_k(jax.random.normal(ks[4], (2, t, t)), 40)

    def written_out(q, k, v):
        outs = []
        for j in range(heads):
            s = jnp.einsum("bqd,bkd->bqk", q[:, :, j],
                           k[:, :, j // (heads // kv)]) / d ** 0.5
            p = jax.nn.softmax(jnp.where(selection, s, -jnp.inf), -1)
            outs.append(jnp.einsum("bqk,bkd->bqd", p,
                                   v[:, :, j // (heads // kv)]))
        return jnp.stack(outs, 2)

    want, vjp = jax.vjp(written_out, q, k, v)
    kernel = lambda q, k, v: A.selected_splash(
        q, k, v, selection, interpret=True)
    masked = lambda q, k, v: A.masked_attention(q, k, v, selection=selection)
    for fn in (masked, kernel):
        got, vjp_got = jax.vjp(fn, q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        for a, b in zip(vjp_got(g), vjp(g)):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    # off the TPU the model's attention IS the masked product
    np.testing.assert_array_equal(
        A.causal_attention(q, k, v, selection=selection), masked(q, k, v))


def _pallas_calls(jaxpr):
    """The names of the Pallas kernels ``jaxpr`` and the programs it
    calls hold, a call each."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


@pytest.mark.parametrize("keep, forward_calls", [
    (None, 2), ((A.KEPT_OUTPUT,), 1), (A.KEPT, 1)],
    ids=["nothing", "output", "output_and_selection"])
def test_a_checkpoint_keeps_what_is_named(keep, forward_calls, monkeypatch):
    """Index, selection and attention over it (the chip's kernels in the
    Pallas interpreter) inside a ``jax.checkpoint``: under a policy that
    saves :data:`KEPT_OUTPUT` the forward kernel is not run again for
    the backward pass, with :data:`KEPT_SELECTION` (the name a layer
    puts on its selection) neither are the index and the top-k; the
    names change no number, kept or not."""
    from jax.ad_checkpoint import checkpoint_name

    monkeypatch.setattr(A, "BLOCK", 128)
    t, d = 256, 128
    ks = jax.random.split(jax.random.key(7), 7)
    q = jax.random.normal(ks[0], (1, t, 2, d))
    k = jax.random.normal(ks[1], (1, t, 1, d))
    v = jax.random.normal(ks[2], (1, t, 1, d))
    qi = jax.random.normal(ks[3], (1, t, 2, 8))
    ki = jax.random.normal(ks[4], (1, t, 8))
    w = jax.random.normal(ks[5], (1, t, 2))
    out = jax.random.normal(ks[6], (2 * d, 4))

    def layer(q, k, v, out):
        scores = A.index_scores_kernel(qi, ki, w, interpret=True)
        selection = checkpoint_name(
            A.select_top_k_kernel(scores, 40, interpret=True),
            A.KEPT_SELECTION)
        a = A.selected_splash(q, k, v, selection, interpret=True)
        return jnp.sum((a.reshape(1, t, 2 * d) @ out) ** 2)

    policy = keep and jax.checkpoint_policies.save_only_these_names(*keep)
    grad = jax.grad(jax.checkpoint(layer, policy=policy), argnums=(0, 1, 2, 3))
    calls = list(_pallas_calls(jax.make_jaxpr(grad)(q, k, v, out).jaxpr))
    count = lambda prefix: sum(name.startswith(prefix) for name in calls)
    ranks = 1 if keep == A.KEPT else 2
    assert count("splash_mqa_fwd") == forward_calls
    # the backward pass walks its score blocks once: dq comes out of
    # the dk/dv kernel, there is no dq kernel
    assert count("splash_mqa_dq") == 0 and count("splash_mqa_dkv") == 1
    assert count("sparse_select_top_k") == ranks
    assert count("sparse_index_scores") == ranks
    for got, want in zip(jax.jit(grad)(q, k, v, out), jax.jit(jax.grad(
            layer, argnums=(0, 1, 2, 3)))(q, k, v, out)):
        np.testing.assert_array_equal(got, want)
