"""The router's ``top_k`` (``fedml_tpu/ops/moe.py:route_top_k``) against
the form it replaced, BIT FOR BIT: ``jax.lax.top_k``'s ids, order and
choice at a tie forward, and the scatter-add its rule transposed
``take_along_axis`` to backward — kept HERE as the oracle. At the five
decoder cells' ``(E, top_k)``, written out and as the TPU's kernel in
the Pallas interpreter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_decoder import _eqns

from fedml_tpu.ops import moe as MOE

#: ``(experts, top_k, scoring, with a bias in the choice)`` of the
#: benchmark's decoder cells
CELLS = {"nemotron": (512, 22, "sigmoid", False),
         "keye": (128, 8, "softmax", False),
         "laguna": (256, 8, "sigmoid", False),
         "smallthinker": (64, 6, "softmax", False),
         "joyai": (256, 8, "sigmoid", True)}
TOKENS = 128


def _scores(cell, case, tokens=TOKENS):
    """Router logits whose probabilities are ``random``; exactly 0 or 1
    (``ties``: whole rows of equal values under ``sigmoid``); ``equal``
    all along a row; or of a ``few`` distinct values."""
    experts = CELLS[cell][0]
    s = 3 * jax.random.normal(
        jax.random.key(experts), (tokens, experts), jnp.float32)
    return {"random": s, "ties": 60 * jnp.sign(s),
            "equal": jnp.zeros_like(s), "few": jnp.round(s)}[case]


def _bias(cell, biased):
    if not (biased or CELLS[cell][3]):
        return None
    # a few experts share a bias, so probability plus bias ties as well
    return jnp.round(
        jax.random.normal(jax.random.key(3), (CELLS[cell][0],)), 1) / 4


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


def _same_bits(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


# ---- the parent's form (PR 41's), the oracle -------------------------------

def _sorted_ranked(scores, top_k, scoring, choice_bias=None):
    p = MOE.SCORINGS[scoring](scores)
    if choice_bias is None:
        return jax.lax.top_k(p, top_k)
    top_e = jax.lax.top_k(p + choice_bias, top_k)[1]
    return jnp.take_along_axis(p, top_e, -1), top_e


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _sorted_route_top_k(scores, top_k, scale, scoring, choice_bias=None):
    top_p, top_e = _sorted_ranked(scores, top_k, scoring, choice_bias)
    return top_e, MOE._weights(top_p, scale)


def _sorted_fwd(scores, top_k, scale, scoring, choice_bias):
    top_p, top_e = _sorted_ranked(scores, top_k, scoring, choice_bias)
    return (top_e, MOE._weights(top_p, scale)), (
        scores, top_p, top_e, choice_bias)


def _scattered_bwd(top_k, scale, scoring, res, cotangents):
    scores, top_p, top_e, choice_bias = res
    d_top_p, = jax.vjp(lambda top_p: MOE._weights(top_p, scale), top_p)[1](
        cotangents[1])
    p, scored = jax.vjp(MOE.SCORINGS[scoring], scores)
    d_p, = jax.linear_transpose(
        lambda p: jnp.take_along_axis(p, top_e, -1), p)(d_top_p)
    return (*scored(d_p), jax.tree.map(jnp.zeros_like, choice_bias))


_sorted_route_top_k.defvjp(_sorted_fwd, _scattered_bwd)


# ---- forward ---------------------------------------------------------------

@pytest.mark.parametrize("biased", [False, True], ids=["plain", "biased"])
@pytest.mark.parametrize("case", ["random", "ties", "equal", "few"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_choice_is_lax_top_k_to_the_bit(cell, case, biased):
    """Ids, their order (descending, equal values by the lower id) and
    the weights, under ``jit``."""
    _, k, scoring, _ = CELLS[cell]
    scores, bias = _scores(cell, case), _bias(cell, biased)
    got = jax.jit(lambda s: MOE.route_top_k(s, k, 2.5, scoring, bias))(scores)
    want = jax.jit(
        lambda s: _sorted_route_top_k(s, k, 2.5, scoring, bias))(scores)
    _same_bits(got, want)
    assert got[0].shape == got[1].shape == (TOKENS, k)


@pytest.mark.parametrize("biased", [False, True], ids=["plain", "biased"])
@pytest.mark.parametrize("case", ["random", "ties", "equal"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kernel_ranks_as_lax_top_k_to_the_bit(cell, case, biased):
    """The TPU's kernel (Pallas interpreter; two grid steps of 128
    tokens): probabilities ``[E, N]`` in, ``[k, N]`` out."""
    _, k, scoring, _ = CELLS[cell]
    scores, bias = _scores(cell, case, 256), _bias(cell, biased)
    p = MOE.SCORINGS[scoring](scores)
    ranked = p if bias is None else p + bias
    want_p, want_e = _sorted_ranked(scores, k, scoring, bias)
    top_e, top_p = MOE.largest_kernel(
        ranked.T, None if bias is None else p.T, k, tokens=128,
        interpret=True)
    _same_bits((top_p.T, top_e.T), (want_p, want_e))


# ---- the rule --------------------------------------------------------------

def _rules(cell, bias):
    """``(kept, cotangent of the weights) -> cotangents``: this tree's
    rule and the parent's, over what the forward pass kept."""
    _, k, scoring, _ = CELLS[cell]
    return (lambda kept, g: MOE._route_top_k_bwd(
                k, 2.5, scoring, MOE.ROUTE, 0.0, MOE.ONE_GROUP, kept, (None, g)),
            lambda kept, g: _scattered_bwd(k, 2.5, scoring, kept, (None, g)))


def _kept(cell, scores, bias):
    _, k, scoring, _ = CELLS[cell]
    return MOE._route_top_k_fwd(scores, k, 2.5, scoring, bias, MOE.ROUTE)[1]


@pytest.mark.parametrize("biased", [False, True], ids=["plain", "biased"])
@pytest.mark.parametrize("case", ["random", "ties", "equal", "few"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rules_cotangent_is_the_scatters_to_the_bit(cell, case, biased):
    """A token's ids are distinct, so a compare and a sum over its ways
    leave what the scatter-add of ``N x k`` updates left; the bias gets
    zeros."""
    k = CELLS[cell][1]
    scores, bias = _scores(cell, case), _bias(cell, biased)
    g = jax.random.normal(jax.random.key(11), (TOKENS, k))
    g = g.at[:, 0].set(-0.0)  # a signed zero arrives as the scatter's
    kept = _kept(cell, scores, bias)
    rule, scattered = _rules(cell, bias)
    got, want = jax.jit(rule)(kept, g), jax.jit(scattered)(kept, g)
    _same_bits(got, want)
    assert got[0].shape == scores.shape
    if bias is not None:
        np.testing.assert_array_equal(got[1], 0)
    if case == "random":  # and through jax.grad it is the rule that runs
        scoring = CELLS[cell][2]
        d = jax.grad(lambda s: jnp.sum(
            MOE.route_top_k(s, k, 2.5, scoring, bias)[1] * g))(scores)
        size = float(jnp.max(jnp.abs(want[0])))
        assert size > 0
        np.testing.assert_allclose(d, want[0], rtol=0, atol=1e-3 * size)


# ---- under the cohort's vmap ----------------------------------------------

@pytest.mark.parametrize("clients", [1, 3])
@pytest.mark.parametrize("cell", list(CELLS))
def test_under_vmap_over_a_client_axis(cell, clients):
    """Mapped over a leading client axis (the bulk engine at a block of
    1 maps one client), choice and cotangent are each client's own, to
    the bit — written out and through the kernel's batching rule."""
    _, k, scoring, _ = CELLS[cell]
    bias = _bias(cell, False)
    scores = jnp.stack([_scores(cell, "random") + c for c in range(clients)])
    g = jax.random.normal(jax.random.key(13), (clients, TOKENS, k))
    choice = jax.jit(jax.vmap(
        lambda s: MOE.route_top_k(s, k, 2.5, scoring, bias)))(scores)
    _same_bits(choice, jax.vmap(
        lambda s: _sorted_route_top_k(s, k, 2.5, scoring, bias))(scores))
    kept = jax.vmap(lambda s: _kept(cell, s, bias))(scores)
    rule, scattered = _rules(cell, bias)
    _same_bits(jax.jit(jax.vmap(rule))(kept, g),
               jax.jit(jax.vmap(scattered))(kept, g))
    p = jax.vmap(MOE.SCORINGS[scoring])(scores)
    ranked = p if bias is None else p + bias
    top_e, top_p = jax.vmap(lambda b, own: MOE.largest_kernel(
        b.T, None if own is None else own.T, k, tokens=TOKENS,
        interpret=True))(ranked, None if bias is None else p)
    _same_bits((top_e.transpose(0, 2, 1), top_p.transpose(0, 2, 1)),
               jax.vmap(lambda s: _sorted_ranked(s, k, scoring, bias)[::-1])(
                   scores))


# ---- what a sparse layer's gradient holds ---------------------------------

@pytest.mark.parametrize("cell", list(CELLS))
def test_a_sparse_layers_gradient_ranks_without_a_sort_or_a_scatter(cell):
    """A sparse layer's training gradient at the cell's ``(E, top_k)``:
    the lowered program holds no ``top_k``; no ``sort`` and no
    ``scatter`` touches an array of ``[N, E]`` (the sorts left order the
    ``N x top_k`` ids); and the router ranks ONCE, forward."""
    experts, k, scoring, biased = CELLS[cell]
    count, width = 8, 16
    keys = jax.random.split(jax.random.key(17), 5)
    p = {"router": jax.random.normal(keys[0], (width, experts)),
         "w1": jax.random.normal(keys[1], (count, width, 8)),
         "w3": jax.random.normal(keys[2], (count, width, 8)),
         "w2": jax.random.normal(keys[3], (count, 8, width))}
    if biased:
        p["router_bias"] = _bias(cell, True)
    h = jax.random.normal(keys[4], (TOKENS, width))
    gradient = jax.grad(lambda p, h: jnp.sum(MOE.moe_layer(
        p, h, (4, count), k, 2.5, scoring)[0]), argnums=(0, 1),
                        allow_int=True)
    text = jax.jit(gradient).lower(p, h).as_text()
    assert "top_k" not in text
    eqns = list(_eqns(jax.make_jaxpr(gradient)(p, h).jaxpr,
                      bounded_side_only=False))
    names = [e.primitive.name for e in eqns]
    assert "top_k" not in names
    for eqn in eqns:
        if eqn.primitive.name == "sort" or "scatter" in eqn.primitive.name:
            shapes = {v.aval.shape for v in (*eqn.invars, *eqn.outvars)}
            assert (TOKENS, experts) not in shapes, eqn
    ranks = [e for e in eqns if e.primitive.name == "jit"
             and e.params["name"] == "largest"]
    assert len(ranks) == 1
