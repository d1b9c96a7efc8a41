"""The decoder stack with gated delta-rule layers (a decay a channel of
the key), a latent-attention layer WITHOUT a query latent, with a head
share and with a gate a head, and a router whose choice is limited to a
token's open groups of experts — against the plain reference of
``benchmarks/configs/ling3-flash-share64`` at tiny widths with every
ratio kept; the grouped choice against a sort-based one; the head
shares and the expert shares tied to the uncut layer; the fresh gate's
law; what ``decoder_from_extra`` refuses; the published share's size."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmarks"),
           os.path.join(ROOT, "benchmarks", "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import tiny_ling as TL  # noqa: E402
from test_decoder import _assert_trees_close as _close  # noqa: E402
from test_decoder import _loss, _model_config, _sim  # noqa: E402
from test_delta import _interpreted as interpreted_kernels  # noqa: E402
from test_smallthinker import _layer_params  # noqa: E402

from fedml_tpu.config import ModelConfig  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import decoder as D  # noqa: E402
from fedml_tpu.ops import attention as A  # noqa: E402
from fedml_tpu.ops import delta as DL  # noqa: E402
from fedml_tpu.ops import moe as MOE  # noqa: E402

HIDDEN = 64
GROUPS = (8, 4)


def _assert_trees_close(got, want, rtol, but=()):
    """``test_decoder``'s comparison, and no leaf of ``want`` all zero
    but those whose path holds one of ``but``."""
    for path, r in jax.tree_util.tree_leaves_with_path(want):
        name = jax.tree_util.keystr(path)
        zero = float(jnp.max(jnp.abs(r))) == 0.0
        assert zero == any(b in name for b in but), name
    _close(got, want, rtol)


@pytest.mark.parametrize("pattern", [
    ("kD",), ("kS",), ("lD",), ("lS",), TL.PATTERN],
    ids=["delta+dense", "delta+sparse", "latent+dense", "latent+sparse",
         "stack"])
def test_program_against_reference_logits_and_gradients(pattern, tmp_path):
    """float32: each mixer under each feed-forward, and the six-layer
    stack: the variable trees agree leaf for leaf, and so do the logits,
    the loss and every parameter's gradient — the bias's is zero on
    both sides."""
    config = TL.tiny_config(pattern=pattern)
    ref = TL.load_reference(str(tmp_path), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, TL.SEQ + 1), 0, TL.VOCAB)
    x, y = tokens[:, :-1], tokens[:, 1:]
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert shapes(model.init(jax.random.key(0))) == shapes(variables)

    def program(params):
        logits, _, counted = model.apply_train_counted(
            {"params": params}, x, jax.random.key(0))
        return _loss(logits, y), (logits, counted)

    def reference(params):
        logits, _ = ref.forward({"params": params}, x, True)
        return _loss(logits, y), logits

    (loss, (ours, counted)), g_ours = jax.value_and_grad(
        program, has_aux=True)(variables["params"])
    (loss_ref, theirs), g_ref = jax.value_and_grad(
        reference, has_aux=True)(variables["params"])
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-5)
    _assert_trees_close(g_ours, g_ref, 2e-3, but=("router_bias",))
    sparse = sum(layer[1] == "S" for layer in pattern)
    if sparse:
        delta = any(layer[0] == "k" for layer in pattern)
        assert set(counted) == set(
            MOE.MOE_COUNTERS + DL.DELTA_COUNTERS * delta)
        assert float(counted["moe_rows_routed"]) == x.size * 4 * sparse
        assert 0 < float(counted["moe_rows_held"]) < x.size * 4 * sparse
        # one group of eight held, four open a token: about half
        assert 0.2 < float(counted["moe_tokens_group_open"]) / (
            x.size * sparse) < 0.8


# ---------------------------------------------------------------------------
# one mixer alone
# ---------------------------------------------------------------------------


def _one_layer(kind, **change):
    """A one-layer stack of mixer ``kind`` and no feed-forward at the
    tiny sizes -> the layer."""
    extra = {**TL.tiny_config(pattern=(kind + "N",))["model"]["extra"],
             **change}
    return D.DecoderLayer(D.decoder_from_extra(extra, TL.VOCAB).cfg, 0)


def _stream():
    return jax.random.normal(jax.random.key(8), (2, TL.SEQ, HIDDEN))


DELTA_LEAVES = {
    "delta_norm", "q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv",
    "f_proj", "dt_bias", "A_log", "b_proj", "g_proj", "o_norm", "o_proj"}
LATENT_LEAVES = {"attn_norm", "q_proj", "kv_a_proj", "kv_a_norm",
                 "kv_b_proj", "g_proj", "o_proj"}


@pytest.mark.parametrize("kind, leaves, scopes", [
    ("k", DELTA_LEAVES, ("fedml.model.delta", "fedml.model.delta.mix",
                         "fedml.model.delta.scan")),
    ("l", LATENT_LEAVES, ("fedml.model.attn.latent",
                          "fedml.model.attn.kernel")),
], ids=["delta", "latent"])
def test_a_mixer_alone_against_the_reference(kind, leaves, scopes, tmp_path):
    """``x + mixer(norm(x))`` against the reference's own function:
    values, the stream's gradient and every parameter's; a latent layer
    with ``q_lora_rank`` null has ONE query projection and no
    ``q_a_proj`` / ``q_a_norm`` leaves; the scopes are in the program."""
    ref = TL.load_reference(str(tmp_path), TL.tiny_config())
    layer = _one_layer(kind)
    x = _stream()
    params = _layer_params(layer, x)
    assert set(params) == leaves
    weigh = jax.random.normal(jax.random.key(9), x.shape)
    both = lambda fn: jax.value_and_grad(
        lambda p, x: jnp.sum(fn(p, x) * weigh), argnums=(0, 1))
    got, g_got = both(lambda p, x: layer.apply({"params": p}, x)[0])(
        params, x)
    plain = ref._delta if kind == "k" else ref._attention
    want, g_want = both(lambda p, x: plain(x, p, None))(params, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    _assert_trees_close(g_got, g_want, 2e-4)
    text = jax.jit(layer.apply).lower({"params": params}, x).as_text(
        debug_info=True)
    for scope in scopes:
        assert scope in text


def test_the_latent_layers_gate_is_one_scalar_a_head_and_token():
    """``gating`` on a latent layer against the layer written out: the
    ungated layer's heads times ``sigmoid(h W_gate)``, one number a
    head, before the output projection."""
    gated, plain = _one_layer("l"), _one_layer("l", gating=False)
    x = _stream()
    params = _layer_params(gated, x)
    held = TL.HELD[1]
    assert params["g_proj"]["kernel"].shape == (HIDDEN, held)
    assert params["q_proj"]["kernel"].shape == (HIDDEN, held * (16 + 8))
    ungated = {k: v for k, v in params.items() if k != "g_proj"}
    dv = TL.LATENT["v_head_dim"]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (
        params["attn_norm"]["scale"])
    gate = jax.nn.sigmoid(h @ params["g_proj"]["kernel"])
    # with W_gate = 0 every gate is a half: half the ungated mixer
    zero = {**params, "g_proj": {"kernel": jnp.zeros((HIDDEN, held))}}
    half = gated.apply({"params": zero}, x)[0] - x
    whole = plain.apply({"params": ungated}, x)[0] - x
    np.testing.assert_allclose(half, 0.5 * whole, rtol=1e-5, atol=1e-6)
    # a gate of its own a head: closing head 0's leaves head 1's part
    o = params["o_proj"]["kernel"].reshape(held, dv, HIDDEN)
    only = lambda j: plain.apply({"params": {**ungated, "o_proj": {
        "kernel": o.at[1 - j].set(0.0).reshape(held * dv, HIDDEN)}}}, x)[0] - x
    want = sum(jnp.einsum("btd,bt->btd", only(j), gate[..., j])
               for j in range(held))
    got = gated.apply({"params": params}, x)[0] - x
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the choice inside a token's open groups
# ---------------------------------------------------------------------------


def _sorted_choice(scores, bias, k, scale, groups):
    """The grouped choice written out with sorts."""
    n_group, topk_group = groups
    s = jax.nn.sigmoid(scores)
    c = s + bias
    n, e = c.shape
    inside = c.reshape(n, n_group, e // n_group)
    group = jnp.sort(inside, -1)[..., -2:].sum(-1)
    best = jax.lax.top_k(group, topk_group)[1]
    is_open = jnp.any(best[..., None] == jnp.arange(n_group), -2)
    c = jnp.where(jnp.repeat(is_open, e // n_group, -1), c, -jnp.inf)
    top_e = jax.lax.top_k(c, k)[1]
    top_s = jnp.take_along_axis(s, top_e, -1)
    return top_e, scale * top_s / top_s.sum(-1, keepdims=True), is_open


def _logits(case, n=256, e=64):
    scores = jax.random.normal(jax.random.key(11), (n, e))
    if case == "ties":  # a few distinct values: ties inside and between groups
        scores = jnp.round(scores * 2) / 2
    if case == "equal":
        scores = jnp.zeros_like(scores)
    return scores


@pytest.mark.parametrize("case", ["random", "ties", "equal"])
@pytest.mark.parametrize("biased", [True, False], ids=["biased", "plain"])
def test_the_grouped_choice_is_the_sorted_one(case, biased):
    """Open groups, ids in ``lax.top_k``'s order, weights from the
    unbiased scores — to the bit, ties by the lower group and the lower
    expert; the counter's open groups are the choice's."""
    scores = _logits(case)
    bias = (0.01 * jax.random.normal(jax.random.key(12), (64,))
            if biased else jnp.zeros((64,)))
    top_e, top_w = jax.jit(lambda s: MOE.route_top_k(
        s, 4, 2.5, "sigmoid", bias if biased else None, MOE.ROUTE, 0.0,
        GROUPS))(scores)
    want_e, want_w, is_open = _sorted_choice(scores, bias, 4, 2.5, GROUPS)
    np.testing.assert_array_equal(top_e, want_e)
    np.testing.assert_array_equal(top_w, want_w)
    ranked = jax.nn.sigmoid(scores) + bias
    np.testing.assert_array_equal(MOE.open_groups(ranked.T, GROUPS).T, is_open)
    assert bool(jnp.all(is_open.sum(-1) == 4))
    # every chosen expert lies in an open group
    assert bool(jnp.all(jnp.take_along_axis(is_open, top_e // 8, -1)))


def test_the_groups_move_the_choice_and_get_no_gradient():
    """Tokens whose chosen set differs from the ungrouped one; no
    gradient to the bias; the logits' gradient is that of the weights at
    the chosen ids alone (the sorted form's)."""
    scores = _logits("random")
    bias = 0.01 * jax.random.normal(jax.random.key(12), (64,))
    grouped = MOE.route_top_k(scores, 4, 2.5, "sigmoid", bias, MOE.ROUTE,
                              0.0, GROUPS)
    plain = MOE.route_top_k(scores, 4, 2.5, "sigmoid", bias)
    differ = jnp.any(jnp.sort(grouped[0], -1) != jnp.sort(plain[0], -1), -1)
    assert 0 < int(differ.sum()) < scores.shape[0]
    weigh = jax.random.normal(jax.random.key(13), grouped[1].shape)
    ours = jax.grad(lambda s, b: jnp.sum(MOE.route_top_k(
        s, 4, 2.5, "sigmoid", b, MOE.ROUTE, 0.0, GROUPS)[1] * weigh),
        argnums=(0, 1))(scores, bias)
    theirs = jax.grad(lambda s, b: jnp.sum(
        _sorted_choice(s, b, 4, 2.5, GROUPS)[1] * weigh),
        argnums=(0, 1))(scores, bias)
    assert float(jnp.max(jnp.abs(ours[1]))) == 0.0
    assert float(jnp.max(jnp.abs(theirs[1]))) == 0.0
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("biased", [True, False], ids=["biased", "plain"])
def test_one_group_is_the_choice_of_before_to_the_bit(biased):
    """``groups = (1, 1)`` and no ``groups`` at all: the same ids, the
    same weights, the same cotangent, and ``lax.top_k``'s."""
    scores = _logits("ties")
    bias = 0.01 * jax.random.normal(jax.random.key(12), (64,)) if biased \
        else None
    run = lambda *more: jax.jit(lambda s: MOE.route_top_k(
        s, 4, 2.5, "sigmoid", bias, *more))(scores)
    before, one = run(), run(MOE.ROUTE, 0.0, MOE.ONE_GROUP)
    np.testing.assert_array_equal(before[0], one[0])
    np.testing.assert_array_equal(before[1], one[1])
    p = jax.nn.sigmoid(scores)
    top_e = jax.lax.top_k(p if bias is None else p + bias, 4)[1]
    np.testing.assert_array_equal(one[0], top_e)
    weigh = jax.random.normal(jax.random.key(13), one[1].shape)
    grad = lambda *more: jax.grad(lambda s: jnp.sum(MOE.route_top_k(
        s, 4, 2.5, "sigmoid", bias, *more)[1] * weigh))(scores)
    np.testing.assert_array_equal(
        grad(), grad(MOE.ROUTE, 0.0, MOE.ONE_GROUP))


def test_the_kernel_ranks_inside_the_open_groups():
    """The TPU's ranking kernel (Pallas interpreter) on columns whose
    closed groups are marked out: the passes over the whole array, to
    the bit, and never an expert of a closed group."""
    scores = _logits("ties")
    p = jax.nn.sigmoid(scores)
    ranked = (p + 0.01 * jax.random.normal(jax.random.key(12), (64,))).T
    is_open = jnp.repeat(MOE.open_groups(ranked, GROUPS), 8, 0)
    marked = jnp.where(is_open, ranked, -jnp.inf)
    ids, values = MOE.largest_kernel(marked, p.T, 4, interpret=True)
    want_ids, want_values = MOE._largest(marked, p.T, 4)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(values, want_values)
    assert bool(jnp.all(jnp.take_along_axis(is_open, ids, 0)))
    top_p, top_e = MOE.largest(ranked.T, p, 4, False, GROUPS)
    np.testing.assert_array_equal(top_e, ids.T)
    np.testing.assert_array_equal(top_p, values.T)


# ---------------------------------------------------------------------------
# the shares tied to the uncut layer
# ---------------------------------------------------------------------------


def _head_share(params, first, count, heads=TL.HEADS):
    """The leaves of heads ``[first, first + count)`` of a mixer's
    ``params``: columns of what leads in, rows of what leads out; what
    every chip holds whole as it is."""
    def columns(a):  # [..., heads * w] -> the share's
        w = a.shape[-1] // heads
        return a.reshape(*a.shape[:-1], heads, w)[
            ..., first:first + count, :].reshape(*a.shape[:-1], count * w)

    out = {}
    for name, leaf in params.items():
        if name in ("delta_norm", "attn_norm", "o_norm", "kv_a_proj",
                    "kv_a_norm"):
            out[name] = leaf
        elif name == "o_proj":
            k = leaf["kernel"]
            out[name] = {"kernel": k.reshape(heads, -1, k.shape[-1])[
                first:first + count].reshape(-1, k.shape[-1])}
        elif isinstance(leaf, dict):
            out[name] = {"kernel": columns(leaf["kernel"])}
        else:
            out[name] = columns(leaf)
    return out


@pytest.mark.parametrize("kind", ["k", "l"], ids=["delta", "latent"])
def test_the_head_shares_add_up_to_the_uncut_mixer(kind, tmp_path):
    """Four heads as two tensor-parallel chips hold them (2 each): what
    the two shares add to the stream, summed, is what the uncut mixer
    adds — every head's q, k, v, taps, decay, gate and state (or its own
    queries and keys-values out of the shared latent) is its own — and
    the uncut mixer is the reference's with all heads held."""
    whole = _one_layer(kind, query_heads_held=None)
    x = _stream()
    params = _layer_params(whole, x)
    want = whole.apply({"params": params}, x)[0] - x
    uncut = TL.tiny_config()
    del uncut["model"]["extra"]["query_heads_held"]
    ref = TL.load_reference(str(tmp_path), uncut)
    plain = ref._delta if kind == "k" else ref._attention
    np.testing.assert_allclose(
        want, plain(x, params, None) - x, rtol=2e-5, atol=2e-5)
    total = 0.0
    for first in (0, 2):
        share = _one_layer(kind, query_heads_held=[first, 2])
        mine = _head_share(params, first, 2)
        assert jax.tree.map(jnp.shape, mine) == jax.tree.map(
            jnp.shape, share.init(jax.random.key(0), x)["params"])
        total = total + share.apply({"params": mine}, x)[0] - x
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """64 experts in 8 groups, 4 a token out of 4 open groups, as 8
    chips hold them (8 each: a group a chip) beside a shared expert and
    a router, bias included, that stand whole on every chip: the chips'
    routed parts and the shared expert counted ONCE add up to the uncut
    layer's feed-forward written out, their held rows are every
    assignment made, and a token's group is open on 4 of the 8 chips."""
    d, f, experts = HIDDEN, 16, 64
    ks = iter(jax.random.split(jax.random.key(21), 9))
    n = lambda *s: jax.random.normal(next(ks), s) * s[-2] ** -0.5
    whole = {"router": n(d, experts), "w1": n(experts, d, f),
             "w3": n(experts, d, f), "w2": n(experts, f, d),
             "shared": (n(d, f), n(d, f), n(f, d)),
             "router_bias": D.ROUTER_BIAS_STD * jax.random.normal(
                 next(ks), (experts,))}
    rows = jax.random.normal(next(ks), (96, d))
    want, counters = MOE.moe_layer(
        whole, rows, (0, experts), 4, 2.5, groups=GROUPS)
    counted = dict(zip(MOE.MOE_COUNTERS, map(float, counters)))
    assert counted["moe_rows_held"] == counted["moe_rows_routed"] == 96 * 4
    assert counted["moe_tokens_group_open"] == 96
    top_e, weight, _ = _sorted_choice(
        rows @ whole["router"], whole["router_bias"], 4, 2.5, GROUPS)
    plain = MOE.ffn(MOE.SILU_GATED, rows, *whole["shared"])
    for e in range(experts):
        share = jnp.where(top_e == e, weight, 0.0).sum(-1)
        plain += share[:, None] * MOE.ffn(
            MOE.SILU_GATED, rows, whole["w1"][e], whole["w3"][e],
            whole["w2"][e])
    np.testing.assert_allclose(want, plain, rtol=2e-5, atol=2e-5)

    total = MOE.ffn(MOE.SILU_GATED, rows, *whole["shared"])
    held = opened = 0.0
    for chip in range(8):
        e = slice(8 * chip, 8 * chip + 8)
        mine = {"router": whole["router"],
                "router_bias": whole["router_bias"],
                **{m: whole[m][e] for m in ("w1", "w3", "w2")}}
        y, c = MOE.moe_layer(mine, rows, (e.start, 8), 4, 2.5, groups=GROUPS)
        c = dict(zip(MOE.MOE_COUNTERS, map(float, c)))
        total = total + y
        held, opened = held + c["moe_rows_held"], (
            opened + c["moe_tokens_group_open"])
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert held == 96 * 4 and opened == 96 * 4


# ---------------------------------------------------------------------------
# the fresh gate
# ---------------------------------------------------------------------------


def test_a_fresh_gate_decays_neither_nothing_nor_everything():
    """At the PUBLISHED widths (hidden 2,560, 16 heads of 128, bound -5,
    chunks of 64) and the program's own initialisers: the median
    channel keeps strictly between 0.01 and 0.99 of its state over a
    chunk, every channel's decay a token lies inside the bound, and no
    chunk's running sum leaves what a sub-block's origin can carry."""
    extra = TL.real_config()["model"]["extra"]
    layer = D.DecoderLayer(D.decoder_from_extra({
        **extra, "layer_types": ["delta_attention"],
        "mlp_layer_types": ["none"], "heads_per_layer": [32],
        "router_score_bias": False}, TL.VOCAB).cfg, 0)
    record = extra["delta_attention"]
    chunk, bound = record["chunk_size"], record["gate_lower_bound"]
    x = jax.random.normal(jax.random.key(1), (1, chunk, 2560))
    p = jax.jit(layer.init)(jax.random.key(2), x)["params"]
    assert p["A_log"].shape == (16,) and p["dt_bias"].shape == (16 * 128,)
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    gamma = bound * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(p["A_log"]), 128)
        * (h @ p["f_proj"]["kernel"] + p["dt_bias"]))
    assert bound < float(gamma.min()) and float(gamma.max()) < 0
    kept = jnp.exp(gamma.sum(1))[0]  # a channel's decay over the chunk
    assert 0.01 < float(jnp.median(kept)) < 0.99
    assert 0.05 < float(jnp.mean((kept > 0.01) & (kept < 0.99)))


def test_the_state_entering_a_chunk_reaches_the_layers_output(monkeypatch):
    """A delta-rule layer at a seed with the entering states zeroed
    gives another output after its first chunk: the state is not
    forgotten within a chunk."""
    layer = _one_layer("k")
    x = _stream()
    params = layer.init(jax.random.key(5), x)["params"]
    want = layer.apply({"params": params}, x)[0]
    monkeypatch.setattr(
        DL, "entering_states",
        lambda decay, kt, w, u: jnp.zeros(
            (*decay.shape, u.shape[-1]), jnp.float32))
    alone = layer.apply({"params": params}, x)[0]
    chunk = TL.DELTA["chunk_size"]
    np.testing.assert_allclose(alone[:, :chunk], want[:, :chunk], atol=1e-6)
    gap = jnp.abs(alone[:, chunk:] - want[:, chunk:])
    assert float(gap.max()) > 1e-3 * float(jnp.abs(want - x).max())


# ---------------------------------------------------------------------------
# what the delta-rule layers count
# ---------------------------------------------------------------------------

MOE_ONLY, SELECTED = MOE.MOE_COUNTERS, MOE.MOE_COUNTERS + A.ATTN_COUNTERS


@pytest.mark.parametrize("config, counters", [
    ("laguna-xs2-share8", MOE_ONLY),
    ("keye-vl2-a3b-share8", SELECTED),
    ("nemotron3-super-share64", MOE_ONLY),
    ("smallthinker-21b-share4", MOE_ONLY),
    ("joyai-llm-flash-share16", MOE_ONLY),
    ("lfm2-8b-a1b-share4", MOE_ONLY),
    ("ling3-flash-share64", MOE_ONLY + DL.DELTA_COUNTERS),
])
def test_only_a_stack_with_delta_layers_counts_chunks(config, counters):
    """The counters a benchmark configuration's stack is built with:
    the tuple of before for the six without ``delta_attention`` layers
    (their round programs sow and sum what they did), the delta pair
    last for the one with."""
    with open(os.path.join(
            ROOT, "benchmarks", "configs", config + ".json")) as f:
        kinds = json.load(f)["model"]["extra"]["layer_types"]
    assert D.counter_names(kinds) == counters
    assert D.attention_counters(kinds) == counters[len(MOE_ONLY):]


def test_log_span_carries_the_delta_counters():
    from fedml_tpu.core.tracing import log_span

    attrs = log_span({"round": 3, "train_loss": 1.0, "delta_chunks": 40960.0,
                      "delta_chunks_fused": 40960.0}).attrs
    assert attrs == {"round": 3, "delta_chunks": 40960,
                     "delta_chunks_fused": 40960}


def test_both_pairs_keep_their_places_in_one_stack():
    kinds = ("sparse_attention", "delta_attention", "full_attention")
    assert D.attention_counters(kinds) == (
        A.ATTN_COUNTERS + DL.DELTA_COUNTERS)
    assert D.attention_counters(kinds[::2]) == A.ATTN_COUNTERS
    assert D.attention_counters(kinds[2:]) == ()


def test_a_delta_layer_counts_its_chunks_and_the_model_sows_them():
    """2 sequences x 2 held heads x 4 chunks of 16 a delta-rule layer
    and none a latent one; off the chip none of them fused. The model
    sows the pair under ``DELTA_COUNTERS``' names, summed over layers."""
    x = _stream()
    extra = TL.tiny_config(pattern=("kN", "lN"))["model"]["extra"]
    cfg = D.decoder_from_extra(extra, TL.VOCAB).cfg
    for index, chunks in ((0, 16.0), (1, 0.0)):
        layer = D.DecoderLayer(cfg, index)
        counted = layer.apply(
            {"params": layer.init(jax.random.key(5), x)["params"]}, x)[1]
        assert counted.shape == (len(MOE_ONLY) + 2,)
        assert [float(c) for c in counted[-2:]] == [chunks, 0.0]
        assert float(jnp.abs(counted[:-2]).max()) == 0.0
    model = create_model(_model_config(TL.tiny_config()))
    assert model.counters == MOE_ONLY + DL.DELTA_COUNTERS
    variables = model.init(jax.random.key(0))
    tokens = jnp.zeros((2, TL.SEQ), jnp.int32)
    _, sown = model.module.apply(
        {"params": variables["params"]}, tokens, train=True,
        mutable=["counters"])
    delta_layers = TL.tiny_config()["model"]["extra"]["layer_types"].count(
        "delta_attention")
    assert float(sown["counters"]["delta_chunks"]) == 16.0 * delta_layers
    assert float(sown["counters"]["delta_chunks_fused"]) == 0.0


def test_a_delta_layer_through_the_kernels_is_the_layer(monkeypatch):
    """A delta-rule layer at heads of 128 and chunks of 64 — a shape
    the kernels take — through the kernels (the Pallas interpreter)
    against the same layer through the plain form: output, gradients,
    and every chunk counted fused."""
    layer = _one_layer("k", delta_attention={
        **TL.DELTA, "head_dim": 128, "chunk_size": 64})
    x = jax.random.normal(jax.random.key(8), (1, 128, HIDDEN))
    params = layer.init(jax.random.key(5), x)["params"]
    run = lambda: jax.value_and_grad(lambda p: jnp.sum(
        layer.apply({"params": p}, x)[0] ** 2))(params)
    counts = lambda: [float(c) for c in layer.apply(
        {"params": params}, x)[1][-2:]]
    plain = run()
    assert counts() == [4.0, 0.0]
    interpreted_kernels(monkeypatch)
    fused = run()
    assert counts() == [4.0, 4.0]
    np.testing.assert_allclose(fused[0], plain[0], rtol=1e-5)
    _close(fused[1], plain[1], 1e-4)


# ---------------------------------------------------------------------------
# what cannot be built is refused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change, message", [
    ({"delta_attention": {k: v for k, v in TL.DELTA.items()
                          if k != "chunk_size"}},
     "delta_attention lacks chunk_size"),
    ({"delta_attention": None},
     "delta_attention lacks head_dim, conv_kernel, gate_lower_bound"),
    ({"delta_attention": {**TL.DELTA, "num_heads": 4}}, "no other key given"),
    ({"delta_attention": {**TL.DELTA, "conv_kernel": 0}}, "at least 1"),
    ({"delta_attention": {**TL.DELTA, "gate_lower_bound": -0.05}},
     "lower bound under -0.1"),
    ({"delta_attention": {**TL.DELTA, "gate_lower_bound": True}},
     "delta_attention lacks gate_lower_bound"),
    ({"key_value_heads_held": [0, 2]},
     "states its share as query_heads_held alone"),
    ({"query_heads_held": [3, 2]}, "does not lie in layer 5's 4 heads"),
    ({"latent_attention": {k: v for k, v in TL.LATENT.items()
                           if k != "q_lora_rank"}},
     "latent_attention lacks q_lora_rank"),
    ({"router_groups": [8, 0]}, "router_groups"),
    ({"router_groups": [5, 2]}, "whole groups"),
    ({"router_groups": [64, 4]}, "at least 2"),
    ({"router_groups": [8, 1], "num_experts_per_tok": 9}, "room in the open"),
    ({"layer_types": ["delta"] * 6}, "known layer_types"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    extra = {**TL.tiny_config()["model"]["extra"], **change}
    with pytest.raises(ValueError, match=message):
        create_model(ModelConfig(
            name="decoder", num_classes=TL.VOCAB, input_shape=(TL.SEQ,),
            extra=tuple(extra.items())))


# ---------------------------------------------------------------------------
# the published share
# ---------------------------------------------------------------------------


def _count(tree):
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))


@pytest.mark.parametrize("held, total", [
    (16, 586_929_872), (8, 500_495_016)], ids=["two-way", "four-way"])
def test_published_share_parameter_count(held, total):
    """The cut Ling-3.0-flash as the configuration's file gives it (16
    of 32 heads) and the fallback ISSUE 49 allowed (8), counted from
    ``eval_shape`` alone, with the issue's table."""
    config = TL.real_config()
    config["model"]["extra"]["query_heads_held"] = [0, held]
    model = create_model(_model_config(config))
    assert model.counters == MOE.MOE_COUNTERS + DL.DELTA_COUNTERS
    shapes = jax.eval_shape(model.init, jax.random.key(0))["params"]
    assert _count(shapes) == total
    if held != 16:
        return
    delta, latent, sparse = (shapes["layer_0"], shapes["layer_5"],
                             shapes["layer_2"])
    mixer = lambda layer, names: sum(_count(layer[k]) for k in names)
    assert [_count(delta[k]) for k in (
        "q_proj", "k_proj", "v_proj", "f_proj", "g_proj", "o_proj")] == [
            5_242_880] * 6
    assert sum(_count(delta[m + "_conv"]) for m in "qkv") == 24_576
    assert [_count(delta[k]) for k in ("b_proj", "dt_bias", "A_log")] == [
        40_960, 2_048, 16]
    assert _count(delta["delta_norm"]) + _count(delta["o_norm"]) == 2_688
    assert mixer(delta, DELTA_LEAVES) == 31_527_568
    assert [mixer(shapes[f"layer_{l}"], DELTA_LEAVES)
            for l in range(5)] == [31_527_568] * 5
    assert mixer(latent, LATENT_LEAVES) == 16_722_944
    assert latent["q_proj"]["kernel"].shape == (2560, 16 * 192)
    assert latent["kv_a_proj"]["kernel"].shape == (2560, 512 + 64)
    assert latent["kv_b_proj"]["kernel"].shape == (512, 16 * (128 + 128))
    assert latent["g_proj"]["kernel"].shape == (2560, 16)
    assert latent["o_proj"]["kernel"].shape == (16 * 128, 2560)
    feed = ("gate_proj", "up_proj", "down_proj", "mlp_norm")
    assert sum(mixer(shapes[f"layer_{l}"], feed) for l in (0, 1)) == (
        94_376_960)
    outside = ("router", "router_bias", "shared_w1", "shared_w3",
               "shared_w2", "mlp_norm")
    experts = ("experts_w1", "experts_w3", "experts_w2")
    assert sum(mixer(shapes[f"layer_{l}"], outside)
               for l in range(2, 6)) == 28_848_128
    assert sum(mixer(shapes[f"layer_{l}"], experts)
               for l in range(2, 6)) == 188_743_680
    assert sparse["experts_w1"].shape == (8, 2560, 768)
    assert sparse["router"].shape == (2560, 512)
    assert sparse["router_bias"].shape == (512,)
    assert _count(shapes["embed"]) + _count(shapes["lm_head"]) == 100_597_760
    assert _count(shapes["final_norm"]) == 2_560


def test_the_configuration_keeps_every_published_width():
    """No width differs from the published config; every count held is
    listed under ``reduced`` with the published one beside it; every
    other number of the catalog's entry stands as it is."""
    config = TL.real_config()
    extra, published = config["model"]["extra"], config["published"]
    record, latent = extra["delta_attention"], extra["latent_attention"]
    for key in D.LATENT_SIZES:
        assert latent[key] == config[key], key
    assert latent["q_lora_rank"] is None
    assert (record["head_dim"], record["conv_kernel"],
            record["gate_lower_bound"]) == (
        config["head_dim"], config["short_conv_kernel_size"],
        config["kda_lower_bound"]) == (128, 4, -5)
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor",
                "rms_norm_eps"):
        assert extra[key] == config[key], key
    assert extra["shared_expert_intermediate_size"] == config[
        "moe_shared_expert_intermediate_size"] * config["num_shared_experts"]
    assert extra["router_groups"] == [config["n_group"],
                                      config["topk_group"]] == [8, 4]
    assert extra["router_scoring"] == config["score_function"] == "sigmoid"
    assert extra["router_score_bias"] is config[
        "moe_router_enable_expert_bias"] is True
    rope = extra["rope"]["latent_attention"]
    assert rope["rope_theta"] == config["rope_theta"] == 6_000_000
    assert config["rope_interleave"] and rope["rope_pairing"] == "adjacent"
    assert extra["gating"] is True and config[
        "gated_attention_proj_granularity_type"] == "head_wise"
    period = config["layer_group_size"]
    assert extra["layer_types"] == [
        "latent_attention" if (l + 1) % period == 0 else "delta_attention"
        for l in range(config["num_hidden_layers"])]
    assert extra["mlp_layer_types"] == ["dense"] * config[
        "first_k_dense_replace"] + ["sparse"] * 4
    assert set(config["reduced"]) == set(published) - {
        "chips_that_share_a_layer", "tensor_parallel_chips",
        "expert_parallel_chips", "data_parallel_pairs"}
    chips, pairs = published["chips_that_share_a_layer"], published[
        "tensor_parallel_chips"]
    assert chips == published["data_parallel_pairs"] * pairs == 64
    assert config["num_experts"] * chips == published["num_experts"] == (
        extra["num_experts"]) == 512
    assert extra["experts_held"] == [0, config["num_experts"]]
    assert config["num_attention_heads"] * pairs == published[
        "num_attention_heads"] == extra["heads_per_layer"][0] == 32
    assert extra["query_heads_held"] == [0, config["num_attention_heads"]]
    assert config["vocab_size"] * 8 == published["vocab_size"] == 157_184
    assert (config["num_hidden_layers"], published["num_hidden_layers"]) == (
        6, 42)
    assert (config["num_nextn_predict_layers"],
            published["num_nextn_predict_layers"]) == (0, 1)
    for key in ("layer_equations", "kda_gate", "no_kda_lora", "linear_silu",
                "use_qk_norm", "group_norm_size", "gate_granularity",
                "rotary", "router", "gate_law", "prediction_module",
                "swiglu_limits"):
        assert config["assumed"][key], key


def test_a_round_trains_every_leaf_but_the_biases():
    """``FedAvgSim``, bulk engine at a block of one, over the tiny stack
    through ``run``'s own loop: every parameter moves but the
    score-correction biases, which no gradient reaches, and the round
    record carries the expert counters, the open groups' among them."""
    sim = _sim(TL.tiny_config(), 1, seq=TL.SEQ, vocab=TL.VOCAB)

    class Sink:
        records = []

        def log(self, record):
            self.records.append(dict(record))

    before = jax.device_get(sim.init().variables)
    after = jax.device_get(sim.run(metrics_sink=Sink()).variables)
    biases = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(after)):
        name = jax.tree_util.keystr(path)
        assert np.array_equal(a, b) == ("router_bias" in name), name
        biases += "router_bias" in name
    assert biases == 4
    steps = 2 * 2
    for record in Sink.records:
        assert set(MOE.MOE_COUNTERS) <= set(record)
        tokens = steps * 2 * TL.SEQ * 4  # steps x batch x seq x layers
        assert record["moe_rows_routed"] == tokens * 4
        assert 0 < record["moe_rows_held"] < record["moe_rows_routed"]
        assert 0 < record["moe_tokens_group_open"] < tokens
    assert "test_acc" in Sink.records[-1]
