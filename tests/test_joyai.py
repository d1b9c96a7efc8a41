"""The decoder stack with LATENT attention — queries and keys-values
through low-rank projections, a rotary part held apart and turned in
adjacent pairs, keys wider than values — and a score-correction bias in
the router's choice, against the plain reference of
``benchmarks/configs/joyai-llm-flash-share16`` at tiny widths with
every ratio kept (the rotary part a third of the key, values smaller
than keys); the blockwise kernel at two head sizes in the Pallas
interpreter; the pairing; the bias; the 16 expert-parallel shares tied
to the uncut layer; what ``decoder_from_extra`` refuses; the published
share's size."""

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

import tiny_joyai as TJ  # noqa: E402
from test_decoder import (  # noqa: E402
    _assert_trees_close as _close, _loss, _model_config, _sim,
)
from test_smallthinker import _layer_params  # noqa: E402

from fedml_tpu.config import ModelConfig  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import decoder as D  # noqa: E402
from fedml_tpu.ops import attention as A  # noqa: E402
from fedml_tpu.ops import moe as MOE  # noqa: E402

HIDDEN = 64


def _assert_trees_close(got, want, rtol, but=()):
    """``test_decoder``'s comparison, and no leaf of ``want`` all zero
    but those whose path holds one of ``but``."""
    for path, r in jax.tree_util.tree_leaves_with_path(want):
        name = jax.tree_util.keystr(path)
        zero = float(jnp.max(jnp.abs(r))) == 0.0
        assert zero == any(b in name for b in but), name
    _close(got, want, rtol)


@pytest.mark.parametrize("pattern", ["D", "S", TJ.PATTERN])
def test_program_against_reference_logits_and_gradients(pattern, tmp_path):
    """float32: a latent-attention layer under the dense feed-forward,
    under a sparse one with the bias, and the five-layer stack: the
    variable trees agree leaf for leaf, and so do the logits and every
    parameter's gradient — the bias's is zero on both sides."""
    config = TJ.tiny_config(pattern=pattern)
    ref = TJ.load_reference(str(tmp_path), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, TJ.SEQ + 1), 0, TJ.VOCAB)
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

    (_, (ours, counted)), g_ours = jax.value_and_grad(
        program, has_aux=True)(variables["params"])
    (_, theirs), g_ref = jax.value_and_grad(
        reference, has_aux=True)(variables["params"])
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)
    _assert_trees_close(g_ours, g_ref, 2e-3, but=("router_bias",))
    sparse = pattern.count("S")
    if sparse:
        assert set(counted) == set(MOE.MOE_COUNTERS)
        assert float(counted["moe_rows_routed"]) == x.size * 4 * sparse
        assert 0 < float(counted["moe_rows_held"]) < x.size * 4 * sparse


# ---------------------------------------------------------------------------
# a latent-attention layer alone
# ---------------------------------------------------------------------------


def _one_layer(**change):
    """A one-layer stack at the tiny sizes -> (configuration, layer)."""
    extra = {**TJ.tiny_config(pattern="S")["model"]["extra"], **change}
    cfg = D.decoder_from_extra(extra, TJ.VOCAB).cfg
    return cfg, D.DecoderLayer(cfg, 0)


def _stream():
    return jax.random.normal(jax.random.key(8), (2, TJ.SEQ, HIDDEN))


def test_a_latent_attention_layer_alone_against_the_reference(tmp_path):
    """The mixer with no feed-forward, ``x + attention(norm(x))``,
    against the reference's own ``_attention``: values, the stream's
    gradient and every parameter's; the scope of the latent projections
    is in the program beside the kernel's."""
    ref = TJ.load_reference(str(tmp_path), TJ.tiny_config(pattern="S"))
    _, layer = _one_layer(mlp_layer_types=["none"], router_score_bias=False)
    x = _stream()
    params = _layer_params(layer, x)
    assert set(params) == {
        "attn_norm", "q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
        "kv_a_norm", "kv_b_proj", "o_proj"}
    weigh = jax.random.normal(jax.random.key(9), x.shape)
    both = lambda fn: jax.value_and_grad(
        lambda p, x: jnp.sum(fn(p, x) * weigh), argnums=(0, 1))
    got, g_got = both(lambda p, x: layer.apply({"params": p}, x)[0])(
        params, x)
    want, g_want = both(lambda p, x: ref._attention(x, p, None))(params, x)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    _assert_trees_close(g_got, g_want, 2e-4)
    text = jax.jit(layer.apply).lower({"params": params}, x).as_text(
        debug_info=True)
    for scope in ("fedml.model.attn.latent", "fedml.model.attn.kernel"):
        assert scope in text


# ---------------------------------------------------------------------------
# the blockwise kernel at two head sizes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 96])
def test_kernel_with_values_smaller_than_keys(window, monkeypatch):
    """Keys of 24 beside values of 16, 4 key-value heads of group 1:
    the masked product and the splash kernel (Pallas interpreter)
    against scores written out a head at a time, forward and backward;
    the scale is the KEYS' size and the output the values'."""
    monkeypatch.setattr(A, "BLOCK", 128)
    t, heads, dk, dv = 256, 4, 24, 16
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (2, t, heads, dk))
    k = jax.random.normal(ks[1], (2, t, heads, dk))
    v = jax.random.normal(ks[2], (2, t, heads, dv))
    g = jax.random.normal(ks[3], (2, t, heads, dv))

    def written_out(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / dk ** 0.5
        s = jnp.where(A.attention_mask(t, window), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    want, vjp = jax.vjp(written_out, q, k, v)
    assert want.shape == (2, t, heads, dv)
    kernel = lambda q, k, v: A.splash_attention(
        q, k, v, window=window, interpret=True)
    masked = lambda q, k, v: A.masked_attention(q, k, v, window=window)
    for fn in (masked, kernel):
        got, vjp_got = jax.vjp(fn, q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        for a, b in zip(vjp_got(g), vjp(g)):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    np.testing.assert_array_equal(
        A.causal_attention(q, k, v, window=window), masked(q, k, v))


# ---------------------------------------------------------------------------
# rotary pairing
# ---------------------------------------------------------------------------


def test_adjacent_pairing_turns_2i_with_2i_plus_1_and_half_is_unchanged():
    t, rot, rest = 12, 8, 5
    x = jax.random.normal(jax.random.key(2), (2, t, 3, rot + rest))
    rope = {"rope_theta": 100.0}
    angles = np.arange(t)[:, None] * 100.0 ** (
        -np.arange(0, rot, 2) / rot)[None, :]
    cos, sin = np.cos(angles)[None, :, None], np.sin(angles)[None, :, None]
    xs = np.asarray(x, np.float64)

    adjacent = {**rope, "rope_pairing": "adjacent"}
    got = D.apply_rope(x, *D.rope_tables(adjacent, rot, t), "adjacent")
    even, odd = xs[..., 0:rot:2], xs[..., 1:rot:2]
    want = np.empty((2, t, 3, rot))
    want[..., 0::2] = even * cos - odd * sin
    want[..., 1::2] = odd * cos + even * sin
    np.testing.assert_allclose(got[..., :rot], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])

    # ``half``, named or by default: dimension i with i + rot / 2
    for record in (rope, {**rope, "rope_pairing": "half"}):
        assert D.rope_pairing(record) == "half"
        got = D.apply_rope(x, *D.rope_tables(record, rot, t))
        a, b = xs[..., :rot // 2], xs[..., rot // 2:rot]
        want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
        np.testing.assert_allclose(got[..., :rot], want, rtol=1e-5,
                                   atol=1e-6)
    # the two are one rotation under a permutation of the dimensions,
    # the same for queries and keys: scores do not tell them apart
    order = np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2)])
    turned = D.apply_rope(x[..., :rot], *D.rope_tables(adjacent, rot, t),
                          "adjacent")
    np.testing.assert_allclose(
        turned[..., order],
        D.apply_rope(x[..., order], *D.rope_tables(rope, rot, t)),
        rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the score-correction bias
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_bias_moves_the_choice_and_not_the_weights(scoring):
    """Some tokens' chosen sets differ from the unbiased ones; the
    weights are the chosen experts' UNBIASED probabilities renormalised
    and scaled; the logits' gradient is the written-out one and the
    bias's exactly zero; without a bias nothing changed."""
    n, e, k, scale = 64, 32, 4, 2.5
    logits = jax.random.normal(jax.random.key(5), (n, e))
    bias = D.ROUTER_BIAS_STD * jax.random.normal(jax.random.key(6), (e,))
    weigh = jax.random.normal(jax.random.key(7), (n, k))
    prob = MOE.SCORINGS[scoring](logits)

    top_e, top_w = MOE.route_top_k(logits, k, scale, scoring, bias)
    plain_e, plain_w = MOE.route_top_k(logits, k, scale, scoring)
    want_e = jax.lax.top_k(prob + bias, k)[1]
    np.testing.assert_array_equal(top_e, want_e)
    moved = np.any(np.sort(top_e, -1) != np.sort(plain_e, -1), -1)
    assert 0 < moved.sum() < n  # some tokens, not all
    chosen = jnp.take_along_axis(prob, top_e, -1)
    np.testing.assert_allclose(
        top_w, scale * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_array_equal(
        plain_e, jax.lax.top_k(prob, k)[1])

    def ours(logits, bias):
        return jnp.sum(MOE.route_top_k(logits, k, scale, scoring, bias)[1]
                       * weigh)

    def written_out(logits, bias):
        p = MOE.SCORINGS[scoring](logits)
        top = jnp.take_along_axis(p, jax.lax.top_k(p + bias, k)[1], -1)
        return jnp.sum(scale * top / top.sum(-1, keepdims=True) * weigh)

    g_logits, g_bias = jax.grad(ours, argnums=(0, 1))(logits, bias)
    w_logits, w_bias = jax.grad(written_out, argnums=(0, 1))(logits, bias)
    np.testing.assert_allclose(g_logits, w_logits, rtol=1e-5, atol=1e-7)
    assert float(jnp.max(jnp.abs(g_bias))) == 0.0 == float(
        jnp.max(jnp.abs(w_bias)))
    assert float(jnp.max(jnp.abs(g_logits))) > 0


def test_a_layer_reads_its_bias_in_float32_and_only_where_asked():
    x = _stream()
    _, with_bias = _one_layer()
    _, without = _one_layer(router_score_bias=False)
    params = with_bias.init(jax.random.key(5), x)["params"]
    assert params["router_bias"].shape == (32,)
    assert params["router_bias"].dtype == jnp.float32
    assert 0 < float(jnp.std(params["router_bias"])) < 3 * D.ROUTER_BIAS_STD
    assert "router_bias" not in without.init(jax.random.key(5), x)["params"]
    # the bias is read: another one gives another output ...
    y, _ = with_bias.apply({"params": params}, x)
    other = {**params, "router_bias": -params["router_bias"]}
    assert float(jnp.max(jnp.abs(
        with_bias.apply({"params": other}, x)[0] - y))) > 1e-4
    # ... and a zero one the layer without
    zero = {**params, "router_bias": jnp.zeros((32,))}
    rest = {k: v for k, v in params.items() if k != "router_bias"}
    np.testing.assert_array_equal(
        with_bias.apply({"params": zero}, x)[0],
        without.apply({"params": rest}, x)[0])
    # under a bfloat16 step the choice still reads a float32 bias
    half = jax.tree.map(lambda p: p.astype(jnp.bfloat16), rest)
    _, counters = with_bias.apply(
        {"params": {**half, "router_bias": params["router_bias"]}},
        x.astype(jnp.bfloat16))
    assert float(counters[1]) == x.shape[0] * TJ.SEQ * 4


# ---------------------------------------------------------------------------
# the 16-chip share tied to the uncut layer
# ---------------------------------------------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """256 experts, 8 a token, as 16 expert-parallel chips hold them
    (16 each) beside a shared expert and a router, bias included, that
    stand whole on every chip: the chips' routed parts and the shared
    expert counted ONCE add up to the uncut layer's feed-forward, and
    their held rows are every assignment made."""
    d, f = HIDDEN, 16
    ks = iter(jax.random.split(jax.random.key(21), 9))
    n = lambda *s: jax.random.normal(next(ks), s) * s[-2] ** -0.5
    whole = {"router": n(d, 256), "w1": n(256, d, f), "w3": n(256, d, f),
             "w2": n(256, f, d), "shared": (n(d, f), n(d, f), n(f, d)),
             "router_bias": D.ROUTER_BIAS_STD * jax.random.normal(
                 next(ks), (256,))}
    rows = jax.random.normal(next(ks), (96, d))
    want, counters = MOE.moe_layer(whole, rows, (0, 256), 8, 2.5)
    assert float(counters[0]) == float(counters[1]) == 96 * 8
    # the uncut layer, written out
    prob = jax.nn.sigmoid(rows @ whole["router"])
    top_e = jax.lax.top_k(prob + whole["router_bias"], 8)[1]
    top_p = jnp.take_along_axis(prob, top_e, -1)
    weight = 2.5 * top_p / top_p.sum(-1, keepdims=True)
    plain = MOE.ffn(MOE.SILU_GATED, rows, *whole["shared"])
    for e in range(256):
        share = jnp.where(top_e == e, weight, 0.0).sum(-1)
        plain += share[:, None] * MOE.ffn(
            MOE.SILU_GATED, rows, whole["w1"][e], whole["w3"][e],
            whole["w2"][e])
    np.testing.assert_allclose(want, plain, rtol=2e-5, atol=2e-5)

    total, held = MOE.ffn(MOE.SILU_GATED, rows, *whole["shared"]), 0.0
    for chip in range(16):
        e = slice(16 * chip, 16 * chip + 16)
        mine = {"router": whole["router"],
                "router_bias": whole["router_bias"],
                **{m: whole[m][e] for m in ("w1", "w3", "w2")}}
        y, counted = MOE.moe_layer(mine, rows, (e.start, 16), 8, 2.5)
        total, held = total + y, held + float(counted[0])
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert held == 96 * 8


# ---------------------------------------------------------------------------
# what cannot be built is refused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change, message", [
    ({"latent_attention": {k: v for k, v in TJ.LATENT.items()
                           if k != "kv_lora_rank"}},
     "latent_attention lacks kv_lora_rank"),
    ({"latent_attention": None},
     "latent_attention lacks q_lora_rank, kv_lora_rank"),
    ({"latent_attention": {**TJ.LATENT, "qk_rope_head_dim": 7}},
     "the rotary part even"),
    ({"latent_attention": {**TJ.LATENT, "head_dim": 24}},
     "no other key given"),
    ({"rope": {"latent_attention": {
        "rope_theta": 1e4, "rope_pairing": "interleaved"}}},
     "unknown rope_pairing 'interleaved'"),
    ({"mlp_layer_types": ["dense"] * 5}, "router_score_bias needs a sparse"),
    ({"query_heads_held": [0, 2], "key_value_heads_held": [0, 2]},
     "states its share as query_heads_held alone"),
    ({"query_heads_held": [2, 3]}, "does not lie in layer 0's 4 heads"),
    ({"qk_norm": True}, "norms its latents alone"),
    ({"router_groups": [3, 2]}, "router_groups"),
    ({"router_groups": [8, 9]}, "router_groups"),
    ({"layer_types": ["latent"] * 5}, "known layer_types"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    extra = {**TJ.tiny_config()["model"]["extra"], **change}
    with pytest.raises(ValueError, match=message):
        create_model(ModelConfig(
            name="decoder", num_classes=TJ.VOCAB, input_shape=(TJ.SEQ,),
            extra=tuple(extra.items())))


# ---------------------------------------------------------------------------
# the published share
# ---------------------------------------------------------------------------


def test_published_share_has_564_954_112_parameters():
    """The cut JoyAI-LLM-Flash as the configuration's file gives it,
    counted from ``eval_shape`` alone, with the table of ISSUE 42; no
    width differs from the published config, and every count held is
    listed with the published one beside it."""
    config = TJ.real_config()
    extra = config["model"]["extra"]
    model = create_model(_model_config(config))
    assert model.counters == MOE.MOE_COUNTERS
    shapes = jax.eval_shape(model.init, jax.random.key(0))["params"]
    count = lambda tree: sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    assert count(shapes) == 564_954_112
    dense, sparse = shapes["layer_0"], shapes["layer_1"]
    attention = ("q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
                 "kv_a_norm", "kv_b_proj", "o_proj")
    assert [count(sparse[k]) for k in attention] == [
        3_145_728, 1_536, 9_437_184, 1_179_648, 512, 4_194_304, 8_388_608]
    assert sum(count(dense[k]) for k in attention) == 26_347_520
    assert count(dense) == 70_391_808
    assert sum(count(dense[k]) for k in (
        "gate_proj", "up_proj", "down_proj")) == 44_040_192
    assert [count(shapes[f"layer_{l}"]) for l in range(1, 5)] == [
        107_092_224] * 4
    assert count(sparse["router"]) == 524_288
    assert sparse["router_bias"].shape == (256,)
    assert sparse["router_bias"].dtype == jnp.float32
    assert "router_bias" not in dense
    assert sum(count(sparse["shared_" + m])
               for m in ("w1", "w3", "w2")) == 4_718_592
    assert sum(count(sparse["experts_" + m])
               for m in ("w1", "w3", "w2")) == 75_497_472
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 66_191_360
    assert sparse["experts_w1"].shape == (16, 2048, 768)
    assert sparse["q_b_proj"]["kernel"].shape == (1536, 32 * 192)
    assert sparse["kv_a_proj"]["kernel"].shape == (2048, 512 + 64)
    assert sparse["kv_b_proj"]["kernel"].shape == (512, 32 * (128 + 128))
    assert sparse["o_proj"]["kernel"].shape == (32 * 128, 2048)
    assert dense["gate_proj"]["kernel"].shape == (2048, 7168)
    assert shapes["lm_head"]["kernel"].shape == (2048, 16160)
    # no width differs from the published config
    latent = extra["latent_attention"]
    for key in D.LATENT_SIZES:
        assert latent[key] == config[key], key
    assert latent["qk_nope_head_dim"] + latent["qk_rope_head_dim"] == (
        config["qk_head_dim"]) == 192
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor",
                "rms_norm_eps"):
        assert extra[key] == config[key], key
    assert extra["shared_expert_intermediate_size"] == (
        config["n_shared_experts"] * config["moe_intermediate_size"])
    assert extra["heads_per_layer"] == [config["num_attention_heads"]] * 5
    assert extra["router_scoring"] == config["scoring_func"] == "sigmoid"
    assert extra["router_score_bias"] is True
    assert config["topk_method"] == "noaux_tc"
    rope = extra["rope"]["latent_attention"]
    assert rope["rope_theta"] == config["rope_theta"] == 32_000_000
    assert config["rope_interleave"] is True
    assert rope["rope_pairing"] == "adjacent"
    assert extra["mlp_layer_types"] == ["dense"] * config[
        "first_k_dense_replace"] + ["sparse"] * 4
    # every count held is listed, with the published one beside it
    published = config["published"]
    assert set(config["reduced"]) == set(published) - {
        "chips_that_share_a_layer", "tensor_parallel_chips",
        "expert_parallel_chips"}
    chips = published["expert_parallel_chips"]
    assert chips == published["chips_that_share_a_layer"] == 16
    assert config["n_routed_experts"] * chips == published[
        "n_routed_experts"] == extra["num_experts"]
    assert extra["experts_held"] == [0, config["n_routed_experts"]]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert (config["num_hidden_layers"], published["num_hidden_layers"]) == (
        5, 40)
    assert (config["num_nextn_predict_layers"],
            published["num_nextn_predict_layers"]) == (0, 1)


def test_a_round_trains_every_leaf_but_the_biases():
    """``FedAvgSim``, bulk engine at a block of one, over the tiny stack
    through ``run``'s own loop: every parameter moves but the
    score-correction biases, which no gradient reaches, and the round
    record carries the five expert counters."""
    sim = _sim(TJ.tiny_config(), 1, seq=TJ.SEQ, vocab=TJ.VOCAB)

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
        assert record["moe_rows_routed"] == steps * 2 * TJ.SEQ * 4 * 4
        assert 0 < record["moe_rows_held"] < record["moe_rows_routed"]
        assert record["moe_rows_max_expert"] <= record["moe_rows_held"]
        # 4 ways over 8 held: the combine reads a row a way
        assert record["moe_rows_combined"] == record["moe_rows_routed"]
        assert 0 < record["moe_rows_compact"] <= record["moe_rows_routed"]
    assert "test_acc" in Sink.records[-1]
