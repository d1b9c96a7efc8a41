"""The decoder stack with GATED SHORT-CONVOLUTION layers beside
grouped-query attention of small heads, ONE table for embedding and
head, and the family's 1e-6 in the routing weights' denominator,
against the plain reference of ``benchmarks/configs/lfm2-8b-a1b-share4``
at tiny widths with every ratio kept; the taps against a direct loop
over ``t``; the tied table's gradient as the sum of its two roads; the
four expert-parallel shares tied to the uncut layer; the blockwise
kernel at heads of 64 in the Pallas interpreter; a state-space layer's
convolution lowering as it did; what ``decoder_from_extra`` and
``peft`` refuse; the published share's size."""

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

import tiny_lfm2 as TL  # noqa: E402
from lib import fedref  # noqa: E402
from test_decoder import (  # noqa: E402
    _assert_trees_close as _close, _loss, _model_config, _sim,
)
from test_joyai import _assert_trees_close  # noqa: E402
from test_smallthinker import _layer_params  # noqa: E402

from fedml_tpu.config import ExperimentConfig, FedConfig  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import decoder as D  # noqa: E402
from fedml_tpu.ops import attention as A  # noqa: E402
from fedml_tpu.ops import moe as MOE  # noqa: E402

HIDDEN = 64
EPSILON = 1e-6


def _tokens():
    tokens = jax.random.randint(
        jax.random.key(4), (2, TL.SEQ + 1), 0, TL.VOCAB)
    return tokens[:, :-1], tokens[:, 1:]


def _both(model, ref, x, y):
    """-> (program, reference): ``params -> (loss, logits)``."""
    def program(params):
        logits, _, counted = model.apply_train_counted(
            {"params": params}, x, jax.random.key(0))
        return _loss(logits.astype(jnp.float32), y), (logits, counted)

    def reference(params, quant=None):
        logits, _ = ref.forward({"params": params}, x, True, quant)
        return _loss(logits, y), logits

    return program, reference


@pytest.mark.parametrize("pattern", [("cD",), ("aS",), TL.PATTERN],
                         ids=["conv", "attention", "stack"])
def test_program_against_reference_logits_loss_and_gradients(
        pattern, tmp_path):
    """float32: a convolution layer under a dense feed-forward, an
    attention layer with heads of 16 under a sparse one, and the stack
    conv+dense, conv+dense, attention+sparse, conv+sparse: the variable
    trees agree leaf for leaf (no ``lm_head``), and so do the logits,
    the loss and every parameter's gradient — the expert bias's is zero
    on both sides. Tolerances: float32 sums in another order (the
    kernel's masked product against blocks of queries, a sorted row
    buffer against a dense loop): 2e-4 of a logit, 2e-3 of a leaf's
    largest gradient."""
    config = TL.tiny_config(pattern=pattern)
    ref = TL.load_reference(str(tmp_path), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))
    x, y = _tokens()
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert shapes(model.init(jax.random.key(0))) == shapes(variables)
    assert "lm_head" not in variables["params"]
    program, reference = _both(model, ref, x, y)
    (loss, (ours, counted)), g_ours = jax.value_and_grad(
        program, has_aux=True)(variables["params"])
    (loss_ref, theirs), g_ref = jax.value_and_grad(
        reference, has_aux=True)(variables["params"])
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-5)
    _assert_trees_close(g_ours, g_ref, 2e-3, but=("router_bias",))
    sparse = sum(layer[1] == "S" for layer in pattern)
    if sparse:
        assert set(counted) == set(MOE.MOE_COUNTERS)
        assert float(counted["moe_rows_routed"]) == x.size * 4 * sparse
        assert 0 < float(counted["moe_rows_held"]) < x.size * 4 * sparse
    else:
        assert counted == {}  # the mixer counts nothing


def test_bfloat16_program_lies_between_float32_and_the_float8_control(
        tmp_path):
    """The stack in bfloat16 against the float32 reference, by the
    harness's own number (``fedref.rel_err`` of the tied table's
    gradient): the bfloat16 program reads 0.02-0.03 here and the
    reference with every product's inputs in float8 0.13 or more, so
    the tolerance 0.06 passes the one and would fail the other."""
    config = TL.tiny_config()
    ref = TL.load_reference(str(tmp_path), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))["params"]
    x, y = _tokens()
    program, reference = _both(model, ref, x, y)
    grad = lambda fn, p: jax.grad(lambda p: fn(p)[0])(p)
    want = grad(reference, variables)["embed"]
    half = jax.tree.map(lambda p: p.astype(jnp.bfloat16), variables)
    sound = fedref.rel_err(grad(program, half)["embed"], want)
    control = fedref.rel_err(grad(
        lambda p: reference(p, fedref.FP8), variables)["embed"], want)
    assert 0 < sound < 0.06 < control, (sound, control)
    np.testing.assert_allclose(
        program(half)[0], reference(variables)[0], rtol=5e-3)


# ---------------------------------------------------------------------------
# a layer alone
# ---------------------------------------------------------------------------


def _one_layer(kind: str, **change):
    """A one-layer stack at the tiny sizes with no feed-forward ->
    (configuration, layer)."""
    extra = {**TL.tiny_config(pattern=(kind + "N",))["model"]["extra"],
             **change}
    cfg = D.decoder_from_extra(extra, TL.VOCAB).cfg
    return cfg, D.DecoderLayer(cfg, 0)


def _stream():
    return jax.random.normal(jax.random.key(8), (2, TL.SEQ, HIDDEN))


def _against(layer, params, x, written_out, rtol=2e-5):
    weigh = jax.random.normal(jax.random.key(9), x.shape)
    both = lambda fn: jax.value_and_grad(
        lambda p, x: jnp.sum(fn(p, x) * weigh), argnums=(0, 1))
    got, g_got = both(lambda p, x: layer.apply({"params": p}, x)[0])(
        params, x)
    want, g_want = both(written_out)(params, x)
    np.testing.assert_allclose(got, want, rtol=rtol)
    _assert_trees_close(g_got, g_want, 2e-4)


@pytest.mark.parametrize("bias", [False, True])
def test_a_convolution_layer_alone_against_the_reference(bias, tmp_path):
    """The mixer with no feed-forward, ``x + out(C * taps(B * u))``,
    against the reference's own ``_convolution`` (three shifted
    products): values, the stream's gradient and every parameter's,
    with the bias a ``short_conv`` record may ask for and, as
    published, without; both scopes are in the program."""
    record = {"kernel": 3, "bias": bias}
    ref = TL.load_reference(str(tmp_path), TL.tiny_config(short_conv=record))
    _, layer = _one_layer("c", short_conv=record)
    x = _stream()
    params = _layer_params(layer, x)
    assert set(params) == {"conv_norm", "in_proj", "conv_kernel",
                           "out_proj"} | ({"conv_bias"} if bias else set())
    assert params["in_proj"]["kernel"].shape == (HIDDEN, 3 * HIDDEN)
    assert params["conv_kernel"].shape == (3, HIDDEN)
    _against(layer, params, x, lambda p, x: ref._convolution(x, p, None))
    text = jax.jit(layer.apply).lower({"params": params}, x).as_text(
        debug_info=True)
    for scope in ("fedml.model.conv", "fedml.model.conv.mix"):
        assert scope in text


def test_an_attention_layer_with_heads_of_16_alone_against_the_reference(
        tmp_path):
    """8 query heads over 2 key-value heads of 16, each q and k head
    RMS-normed and THEN turned by halves, against the reference's own
    ``_attention``."""
    ref = TL.load_reference(str(tmp_path))
    _, layer = _one_layer("a")
    x = _stream()
    params = _layer_params(layer, x)
    assert set(params) == {"attn_norm", "q_proj", "k_proj", "v_proj",
                           "q_norm", "k_norm", "o_proj"}
    assert params["q_proj"]["kernel"].shape == (HIDDEN, 8 * 16)
    assert params["k_norm"]["scale"].shape == (16,)
    _against(layer, params, x, lambda p, x: ref._attention(x, p, None))


# ---------------------------------------------------------------------------
# the taps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("biased", [False, True], ids=["plain", "bias"])
@pytest.mark.parametrize("taps", [3, 4])
def test_convolution_against_a_direct_loop_over_t(taps, biased):
    """``causal_depthwise_conv`` against a loop over ``t`` in float64:
    zeros before the sequence (output 0 is the LAST tap times input 0),
    and output ``t`` does not move, to the bit, when inputs after ``t``
    do."""
    b, t, c = 2, 12, 5
    ks = jax.random.split(jax.random.key(31), 4)
    x = jax.random.normal(ks[0], (b, t, c))
    kernel = jax.random.normal(ks[1], (taps, c))
    bias = jax.random.normal(ks[2], (c,)) if biased else None
    got = D.causal_depthwise_conv(x, kernel, bias)
    xs, kk = np.asarray(x, np.float64), np.asarray(kernel, np.float64)
    want = np.zeros((b, t, c))
    for at in range(t):
        for i in range(taps):
            src = at - (taps - 1) + i
            if src >= 0:
                want[:, at] += kk[i] * xs[:, src]
    if biased:
        want += np.asarray(bias, np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        got[:, 0] - (bias if biased else 0.0), kernel[-1] * x[:, 0],
        rtol=1e-5, atol=1e-6)
    cut = 7
    later = x.at[:, cut + 1:].set(jax.random.normal(ks[3], x[:, cut + 1:].shape))
    moved = D.causal_depthwise_conv(later, kernel, bias)
    np.testing.assert_array_equal(moved[:, :cut + 1], got[:, :cut + 1])
    assert float(jnp.max(jnp.abs(moved[:, cut + 1:] - got[:, cut + 1:]))) > 0


def _parents_conv(x, kernel, bias):
    """``causal_depthwise_conv`` as it stood before the bias was
    optional (commit 26aa85c), word for word."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(kernel[i] * padded[:, i:i + t] for i in range(k))


def test_a_state_space_layers_convolution_lowers_as_it_did():
    """With a bias (a state-space layer always gives one) the function
    is the program it was: the same StableHLO, values and gradients to
    the bit."""
    ks = jax.random.split(jax.random.key(33), 3)
    x = jax.random.normal(ks[0], (2, 16, 6))
    kernel, bias = jax.random.normal(ks[1], (4, 6)), jax.random.normal(
        ks[2], (6,))
    loss = lambda fn: jax.jit(jax.value_and_grad(
        lambda x, k, b: jnp.sum(jax.nn.silu(fn(x, k, b)) ** 2),
        argnums=(0, 1, 2)))
    now, then = loss(D.causal_depthwise_conv), loss(_parents_conv)
    assert now.lower(x, kernel, bias).as_text() == then.lower(
        x, kernel, bias).as_text()
    for a, b in zip(jax.tree.leaves(now(x, kernel, bias)),
                    jax.tree.leaves(then(x, kernel, bias))):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# one table for embedding and head
# ---------------------------------------------------------------------------


def test_tied_table_gradient_is_the_sum_of_its_two_roads(tmp_path):
    """The tied stack has no ``lm_head`` leaf, and its table's gradient
    is the lookup's (the untied stack's ``embed`` gradient, by
    ``ops/embedding.py``'s rule) plus the head product's (the untied
    stack's ``lm_head`` gradient, transposed), the untied stack's head
    being the same table; every other leaf's gradient is the untied
    stack's own."""
    config = TL.tiny_config()
    tied = create_model(_model_config(config))
    untied = create_model(_model_config(
        TL.tiny_config(tie_word_embeddings=False)))
    params = jax.jit(TL.load_reference(str(tmp_path), config).init)(
        jax.random.key(3))["params"]
    assert "lm_head" not in tied.init(jax.random.key(0))["params"]
    assert "lm_head" in untied.init(jax.random.key(0))["params"]
    x, y = _tokens()

    def loss(model):
        def fn(params):
            logits, _, _ = model.apply_train_counted(
                {"params": params}, x, jax.random.key(0))
            return _loss(logits, y)
        return jax.value_and_grad(fn)

    table = params["embed"]["embedding"]
    apart = {**params, "lm_head": {"kernel": table.T}}
    value, g = loss(tied)(params)
    value_apart, g_apart = loss(untied)(apart)
    np.testing.assert_allclose(value, value_apart, rtol=1e-6)
    lookup = g_apart["embed"]["embedding"]
    head = g_apart.pop("lm_head")["kernel"].T
    assert float(jnp.max(jnp.abs(lookup))) > 0 < float(jnp.max(jnp.abs(head)))
    np.testing.assert_allclose(
        g["embed"]["embedding"], lookup + head, rtol=1e-5, atol=1e-7)
    rest = lambda tree: {k: v for k, v in tree.items() if k != "embed"}
    _close(rest(g), rest(g_apart), 1e-5)


# ---------------------------------------------------------------------------
# the weights' denominator
# ---------------------------------------------------------------------------


def test_the_renormalisation_adds_its_epsilon_forward_and_in_the_rule():
    """Small probabilities (logits about -6: a token's four sum to
    about 0.02), so that the family's 1e-6 is 5e-5 of the sum: the
    weights are ``scale s_e / (sum s + 1e-6)`` in float64 arithmetic on
    the host, they sum to ``scale / (1 + 1e-6 / sum s)``, the rule's
    cotangent is the written-out function's, and without an epsilon
    nothing changed to the bit."""
    n, e, k, scale = 64, 32, 4, 1.0
    logits = jax.random.normal(jax.random.key(5), (n, e)) - 6.0
    bias = D.ROUTER_BIAS_STD * jax.random.normal(jax.random.key(6), (e,))
    weigh = jax.random.normal(jax.random.key(7), (n, k))
    top_e, top_w = MOE.route_top_k(
        logits, k, scale, "sigmoid", bias, MOE.ROUTE, EPSILON)
    plain_e, plain_w = MOE.route_top_k(logits, k, scale, "sigmoid", bias)
    np.testing.assert_array_equal(top_e, plain_e)
    prob = np.asarray(jnp.take_along_axis(
        jax.nn.sigmoid(logits), top_e, -1), np.float64)
    total = prob.sum(-1, keepdims=True)
    np.testing.assert_allclose(top_w, scale * prob / (total + EPSILON),
                               rtol=5e-7)
    np.testing.assert_allclose(
        np.asarray(top_w, np.float64).sum(-1),
        scale / (1 + EPSILON / total[:, 0]), rtol=1e-6)
    # the epsilon is there: the bare renormalisation lies 1e-5 to 1e-4 off
    off = np.abs(np.asarray(plain_w, np.float64).sum(-1)
                 - np.asarray(top_w, np.float64).sum(-1))
    assert 5e-6 < off.min() and off.max() < 1e-3
    np.testing.assert_array_equal(
        plain_w, MOE._weights(jnp.asarray(prob, jnp.float32), scale))

    def ours(logits):
        return jnp.sum(MOE.route_top_k(
            logits, k, scale, "sigmoid", bias, MOE.ROUTE, EPSILON)[1] * weigh)

    def written_out(logits):
        p = jax.nn.sigmoid(logits)
        top = jnp.take_along_axis(p, jax.lax.top_k(p + bias, k)[1], -1)
        return jnp.sum(
            scale * top / (top.sum(-1, keepdims=True) + EPSILON) * weigh)

    np.testing.assert_allclose(
        jax.grad(ours)(logits), jax.grad(written_out)(logits),
        rtol=1e-5, atol=1e-8)


# ---------------------------------------------------------------------------
# the four-chip host's share tied to the uncut layer
# ---------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """32 experts, 4 a token, as the four expert-parallel chips of a
    host hold them (8 each) beside a router, bias included, that stands
    whole on every chip: the chips' parts add up to the uncut layer's
    feed-forward — weights over all four chosen with the 1e-6 in their
    denominator, written out — and their held rows are every assignment
    made."""
    d, f = HIDDEN, 16
    ks = iter(jax.random.split(jax.random.key(21), 6))
    n = lambda *s: jax.random.normal(next(ks), s) * s[-2] ** -0.5
    whole = {"router": n(d, 32), "w1": n(32, d, f), "w3": n(32, d, f),
             "w2": n(32, f, d),
             "router_bias": D.ROUTER_BIAS_STD * jax.random.normal(
                 next(ks), (32,))}
    rows = jax.random.normal(next(ks), (96, d))
    layer = lambda p, held: MOE.moe_layer(
        p, rows, held, 4, 1.0, renorm_epsilon=EPSILON)
    want, counters = layer(whole, (0, 32))
    assert float(counters[0]) == float(counters[1]) == 96 * 4
    prob = jax.nn.sigmoid(rows @ whole["router"])
    top_e = jax.lax.top_k(prob + whole["router_bias"], 4)[1]
    top_p = jnp.take_along_axis(prob, top_e, -1)
    weight = top_p / (top_p.sum(-1, keepdims=True) + EPSILON)
    plain = jnp.zeros_like(rows)
    for e in range(32):
        share = jnp.where(top_e == e, weight, 0.0).sum(-1)
        plain += share[:, None] * MOE.ffn(
            MOE.SILU_GATED, rows, whole["w1"][e], whole["w3"][e],
            whole["w2"][e])
    np.testing.assert_allclose(want, plain, rtol=2e-5, atol=2e-5)

    total, held = 0.0, 0.0
    for chip in range(4):
        e = slice(8 * chip, 8 * chip + 8)
        mine = {"router": whole["router"],
                "router_bias": whole["router_bias"],
                **{m: whole[m][e] for m in ("w1", "w3", "w2")}}
        y, counted = layer(mine, (e.start, 8))
        total, held = total + y, held + float(counted[0])
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert held == 96 * 4


# ---------------------------------------------------------------------------
# the blockwise kernel at heads of 64
# ---------------------------------------------------------------------------


def test_kernel_at_heads_of_64_in_the_interpreter(monkeypatch):
    """8 query heads over 2 key-value heads of 64, half a lane tile: the
    splash kernel (Pallas interpreter) and the masked product against
    scores written out a head at a time, scaled by 1 / 8, forward and
    backward."""
    monkeypatch.setattr(A, "BLOCK", 128)
    t, heads, kv, d = 256, 8, 2, 64
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (1, t, heads, d))
    k = jax.random.normal(ks[1], (1, t, kv, d))
    v = jax.random.normal(ks[2], (1, t, kv, d))
    g = jax.random.normal(ks[3], (1, t, heads, d))

    def written_out(q, k, v):
        k, v = (jnp.repeat(a, heads // kv, 2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 8.0
        s = jnp.where(A.attention_mask(t, None), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    want, vjp = jax.vjp(written_out, q, k, v)
    kernel = lambda q, k, v: A.splash_attention(q, k, v, interpret=True)
    for fn in (A.masked_attention, kernel):
        got, vjp_got = jax.vjp(fn, q, k, v)
        assert got.shape == (1, t, heads, d)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        for a, b in zip(vjp_got(g), vjp(g)):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# what cannot be built is refused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change, message", [
    ({"short_conv": None}, "short_conv lacks kernel, bias"),
    ({"short_conv": {"kernel": 3}}, "short_conv lacks bias"),
    ({"short_conv": {"kernel": 3.0, "bias": False}},
     "short_conv lacks kernel"),
    ({"short_conv": {"kernel": 3, "bias": False, "activation": "silu"}},
     "no other key"),
    ({"short_conv": {"kernel": 0, "bias": False}}, "at least one tap"),
    ({"query_heads_held": [0, 4], "key_value_heads_held": [0, 1]},
     "holds every channel"),
    ({"layer_types": ["conv"] * 4}, "known layer_types"),
    ({"router_renorm_epsilon": -1e-6}, "router_renorm_epsilon"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    extra = {**TL.tiny_config()["model"]["extra"], **change}
    with pytest.raises(ValueError, match=message):
        create_model(_model_config({"model": {
            "name": "decoder", "num_classes": TL.VOCAB,
            "input_shape": [TL.SEQ], "extra": extra}}))


def test_peft_beside_a_tied_table_is_refused_when_the_sim_is_built():
    """``fed.peft`` trains ``lm_head`` densely and a tied stack has
    none; no adapter on the tied table is built, and ``build_peft`` —
    what ``FedAvgSim.__init__`` calls before anything is compiled —
    says so."""
    from fedml_tpu import peft as PF

    cfg = ExperimentConfig(model=_model_config(TL.tiny_config()),
                           fed=FedConfig(peft="lora"))
    with pytest.raises(ValueError, match="beside tie_word_embeddings"):
        PF.build_peft(create_model(cfg.model), cfg)


# ---------------------------------------------------------------------------
# the published share
# ---------------------------------------------------------------------------


def test_published_share_has_568_647_936_parameters():
    """The cut LFM2-8B-A1B as the configuration's file gives it, counted
    from ``eval_shape`` alone, with the table of ISSUE 46; no width
    differs from the published config, and every count held is listed
    with the published one beside it."""
    config = TL.real_config()
    extra = config["model"]["extra"]
    model = create_model(_model_config(config))
    assert model.counters == MOE.MOE_COUNTERS
    shapes = jax.eval_shape(model.init, jax.random.key(0))["params"]
    count = lambda tree: sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    assert count(shapes) == 568_647_936
    assert set(shapes) == {"embed", "final_norm"} | {
        f"layer_{l}" for l in range(6)}  # no lm_head
    conv, attn = shapes["layer_0"], shapes["layer_2"]
    assert [count(conv[k]) for k in ("in_proj", "conv_kernel", "out_proj")
            ] == [12_582_912, 6_144, 4_194_304]
    assert "conv_bias" not in conv
    assert sum(count(attn[k]) for k in (
        "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm")) == (
            10_485_888)
    assert sum(count(conv[k]) for k in (
        "gate_proj", "up_proj", "down_proj")) == 44_040_192
    sparse = ("router", "router_bias", "experts_w1", "experts_w3",
              "experts_w2")
    assert sum(count(attn[k]) for k in sparse) == 88_145_952
    assert [count(shapes[f"layer_{l}"]) for l in range(6)] == [
        60_827_648, 60_827_648, 98_635_936] + [104_933_408] * 3
    assert count(shapes["embed"]) == 33_554_432
    assert count(shapes["final_norm"]) == 2_048
    assert conv["in_proj"]["kernel"].shape == (2048, 3 * 2048)
    assert conv["conv_kernel"].shape == (3, 2048)
    assert attn["q_proj"]["kernel"].shape == (2048, 32 * 64)
    assert attn["k_proj"]["kernel"].shape == (2048, 8 * 64)
    assert attn["q_norm"]["scale"].shape == (64,)
    assert attn["experts_w1"].shape == (8, 2048, 1792)
    assert attn["router"].shape == (2048, 32)
    assert attn["router_bias"].shape == (32,)
    assert attn["router_bias"].dtype == jnp.float32
    assert shapes["embed"]["embedding"].shape == (16384, 2048)
    # no width differs from the published config
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_key_value_heads"):
        assert extra[key] == config[key], key
    assert extra["rms_norm_eps"] == config["norm_eps"] == 1e-5
    assert extra["head_dim"] * config["num_attention_heads"] == (
        config["hidden_size"])
    assert max(extra["heads_per_layer"]) == config["num_attention_heads"]
    assert extra["short_conv"] == {
        "kernel": config["conv_L_cache"], "bias": config["conv_bias"]}
    assert extra["routed_scaling_factor"] == config["routed_scaling_factor"]
    assert extra["router_score_bias"] is config["use_expert_bias"] is True
    assert extra["router_renorm_epsilon"] == 1e-6
    assert extra["tie_word_embeddings"] is True and extra["qk_norm"] is True
    assert extra["rope"]["full_attention"]["rope_theta"] == (
        config["rope_theta"]) == 1_000_000
    held = config["layer_types_held"]
    assert held == config["layer_types"][:6] and len(
        config["layer_types"]) == 24
    assert extra["layer_types"] == [
        {"conv": "short_conv"}.get(kind, kind) for kind in held]
    assert extra["mlp_layer_types"] == ["dense"] * config[
        "num_dense_layers"] + ["sparse"] * 4
    # every count held is listed, with the published one beside it
    published = config["published"]
    assert set(config["reduced"]) == set(published) - {
        "chips_that_share_a_layer", "tensor_parallel_chips",
        "expert_parallel_chips"}
    chips = published["expert_parallel_chips"]
    assert chips == published["chips_that_share_a_layer"] == 4
    assert published["tensor_parallel_chips"] == 1
    assert config["num_experts"] * chips == published["num_experts"] == (
        extra["num_experts"]) == 32
    assert extra["experts_held"] == [0, config["num_experts"]]
    assert config["vocab_size"] * chips == published["vocab_size"]
    assert (config["num_hidden_layers"], published["num_hidden_layers"]) == (
        6, 24)


def test_a_round_trains_every_leaf_but_the_biases():
    """``FedAvgSim``, bulk engine at a block of one, over the tiny stack
    through ``run``'s own loop: every parameter moves — the tied table,
    the taps — but the expert biases, which no gradient reaches, and
    the round record carries the five expert counters."""
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
    assert biases == 2
    steps = 2 * 2
    for record in Sink.records:
        assert set(MOE.MOE_COUNTERS) <= set(record)
        assert record["moe_rows_routed"] == steps * 2 * TL.SEQ * 4 * 2
        assert 0 < record["moe_rows_held"] < record["moe_rows_routed"]
        assert record["moe_rows_combined"] == record["moe_rows_routed"]
    assert "test_acc" in Sink.records[-1]
