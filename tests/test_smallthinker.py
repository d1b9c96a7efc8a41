"""The decoder stack with a router BEFORE attention and ReLU-gated
experts — position-free full layers among rotary windows — against the
plain reference of ``benchmarks/configs/smallthinker-21b-share4`` at
tiny widths; the router's two roads into the stream; ``relu_gated``
through both sides of ``moe_layer``'s row buffer; the four-chip host's
shares tied to the uncut layer; what ``decoder_from_extra`` refuses;
the published share's size."""

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

import tiny_smallthinker as TS  # noqa: E402
from test_decoder import (  # noqa: E402
    _assert_trees_close as _close, _loss, _model_config, _sim,
)

from fedml_tpu.config import ModelConfig  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    DecoderLayer, decoder_from_extra,
)
from fedml_tpu.ops import moe as MOE  # noqa: E402


def _assert_trees_close(got, want, rtol):
    """``test_decoder``'s comparison, and no leaf of ``want`` all zero
    (every parameter is trained)."""
    for path, r in jax.tree_util.tree_leaves_with_path(want):
        assert float(jnp.max(jnp.abs(r))) > 1e-9, jax.tree_util.keystr(path)
    _close(got, want, rtol)


@pytest.mark.parametrize("pattern", ["F", "W", TS.PATTERN])
def test_program_against_reference_logits_and_gradients(pattern, tmp_path):
    """float32: a full layer without positions, a window layer with
    rotary, and the period ``FWWW``: the variable trees agree, and so do
    the logits and every parameter's gradient — the router's too, which
    the loss reaches only through the weights of the chosen experts."""
    config = TS.tiny_config(pattern=pattern)
    ref = TS.load_reference(str(tmp_path), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, TS.SEQ + 1), 0, TS.VOCAB)
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
    _assert_trees_close(g_ours, g_ref, 2e-3)
    assert set(counted) == set(MOE.MOE_COUNTERS)
    assert float(counted["moe_rows_routed"]) == x.size * 3 * len(pattern)
    assert 0 < float(counted["moe_rows_held"]) < x.size * 3 * len(pattern)


# ---------------------------------------------------------------------------
# the router's two roads
# ---------------------------------------------------------------------------

HIDDEN = 64


def _one_layer(kind, **share):
    """A one-layer stack of ``kind`` at the tiny sizes, UNCUT but for
    ``share``'s keys -> (frozen configuration, its layer)."""
    extra = {**TS.tiny_config(pattern=kind)["model"]["extra"],
             "key_value_heads_held": None, "query_heads_held": None,
             "experts_held": [0, 16], **share}
    cfg = decoder_from_extra(extra, TS.VOCAB).cfg
    return cfg, DecoderLayer(cfg, 0)


def _stream():
    return jax.random.normal(jax.random.key(8), (2, TS.SEQ, HIDDEN))


def _layer_params(layer, x):
    params = layer.init(jax.random.key(5), x)["params"]
    # norm scales away from their init values
    return jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.key(6), p.shape),
        params)


def _norm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _chosen(logits, top_k):
    """SmallThinker's router written as published: the ``top_k``
    largest LOGITS, then a softmax over those alone."""
    top_l, top_e = jax.lax.top_k(logits, top_k)
    return top_e, jax.nn.softmax(top_l, -1)


def _written_out(p, rows, read, first, count, top_k, cut_weights_road=False):
    """A share of ReLU-gated experts written out: chosen and weighted
    from ``read``, every held expert on every token of ``rows``, a mask
    for the chosen ones. ``cut_weights_road``: no gradient passes the
    router's logits."""
    logits = read @ p["router"]
    top_e, w = _chosen(
        jax.lax.stop_gradient(logits) if cut_weights_road else logits, top_k)
    y = jnp.zeros_like(rows)
    for e in range(count):
        share = jnp.where(top_e == first + e, w, 0.0).sum(-1)
        y += share[:, None] * (
            (jax.nn.relu(rows @ p["w1"][e]) * (rows @ p["w3"][e]))
            @ p["w2"][e])
    return y


@pytest.mark.parametrize("kind", ["F", "W"])
def test_a_layers_gradient_reaches_the_stream_by_both_roads(kind):
    """One uncut layer whose router reads the attention's input, against
    the layer written out (``h = norm_in(x)``; the choice and weights
    from ``h``; ``x' = x + attention``; the experts on ``norm_post(x')``):
    the output, the stream's gradient and every parameter's. With the
    weights' road cut (``stop_gradient`` on the router's logits) the
    written-out stream gradient is another one and the router's is
    zero, so the first road is there and is what trains ``W_r``."""
    x = _stream()
    _, layer = _one_layer(kind)
    params = _layer_params(layer, x)
    weigh = jax.random.normal(jax.random.key(9), x.shape)
    _, attention_only = _one_layer(kind, mlp_layer_types=["none"])
    attention = {k: params[k] for k in (
        "attn_norm", "q_proj", "k_proj", "v_proj", "o_proj")}

    def written_out(params, x, cut_weights_road=False):
        b, t, d = x.shape
        h = _norm(x, params["attn_norm"]["scale"])
        after, _ = attention_only.apply(
            {"params": {k: params[k] for k in attention}}, x)
        g = _norm(after, params["mlp_norm"]["scale"])
        y = _written_out(
            {"router": params["router"],
             **{m: params["experts_" + m] for m in ("w1", "w3", "w2")}},
            g.reshape(b * t, d), h.reshape(b * t, d), 0, 16, 3,
            cut_weights_road)
        return after + y.reshape(b, t, d)

    def ours(params, x):
        return layer.apply({"params": params}, x)[0]

    both = lambda fn, **kw: jax.value_and_grad(
        lambda p, x: jnp.sum(fn(p, x, **kw) * weigh), argnums=(0, 1))
    (got, g_got), (want, g_want) = both(ours)(params, x), both(
        written_out)(params, x)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    _assert_trees_close(g_got, g_want, 2e-4)
    _, (cut_p, cut_x) = both(written_out, cut_weights_road=True)(params, x)
    assert float(jnp.max(jnp.abs(cut_p["router"]))) == 0.0
    assert float(jnp.max(jnp.abs(cut_x - g_want[1]))) > 1e-3 * float(
        jnp.max(jnp.abs(g_want[1])))


def test_the_router_scope_is_there_only_where_the_router_reads_apart():
    """``fedml.model.moe.router`` names the logits, top-k and weights of
    a router with an input of its own; a layer whose router reads the
    rows it routes has no such scope (its cells' ``moe_route_ms`` reads
    what it read)."""
    x = _stream()

    def scopes(**extra):
        _, layer = _one_layer("F", **extra)
        shapes = jax.eval_shape(layer.init, jax.random.key(0), x)
        text = jax.jit(layer.apply).lower(shapes, x).as_text(debug_info=True)
        return {s for s in (MOE.ROUTER, MOE.ROUTE, MOE.EXPERTS) if s in text}

    assert scopes() == {MOE.ROUTER, MOE.ROUTE, MOE.EXPERTS}
    assert scopes(router_input="feed_forward_input") == {
        MOE.ROUTE, MOE.EXPERTS}


# ---------------------------------------------------------------------------
# ``relu_gated`` through both sides of the row buffer
# ---------------------------------------------------------------------------

TOKENS = 64
# (experts, first held, held, ways a token)
SHAPES = {"ways_3_of_4_held": (16, 4, 4, 3),
          "ways_6_of_16_held_as_published": (64, 0, 16, 6)}


def _moe_params(key, experts, d=HIDDEN, f=32):
    ks = iter(jax.random.split(key, 4))
    n = lambda *s: jax.random.normal(next(ks), s) * s[-2] ** -0.5
    return {"router": n(d, experts), "w1": n(experts, d, f),
            "w3": n(experts, d, f), "w2": n(experts, f, d)}


def _cases():
    for shape in SHAPES:
        for steer in ("fresh", "crowded"):
            for read_apart in (True, False):
                yield shape, steer, read_apart, False
        yield shape, "fresh", True, True
        yield shape, "crowded", True, True


@pytest.mark.parametrize(
    "case", list(_cases()), ids=lambda c: "-".join(
        [c[0], c[1], "router_input" if c[2] else "one_tensor",
         "vmap" if c[3] else "unmapped"]))
def test_relu_gated_share_against_the_written_out_share(case):
    """Values, the rows' gradient, the router input's and every
    parameter's against the share written out, through the bounded
    buffer (``fresh`` routers) and through the worst-case one
    (``crowded``: every token names held experts only), with the router
    reading a tensor of its own or the rows, unmapped and as a mapped
    batch. The written-out router takes the top-k LOGITS and then their
    softmax; ``moe_layer`` takes the softmax over all experts and
    renormalises over the chosen: the same weights."""
    shape, steer, read_apart, mapped = case
    experts, first, count, top_k = SHAPES[shape]
    key = jax.random.key(41)
    p = _moe_params(key, experts)
    mine = {**p, **{m: p[m][first:first + count]
                    for m in ("w1", "w3", "w2")}}
    rows = jax.random.normal(jax.random.fold_in(key, 1), (TOKENS, HIDDEN))
    read = jax.random.normal(jax.random.fold_in(key, 2), (TOKENS, HIDDEN))
    if steer == "crowded":  # a marker feature sends every way to held ones
        mine["router"] = (0.1 * mine["router"]).at[0].set(0.0).at[
            0, first:first + count].set(9.0)
        read = read.at[:, 0].set(1.0)
    if not read_apart:
        rows = read
    weigh = jax.random.normal(jax.random.fold_in(key, 3), rows.shape)
    if mapped:  # the second instance fresh: the batch follows the first
        fresh = lambda i: jax.random.normal(
            jax.random.fold_in(key, i), rows.shape)
        rows, read = jnp.stack([rows, fresh(4)]), jnp.stack([read, fresh(5)])

    def share(p, rows, read):
        y, counters = MOE.moe_layer(
            p, rows, (first, count), top_k, 1.0, scoring="softmax",
            activation=MOE.RELU_GATED,
            router_input=read if read_apart else None)
        return jnp.sum(y * weigh), (y, counters)

    def plain(p, rows, read):
        y = _written_out(p, rows, read if read_apart else rows, first,
                         count, top_k)
        return jnp.sum(y * weigh), y

    both = lambda fn: jax.value_and_grad(
        fn, argnums=(0, 1, 2), has_aux=True)
    ours, theirs = both(share), both(plain)
    if mapped:
        ours, theirs = (jax.vmap(fn, in_axes=(None, 0, 0))
                        for fn in (ours, theirs))
    (_, (y, counters)), grads = jax.jit(ours)(mine, rows, read)
    (_, want), want_grads = jax.jit(theirs)(mine, rows, read)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    if not read_apart:  # one tensor: ``read`` is unused on both sides
        grads, want_grads = grads[:2], want_grads[:2]
    _assert_trees_close(grads, want_grads, 2e-4)
    counters = counters.reshape(-1, len(MOE.MOE_COUNTERS))
    nk = TOKENS * top_k
    buffer = MOE.row_buffer(TOKENS, top_k, count, experts)
    assert buffer < nk
    for row in counters.tolist():
        held, routed, compact, combined = (
            row[MOE.MOE_COUNTERS.index("moe_rows_" + name)]
            for name in ("held", "routed", "compact", "combined"))
        assert routed == nk and 0 < held <= nk
        assert compact == (nk if steer == "fresh" else 0.0)
        assert combined == TOKENS * top_k
    if steer == "crowded":  # over the buffer, as steered
        assert counters[0, 0] == nk > buffer


def test_the_activations_by_name():
    """``ffn`` and the layer's parameters follow the activation's name,
    not a count of matrices: the two gated ones take the same three."""
    x = jax.random.normal(jax.random.key(1), (5, 8))
    w1, w3, w2 = (jax.random.normal(jax.random.key(i), s) for i, s in (
        (2, (8, 6)), (3, (8, 6)), (4, (6, 8))))
    np.testing.assert_allclose(
        MOE.ffn(MOE.RELU_GATED, x, w1, w3, w2),
        (jax.nn.relu(x @ w1) * (x @ w3)) @ w2, rtol=1e-6)
    np.testing.assert_allclose(
        MOE.ffn(MOE.SILU_GATED, x, w1, w3, w2),
        (jax.nn.silu(x @ w1) * (x @ w3)) @ w2, rtol=1e-6)
    np.testing.assert_allclose(
        MOE.ffn(MOE.RELU2, x, w1, w2), jnp.square(jax.nn.relu(x @ w1)) @ w2,
        rtol=1e-6)
    assert MOE.leading(MOE.RELU_GATED) == MOE.leading(MOE.SILU_GATED) == (
        "w1", "w3")
    assert MOE.leading(MOE.RELU2) == ("w1",)
    assert set(MOE.ACTIVATIONS) == {"silu_gated", "relu_gated", "relu2"}


# ---------------------------------------------------------------------------
# the four-chip host's shares tied to the uncut layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["F", "W"])
def test_the_four_shares_add_up_to_the_uncut_layer(kind):
    """8 query heads over 4 key-value heads and 16 experts as the four
    chips of a host hold them (2 query heads over the ONE key-value head
    they read, 4 experts: the published 7 of 28 over 1 of 4, and 16 of
    64). What the host does: every chip's attention part is summed into
    the stream (``x' = x + sum_i a_i``), then every chip's held experts
    read the same ``norm_post(x')`` under the weights the router — whole
    on every chip, counted once — drew from ``norm_in(x)``, and their
    parts are summed. That sum is the uncut layer's output, and the
    shares' held rows are every assignment made."""
    d = 16
    x = _stream()
    _, whole = _one_layer(kind)
    params = _layer_params(whole, x)
    want, counters = whole.apply({"params": params}, x)
    assert float(counters[0]) == float(counters[1]) == x.shape[0] * TS.SEQ * 3

    cols = lambda name, a, b: {
        "kernel": params[name]["kernel"][:, a * d:b * d]}
    after = x
    for chip in range(4):
        mine = {
            "attn_norm": params["attn_norm"],
            "q_proj": cols("q_proj", 2 * chip, 2 * chip + 2),
            "k_proj": cols("k_proj", chip, chip + 1),
            "v_proj": cols("v_proj", chip, chip + 1),
            "o_proj": {"kernel": params["o_proj"]["kernel"][
                2 * chip * d:(2 * chip + 2) * d]},
        }
        _, layer = _one_layer(
            kind, mlp_layer_types=["none"], query_heads_held=[2 * chip, 2],
            key_value_heads_held=[chip, 1])
        after = after + layer.apply({"params": mine}, x)[0] - x
    flat = lambda v: v.reshape(-1, HIDDEN)
    read = flat(_norm(x, params["attn_norm"]["scale"]))
    rows = flat(_norm(after, params["mlp_norm"]["scale"]))
    total, held = flat(after), 0.0
    for chip in range(4):
        e = slice(4 * chip, 4 * chip + 4)
        mine = {"router": params["router"],
                **{m: params["experts_" + m][e] for m in ("w1", "w3", "w2")}}
        y, counted = MOE.moe_layer(
            mine, rows, (e.start, 4), 3, 1.0, scoring="softmax",
            activation=MOE.RELU_GATED, router_input=read)
        total, held = total + y, held + float(counted[0])
    np.testing.assert_allclose(
        total.reshape(x.shape), want, rtol=2e-5, atol=2e-5)
    assert held == x.shape[0] * TS.SEQ * 3


# ---------------------------------------------------------------------------
# what cannot be built is refused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change, message", [
    ({"mlp_activation": "gelu_gated"}, "mlp_activation 'gelu_gated'"),
    ({"mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
      "intermediate_size": 32}, "mlp_activation 'relu_gated'"),
    ({"router_input": "embedding"}, "unknown router_input 'embedding'"),
    ({"layer_types": ["full_attention", "none", "sliding_attention",
                      "sliding_attention"]},
     "needs an attention mixer in every sparse layer; layer 1 has 'none'"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    extra = {**TS.tiny_config()["model"]["extra"], **change}
    with pytest.raises(ValueError, match=message):
        create_model(ModelConfig(
            name="decoder", num_classes=TS.VOCAB, input_shape=(TS.SEQ,),
            extra=tuple(extra.items())))


# ---------------------------------------------------------------------------
# the published share
# ---------------------------------------------------------------------------


def test_published_share_has_593_615_360_parameters():
    """The cut SmallThinker-21BA3B-Instruct as the configuration's file
    gives it, counted from ``eval_shape`` alone, with the table of
    ISSUE 39; no width differs from the published config, and every
    count held is listed with the published one beside it."""
    config = TS.real_config()
    extra = config["model"]["extra"]
    model = create_model(_model_config(config))
    assert model.counters == MOE.MOE_COUNTERS
    shapes = jax.eval_shape(model.init, jax.random.key(0))["params"]
    count = lambda tree: sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    assert count(shapes) == 593_615_360
    layer = shapes["layer_0"]
    assert [count(shapes[f"layer_{l}"]) for l in range(4)] == [99_783_680] * 4
    assert count(layer["q_proj"]) == count(layer["o_proj"]) == 2_293_760
    assert count(layer["k_proj"]) == count(layer["v_proj"]) == 327_680
    assert count(layer["router"]) == 163_840
    assert sum(count(layer["experts_" + m])
               for m in ("w1", "w3", "w2")) == 94_371_840
    assert count(layer["attn_norm"]) + count(layer["mlp_norm"]) == 5_120
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 194_478_080
    assert layer["experts_w1"].shape == (16, 2560, 768)
    assert layer["experts_w2"].shape == (16, 768, 2560)
    assert layer["router"].shape == (2560, 64)
    assert layer["q_proj"]["kernel"].shape == (2560, 7 * 128)
    assert layer["k_proj"]["kernel"].shape == (2560, 128)
    assert shapes["lm_head"]["kernel"].shape == (2560, 37984)
    # no width differs from the published config
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("head_dim", "head_dim"),
                         ("moe_intermediate_size", "moe_ffn_hidden_size"),
                         ("num_experts_per_tok",
                          "moe_num_active_primary_experts"),
                         ("sliding_window", "sliding_window_size"),
                         ("rms_norm_eps", "rms_norm_eps")):
        assert extra[ours] == config[theirs], ours
    assert extra["rope"]["sliding_attention"]["rope_theta"] == config[
        "rope_theta"] == 1_500_000
    assert extra["rope"]["full_attention"] == {"rope_type": "none"}
    # the layers held are the first period of the published layout
    held = config["layout_held"]
    for key in ("rope_layout", "sliding_window_layout"):
        assert len(config[key]) == config["published"]["num_hidden_layers"]
        assert config[key][:4] == held[key] == [0, 1, 1, 1]
    assert extra["layer_types"] == [
        "sliding_attention" if w else "full_attention"
        for w in held["sliding_window_layout"]]
    # every count held is listed, with the published one beside it
    published = config["published"]
    assert set(config["reduced"]) == set(published) - {
        "chips_that_share_a_layer", "tensor_parallel_chips",
        "expert_parallel_chips"}
    chips = published["chips_that_share_a_layer"]
    for key in ("moe_num_primary_experts", "vocab_size",
                "num_attention_heads", "num_key_value_heads"):
        assert config[key] * chips == published[key], key
    assert extra["num_experts"] == published["moe_num_primary_experts"]
    assert extra["experts_held"] == [0, config["moe_num_primary_experts"]]
    assert extra["heads_per_layer"] == [published["num_attention_heads"]] * 4
    assert extra["query_heads_held"] == [0, config["num_attention_heads"]]
    assert extra["num_key_value_heads"] == published["num_key_value_heads"]
    assert extra["key_value_heads_held"] == [0, config["num_key_value_heads"]]
    assert extra["mlp_activation"] == "relu_gated"
    assert extra["router_input"] == "attention_input"


def test_a_round_trains_every_leaf_and_carries_the_five_counters():
    """``FedAvgSim``, bulk engine at a block of one, over the tiny period
    through ``run``'s own loop: every parameter moves — the routers too,
    which only the weights' road trains — and the round record carries
    the five expert counters."""
    sim = _sim(TS.tiny_config(), 1, seq=TS.SEQ, vocab=TS.VOCAB)

    class Sink:
        records = []

        def log(self, record):
            self.records.append(dict(record))

    before = jax.device_get(sim.init().variables)
    after = jax.device_get(sim.run(metrics_sink=Sink()).variables)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(after)):
        assert not np.array_equal(a, b), jax.tree_util.keystr(path)
    steps = 2 * 2
    for record in Sink.records:
        assert set(MOE.MOE_COUNTERS) <= set(record)
        assert record["moe_rows_routed"] == steps * 2 * TS.SEQ * 3 * 4
        assert 0 < record["moe_rows_held"] < record["moe_rows_routed"]
        assert record["moe_rows_max_expert"] <= record["moe_rows_held"]
        # 3 ways over 4 held: the combine reads a row a way
        assert record["moe_rows_combined"] == record["moe_rows_routed"]
        assert 0 < record["moe_rows_compact"] <= record["moe_rows_routed"]
    assert "test_acc" in Sink.records[-1]
