"""The configured decoder stack (``create_model`` name ``decoder``)
against the plain reference of ``benchmarks/configs/laguna-xs2-share8``
at tiny widths, and the pieces it is made of: windowed grouped-query
attention, the chip's share of a sparse-expert layer, the counters a
round carries."""

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

import tiny_decoder as TD  # noqa: E402

from fedml_tpu.config import (  # noqa: E402
    DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
)
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.ops import attention as A  # noqa: E402
from fedml_tpu.ops import moe as MOE  # noqa: E402


def _model_config(config):
    m = config["model"]
    return ModelConfig(name=m["name"], num_classes=m["num_classes"],
                       input_shape=tuple(m["input_shape"]),
                       extra=tuple(m["extra"].items()))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config = TD.tiny_config()
    ref = TD.load_reference(str(tmp_path_factory.mktemp("ref")), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, TD.SEQ + 1), 0, TD.VOCAB)
    return config, ref, model, variables, tokens


def _loss(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def test_program_against_reference_logits_and_gradients(tiny):
    """(a) float32, every layer kind (dense + full, sparse + sliding,
    sparse + full): logits and every parameter's gradient."""
    _, ref, model, variables, tokens = tiny
    x, y = tokens[:, :-1], tokens[:, 1:]
    assert (jax.tree.structure(model.init(jax.random.key(0)))
            == jax.tree.structure(variables))

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
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(g_ref))
    for path, g in jax.tree_util.tree_leaves_with_path(g_ours):
        r = flat_ref[path]
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-3 * scale, (
            jax.tree_util.keystr(path))
        assert scale > 1e-9, jax.tree_util.keystr(path)  # it is trained
    tokens_routed = x.size * 4 * 4  # tokens x top-4 x four sparse layers
    assert float(counted["moe_rows_routed"]) == tokens_routed
    assert 0 < float(counted["moe_rows_held"]) < tokens_routed
    assert float(counted["moe_rows_max_expert"]) <= float(
        counted["moe_rows_held"])


def test_eval_mode_counts_nothing_and_agrees(tiny):
    _, _, model, variables, tokens = tiny
    x = tokens[:, :-1]
    logits, same = model.apply_train(variables, x, jax.random.key(0))
    assert same is variables
    np.testing.assert_allclose(
        logits, model.apply_eval(variables, x), rtol=1e-6, atol=1e-6)


def _layer_params(key, d=64, experts=16, f=32, first=0, count=16,
                  shared=True):
    ks = jax.random.split(key, 7)
    n = lambda k, *s: jax.random.normal(k, s) * s[-2] ** -0.5
    every = {"w1": n(ks[1], experts, d, f), "w3": n(ks[2], experts, d, f),
             "w2": n(ks[3], experts, f, d)}
    p = {"router": n(ks[0], d, experts),
         **{k: v[first:first + count] for k, v in every.items()}}
    if shared:
        p["shared"] = (n(ks[4], d, f), n(ks[5], d, f), n(ks[6], f, d))
    return p, every


def _uncut_layer(p, every, h, top_k, scale):
    """The whole layer written out: every expert on every token, a
    mask for the chosen ones (no share, no sort)."""
    prob = jax.nn.sigmoid(h @ p["router"])
    top_p, top_e = jax.lax.top_k(prob, top_k)
    w = scale * top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(every["w1"].shape[0]):
        share = jnp.where(top_e == e, w, 0.0).sum(-1)
        y += share[:, None] * MOE.gated_ffn(
            h, every["w1"][e], every["w3"][e], every["w2"][e])
    return y + MOE.gated_ffn(h, *p["shared"])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """(b) one sparse layer, 16 experts top-4: the outputs of all 8
    shares (held = (0, 2), (2, 2), ...), the shared expert counted
    once, sum to the uncut layer's output; their held rows sum to every
    assignment made."""
    key = jax.random.key(7)
    h = jax.random.normal(jax.random.fold_in(key, 1), (48, 64))
    full, every = _layer_params(key)
    whole = _uncut_layer(full, every, h, 4, 2.5)
    total, rows = jnp.zeros_like(h), 0.0
    for share in range(8):
        p, _ = _layer_params(key, first=2 * share, count=2)
        if share:
            del p["shared"]  # what every chip computes alike: once
        y, counters = MOE.moe_layer(p, h, (2 * share, 2), 4, 2.5)
        total, rows = total + y, rows + float(counters[0])
        assert float(counters[1]) == 48 * 4
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    assert rows == 48 * 4


@pytest.mark.parametrize("heads", [6, 8])
@pytest.mark.parametrize("window", [None, 40])
def test_windowed_attention_against_the_masked_product(heads, window,
                                                       monkeypatch):
    """(c) the blockwise kernel (Pallas interpreter here; 256 tokens in
    blocks of 128, so a window of 40 leaves key blocks unvisited) and
    the masked product the CPU runs, against scores written out a query
    head at a time: forward and backward, both head counts."""
    monkeypatch.setattr(A, "BLOCK", 128)
    t, d, kv = 256, 128, 2
    ks = jax.random.split(jax.random.key(heads), 4)
    q = jax.random.normal(ks[0], (2, t, heads, d))
    k = jax.random.normal(ks[1], (2, t, kv, d))
    v = jax.random.normal(ks[2], (2, t, kv, d))
    g = jax.random.normal(ks[3], (2, t, heads, d))

    def written_out(q, k, v):  # [T, T] scores, one query head at a time
        outs = []
        for j in range(heads):
            s = jnp.einsum("bqd,bkd->bqk", q[:, :, j],
                           k[:, :, j // (heads // kv)]) / d ** 0.5
            s = jnp.where(A.attention_mask(t, window), s, -jnp.inf)
            outs.append(jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1),
                                   v[:, :, j // (heads // kv)]))
        return jnp.stack(outs, 2)

    want, vjp = jax.vjp(written_out, q, k, v)
    kernel = lambda q, k, v: A.splash_attention(
        q, k, v, window=window, interpret=True)
    masked = lambda q, k, v: A.masked_attention(q, k, v, window=window)
    for fn in (masked, kernel):
        got, vjp_got = jax.vjp(fn, q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        for a, b in zip(vjp_got(g), vjp(g)):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    # off the TPU the model's attention IS the masked product
    np.testing.assert_array_equal(
        A.causal_attention(q, k, v, window=window), masked(q, k, v))


def test_dropless_under_imbalance():
    """(d) a router biased so that every token picks the same held
    experts loses no row: the layer still equals the written-out one,
    and the counters say every assignment landed here."""
    key = jax.random.key(11)
    h = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (40, 64)))
    full, every = _layer_params(key)
    bias = jnp.zeros((64, 16)).at[:, 4:8].set(5.0)  # experts 4..7 always
    full["router"] = full["router"] + bias
    p = {**full, **{k: every[k][4:8] for k in every}}
    y, counters = MOE.moe_layer(p, h, (4, 4), 4, 2.5)
    np.testing.assert_allclose(
        y, _uncut_layer(full, every, h, 4, 2.5), rtol=2e-5, atol=2e-5)
    assert [float(c) for c in counters[:2]] == [160.0, 160.0]
    assert float(counters[2]) == 40.0  # every token on every held expert


def _sim(config, block):
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.data.federated import FederatedData

    rng = np.random.default_rng(5)
    seq = rng.integers(0, TD.VOCAB, (32, TD.SEQ + 1)).astype(np.int32)
    maps = {c: np.arange(4 * c, 4 * c + 4) for c in range(8)}
    data = FederatedData(
        seq[:, :-1], seq[:, 1:], seq[:8, :-1], seq[:8, 1:], maps,
        {c: np.arange(c, c + 1) for c in range(8)}, TD.VOCAB, "nwp")
    cfg = ExperimentConfig(
        data=DataConfig(dataset="tokens", num_clients=8, batch_size=2),
        model=_model_config(config), train=TrainConfig(lr=0.05, epochs=1),
        fed=FedConfig(num_rounds=2, clients_per_round=2, eval_every=2,
                      client_block_size=block))
    return FedAvgSim(create_model(cfg.model), data, cfg)


def test_round_at_block_one_equals_the_stacked_round(tiny):
    """(e) ``FedAvgSim`` with ``client_block_size`` 1 (the bulk engine,
    one client's update at a time, not mapped) against the stacked
    round, through ``run``'s own loop and sink; the records carry the
    counters, a per-client list among them."""
    config = tiny[0]

    class Sink:
        def __init__(self):
            self.records = []

        def log(self, record):
            self.records.append(dict(record))

    states, sinks = [], []
    for block in (0, 1):
        sink = Sink()
        states.append(_sim(config, block).run(metrics_sink=sink))
        sinks.append(sink.records)
    assert states[0].momentum == ()  # gmf 0: no model-sized buffer
    for a, b in zip(jax.tree.leaves(states[0].variables),
                    jax.tree.leaves(states[1].variables)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for stacked, bulk in zip(*sinks):
        assert stacked["moe_rows_routed"] == 2 * 2 * 2 * TD.SEQ * 4 * 4
        for name in ("moe_rows_held", "moe_rows_routed",
                     "moe_rows_max_expert", "moe_rows_held_by_client"):
            assert stacked[name] == bulk[name], name
        assert len(bulk["moe_rows_held_by_client"]) == 2
        assert sum(bulk["moe_rows_held_by_client"]) == bulk["moe_rows_held"]
        np.testing.assert_allclose(
            stacked["train_loss"], bulk["train_loss"], rtol=1e-5)
    assert "test_acc" in sinks[1][-1]


def test_log_span_carries_the_counters():
    from fedml_tpu.core.tracing import log_span

    attrs = log_span({"round": 3, "train_loss": 1.0, "moe_rows_held": 9.0,
                      "moe_rows_held_by_client": [4.0, 5.0]}).attrs
    assert attrs == {"round": 3, "moe_rows_held": 9,
                     "moe_rows_held_by_client": "[4, 5]"}


def test_kernels_keep_their_scope_in_the_scope_map():
    """Two things the chip's round showed (PR 27): the compiler renames
    ``ragged_dot`` and cuts its name stack short of the model's scopes,
    and a Mosaic call's instruction runs over several lines, its
    ``op_name`` on the last."""
    from fedml_tpu.core.memscope import parse_scopes

    text = (
        "%fused_computation.7 (p: f32[2]) -> f32[2] {\n"
        '  %mul.3 = f32[2]{0} multiply(%p, %p), '
        'metadata={op_name="jit(f)/fedml.local.update/mul"}\n'
        "}\n"
        "ENTRY %main (a: f32[2]) -> f32[2] {\n"
        '  %ragged-dot-none.1 = f32[2]{0} custom-call(%a), metadata='
        '{op_name="jit(f)/while/body/fedml.local/closed_call/'
        'ragged-dot-none"}\n'
        "  %splash_mqa_fwd.12 = f32[2]{0} custom-call(%a), "
        'custom_call_target="tpu_custom_call", frontend_attributes='
        "{kernel_metadata={\n"
        '}}, metadata={op_name="jit(f)/fedml.local.grad/fedml.model.attn/'
        'fedml.model.attn.kernel/pallas_call"}, backend_config={}\n'
        '  %fusion.2 = f32[2]{0} fusion(%a), kind=kLoop, '
        'metadata={op_name="jit(f)/fedml.local.grad/fedml.model.attn/mul"}\n'
        "  %fusion.9 = f32[2]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.7\n"
        "}\n")
    scopes = parse_scopes(text)
    assert scopes["ragged-dot-none.1"] == "fedml.model.moe.experts"
    assert scopes["splash_mqa_fwd.12"] == "fedml.model.attn.kernel"
    assert scopes["fusion.2"] == "fedml.model.attn"
    assert scopes["fusion.9"] == "fedml.local.update"


def test_published_share_has_691_million_parameters():
    """The cut Laguna-XS.2 as the configuration's file gives it, counted
    from ``eval_shape`` alone."""
    model = create_model(_model_config(TD.real_config()))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert set(shapes) == {"params"}
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 691e6 < count < 692e6, count
    layer = shapes["params"]["layer_1"]
    assert layer["experts_w1"].shape == (32, 2048, 512)
    assert layer["router"].shape == (2048, 256)
    assert layer["q_proj"]["kernel"].shape == (2048, 64 * 128)
    assert shapes["params"]["layer_0"]["q_proj"]["kernel"].shape == (
        2048, 48 * 128)


def test_every_configuration_brings_its_reference():
    """(f) every configuration file under ``benchmarks/configs/`` names
    a reference that exports the five names the harness calls, and its
    ``init`` has the tree of the program's own variables (``eval_shape``
    alone): the tier-1 copy of ``benchmarks/tests/test_reference.py``'s
    test of the same name, so the floor guards the protocol."""
    import glob
    import json

    import run

    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    files = sorted(glob.glob(os.path.join(TD.BENCH, "configs", "*.json")))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    assert {os.path.join(ROOT, c["file"]) for c in doc["configs"]} <= set(
        files)
    for path in files:
        with open(path) as f:
            config = json.load(f)
        ref = run._load_py(os.path.join(
            os.path.dirname(path), config["reference"]), "ref")
        assert all(hasattr(ref, n) for n in run.REFERENCE_EXPORTS), path
        cfg = run.experiment_config(config, {
            "population": 10, "clients_per_round": 2, "eval_every": 1})
        ours = jax.eval_shape(ref.init, jax.random.key(0))
        theirs = jax.eval_shape(
            create_model(cfg.model).init, jax.random.key(0))
        assert shapes(ours) == shapes(theirs), path
        leaf = ours["params"]
        for key in ref.HEAD:
            leaf = leaf[key]
        assert jax.tree.leaves(leaf), path
        assert ref.step_flops(2) == 2 * ref.step_flops(1) > 0
