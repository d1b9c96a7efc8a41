"""The configured decoder stack (``create_model`` name ``decoder``)
against the plain references of ``benchmarks/configs/laguna-xs2-share8``
and ``keye-vl2-a3b-share8`` at tiny widths, and the pieces it is made
of: windowed grouped-query attention, attention over the keys a learned
index selects, the chip's share of a sparse-expert layer under either
router scoring, the counters a round carries, and the embedding
lookup's own backward rule against ``jnp.take``'s."""

import contextlib
import functools
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
import tiny_keye as TK  # noqa: E402
from test_sparse_attention import _pallas_calls  # noqa: E402

from fedml_tpu.config import (  # noqa: E402
    DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
)
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import decoder as DEC  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    KEPT as DECODER_KEPT, decoder_from_extra,
)
from fedml_tpu.ops import attention as A  # noqa: E402
from fedml_tpu.ops import embedding as EMB  # noqa: E402
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


def _uncut_layer(p, every, h, top_k, scale, first=0, scoring="sigmoid"):
    """The whole layer written out: every expert on every token, a
    mask for the chosen ones (no share, no sort). ``every`` holds the
    experts from id ``first`` on: a share of them written out."""
    prob = (jax.nn.sigmoid(h @ p["router"]) if scoring == "sigmoid"
            else jax.nn.softmax(h @ p["router"], -1))
    top_p, top_e = jax.lax.top_k(prob, top_k)
    w = scale * top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(every["w1"].shape[0]):
        share = jnp.where(top_e == first + e, w, 0.0).sum(-1)
        y += share[:, None] * MOE.ffn(
            MOE.SILU_GATED, h, every["w1"][e], every["w3"][e],
            every["w2"][e])
    if "shared" in p:
        y = y + MOE.ffn(MOE.SILU_GATED, h, *p["shared"])
    return y


@pytest.mark.parametrize("scoring, scale, shared", [
    ("sigmoid", 2.5, True), ("softmax", 1.0, False)])
def test_the_eight_shares_add_up_to_the_uncut_layer(scoring, scale, shared):
    """(b) one sparse layer, 16 experts top-4, under either router
    scoring: the outputs of all 8 shares (held = (0, 2), (2, 2), ...),
    the shared expert — where the layer has one — counted once, sum to
    the uncut layer's output; their held rows sum to every assignment
    made."""
    key = jax.random.key(7)
    h = jax.random.normal(jax.random.fold_in(key, 1), (48, 64))
    full, every = _layer_params(key, shared=shared)
    whole = _uncut_layer(full, every, h, 4, scale, scoring=scoring)
    total, rows = jnp.zeros_like(h), 0.0
    for share in range(8):
        p, _ = _layer_params(key, first=2 * share, count=2,
                             shared=shared and not share)
        # (what every chip computes alike, the shared expert: once)
        y, counters = MOE.moe_layer(p, h, (2 * share, 2), 4, scale, scoring)
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


def test_softmax_routing_ranks_over_all_experts_and_renormalises():
    logits = jax.random.normal(jax.random.key(5), (12, 16))
    top_e, top_w = MOE.route_top_k(logits, 4, 1.0, "softmax")
    prob = np.asarray(jax.nn.softmax(logits, -1))
    np.testing.assert_array_equal(
        np.sort(top_e, -1), np.sort(np.argsort(-prob, -1)[:, :4], -1))
    np.testing.assert_allclose(top_w.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        top_w, np.take_along_axis(prob, np.asarray(top_e), -1)
        / np.take_along_axis(prob, np.asarray(top_e), -1).sum(-1,
                                                              keepdims=True),
        rtol=1e-6)
    with pytest.raises(KeyError):
        MOE.route_top_k(logits, 4, 1.0, "argmax")


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
    # 160 rows where the bounded buffer holds 128: the worst-case path
    assert MOE.row_buffer(40, 4, 4, 16) == 128
    assert float(counters[3]) == 0.0


# held (4, 4) of 16 experts, 64 tokens top-4: 256 assignments, a bounded
# buffer of 128 rows
STEERED = {"well_under": (8, 8), "exactly": (31, 4), "one_over": (31, 5)}


def _steered(key, on_all_held, on_one_held, tokens=64):
    """A share of a layer (held (4, 4) of 16, top-4) and tokens whose
    routing is set by three marker features: ``on_all_held`` tokens pick
    experts 4-7 (four held rows each), ``on_one_held`` pick 4, 8, 9, 10
    (one held row), the rest 8-11 (none). -> (params, h, held rows)."""
    p, _ = _layer_params(key, first=4, count=4)
    picks = jnp.zeros((3, 16)).at[0, 4:8].set(9.0).at[
        1, jnp.array([4, 8, 9, 10])].set(9.0).at[2, 8:12].set(9.0)
    p["router"] = (0.1 * p["router"]).at[:3].set(picks)
    kind = jnp.where(jnp.arange(tokens) < on_all_held, 0, jnp.where(
        jnp.arange(tokens) < on_all_held + on_one_held, 1, 2))
    h = jax.random.normal(jax.random.fold_in(key, 1), (tokens, 64))
    h = h.at[:, :3].set(jax.nn.one_hot(kind, 3))
    return p, h, 4 * on_all_held + on_one_held


def _written_out(p, h):
    return _uncut_layer(p, {k: p[k] for k in ("w1", "w3", "w2")}, h, 4,
                        2.5, first=4)


def _value_and_gradients(layer, p, h, weigh):
    """-> (y, counters or None, gradients of sum(y * weigh) by every
    parameter and by ``h``)."""
    def loss(p, h):
        out = layer(p, h)
        y, counters = out if isinstance(out, tuple) else (out, None)
        return jnp.sum(y * weigh), (y, counters)

    (_, (y, counters)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(p, h)
    return y, counters, grads


def _assert_trees_close(got, want, rtol=2e-4):
    flat = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        scale = float(jnp.max(jnp.abs(flat[path]))) + 1e-12
        assert float(jnp.max(jnp.abs(g - flat[path]))) <= rtol * scale, (
            jax.tree_util.keystr(path))


def _share(p, h):
    return MOE.moe_layer(p, h, (4, 4), 4, 2.5)


@pytest.mark.parametrize("case", list(STEERED))
def test_bounded_buffer_against_the_written_out_layer(case):
    """(d') held rows well under the bounded buffer's 128, exactly 128
    and 129: values, the gradient of the tokens and of every parameter
    against the written-out share; the fourth counter says which buffer
    the call went through, the fifth what the combine read (4 ways over
    4 held: a row a way)."""
    key = jax.random.key(13)
    p, h, held_rows = _steered(key, *STEERED[case])
    assert MOE.row_buffer(64, 4, 4, 16) == 128
    weigh = jax.random.normal(jax.random.fold_in(key, 2), h.shape)
    y, counters, grads = _value_and_gradients(_share, p, h, weigh)
    want, _, want_grads = _value_and_gradients(_written_out, p, h, weigh)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    _assert_trees_close(grads, want_grads)
    assert float(jnp.max(jnp.abs(grads[0]["router"]))) > 0
    # the buffer of the side taken is filled forward and in the rule
    buffered = 256.0 if case == "one_over" else 128.0
    assert dict(zip(MOE.MOE_COUNTERS, map(float, counters))) == {
        "moe_rows_held": held_rows, "moe_rows_routed": 256.0,
        "moe_rows_max_expert": sum(STEERED[case]),  # expert 4: the fullest
        "moe_rows_compact": 0.0 if case == "one_over" else 256.0,
        "moe_rows_combined": 256.0,
        "moe_rows_gathered": 2 * buffered + 2 * 256.0,
        "moe_rows_tiled": 0.0,  # off the chip the products are ragged_dot's
        "moe_tokens_group_open": 64.0}  # one group: open for every token


@pytest.mark.parametrize("cases", [("one_over", "well_under"),
                                   ("exactly", "well_under")])
def test_a_mapped_batch_goes_one_way_together(cases):
    """Under ``vmap`` one instance over the bounded buffer sends the
    whole batch through the worst-case one (a mapped ``cond`` would run
    both sides for everyone): results equal the unmapped calls instance
    by instance, and every instance's counter names the batch's path."""
    key = jax.random.key(17)
    made = [_steered(jax.random.fold_in(key, i), *STEERED[c])
            for i, c in enumerate(cases)]
    p = jax.tree.map(lambda *x: jnp.stack(x), *[m[0] for m in made])
    h = jnp.stack([m[1] for m in made])
    weigh = jax.random.normal(jax.random.fold_in(key, 9), h.shape[1:])
    one = lambda p, h: _value_and_gradients(_share, p, h, weigh)
    y, counters, grads = jax.jit(jax.vmap(one))(p, h)
    every_fits = "one_over" not in cases
    for i, (p_i, h_i, held_rows) in enumerate(made):
        y_i, counters_i, grads_i = one(p_i, h_i)
        np.testing.assert_allclose(y[i], y_i, rtol=2e-5, atol=2e-5)
        _assert_trees_close(jax.tree.map(lambda g: g[i], grads), grads_i)
        assert float(counters[i][0]) == float(counters_i[0]) == held_rows
        assert float(counters[i][3]) == (256.0 if every_fits else 0.0)
    # shared parameters, mapped tokens: the cohort's first step
    y, counters, _ = jax.vmap(one, in_axes=(None, 0))(
        made[0][0], jnp.stack([made[0][1], made[0][1]]))
    np.testing.assert_allclose(y[1], one(*made[0][:2])[0], rtol=2e-5,
                               atol=2e-5)


def _eqns(jaxpr, bounded_side_only=True):
    """Every equation of ``jaxpr`` and of the programs it calls; of a
    ``cond``, only the side taken when the predicate holds (the rows
    fit the bounded buffer) unless told otherwise."""
    for eqn in jaxpr.eqns:
        yield eqn
        for name, value in eqn.params.items():
            values = value if isinstance(value, (tuple, list)) else (value,)
            if (eqn.primitive.name == "cond" and name == "branches"
                    and bounded_side_only):
                values = values[1:]
            for v in values:
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, bounded_side_only)


#: a layer share under ``remat``: with no policy (forward, recomputation
#: and backward) and with ``DecoderLM``'s (forward and backward: the
#: routing and the rows are kept)
REMATS = {"no_policy": None,
          "decoder_policy": jax.checkpoint_policies.save_only_these_names(
              *DECODER_KEPT)}


@pytest.mark.parametrize("remat", list(REMATS))
@pytest.mark.parametrize("mapped", [False, True], ids=["unmapped", "vmap"])
def test_no_worst_case_sized_array_on_the_bounded_path(mapped, remat):
    """Forward, recomputation (where the policy keeps nothing) and
    backward of a layer share under
    ``remat``: outside the ``cond`` sides that run when the rows do not
    fit, no array has ``N x top_k`` rows by a model or expert width (a
    differentiated ``cond`` would return both sides' residuals) — but
    for the result of the gather in which every assignment reads its
    row of the buffer's 128, and the select that makes a zero of what no
    held expert fills (4 ways a token are no whole tile, so it is cut
    way by way: ``ops/moe._by_slot``)."""
    p, h, _ = _steered(jax.random.key(19), *STEERED["well_under"])
    weigh = jnp.ones_like(h)
    layer = jax.checkpoint(_share, policy=REMATS[remat])
    fn = lambda p, h: _value_and_gradients(layer, p, h, weigh)[2]
    if mapped:
        fn, h = jax.vmap(fn, in_axes=(None, 0)), jnp.stack([h, h])
    jaxpr = jax.make_jaxpr(fn)(p, h).jaxpr
    lead = (2,) if mapped else ()

    own = {leaf.shape for leaf in jax.tree.leaves(p)}  # w1: (4, 64, 32)

    def wide(eqns):
        found = {}
        for eqn in eqns:
            for v in eqn.outvars:
                s = v.aval.shape
                # a mapped batch: the stacked arrays, and one client's
                # on its way into the stack
                s = s[1:] if mapped and s[:1] in (lead, (1,)) else s
                if s not in own and len(s) >= 2 and s[-1] in (64, 32) and (
                        s[-2] == 256 or s[-3:-1] == (4, 64)):
                    found.setdefault(s, []).append(eqn)
        return found

    found = wide(_eqns(jaxpr))
    assert set(found) == {(4, 64, 64)}
    made = {e.primitive.name for e in found[(4, 64, 64)]}
    # the gather, way by way (once a client: no mapped axis on it;
    # forward inside the ``cond`` that reads zeros where no row is
    # held), the select that puts the zeros in in the rule, and a mapped
    # batch's stack of them
    assert "gather" in made and made <= {
        "gather", "cond", "select_n", "broadcast_in_dim", "jit",
        "custom_vmap_call", "concatenate"}
    assert all(e.invars[0].aval.shape == (128, 64)
               for e in found[(4, 64, 64)] if e.primitive.name == "gather")
    # the other side holds them, as it must: it is looked at
    assert (256, 32) in wide(_eqns(jaxpr, bounded_side_only=False))


def _unwritten(fill):
    """:func:`MOE.grouped_product` as the chip runs it: rows past
    ``sum(sizes)`` hold ``fill``, in the result and in the rows'
    cotangent (the CPU's expansion writes zeros there)."""
    real = MOE.grouped_product
    past = lambda x, sizes: (jnp.arange(x.shape[-2]) >= jnp.sum(sizes))[
        :, None]

    @jax.custom_vjp
    def double(x, w, sizes):
        return jnp.where(past(x, sizes), fill, real(x, w, sizes))

    def fwd(x, w, sizes):
        return double(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        d_x, d_w = jax.vjp(lambda x, w: real(x, w, sizes), x, w)[1](g)
        return jnp.where(past(x, sizes), fill, d_x), d_w, None

    double.defvjp(fwd, bwd)
    return double


@pytest.mark.parametrize("case", ["well_under", "one_over"])
def test_rows_the_chip_leaves_unwritten_reach_nothing(case, monkeypatch):
    """The chip leaves the rows past the held groups unwritten in a
    grouped product's result and in the rows' cotangent (PR 27's first
    chip run read NaN losses no CPU test had seen). With NaN there, on
    the bounded path (rows from the held count to 128) and on the
    worst-case one, the output and every gradient are finite and equal
    what they are without."""
    key = jax.random.key(23)
    p, h, _ = _steered(key, *STEERED[case])
    weigh = jax.random.normal(jax.random.fold_in(key, 2), h.shape)
    layer = jax.checkpoint(_share)
    want = _value_and_gradients(layer, p, h, weigh)
    monkeypatch.setattr(MOE, "grouped_product", _unwritten(jnp.nan))
    y, counters, grads = _value_and_gradients(layer, p, h, weigh)
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves((y, grads)))
    np.testing.assert_allclose(y, want[0], rtol=1e-6, atol=1e-6)
    _assert_trees_close(grads, want[2], rtol=1e-5)
    assert float(counters[3]) == (0.0 if case == "one_over" else 256.0)


def _sim(config, block, seq=TD.SEQ, vocab=TD.VOCAB):
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.data.federated import FederatedData

    rng = np.random.default_rng(5)
    seq = rng.integers(0, vocab, (32, seq + 1)).astype(np.int32)
    maps = {c: np.arange(4 * c, 4 * c + 4) for c in range(8)}
    data = FederatedData(
        seq[:, :-1], seq[:, 1:], seq[:8, :-1], seq[:8, 1:], maps,
        {c: np.arange(c, c + 1) for c in range(8)}, vocab, "nwp")
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
                     "moe_rows_max_expert", "moe_rows_compact",
                     "moe_rows_combined", "moe_rows_held_by_client"):
            assert stacked[name] == bulk[name], name
        # 64 tokens top-4 a step, 4 of 16 experts held: a bounded buffer
        # of 128 rows, which fresh routers (64 rows expected) stay under
        assert 0 < stacked["moe_rows_compact"] <= stacked["moe_rows_routed"]
        # 4 ways over 4 held: a token's slots are its ways
        assert stacked["moe_rows_combined"] == stacked["moe_rows_routed"]
        assert len(bulk["moe_rows_held_by_client"]) == 2
        assert sum(bulk["moe_rows_held_by_client"]) == bulk["moe_rows_held"]
        np.testing.assert_allclose(
            stacked["train_loss"], bulk["train_loss"], rtol=1e-5)
    assert "test_acc" in sinks[1][-1]


def test_log_span_carries_the_counters():
    from fedml_tpu.core.tracing import log_span

    attrs = log_span({"round": 3, "train_loss": 1.0, "moe_rows_held": 9.0,
                      "moe_rows_compact": 64.0,
                      "moe_rows_held_by_client": [4.0, 5.0]}).attrs
    assert attrs == {"round": 3, "moe_rows_held": 9, "moe_rows_compact": 64,
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


# ---------------------------------------------------------------------------
# a stack of sparse-attention layers (``keye-vl2-a3b-share8`` at tiny widths)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_keye(tmp_path_factory):
    config = TK.tiny_config()
    ref = TK.load_reference(str(tmp_path_factory.mktemp("keye_ref")), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, TK.SEQ + 1), 0, TK.VOCAB)
    return config, ref, model, variables, tokens


INDEX_LEAVES = ("index_q_proj", "index_k_proj", "index_w_proj")


def test_sparse_attention_stack_against_reference_logits_and_gradients(
        tiny_keye):
    """float32, two layers of selected-keys attention (64 tokens, 16
    keys a query, per-head q / k norms) over softmax-routed experts (2
    of 8 held, none shared): logits, every parameter's gradient — the
    index projections' exactly zero on both sides — and the counters."""
    _, ref, model, variables, tokens = tiny_keye
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
        if any(name in jax.tree_util.keystr(path) for name in INDEX_LEAVES):
            assert not np.asarray(g).any() and not np.asarray(r).any()
            continue
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-3 * scale, (
            jax.tree_util.keystr(path))
        assert scale > 1e-9, jax.tree_util.keystr(path)  # it is trained
    per_sequence = sum(min(t + 1, 16) for t in range(TK.SEQ))
    assert float(counted["attn_keys_selected"]) == 2 * TK.LAYERS * (
        per_sequence)
    assert float(counted["attn_keys_causal"]) == 2 * TK.LAYERS * (
        TK.SEQ * (TK.SEQ + 1) // 2)
    assert float(counted["moe_rows_routed"]) == x.size * 2 * TK.LAYERS


def test_topk_of_the_sequence_length_selects_every_causal_key(tmp_path):
    """``topk >= T``: the selected share is 100 % and the stack is the
    same stack with the index's choice taken away (every causal key),
    which the reference of the same sizes confirms."""
    config = TK.tiny_config(
        sparse_attention={"index_heads": 4, "index_head_dim": 8,
                          "topk": TK.SEQ})
    ref = TK.load_reference(str(tmp_path), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(6))
    x = jax.random.randint(jax.random.key(7), (1, TK.SEQ), 0, TK.VOCAB)
    logits, _, counted = model.apply_train_counted(
        variables, x, jax.random.key(0))
    assert float(counted["attn_keys_selected"]) == float(
        counted["attn_keys_causal"]) == TK.LAYERS * TK.SEQ * (TK.SEQ + 1) / 2
    np.testing.assert_allclose(
        logits, ref.forward(variables, x, True)[0], rtol=2e-4, atol=2e-4)


def test_a_round_returns_the_index_parameters_unchanged(tiny_keye):
    """The selection is a set: under the next-token loss the index
    projections get exactly zero gradient, so a federated round (bulk
    engine, block of one) hands them back bit for bit while every other
    leaf moves; the round's record carries the two attention counts."""
    config = tiny_keye[0]

    class Sink:
        records = []

        def log(self, record):
            self.records.append(dict(record))

    sim = _sim(config, 1, seq=TK.SEQ, vocab=TK.VOCAB)
    before = jax.device_get(sim.init().variables)
    after = jax.device_get(sim.run(metrics_sink=Sink()).variables)
    flat = dict(jax.tree_util.tree_leaves_with_path(after))
    moved = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(before):
        name = jax.tree_util.keystr(path)
        if any(n in name for n in INDEX_LEAVES):
            np.testing.assert_array_equal(leaf, flat[path], err_msg=name)
        else:
            moved += int(not np.array_equal(leaf, flat[path]))
    assert moved == len(flat) - 3 * TK.LAYERS
    record = Sink.records[0]
    steps = 2 * 2  # clients a round x steps a client, 2 sequences a step
    assert record["attn_keys_causal"] == steps * 2 * TK.LAYERS * (
        TK.SEQ * (TK.SEQ + 1) // 2)
    assert 0 < record["attn_keys_selected"] < record["attn_keys_causal"]
    assert record["moe_rows_routed"] == steps * 2 * TK.SEQ * 2 * TK.LAYERS


@pytest.mark.parametrize("change, message", [
    ({"sparse_attention": {"index_heads": 4, "index_head_dim": 8,
                           "topk": 0}}, "at least 1"),
    ({"sparse_attention": {"index_heads": 4, "topk": 16}},
     "lacks index_head_dim"),
    ({"sparse_attention": None}, "lacks index_heads"),
    ({"router_scoring": "argmax"}, "unknown router_scoring"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    extra = {**TK.tiny_config()["model"]["extra"], **change}
    with pytest.raises(ValueError, match=message):
        create_model(ModelConfig(
            name="decoder", num_classes=TK.VOCAB, input_shape=(TK.SEQ,),
            extra=tuple(extra.items())))


def test_log_span_carries_the_attention_counters():
    from fedml_tpu.core.tracing import log_span

    attrs = log_span({"round": 3, "train_loss": 1.0,
                      "attn_keys_selected": 904.0,
                      "attn_keys_causal": 2080.0}).attrs
    assert attrs == {"round": 3, "attn_keys_selected": 904,
                     "attn_keys_causal": 2080}


@pytest.mark.parametrize("layers, millions", [(5, 562.3), (6, 659.2)])
def test_published_keye_share_parameter_count(layers, millions):
    """The cut Keye-VL-2.0-30B-A3B language model as the configuration's
    file gives it (5 layers) and at the depth it passed over (6),
    counted from ``eval_shape`` alone."""
    config = TK.real_config()
    extra = config["model"]["extra"]
    assert len(extra["layer_types"]) == 5  # what the file runs
    for key in ("heads_per_layer", "layer_types", "mlp_layer_types"):
        extra[key] = extra[key][:1] * layers
    model = create_model(_model_config(config))
    assert model.counters == MOE.MOE_COUNTERS + A.ATTN_COUNTERS
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert round(count / 1e6, 1) == millions, count
    layer = shapes["params"]["layer_0"]
    per_layer = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(layer))
    assert round(per_layer / 1e6, 2) == 96.90
    assert layer["experts_w1"].shape == (16, 2048, 768)
    assert layer["router"].shape == (2048, 128)
    assert layer["q_proj"]["kernel"].shape == (2048, 32 * 128)
    assert layer["k_proj"]["kernel"].shape == (2048, 4 * 128)
    assert layer["index_q_proj"]["kernel"].shape == (2048, 16 * 64)
    assert layer["index_k_proj"]["kernel"].shape == (2048, 64)
    assert layer["index_w_proj"]["kernel"].shape == (2048, 16)
    assert layer["q_norm"]["scale"].shape == (128,)
    assert shapes["params"]["lm_head"]["kernel"].shape == (2048, 18992)


# ---------------------------------------------------------------------------
# what a rematerialised layer keeps for its backward pass
# ---------------------------------------------------------------------------

KINDS = ("full_attention", "sliding_attention", "sparse_attention")
KERNELS = ("splash_mqa_fwd", "splash_mqa_dq", "splash_mqa_dkv",
           "sparse_select_top_k", "sparse_index_scores")


def _kept_stack(kind, layers=2):
    """Two layers of one attention kind over dense feed-forwards, at
    sizes the splash kernel tiles (heads of 128; 256 tokens are two
    blocks of 128 under :func:`kernels`): a window of 40, or an index of
    2 heads of 8 that keeps 40 keys a query."""
    return decoder_from_extra({
        "hidden_size": 64, "head_dim": 128, "num_key_value_heads": 1,
        "heads_per_layer": [2] * layers, "layer_types": [kind] * layers,
        "mlp_layer_types": ["dense"] * layers, "intermediate_size": 64,
        "sliding_window": 40, "qk_norm": True,
        "rope": {k: {"rope_theta": 1e4} for k in KINDS},
        "sparse_attention": {"index_heads": 2, "index_head_dim": 8,
                             "topk": 40}}, 32)


@pytest.fixture
def kernels(monkeypatch):
    """The chip's branch of ``ops/attention.py`` with its four kernels
    in the Pallas interpreter, in blocks of 128."""
    monkeypatch.setattr(A, "BLOCK", 128)
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    for name in ("splash_attention", "selected_splash",
                 "index_scores_kernel", "select_top_k_kernel"):
        monkeypatch.setattr(A, name, functools.partial(
            getattr(A, name), interpret=True))


@pytest.fixture
def plain_remat(monkeypatch):
    """-> a context in which ``DecoderLM`` wraps its layers in
    ``nn.remat`` with no policy: only a layer's input is kept."""
    import flax.linen as nn

    keeps_names = nn.remat

    @contextlib.contextmanager
    def plain():
        with monkeypatch.context() as m:
            m.setattr(nn, "remat", lambda cls, policy: keeps_names(cls))
            yield

    return plain


def _training_gradient(model, tokens):
    def loss(params):
        logits, _ = model.apply({"params": params}, tokens,
                                mutable=["counters"])
        return jnp.mean(logits ** 2)

    return jax.grad(loss)


def _kernel_calls(fn, *args):
    """How often each of :data:`KERNELS` is called in ``fn``'s jaxpr."""
    names = list(_pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr))
    return {k: sum(name.startswith(k) for name in names) for k in KERNELS}


@pytest.mark.parametrize("kind", KINDS)
def test_a_layer_runs_its_forward_kernels_once_a_training_step(
        kind, kernels, plain_remat):
    """In the jaxpr of a training gradient every layer calls the
    blockwise kernel's forward ONCE (its output and log-sum-exp are
    kept: ``ops/attention.KEPT``) and its ONE backward kernel once (the
    dk/dv kernel forms dq as well: ``ops/attention._block_sizes``) —
    and a sparse-attention layer its index and its top-k once, since the
    selection is kept — where ``nn.remat`` with no policy runs each
    forward a second time."""
    model = _kept_stack(kind)
    tokens = jax.random.randint(jax.random.key(0), (1, 256), 0, 32)
    params = jax.eval_shape(model.init, jax.random.key(1), tokens)["params"]
    selects = 2 * (kind == "sparse_attention")
    once = {"splash_mqa_fwd": 2, "splash_mqa_dq": 0, "splash_mqa_dkv": 2,
            "sparse_select_top_k": selects, "sparse_index_scores": selects}
    assert _kernel_calls(_training_gradient(model, tokens), params) == once
    with plain_remat():
        assert _kernel_calls(_training_gradient(model, tokens), params) == {
            **once, "splash_mqa_fwd": 4, "sparse_select_top_k": 2 * selects,
            "sparse_index_scores": 2 * selects}
    # no gradient, nothing to keep: the evaluator's forward pass
    forward = lambda p: model.apply({"params": p}, tokens)
    assert _kernel_calls(forward, params) == {
        **once, "splash_mqa_dkv": 0}


@pytest.mark.parametrize("path", ["written_out", "kernels"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_kept_values_leave_the_gradient_as_it_was(
        kind, path, plain_remat, request):
    """What a layer keeps is what its second run would compute again
    from the same inputs: every parameter's gradient equals the one
    under ``nn.remat`` with no policy to the BIT, on the path the CPU
    runs and on the chip's kernels (Pallas interpreter)."""
    if path == "kernels":
        request.getfixturevalue("kernels")
    model = _kept_stack(kind)
    tokens = jax.random.randint(jax.random.key(0), (2, 256), 0, 32)
    params = model.init(jax.random.key(1), tokens)["params"]
    kept = jax.jit(_training_gradient(model, tokens))(params)
    with plain_remat():
        plain = jax.jit(_training_gradient(model, tokens))(params)
    for (where, a), b in zip(jax.tree_util.tree_leaves_with_path(kept),
                             jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b, jax.tree_util.keystr(where))
        trained = not any(n in jax.tree_util.keystr(where)
                          for n in INDEX_LEAVES)
        assert bool(jnp.any(a != 0)) == trained, jax.tree_util.keystr(where)


# ... and of a sparse layer: its routing and the rows its backward reads

SLOT_FORMS = {"a_slot_a_way": (4, 4), "a_slot_a_held_expert": (3, 4)}
# each activation as its configuration has it: Laguna's and Keye's
# (sigmoid router, a shared expert), SmallThinker's (softmax router
# before attention), Nemotron's (two matrices, latent experts)
AS_CONFIGURED = {
    MOE.SILU_GATED: {"shared_expert_intermediate_size": 32},
    MOE.RELU_GATED: {"router_scoring": "softmax",
                     "router_input": "attention_input"},
    MOE.RELU2: {"moe_latent_size": 16, "shared_expert_intermediate_size": 32},
}


def _kept_sparse_stack(form, activation, layers=2):
    """Two full-attention layers (heads of 128, as :func:`_kept_stack`)
    over sparse feed-forwards: ``count`` of 16 experts held from the
    fifth on, ``top_k`` a token (:data:`SLOT_FORMS`)."""
    count, top_k = SLOT_FORMS[form]
    return decoder_from_extra({
        "hidden_size": 64, "head_dim": 128, "num_key_value_heads": 1,
        "heads_per_layer": [2] * layers, "qk_norm": True,
        "layer_types": ["full_attention"] * layers,
        "mlp_layer_types": ["sparse"] * layers, "intermediate_size": 64,
        "rope": {"full_attention": {"rope_theta": 1e4}},
        "num_experts": 16, "num_experts_per_tok": top_k,
        "experts_held": [4, count], "moe_intermediate_size": 32,
        "routed_scaling_factor": 2.5, "mlp_activation": activation,
        **AS_CONFIGURED[activation]}, 32)


def _sparse_layer_calls(fn, *args):
    """How often ``fn``'s jaxpr calls the grouped product (on the side
    of a ``cond`` that runs when the rows fit the bounded buffer), the
    router's ranking (``ops/moe.py:largest``, a program of its own
    name) and ``sort``."""
    eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    names = [e.primitive.name for e in eqns]
    return {"ragged_dot_general": names.count("ragged_dot_general"),
            "largest": sum(e.primitive.name == "jit"
                           and e.params["name"] == "largest" for e in eqns),
            "sort": names.count("sort")}


@pytest.mark.parametrize("activation", list(AS_CONFIGURED))
@pytest.mark.parametrize("form", list(SLOT_FORMS))
def test_a_sparse_layer_runs_forward_once_a_training_step(
        form, activation, plain_remat):
    """In the jaxpr of a training gradient every sparse layer calls the
    grouped product once forward and twice backward (by the rows, by
    the matrices) for each of its experts' matrices — 9 calls, 6 where
    the experts have two — ranks once and sorts once (twice where a
    token's slots are its ways: the order and its inverse): its routing
    and ``(rows, into, out)`` are kept (``ops/moe.KEPT``). ``nn.remat``
    with no policy runs the forward products, the top-k and the sorts a
    second time: 12 (8) calls. One more sort, in the backward pass
    alone, is the embedding's: its rule orders the step's token ids
    (``ops/embedding.py``)."""
    model = _kept_sparse_stack(form, activation)
    count, top_k = SLOT_FORMS[form]
    tokens = jax.random.randint(jax.random.key(0), (1, 256), 0, 32)
    params = jax.eval_shape(model.init, jax.random.key(1), tokens)["params"]
    layers, matrices = 2, len(MOE.leading(activation)) + 1
    sorts = 2 if top_k <= count else 1
    once = {"ragged_dot_general": layers * 3 * matrices,
            "largest": layers, "sort": layers * sorts + 1}
    gradient = _training_gradient(model, tokens)
    assert _sparse_layer_calls(gradient, params) == once
    with plain_remat():
        assert _sparse_layer_calls(
            _training_gradient(model, tokens), params) == {
                "ragged_dot_general": layers * 4 * matrices,
                "largest": 2 * layers, "sort": 2 * layers * sorts + 1}
    # no gradient, nothing to keep: the evaluator's forward pass
    forward = lambda p: model.apply({"params": p}, tokens)
    assert _sparse_layer_calls(forward, params) == {
        "ragged_dot_general": layers * matrices,
        "largest": layers, "sort": layers * sorts}


@pytest.mark.parametrize("side", ["bounded", "worst_case"])
@pytest.mark.parametrize("activation", list(AS_CONFIGURED))
@pytest.mark.parametrize("form", list(SLOT_FORMS))
def test_the_kept_routing_and_rows_leave_the_gradient_as_it_was(
        form, activation, side, plain_remat, monkeypatch):
    """What a sparse layer keeps is what its second run would compute
    again from the same inputs: every parameter's gradient equals the
    one under ``nn.remat`` with no policy to the BIT — through the
    bounded row buffer (which keeps its rows) and, with a buffer of one
    tile that the held rows overflow, through the worst-case one (which
    keeps zeros and runs forward again inside its own rule)."""
    if side == "worst_case":
        monkeypatch.setattr(MOE, "ROW_SHARE", 0)
        monkeypatch.setattr(MOE, "ROW_FLOOR", 1 / 16)
    model = _kept_sparse_stack(form, activation)
    tokens = jax.random.randint(jax.random.key(0), (2, 256), 0, 32)
    params = model.init(jax.random.key(1), tokens)["params"]
    _, counted = model.apply({"params": params}, tokens,
                             mutable=["counters"])
    compact = float(counted["counters"]["moe_rows_compact"])
    assert compact == (2 * 512 * 4 if side == "bounded" else 0.0)
    kept = jax.jit(_training_gradient(model, tokens))(params)
    with plain_remat():
        plain = jax.jit(_training_gradient(model, tokens))(params)
    for (where, a), b in zip(jax.tree_util.tree_leaves_with_path(kept),
                             jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b, jax.tree_util.keystr(where))
        assert bool(jnp.any(a != 0)), jax.tree_util.keystr(where)


# ---------------------------------------------------------------------------
# the embedding's gradient: ``ops/embedding.py`` against ``jnp.take``'s
# ---------------------------------------------------------------------------

ROWS, WIDTH, TOKENS, MAPPED = 96, 16, 64, 3


def _zipf(rng, shape, rows=ROWS):
    """Ids by weight 1 / rank over a permutation of the rows, as
    ``benchmarks/lib/traffic.py`` draws a topic's tokens."""
    p = 1.0 / np.arange(1, rows + 1)
    return rng.permutation(rows)[rng.choice(rows, size=shape, p=p / p.sum())]


#: ids of one client's step by how they repeat -> (draw, table rows,
#: width); the last two are the cells' ``B x T`` at a small width
ID_CASES = {
    "all_distinct": (lambda rng: rng.permutation(ROWS)[:TOKENS], ROWS, WIDTH),
    "one_id_every_token": (lambda rng: np.full((TOKENS,), 41), ROWS, WIDTH),
    "zipf": (lambda rng: _zipf(rng, (TOKENS,)), ROWS, WIDTH),
    "first_and_last_row": (
        lambda rng: rng.choice([0, ROWS - 1, 5], size=(TOKENS,)), ROWS, WIDTH),
    # jnp.take wraps a negative id once and reads NaN past either end
    "outside_the_table": (lambda rng: rng.choice(
        [-1, -ROWS, -ROWS - 1, ROWS, ROWS + 7, 3], size=(TOKENS,)),
        ROWS, WIDTH),
    "b2_t2048": (lambda rng: _zipf(rng, (2, 2048), 1000), 1000, 8),
    "b1_t8192": (lambda rng: _zipf(rng, (1, 8192), 1000), 1000, 8),
}


def _table_gradient(lookup):
    """The table's gradient of ``sum(lookup(table, ids) * w)``: the rows
    of ``w`` are the cotangent rows."""
    return jax.grad(lambda table, ids, w: jnp.sum(lookup(table, ids) * w))


def _take(table, ids):
    return jnp.take(table, ids, axis=0)


def _lookup_operands(case, mapped=False, dtype=jnp.float32):
    draw, rows, width = ID_CASES[case]
    rng = np.random.default_rng(7)
    ids = np.stack([draw(rng) for _ in range(MAPPED)])
    ids = jnp.asarray(ids if mapped else ids[0], jnp.int32)
    lead = (MAPPED,) if mapped else ()
    table = jnp.asarray(rng.normal(size=lead + (rows, width)), dtype)
    w = jnp.asarray(rng.normal(size=ids.shape + (width,)), dtype)
    return table, ids, w


def _assert_float32_rounding(got, want):
    """Equal to 1e-5 of the largest entry: the order of a float32 sum
    differs and nothing else."""
    assert got.dtype == want.dtype and got.shape == want.shape
    largest = float(jnp.max(jnp.abs(want)))
    assert largest > 0.1
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * largest


@pytest.mark.parametrize("mapped", [False, True], ids=["alone", "vmap"])
@pytest.mark.parametrize("case", list(ID_CASES))
def test_embedding_rule_equals_the_gathers_own_gradient(case, mapped):
    """float32: the lookup is ``jnp.take``'s bit for bit and the rule's
    table gradient ``jax.grad``'s through it to float32 rounding — under
    ``jit``, alone and under ``vmap`` over a leading block axis (every
    operand mapped, as the bulk engine maps a block of clients)."""
    table, ids, w = _lookup_operands(case, mapped)
    wrap = (lambda f: jax.jit(jax.vmap(f))) if mapped else jax.jit
    np.testing.assert_array_equal(
        wrap(EMB.embedding_lookup)(table, ids), wrap(_take)(table, ids))
    _assert_float32_rounding(
        wrap(_table_gradient(EMB.embedding_lookup))(table, ids, w),
        wrap(_table_gradient(_take))(table, ids, w))


@pytest.mark.parametrize(
    "dtype", [jnp.int8, jnp.uint8, jnp.int16, jnp.uint32, jnp.int32])
def test_embedding_rule_takes_ids_of_any_integer_type(dtype):
    table, ids, w = _lookup_operands("zipf")
    _assert_float32_rounding(
        _table_gradient(EMB.embedding_lookup)(table, ids.astype(dtype), w),
        _table_gradient(_take)(table, ids, w))


@pytest.mark.parametrize("block", [5, 8, TOKENS])
@pytest.mark.parametrize("case", [
    "zipf", "one_id_every_token", "first_and_last_row", "outside_the_table"])
def test_embedding_sums_do_not_depend_on_where_the_blocks_are_cut(
        case, block):
    """``block`` sorted tokens a product: a run of one id that crosses
    block ends (``one_id_every_token`` crosses every one) still sums
    whole, and a token count that is no multiple of the block is filled
    up."""
    table, ids, w = _lookup_operands(case)
    _assert_float32_rounding(
        EMB.distinct_row_sums(w, ids, ROWS, block=block),
        _table_gradient(_take)(table, ids, w))


@pytest.mark.parametrize("case", ["zipf", "one_id_every_token", "b2_t2048"])
def test_embedding_rule_sums_bfloat16_rows_in_float32(case):
    """bfloat16 table and cotangent rows: the rule sums in float32 and
    rounds once, ``jnp.take``'s scatter-add rounds at every row, so the
    rule is no further from the float32 sums of the same rows — one
    rounding of them."""
    table, ids, w = _lookup_operands(case, dtype=jnp.bfloat16)
    exact = _table_gradient(_take)(
        table.astype(jnp.float32), ids, w.astype(jnp.float32))
    got = _table_gradient(EMB.embedding_lookup)(table, ids, w)
    scattered = _table_gradient(_take)(table, ids, w)
    assert got.dtype == scattered.dtype == jnp.bfloat16
    far = lambda g: float(jnp.max(jnp.abs(g.astype(jnp.float32) - exact)))
    assert far(got) <= far(scattered)
    assert far(got) <= 2.0 ** -8 * float(jnp.max(jnp.abs(exact)))


@contextlib.contextmanager
def _plain_embed(monkeypatch):
    """``DecoderLM`` over ``flax.linen.Embed`` itself, as it was."""
    import flax.linen as nn

    with monkeypatch.context() as m:
        m.setattr(DEC, "Embedding", nn.Embed)
        yield


def _table_scatter_adds(fn, params, table_shape):
    """-> ``unique_indices`` of every scatter-add in ``fn``'s jaxpr
    whose operand is a ``table_shape`` array."""
    jaxpr = jax.make_jaxpr(fn)(params).jaxpr
    return [eqn.params["unique_indices"]
            for eqn in _eqns(jaxpr, bounded_side_only=False)
            if eqn.primitive.name in ("scatter-add", "scatter_add")
            and eqn.invars[0].aval.shape == table_shape]


def test_decoder_embedding_is_flaxs_but_for_the_gradients_form(
        tiny, monkeypatch):
    """Against the same stack over ``flax.linen.Embed``: the parameter
    tree (names, shapes, dtypes, the drawn values) and the logits are
    the same to the bit, every parameter's gradient to float32 rounding,
    and the training gradient's jaxpr holds no scatter-add into a
    ``[vocabulary, hidden]`` array whose indices are not marked unique
    (``jnp.take``'s transpose is one, and is found where it runs)."""
    config, _, model, _, tokens = tiny
    x, y = tokens[:, :-1], tokens[:, 1:]
    shape = (TD.VOCAB, config["model"]["extra"]["hidden_size"])

    def built(m):
        params = m.init(jax.random.key(5))["params"]

        def step(params):
            logits, _, _ = m.apply_train_counted(
                {"params": params}, x, jax.random.key(0))
            return _loss(logits, y), logits

        (_, logits), grads = jax.jit(
            jax.value_and_grad(step, has_aux=True))(params)
        return params, logits, grads, _table_scatter_adds(
            jax.grad(lambda p: step(p)[0]), params, shape)

    ours = built(model)
    with _plain_embed(monkeypatch):
        plain = built(create_model(_model_config(config)))
    assert ours[0]["embed"]["embedding"].shape == shape
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), ours[0])
            == jax.tree.map(lambda a: (a.shape, a.dtype), plain[0]))
    jax.tree.map(np.testing.assert_array_equal, ours[0], plain[0])
    np.testing.assert_array_equal(ours[1], plain[1])
    wanted = dict(jax.tree_util.tree_leaves_with_path(plain[2]))
    for path, g in jax.tree_util.tree_leaves_with_path(ours[2]):
        want = wanted[path]
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(g - want))) <= 1e-5 * scale, (
            jax.tree_util.keystr(path))
    assert all(ours[3]), ours[3]
    assert plain[3] == [False]
