"""The decoder stack's single-mixer layers — Mamba-2 state space, a
latent sparse-expert layer of squared-ReLU experts, attention with no
position term — against the plain reference of
``benchmarks/configs/nemotron3-super-share64`` at tiny widths; the
chip's share of each layer kind tied to the uncut layer; what
``decoder_from_extra`` refuses; the published share's size."""

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

import tiny_nemotron as TN  # noqa: E402
from test_decoder import (  # noqa: E402
    REMATS, _eqns, _loss, _model_config, _sim,
)
from test_ssm import _count  # noqa: E402

from fedml_tpu.config import ModelConfig  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import decoder  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    DecoderLayer, decoder_from_extra,
)
from fedml_tpu.ops import moe as MOE  # noqa: E402
from fedml_tpu.ops import ssm as SSM  # noqa: E402


@pytest.mark.parametrize("pattern", ["M", "E", "*", TN.PATTERN])
def test_program_against_reference_logits_and_gradients(pattern, tmp_path):
    """float32: each of the three layer kinds alone, and the stack
    ``MEM*E``: the variable trees agree, and so do the logits and every
    parameter's gradient (every parameter is trained)."""
    config = TN.tiny_config(pattern=pattern)
    ref = TN.load_reference(str(tmp_path), config)
    model = create_model(_model_config(config))
    variables = jax.jit(ref.init)(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, TN.SEQ + 1), 0, TN.VOCAB)
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
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(g_ref))
    for path, g in jax.tree_util.tree_leaves_with_path(g_ours):
        r = flat_ref[path]
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-3 * scale, (
            jax.tree_util.keystr(path))
        assert scale > 1e-9, jax.tree_util.keystr(path)  # it is trained
    sparse = pattern.count("E")
    if sparse:
        assert float(counted["moe_rows_routed"]) == x.size * 4 * sparse
        assert 0 < float(counted["moe_rows_held"]) < x.size * 4 * sparse
    else:
        assert counted == {}


# ---------------------------------------------------------------------------
# the share tied to the model: all shares of a layer add up to the uncut one
# ---------------------------------------------------------------------------

HIDDEN = 64


def _one_layer(kind, **share):
    """A one-layer stack of ``kind`` at the tiny sizes, ``share``'s keys
    over the whole layer's -> (frozen configuration, its layer)."""
    extra = {**TN.tiny_config(pattern=kind)["model"]["extra"],
             "key_value_heads_held": None, "query_heads_held": None,
             "shared_expert_columns_held": None, "experts_held": [0, 16],
             "state_space": {**TN.STATE_SPACE, "heads_held": None}}
    extra["state_space"].update(share.pop("state_space", {}))
    extra.update(share)
    cfg = decoder_from_extra(extra, TN.VOCAB).cfg
    return cfg, DecoderLayer(cfg, 0)


def _mixer(layer, params, x):
    """What the layer adds to ``x``."""
    out, _ = layer.apply({"params": params}, x)
    return out - x


def _whole(kind, x):
    _, layer = _one_layer(kind)
    params = layer.init(jax.random.key(5), x)["params"]
    # norm scales and the convolution's bias away from their init values
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.key(6), p.shape),
        params)
    return params, _mixer(layer, params, x)


def _tokens():
    return jax.random.normal(jax.random.key(8), (2, TN.SEQ, HIDDEN))


def test_mamba_head_shares_add_up_to_the_uncut_layer():
    """The 8 heads in 4 groups as 4 shares of one group (2 heads, the
    group's B / C, its convolution channels and its group of the gated
    norm) and as 2 shares of two: every share's ``mixer(norm(x))`` sums
    to the uncut layer's. The norms' statistics are a group's own, so
    nothing crosses shares but the final sum."""
    s = TN.STATE_SPACE
    heads, p, groups, n = (
        s["num_heads"], s["head_dim"], s["n_groups"], s["state_size"])
    inner, per = heads * p, heads // groups
    x = _tokens()
    params, whole = _whole("M", x)
    for held_groups in (1, 2):
        total = jnp.zeros_like(whole)
        for first in range(0, groups, held_groups):
            hs = slice(first * per, (first + held_groups) * per)
            ch = slice(hs.start * p, hs.stop * p)  # the heads' channels
            gs = slice(first * n, (first + held_groups) * n)
            cut = lambda v, *parts: jnp.concatenate(
                [v[..., o + r.start:o + r.stop] for o, r in parts], -1)
            conv = ((0, ch), (inner, gs), (inner + groups * n, gs))
            mine = {
                "ssm_norm": params["ssm_norm"],
                "in_proj": {"kernel": cut(
                    params["in_proj"]["kernel"], (0, ch),
                    *((inner + o, r) for o, r in conv),
                    (2 * inner + 2 * groups * n, hs))},
                "conv_kernel": cut(params["conv_kernel"], *conv),
                "conv_bias": cut(params["conv_bias"], *conv),
                "dt_bias": params["dt_bias"][hs],
                "A_log": params["A_log"][hs], "D": params["D"][hs],
                "gate_norm": params["gate_norm"][ch],
                "out_proj": {"kernel": params["out_proj"]["kernel"][ch]},
            }
            _, layer = _one_layer("M", state_space={
                "heads_held": [hs.start, hs.stop - hs.start]})
            total += _mixer(layer, mine, x)
        np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


def test_attention_head_shares_add_up_to_the_uncut_layer():
    """8 query heads over 2 key-value heads as 4 shares of 2 query heads
    over the ONE key-value head they read, and as 2 shares of a whole
    key-value head with its 4 query heads."""
    d = 16
    x = _tokens()
    params, whole = _whole("*", x)
    cols = lambda name, a, b: {
        "kernel": params[name]["kernel"][:, a * d:b * d]}
    for query_heads in (2, 4):
        total = jnp.zeros_like(whole)
        for first in range(0, 8, query_heads):
            kv = first // 4
            mine = {
                "attn_norm": params["attn_norm"],
                "q_proj": cols("q_proj", first, first + query_heads),
                "k_proj": cols("k_proj", kv, kv + 1),
                "v_proj": cols("v_proj", kv, kv + 1),
                "o_proj": {"kernel": params["o_proj"]["kernel"][
                    first * d:(first + query_heads) * d]},
            }
            _, layer = _one_layer(
                "*", query_heads_held=[first, query_heads],
                key_value_heads_held=[kv, 1])
            total += _mixer(layer, mine, x)
        np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


def test_expert_and_column_shares_add_up_to_the_uncut_layer():
    """16 latent experts as 4 shares of 4, each with 16 of the shared
    expert's 64 columns: the shares' outputs sum to the uncut layer's,
    and their held rows to every assignment made. What every chip
    computes alike — the router, the first latent projection — feeds
    its own share only; the second latent projection and the shared
    expert's second matrix are linear, so their shares' results add."""
    x = _tokens()
    params, whole = _whole("E", x)
    total, rows = jnp.zeros_like(whole), 0.0
    for share in range(4):
        e = slice(4 * share, 4 * share + 4)
        f = slice(16 * share, 16 * share + 16)
        mine = {**params, "experts_w1": params["experts_w1"][e],
                "experts_w2": params["experts_w2"][e],
                "shared_w1": params["shared_w1"][:, f],
                "shared_w2": params["shared_w2"][f]}
        _, layer = _one_layer("E", experts_held=[e.start, 4],
                              shared_expert_columns_held=[f.start, 16])
        out, counters = layer.apply({"params": mine}, x)
        total, rows = total + out - x, rows + float(counters[0])
        assert float(counters[1]) == x.shape[0] * x.shape[1] * 4
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    assert rows == x.shape[0] * x.shape[1] * 4


# ---------------------------------------------------------------------------
# ``moe_layer``: gated or squared-ReLU experts, at full or at latent width
# ---------------------------------------------------------------------------


def _moe_params(key, gated, latent, d=64, experts=16, f=32):
    ks = iter(jax.random.split(key, 12))
    n = lambda *s: jax.random.normal(next(ks), s) * s[-2] ** -0.5
    w = latent or d
    p = {"router": n(d, experts), "w1": n(experts, w, f),
         "w2": n(experts, f, w), "shared": (n(d, f), n(f, d))}
    if gated:
        p["w3"] = n(experts, w, f)
        p["shared"] = (n(d, f), n(d, f), n(f, d))
    if latent:
        p["latent"] = (n(d, w), n(w, d))
    return p


def _activation(p):
    """What :func:`_moe_params` drew ``p`` for, where no test says."""
    return MOE.SILU_GATED if "w3" in p else MOE.RELU2


def _written_out(p, h, first, count, top_k, scale, activation=None,
                 router_input=None):
    """The share written out: every held expert on every token, a mask
    for the chosen ones."""
    activation = activation or _activation(p)
    read = h if router_input is None else router_input
    prob = jax.nn.sigmoid(read @ p["router"])
    top_p, top_e = jax.lax.top_k(prob, top_k)
    w = scale * top_p / top_p.sum(-1, keepdims=True)
    u = h @ p["latent"][0] if "latent" in p else h
    y = jnp.zeros_like(u)
    for e in range(count):
        share = jnp.where(top_e == first + e, w, 0.0).sum(-1)
        y += share[:, None] * MOE.ffn(
            activation, u,
            *(p[m][e] for m in ("w1", "w3", "w2") if m in p))
    y = y @ p["latent"][1] if "latent" in p else y
    return y + MOE.ffn(activation, h, *p["shared"])


# (experts, first held, held, ways a token): what decides the combine's
# form. A token's slots are its ways where ``top_k <= count`` and the
# held experts where those are fewer; whole tiles of 8 slots are summed
# token by token, others slot by slot (``ops/moe._by_slot``).
SHAPES = {
    "ways_4_of_4_held": (16, 4, 4, 4),
    "ways_8_of_8_held": (32, 4, 8, 8),
    "slots_4_held_8_ways": (32, 4, 4, 8),
    "slots_3_held_4_ways": (16, 4, 3, 4),
    "slots_8_held_22_ways": (64, 8, 8, 22),
    # half the experts held: the bounded buffer is the worst case, one path
    "slots_2_held_3_ways_no_smaller_buffer": (4, 1, 2, 3),
}
TOKENS = 64


def _cases():
    """Every shape with gated and squared-ReLU experts at the hidden and
    at a latent width (fresh routers: the bounded side, unmapped), and,
    for latent squared-ReLU experts, on the worst-case side (``crowded``:
    every token names every held expert it can) and under ``vmap``."""
    for shape in SHAPES:
        yield shape, False, 24, "fresh", False
        yield shape, False, 24, "fresh", True
        if "no_smaller_buffer" in shape:  # one path: nothing to crowd
            continue
        for gated, latent in ((True, 0), (True, 24), (False, 0)):
            yield shape, gated, latent, "fresh", False
        yield shape, False, 24, "crowded", False
        yield shape, False, 24, "crowded", True


def _case_id(case):
    shape, gated, latent, steer, mapped = case
    return "-".join([shape, "gated" if gated else "relu2",
                     "latent" if latent else "hidden", steer,
                     "vmap" if mapped else "unmapped"])


def _routed(key, p, first, count, steer):
    """-> (``p``, tokens for its router): ``fresh`` as drawn;
    ``crowded`` with a marker feature, and a router row for it, that
    sends every token to all the held experts its ways can name."""
    h = jax.random.normal(key, (TOKENS, 64))
    if steer == "crowded":
        p = {**p, "router": (0.1 * p["router"]).at[0].set(0.0).at[
            0, first:first + count].set(9.0)}
        h = h.at[:, 0].set(1.0)
    return p, h


@pytest.mark.parametrize("case", list(_cases()), ids=_case_id)
def test_moe_layer_against_the_written_out_share(case):
    """Three gated matrices an expert (the two decoder configurations'
    path, which its own tests hold too) or two with ``relu(.)^2``
    between, at the hidden width or in a latent one: values, the
    tokens' gradient and every parameter's (the router's too) against
    the share written out, in both forms of the combine (:data:`SHAPES`),
    through the bounded buffer and through the worst-case one, unmapped
    and as a mapped batch, which goes one way together."""
    shape, gated, latent, steer, mapped = case
    experts, first, count, top_k = SHAPES[shape]
    key = jax.random.key(29)
    p = _moe_params(key, gated, latent, experts=experts)
    mine = {**p, **{m: p[m][first:first + count]
                    for m in ("w1", "w3", "w2") if m in p}}
    mine, h = _routed(jax.random.fold_in(key, 1), mine, first, count, steer)
    weigh = jax.random.normal(jax.random.fold_in(key, 2), h.shape)
    if mapped:  # the second instance fresh: the batch follows the first
        h = jnp.stack([h, jax.random.normal(
            jax.random.fold_in(key, 3), h.shape)])

    def share(p, h):
        y, counters = MOE.moe_layer(p, h, (first, count), top_k, 5.0,
                                    activation=_activation(p))
        return jnp.sum(y * weigh), (y, counters)

    def plain(p, h):
        y = _written_out(p, h, first, count, top_k, 5.0)
        return jnp.sum(y * weigh), y

    both = lambda fn: jax.value_and_grad(fn, argnums=(0, 1), has_aux=True)
    ours, theirs = both(share), both(plain)
    if mapped:
        ours, theirs = (jax.vmap(fn, in_axes=(None, 0))
                        for fn in (ours, theirs))
    (_, (y, counters)), grads = jax.jit(ours)(mine, h)
    (_, want), want_grads = jax.jit(theirs)(mine, h)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    flat = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        scale = float(jnp.max(jnp.abs(flat[path]))) + 1e-12
        assert float(jnp.max(jnp.abs(g - flat[path]))) <= 2e-4 * scale, (
            jax.tree_util.keystr(path))
    assert float(jnp.max(jnp.abs(grads[0]["router"]))) > 0
    counters = counters.reshape(-1, len(MOE.MOE_COUNTERS))
    nk = TOKENS * top_k
    buffer = MOE.row_buffer(TOKENS, top_k, count, experts)
    assert (buffer < nk) == ("no_smaller_buffer" not in shape)
    for row in counters.tolist():
        held, routed, compact, combined = (
            row[MOE.MOE_COUNTERS.index("moe_rows_" + name)]
            for name in ("held", "routed", "compact", "combined"))
        assert routed == nk and 0 < held <= nk
        assert compact == (nk if steer == "fresh" and buffer < nk else 0.0)
        assert combined == TOKENS * min(top_k, count)
    if steer == "crowded" and buffer < nk:  # over it, as steered
        assert counters[0, 0] == TOKENS * min(top_k, count) > buffer


@pytest.mark.parametrize("steer", [
    "random", "an_expert_with_no_rows", "every_token_on_one_expert",
    "tied_scores"])
def test_a_slots_row_is_where_the_orders_own_inverse_puts_it(steer):
    """6 held of 24 experts, 9 ways: ``_slot_rows``' running count finds
    for every held assignment the row ``argsort(order)`` gives it, the
    one past every buffer for a slot no way names, and in its last row
    the rows a held expert as counted over all ids. That rests on two
    facts: the sort by expert is STABLE (the group keys are all ties
    within an expert), and a token's ``top_k`` ids are distinct (also
    where its scores tie)."""
    n, experts, first, count, k = 40, 24, 5, 6, 9
    scores = jax.random.normal(jax.random.key(31), (n, experts))
    if steer == "an_expert_with_no_rows":
        scores = scores.at[:, first + 2].set(-30.0)
    elif steer == "every_token_on_one_expert":
        scores = scores.at[:, first + 1:first + count].set(-30.0).at[
            :, first].set(30.0)
    elif steer == "tied_scores":  # probabilities of exactly 1 and 0
        scores = 60.0 * jnp.sign(scores)
    top_e, _ = MOE.route_top_k(scores, k, 1.0)
    ids = np.sort(np.asarray(top_e), -1)
    assert (ids[:, 1:] != ids[:, :-1]).all()
    local = np.asarray(top_e) - first
    held = (local >= 0) & (local < count)
    group = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(group, stable=True)
    inverse = np.asarray(jnp.argsort(order)).reshape(n, k)
    sizes = jnp.bincount(group, length=count + 1)[:count].astype(jnp.int32)
    (pos, hit), counted = jax.jit(MOE._slot_rows, static_argnums=(1, 2))(
        jnp.asarray(local), count, n * k)
    pos, hit = np.asarray(pos), np.asarray(hit)
    assert counted.tolist() == sizes.tolist()
    assert held.any() and (hit.any(-1) == held).all()
    assert (hit.sum(1) <= 1).all()
    token, way = np.nonzero(held)
    assert (pos[token, local[token, way]] == inverse[token, way]).all()
    assert (pos[~hit.any(1)] == n * k).all()
    assert (pos[hit.any(1)] < int(sizes.sum())).all()
    if steer == "an_expert_with_no_rows":
        assert int(sizes[2]) == 0
    if steer == "every_token_on_one_expert":
        assert sizes.tolist() == [n, 0, 0, 0, 0, 0]


@pytest.mark.parametrize("remat", list(REMATS))
@pytest.mark.parametrize("shape", ["ways_4_of_4_held", "slots_3_held_4_ways",
                                   "slots_8_held_22_ways"])
def test_one_sort_a_layer_pass_and_no_row_a_way_where_slots_are_fewer(
        shape, remat):
    """Forward, recomputation and backward of a layer share under
    ``remat``: two layer passes that order the assignments with no
    policy, ONE with ``DecoderLM``'s, which keeps the routing. A token's
    slots its ways: two sorts a pass (``order`` and its ``inverse``) and,
    outside the ``cond`` sides that run when the rows do not fit, the
    wide arrays ``tests/test_decoder.py::
    test_no_worst_case_sized_array_on_the_bounded_path`` names. The
    held experts: ONE sort a pass, and no array of ``N x top_k`` rows by
    an expert or model width there, only ``N x count``."""
    experts, first, count, top_k = SHAPES[shape]
    key = jax.random.key(37)
    p = _moe_params(key, False, 0, experts=experts)
    p = {**p, "w1": p["w1"][first:first + count],
         "w2": p["w2"][first:first + count]}
    h = jax.random.normal(jax.random.fold_in(key, 1), (TOKENS, 64))
    layer = jax.checkpoint(
        lambda p, h: MOE.moe_layer(p, h, (first, count), top_k, 5.0,
                                   activation=MOE.RELU2)[0],
        policy=REMATS[remat])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, h: jnp.sum(layer(p, h)), argnums=(0, 1)))(p, h).jaxpr
    slots = min(top_k, count)
    sorts = [e for e in _eqns(jaxpr) if e.primitive.name == "sort"]
    passes = 2 if remat == "no_policy" else 1
    assert len(sorts) == passes * (2 if top_k <= count else 1)
    own = {leaf.shape for leaf in jax.tree.leaves(p)}
    nk = TOKENS * top_k

    def wide(eqns, rows):
        """Shapes of ``rows`` rows (flat, or a token's side by side) by
        the model's width or an expert's — but for the router's rule,
        which compares a token's ways with every expert id, ``[N,
        top_k, E]`` inside its sum (the 64 experts of one shape are as
        many as the model is wide)."""
        return {
            s for eqn in eqns for v in eqn.outvars
            for s in [v.aval.shape]
            if s not in own and s != (TOKENS, top_k, experts)
            and len(s) >= 2 and s[-1] in (64, 32) and (
                s[-2] == TOKENS * rows
                or s[-3:-1] in ((rows, TOKENS), (TOKENS, rows)))}

    bounded = list(_eqns(jaxpr))
    by_slot = ({(TOKENS, slots, 64)} if slots % MOE.SUBLANES == 0
               else {(slots, TOKENS, 64)})
    if top_k <= count:  # gathered slot by slot: no flat ``N x top_k``
        assert wide(bounded, top_k) == by_slot
    else:
        assert wide(bounded, top_k) == set()
        assert wide(bounded, slots) == by_slot
        # the other side holds the worst-case buffer, as it must
        assert (nk, 32) in wide(_eqns(jaxpr, bounded_side_only=False),
                                top_k)


# ---------------------------------------------------------------------------
# what cannot be built is refused
# ---------------------------------------------------------------------------


def _state_space(**change):
    return {"state_space": {**TN.STATE_SPACE, **change}}


@pytest.mark.parametrize("change, message", [
    ({"layer_types": ["mamba", "none", "state_space", "full_attention",
                      "none"]}, "layer 0 is 'mamba'"),
    ({"mlp_layer_types": ["none", "moe", "none", "none", "sparse"]},
     "layer 1 is 'none' \\+ 'moe'"),
    ({"mlp_layer_types": ["none", "none", "none", "none", "sparse"]},
     "layer 1 is nothing"),
    ({"mlp_layer_types": ["none", "dense", "none", "none", "sparse"]},
     "mlp_activation 'relu2'"),
    ({"mlp_activation": "gelu"}, "mlp_activation 'gelu'"),
    (_state_space(chunk_size=None), "state_space lacks chunk_size"),
    ({"state_space": None}, "state_space lacks num_heads"),
    (_state_space(n_groups=3), "n_groups divide num_heads"),
    (_state_space(heads_held=[0, 3]), "not whole groups of 2"),
    (_state_space(heads_held=[6, 4]), "not whole groups of 2"),
    ({"query_heads_held": [2, 4]}, "do not read key_value_heads_held"),
    ({"query_heads_held": [4, 2]}, "do not read key_value_heads_held"),
    ({"key_value_heads_held": [1, 2]}, "does not lie in the 2 key-value"),
    ({"shared_expert_columns_held": [60, 16]}, "does not lie in the 64"),
    ({"experts_held": [14, 4]}, "does not lie in the router's 16"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    extra = {**TN.tiny_config()["model"]["extra"], **change}
    with pytest.raises(ValueError, match=message):
        create_model(ModelConfig(
            name="decoder", num_classes=TN.VOCAB, input_shape=(TN.SEQ,),
            extra=tuple(extra.items())))


# ---------------------------------------------------------------------------
# the published share
# ---------------------------------------------------------------------------


def test_published_share_has_508_million_parameters():
    """The cut Nemotron-3-Super-120B-A12B as the configuration's file
    gives it, counted from ``eval_shape`` alone, with the table of
    ISSUE 33: 13.71 M a Mamba-2 layer, 5.25 M the attention layer,
    60.04 M an expert layer, 134.2 M of embedding and head."""
    config = TN.real_config()
    extra = config["model"]["extra"]
    pattern = config["hybrid_override_pattern_held"]
    assert config["hybrid_override_pattern"].startswith(pattern)
    assert [TN.KINDS[k] for k in pattern] == list(
        zip(extra["layer_types"], extra["mlp_layer_types"]))
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        5, 5, 1)
    model = create_model(_model_config(config))
    assert model.counters == MOE.MOE_COUNTERS
    shapes = jax.eval_shape(model.init, jax.random.key(0))["params"]
    count = lambda tree: sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    assert round(count(shapes) / 1e6, 1) == 508.2, count(shapes)
    by_kind = {k: round(count(shapes[f"layer_{pattern.index(k)}"]) / 1e6, 2)
               for k in "ME*"}
    assert by_kind == {"M": 13.71, "E": 60.04, "*": 5.25}
    mamba, experts, attn = (shapes[f"layer_{pattern.index(k)}"] for k in "ME*")
    assert mamba["in_proj"]["kernel"].shape == (4096, 2 * 1024 + 2 * 128 + 16)
    assert mamba["conv_kernel"].shape == (4, 1280)
    assert mamba["out_proj"]["kernel"].shape == (1024, 4096)
    assert experts["router"].shape == (4096, 512)
    assert experts["experts_w1"].shape == (8, 1024, 2688)
    assert experts["experts_w2"].shape == (8, 2688, 1024)
    assert experts["latent_in"].shape == (4096, 1024)
    assert experts["shared_w1"].shape == (4096, 672)
    assert "experts_w3" not in experts and "shared_w3" not in experts
    assert attn["q_proj"]["kernel"].shape == (4096, 4 * 128)
    assert attn["k_proj"]["kernel"].shape == (4096, 128)
    assert shapes["lm_head"]["kernel"].shape == (4096, 16384)
    # no width differs from the published config
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("head_dim", "head_dim"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("moe_latent_size", "moe_latent_size"),
                         ("shared_expert_intermediate_size",
                          "moe_shared_expert_intermediate_size"),
                         ("num_experts_per_tok", "num_experts_per_tok")):
        assert extra[ours] == config[theirs]
    s = extra["state_space"]
    assert (s["head_dim"], s["state_size"], s["chunk_size"],
            s["conv_kernel"]) == (config["mamba_head_dim"],
                                  config["ssm_state_size"],
                                  config["chunk_size"], config["conv_kernel"])
    # the shared expert's width is no reduced key: its columns held are
    # a share beside it
    assert set(config["reduced"]) == set(config["published"]) - {
        "shared_expert_columns", "chips_that_share_a_layer",
        "data_parallel_groups", "tensor_parallel_chips"}
    assert config["published"]["shared_expert_columns"] == extra[
        "shared_expert_intermediate_size"]
    assert extra["shared_expert_columns_held"] == [0, 672]


def test_a_round_trains_every_leaf_and_carries_the_expert_counters():
    """``FedAvgSim``, bulk engine at a block of one, over the tiny stack
    through ``run``'s own loop: every parameter moves (the state-space
    layers' ``A_log``, ``D``, ``dt_bias`` and convolution too) and the
    round record carries the expert counters."""
    sim = _sim(TN.tiny_config(), 1, seq=TN.SEQ, vocab=TN.VOCAB)

    class Sink:
        records = []

        def log(self, record):
            self.records.append(dict(record))

    before = jax.device_get(sim.init().variables)
    after = jax.device_get(sim.run(metrics_sink=Sink()).variables)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(after)):
        assert not np.array_equal(a, b), jax.tree_util.keystr(path)
    steps, sparse = 2 * 2, TN.PATTERN.count("E")
    for record in Sink.records:
        assert record["moe_rows_routed"] == steps * 2 * TN.SEQ * 4 * sparse
        assert 0 < record["moe_rows_held"] < record["moe_rows_routed"]
        # 4 ways over 4 held: the combine reads a row a way
        assert record["moe_rows_combined"] == record["moe_rows_routed"]
    assert "test_acc" in Sink.records[-1]


def test_a_training_step_runs_the_recurrence_between_chunks_once():
    """``DecoderLM``'s remat keeps the states entering each chunk
    (``ops/ssm.KEPT_STATES``): the gradient of a two-layer state-space
    stack holds one forward and one reversed recurrence a layer, where
    ``nn.remat`` with no policy would hold a third."""
    config = TN.tiny_config(pattern="MM")
    model = create_model(_model_config(config)).module
    tokens = jax.random.randint(jax.random.key(0), (1, TN.SEQ), 0, TN.VOCAB)
    params = jax.eval_shape(model.init, jax.random.key(1), tokens)["params"]

    def loss(params):
        logits, _ = model.apply({"params": params}, tokens,
                                mutable=["counters"])
        return jnp.mean(logits ** 2)

    assert _count(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, "scan") == 4
    assert set(SSM.KEPT) <= set(decoder.KEPT)
