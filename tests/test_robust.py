"""Edge-case coverage for the robust-aggregation primitives
(``core/robust.py``): even-count medians, over-trimmed trimmed mean,
single-client cohorts, and norm-clipping an all-zero delta — the
degenerate cohort shapes a straggler-tolerant server actually produces
once deadlines, quorums, and non-finite screening shrink the round
(docs/FAULT_TOLERANCE.md)."""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core import robust


def _stack(rows):
    return {"w": jnp.asarray(rows, dtype=jnp.float32)}


def test_coordinate_median_even_client_count():
    """Even cohort: the median is the midpoint of the two central
    values, per coordinate."""
    stacked = _stack([[1.0, 10.0], [2.0, 20.0], [3.0, 30.0],
                      [100.0, -100.0]])
    out = robust.coordinate_median(stacked)
    np.testing.assert_allclose(np.asarray(out["w"]), [2.5, 15.0])


def test_coordinate_median_single_client_is_identity():
    stacked = _stack([[7.0, -3.0]])
    out = robust.coordinate_median(stacked)
    np.testing.assert_allclose(np.asarray(out["w"]), [7.0, -3.0])


def test_trimmed_mean_trim_geq_cohort_stays_finite():
    """Over-trimming (trim_frac high enough that k >= cohort/2 — e.g. a
    quorum-shrunk round) must NOT average an empty slice into NaN; the
    defense degrades to the median-most rows."""
    stacked = _stack([[1.0], [2.0], [3.0], [1000.0]])
    out = robust.trimmed_mean(stacked, trim_frac=0.9)
    got = np.asarray(out["w"])
    assert np.all(np.isfinite(got))
    # k clamps to (4-1)//2 = 1: mean of the middle rows [2, 3]
    np.testing.assert_allclose(got, [2.5])


def test_trimmed_mean_single_client_cohort():
    """A one-client cohort cannot trim anything: the 'mean' is that
    client's delta, finite regardless of trim_frac."""
    stacked = _stack([[5.0, -1.0]])
    for frac in (0.0, 0.1, 0.5, 0.99):
        out = robust.trimmed_mean(stacked, trim_frac=frac)
        got = np.asarray(out["w"])
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, [5.0, -1.0])


def test_trimmed_mean_zero_trim_is_mean():
    stacked = _stack([[1.0], [3.0]])
    out = robust.trimmed_mean(stacked, trim_frac=0.0)
    np.testing.assert_allclose(np.asarray(out["w"]), [2.0])


def test_norm_clip_all_zero_delta_no_nan():
    """An all-zero delta (a client whose local update was a no-op) has
    norm 0: the clip scale must not divide 0/0 into NaN — the zero
    delta passes through untouched and its cohort-mates still clip."""
    big = [3.0, 4.0]  # norm 5
    stacked = _stack([[0.0, 0.0], big])
    out = robust.clip_deltas_by_norm(stacked, clip=1.0)
    got = np.asarray(out["w"])
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[0], [0.0, 0.0])
    np.testing.assert_allclose(got[1], [0.6, 0.8], rtol=1e-6)


def test_norm_clip_under_threshold_untouched():
    stacked = _stack([[0.3, 0.4]])  # norm 0.5 < clip
    out = robust.clip_deltas_by_norm(stacked, clip=1.0)
    np.testing.assert_allclose(np.asarray(out["w"]), [[0.3, 0.4]],
                               rtol=1e-6)


def test_norm_clip_preserves_mixed_precision_dtypes():
    """A mixed-precision pytree (bf16 activations-sized leaves next to
    f32 ones) must come back with ITS dtypes: the f32 clip scale used
    to silently upcast every bf16 leaf, doubling the stacked tree's
    footprint mid-aggregation."""
    stacked = {
        "a": jnp.full((3, 4), 2.0, jnp.bfloat16),
        "b": jnp.full((3, 2), 3.0, jnp.float32),
    }
    out = robust.clip_deltas_by_norm(stacked, clip=1.0)
    assert out["a"].dtype == jnp.bfloat16, out["a"].dtype
    assert out["b"].dtype == jnp.float32, out["b"].dtype
    # each client's GLOBAL norm (over both leaves) clips to ~1
    total = np.sqrt(
        np.sum(np.asarray(out["a"], np.float32) ** 2, axis=1)
        + np.sum(np.asarray(out["b"]) ** 2, axis=1)
    )
    assert np.all(total <= 1.05), total  # bf16 round-off headroom


def test_norm_clip_zero_size_leaf_and_empty_tree():
    """Zero-size leaves pass through untouched and a leafless tree is
    returned as-is (vmap over an empty tree cannot infer a batch
    size)."""
    stacked = {"w": jnp.ones((2, 3)), "empty": jnp.zeros((2, 0))}
    out = robust.clip_deltas_by_norm(stacked, clip=1.0)
    assert out["empty"].shape == (2, 0)
    assert np.all(np.isfinite(np.asarray(out["w"])))
    assert robust.clip_deltas_by_norm({}, clip=1.0) == {}


# ---------------------------------------------------------------------------
# selection/scoring defenses: numerics, jit tracing, sharded layouts
# ---------------------------------------------------------------------------


def _delta_stack():
    """7 honest-ish clients around +1 and 2 attackers: row 7 a
    sign-flipped boost, row 8 a colluder (its byte-identical twin is
    appended where a test needs the duplicate signal to fire)."""
    rng = np.random.default_rng(0)
    honest = 1.0 + 0.05 * rng.normal(size=(7, 6)).astype(np.float32)
    flip = -20.0 * np.ones((1, 6), np.float32)
    collude = np.tile(5.0 * rng.normal(size=(1, 6)).astype(np.float32),
                      (1, 1))
    rows = np.concatenate([honest, flip, collude], axis=0)
    return {"w": jnp.asarray(rows)}


def test_krum_selects_a_central_client():
    stacked = _delta_stack()
    sel, scores, best = robust.krum(stacked, num_adversaries=2)
    assert int(best) < 7  # an honest row, never the flipped/colluder
    np.testing.assert_allclose(np.asarray(sel["w"]),
                               np.asarray(stacked["w"])[int(best)])


def test_multi_krum_excludes_the_flipped_client():
    stacked = _delta_stack()
    w = jnp.ones(9)
    agg, scores, mask = robust.multi_krum(stacked, w,
                                          num_adversaries=2)
    mask = np.asarray(mask)
    assert not mask[7], "sign-flipped client survived multi-krum"
    got = np.asarray(agg["w"])
    assert np.all(np.abs(got - 1.0) < 0.5), got  # near the honest mean


def test_zero_weight_rows_never_win_selection():
    """Screened (zero-weight) results are healed to zero deltas on the
    sim path; an exact-zero-distance pair must NOT hijack the Krum
    family (it would freeze the model — a screening-induced DoS) and
    must carry zero fltrust trust."""
    rng = np.random.default_rng(1)
    honest = 1.0 + 0.05 * rng.normal(size=(2, 4)).astype(np.float32)
    stacked = {"w": jnp.concatenate([
        jnp.asarray(honest), jnp.zeros((2, 4), jnp.float32)])}
    w = jnp.asarray([32.0, 32.0, 0.0, 0.0])
    sel, _, best = robust.krum(stacked, 2, w)
    assert int(best) < 2, "krum selected a screened zero row"
    agg, _, mask = robust.multi_krum(stacked, w, 2)
    got = np.asarray(agg["w"])
    assert np.all(np.abs(got - 1.0) < 0.5), got  # zero rows excluded
    _, trust = robust.fltrust(
        stacked, robust.coordinate_median(stacked), weights=w
    )
    assert np.all(np.asarray(trust)[2:] == 0.0)


def test_multikrum_rejects_vacuous_config():
    """f=0 with auto m keeps every client — the plain mean wearing a
    multikrum label; the pipeline refuses it."""
    import pytest

    with pytest.raises(ValueError, match="multikrum"):
        robust.DefensePipeline(method="multikrum")
    # either knob makes it meaningful
    robust.DefensePipeline(method="multikrum", num_adversaries=1)
    robust.DefensePipeline(method="multikrum", multikrum_m=3)


def test_fltrust_zeroes_opposing_deltas():
    stacked = _stack([[1.0, 1.0], [1.0, 0.9], [-10.0, -10.0]])
    ref = {"w": jnp.asarray([1.0, 1.0])}
    agg, trust = robust.fltrust(stacked, ref)
    trust = np.asarray(trust)
    assert trust[2] == 0.0  # cos < 0 -> relu'd away
    assert trust[0] > 0 and trust[1] > 0
    got = np.asarray(agg["w"])
    assert np.all(got > 0), got  # the flipped client cannot drag it


def test_fltrust_all_zero_trust_degrades_to_reference():
    stacked = _stack([[-1.0, -1.0], [-2.0, -2.0]])
    ref = {"w": jnp.asarray([1.0, 2.0])}
    agg, trust = robust.fltrust(stacked, ref)
    assert np.all(np.asarray(trust) == 0.0)
    np.testing.assert_allclose(np.asarray(agg["w"]), [1.0, 2.0])


def test_anomaly_scores_flag_boost_flip_and_collusion():
    stacked = {"w": jnp.concatenate([
        jnp.asarray(_delta_stack()["w"]),
        jnp.asarray(_delta_stack()["w"])[8:9],  # the colluder's twin
    ])}
    d = robust.anomaly_scores(stacked)
    score = np.asarray(d["score"])
    # the flipped/boosted client: large norm z + negative cos-to-median
    assert score[7] > 1.0, score
    # the colluding pair: near-duplicate signal fires for both
    nearest = np.asarray(d["nearest_rel"])
    assert nearest[8] < 1e-3 and nearest[9] < 1e-3
    assert score[8] >= 2.0 and score[9] >= 2.0
    # honest clients stay low
    assert np.all(score[:7] < 1.0), score


def test_defenses_trace_and_lower_under_jit():
    """Every defense must trace under jax.jit (they are documented as
    fusing into the aggregation pass — nothing host-side in the hot
    path)."""
    stacked = _delta_stack()
    w = jnp.ones(9)
    fns = {
        "krum": lambda s: robust.krum(s, 2)[0],
        "multikrum": lambda s: robust.multi_krum(s, w, 2)[0],
        "fltrust": lambda s: robust.fltrust(
            s, robust.coordinate_median(s))[0],
        "median": robust.coordinate_median,
        "trimmed": robust.trimmed_mean,
        "scores": lambda s: robust.anomaly_scores(s)["score"],
        "clip": lambda s: robust.clip_deltas_by_norm(s, 1.0),
        "finite": lambda s: robust.finite_client_mask(s, jnp.ones(9)),
    }
    for name, fn in fns.items():
        jitted = jax.jit(fn)
        jitted.lower(stacked).compile()  # lowers cleanly
        out = jitted(stacked)
        for leaf in jax.tree.leaves(out):
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.floating):
                assert np.all(np.isfinite(arr)), name


def test_defenses_under_explicit_client_sharding():
    """The documented deployment layout: the stacked ``[C, ...]`` tree
    sharded over a `clients` mesh axis. Each defense must accept the
    sharded operand, lower, and match its single-device result."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedml_tpu.parallel import make_client_mesh

    mesh = make_client_mesh(8)
    rows = np.tile(np.arange(8, dtype=np.float32)[:, None], (1, 4))
    rows[3] = -50.0  # one attacker
    stacked = {"w": jnp.asarray(rows)}
    sharded = jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P("clients"))), stacked
    )
    w = jnp.ones(8)
    for name, fn in {
        "median": robust.coordinate_median,
        "krum": lambda s: robust.krum(s, 1)[0],
        "multikrum": lambda s: robust.multi_krum(s, w, 1)[0],
        "fltrust": lambda s: robust.fltrust(
            s, robust.coordinate_median(s))[0],
        "scores": lambda s: robust.anomaly_scores(s)["score"],
    }.items():
        ref = jax.jit(fn)(stacked)
        got = jax.jit(fn)(sharded)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, err_msg=name)
