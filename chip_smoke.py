"""The quickest proof that the federated round still starts on the chip.

``python3 chip_smoke.py`` needs ONE TPU chip and runs, in this order,
each phase a hard failure (non-zero exit, no final ``"ok": true``):

a. device check — ``jax.devices()[0].platform == "tpu"`` and its
   ``device_kind`` has a row in ``fedml_tpu.core.perf.PEAKS``;
b. the headline job — ResNet-56 on CIFAR-10 shapes, 100 clients,
   Dirichlet alpha=0.5, 10 clients a round, batch 32, bf16, one local
   epoch (``headline_config``) — for a few rounds and one
   evaluation through ``fedml_tpu.experiments.run.main`` ->
   ``Experiment`` -> ``FedAvgSim.run``;
c. one float32 round of the same job with a 2-client cohort (full-batch
   clients: one SGD step each, see the bands below) on the chip against
   the same round placed on the in-process CPU device;
d. the Pallas flash-attention kernel, compiled (not interpreted),
   against ``ops.ring_attention.full_attention``.

``--chips 4`` needs four chips and runs ONLY the mesh path: a
``ShardedFedAvg`` round of the ResNet-56 job on a 4-device ``clients``
mesh against the single-device ``FedAvgSim`` round it must equal, a
round on the 2x2 clients x data mesh, and one ``ShardedAggregator``
call against its stacked single-device result — printing where banks,
state and outputs live.

Every phase prints one JSON object per line. Times are facts about one
run on the device named in the line, not a benchmark. The LAST line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
With no TPU (``JAX_PLATFORMS=cpu``) phase a fails and the exit code is
non-zero. One process only: nothing here starts a child.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "runs", "chip_smoke")  # runs/ is git-ignored
ROUNDS = 5
PLATFORM = "tpu"  # what every phase expects its arrays to live on
# Phases that compare two f32 rounds (chip vs CPU; mesh vs one device)
# run a ONE-STEP round: full-batch clients, so each takes a single SGD
# step. A freshly initialised ResNet-56 is chaotic under training: on
# the CPU alone, perturbing the initial parameters by 1e-7 relative
# moves the result of the job's own 5-step round by 2-100 % of a leaf's
# scale, so a whole round cannot be held to any band. After one step
# the same perturbation moves the loss by 6e-8 relative, every
# BatchNorm statistic by < 1e-5 of its scale, and a parameter update
# by up to 0.19 of its size in a single element (ReLU flips). The
# bands below sit above that floor and far below what a wrong program
# gives (a missing, doubled or mis-reduced gradient is an error of 1.0):
LOSS_RTOL = 1e-3  # |loss - ref| <= LOSS_RTOL * |ref|
# every BatchNorm running statistic — a fingerprint of each layer's
# forward pass: per leaf, max|x - ref| <= STATS_TOL * (1 + max|ref|)
STATS_TOL = 1e-3
# each parameter's update (new - initial): per leaf,
# ||update - ref||_2 <= UPDATE_TOL * ||ref||_2
UPDATE_TOL = 0.25
# phase d: bf16 outputs of two different summation orders
KERNEL_TOL = 2e-2


def say(**rec) -> None:
    print(json.dumps(rec, default=float), flush=True)


def require(cond, why) -> None:
    """A failed check fails its phase (``assert`` would vanish under
    ``python -O``)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {why}")


class CompileLog:
    """Where the seconds before a first round go, from JAX's own
    monitoring events: tracing, lowering, and the backend compile —
    which is a read of the persistent cache when the program is there."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax

        self.seen = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: self._add(event, secs)
        )
        jax.monitoring.register_event_listener(
            lambda event, **_: self._add(event, 1)
        )

    def _add(self, event, amount) -> None:
        if event in self.EVENTS:
            self.seen[self.EVENTS[event]] += amount

    def take(self) -> dict:
        """What was seen since the last call."""
        seen, self.seen = self.seen, dict.fromkeys(self.seen, 0)
        return seen


def _tree_bytes(tree) -> int:
    import jax

    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def _worst_leaf(ref, got, excess):
    """The leaf with the largest ``excess(ref_leaf, got_leaf)`` (an
    error as a fraction of its band): (path, that fraction)."""
    import jax
    import numpy as np

    worst = ("", 0.0)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(ref), jax.tree.leaves(got)
    ):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        used = float(excess(a, b)) if a.size else 0.0
        if not used <= worst[1]:  # also catches nan
            worst = (jax.tree_util.keystr(path), used)
    return worst


def _compare_rounds(ref, got) -> dict:
    """Two one-step f32 rounds, each ``(initial variables, new
    variables, metrics)`` on the host, held to the bands above.
    Returns the record; its ``within`` says whether all three held."""
    import jax
    import numpy as np

    (ref0, ref1, ref_m), (got0, got1, got_m) = ref, got
    _assert_finite(got1, "compared round")
    loss_ref, loss_got = float(ref_m["train_loss"]), float(got_m["train_loss"])
    loss_err = abs(loss_got - loss_ref) / abs(loss_ref)
    stats = _worst_leaf(
        ref1.get("batch_stats", {}), got1.get("batch_stats", {}),
        lambda a, b: np.max(np.abs(a - b))
        / (STATS_TOL * (1 + np.max(np.abs(a)))),
    )
    update = lambda new, old: jax.tree.map(
        lambda n, o: np.asarray(n, np.float64) - np.asarray(o, np.float64),
        new["params"], old["params"],
    )
    upd = _worst_leaf(
        update(ref1, ref0), update(got1, got0),
        lambda a, b: np.linalg.norm(a - b)
        / (UPDATE_TOL * max(np.linalg.norm(a), 1e-12)),
    )
    return {
        "train_loss": loss_got,
        "train_loss_ref": loss_ref,
        "loss_rel_err": loss_err,
        "worst_stats_leaf": stats[0],
        "stats_err_over_band": stats[1],
        "worst_update_leaf": upd[0],
        "update_rel_l2_err": upd[1] * UPDATE_TOL,
        "bands": {"loss_rtol": LOSS_RTOL, "stats_tol": STATS_TOL,
                  "update_tol": UPDATE_TOL},
        "within": bool(loss_err <= LOSS_RTOL and stats[1] <= 1.0
                       and upd[1] <= 1.0),
    }


def _assert_finite(tree, what: str) -> None:
    import jax
    import numpy as np

    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            require(np.all(np.isfinite(arr.astype(np.float32))), (
                f"{what}: non-finite values in "
                f"{jax.tree_util.keystr(path)}"
            ))


def _platforms_of(tree) -> set:
    import jax

    return {
        d.platform
        for leaf in jax.tree.leaves(tree)
        for d in leaf.sharding.device_set
    }


# ---------------------------------------------------------------------------
# phase a
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    import jax

    from fedml_tpu.core.compile_cache import enable_compile_cache
    from fedml_tpu.core.perf import device_peaks
    from fedml_tpu.native import codec

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    require(dev.platform == PLATFORM, (
        f"chip_smoke needs a TPU; jax found platform {dev.platform!r} "
        f"({dev.device_kind})"
    ))
    require(len(jax.devices()) >= chips, (
        f"--chips {chips} needs {chips} devices, found "
        f"{len(jax.devices())}"
    ))
    peak_flops, peak_bw, hbm = device_peaks(dev)  # raises if not listed
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "peak_bf16_flops": peak_flops,
        "peak_hbm_bytes_per_s": peak_bw,
        "hbm_bytes": hbm,
        "jax": jax.__version__,
        "compile_cache_dir": cache_dir,
        # core/transport falls back to the pickle wire path without it
        "native_codec_loaded": codec.native_available(),
    }


# ---------------------------------------------------------------------------
# phase b
# ---------------------------------------------------------------------------


def headline_config(num_clients=100, model_name="resnet56"):
    """The headline job: ``num_clients`` clients on CIFAR-10 shapes
    (32x32x3, 10 classes), Dirichlet alpha=0.5, 10 clients a round,
    batch 32, bf16 compute, one local epoch. Phase b runs it through
    the experiment CLI."""
    from fedml_tpu.config import (
        DataConfig,
        ExperimentConfig,
        FedConfig,
        ModelConfig,
        TrainConfig,
    )

    return ExperimentConfig(
        data=DataConfig(
            dataset="fake_cifar10",
            num_clients=num_clients,
            partition_method="hetero",
            partition_alpha=0.5,
            batch_size=32,
            seed=0,
        ),
        model=ModelConfig(
            name=model_name, num_classes=10, input_shape=(32, 32, 3)
        ),
        # bf16 compute; the headline takes the cohort-fused path
        # (fedml_tpu.models.cohort) whose step loop has a dynamic trip
        # count — scan_unroll only applies to the vmapped fallback path
        # cohort_groups=5: size-sorted sub-groups of 2 clients, each with
        # its own dynamic trip count — measured best on v5e for this
        # 10-client cohort (57 -> 38 ms/round vs one lockstep group)
        train=TrainConfig(
            lr=0.03, epochs=1, compute_dtype="bfloat16", scan_unroll=64,
            cohort_groups=5,
        ),
        fed=FedConfig(num_rounds=1000, clients_per_round=10, eval_every=10**9),
        seed=0,
    )


def _smoke_config(**fed):
    cfg = headline_config()
    return dataclasses.replace(
        cfg,
        fed=dataclasses.replace(cfg.fed, **fed),
        out_dir=OUT_DIR,
        run_name=f"resnet56_100c_{int(time.time())}",
    )


def phase_job(chips: int) -> dict:
    """The headline job through the CLI's own ``main``. The only thing
    added to the user's path is a tap on ``FedAvgSim.run`` that keeps
    the sim and the state it returns, so the state can be checked
    where it lives."""
    import jax
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.core import memscope
    from fedml_tpu.experiments import run

    cfg = _smoke_config(num_rounds=ROUNDS, eval_every=ROUNDS)
    os.makedirs(OUT_DIR, exist_ok=True)
    cfg_path = os.path.join(OUT_DIR, f"{cfg.run_name}.json")
    with open(cfg_path, "w") as f:
        f.write(cfg.to_json())

    kept = {}
    inner_run = FedAvgSim.run

    def tapped_run(self, metrics_sink=None):
        kept["sim"] = self
        kept["state"] = inner_run(self, metrics_sink=metrics_sink)
        return kept["state"]

    FedAvgSim.run = tapped_run
    t0 = time.time()
    try:
        rc = run.main([
            "--config", cfg_path,
            # metrics plane on: memscope then records the round
            # program's compile seconds and memory analysis
            "--telemetry_dir",
            os.path.join(OUT_DIR, cfg.run_name + "_rep0", "telemetry"),
        ])
    finally:
        FedAvgSim.run = inner_run
    wall = time.time() - t0
    require(rc == 0, f"experiments.run.main returned {rc}")
    sim, state = kept["sim"], kept["state"]

    with open(os.path.join(
            OUT_DIR, cfg.run_name + "_rep0", "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    rows = [r for r in rows if "round" in r]
    require([r["round"] for r in rows] == list(range(ROUNDS)), rows)
    losses = [r["train_loss"] for r in rows]
    require(all(np.isfinite(losses)), losses)
    require(np.isfinite(rows[-1]["test_loss"]), rows[-1])
    require(int(state.round) == ROUNDS, int(state.round))
    _assert_finite(state.variables, "final state")
    require(_platforms_of(state) == {PLATFORM}, _platforms_of(state))
    require(_platforms_of(sim.arrays.x) == {PLATFORM},
            _platforms_of(sim.arrays.x))

    # the last row's stamp also covers the evaluation: steady rounds
    # are the gaps between the stamps before it
    stamps = [r["_ts"] for r in rows]
    steady = [b - a for a, b in zip(stamps[:-2], stamps[1:-1])]
    programs = memscope.program_table()
    compile_s = sum(
        p.get("compile_s", 0.0) for p in programs.values()
        if p["family"] == "sim_round"
    )

    # does block_until_ready wait for the round? Time a round that
    # ends in it, then the device_get that follows: if it waited, the
    # get costs a fetch, not a round.
    def timed(sync):
        nonlocal state
        t = time.perf_counter()
        state, m = sim.run_round(state)
        sync(state, m)
        return time.perf_counter() - t, m

    bur, get_after, get_only = [], [], []
    for _ in range(3):
        dt, m = timed(lambda s, m: jax.block_until_ready((s, m)))
        t = time.perf_counter()
        jax.device_get(m)
        get_after.append(time.perf_counter() - t)
        bur.append(dt)
        dt, _ = timed(lambda s, m: jax.device_get(m))
        get_only.append(dt)
    fetch = []
    for _ in range(3):
        t = time.perf_counter()
        float(np.asarray(jax.device_get(state.round)))
        fetch.append(time.perf_counter() - t)

    stats = jax.devices()[0].memory_stats() or {}
    return {
        "rounds": ROUNDS,
        "train_loss": losses,
        "test_loss": rows[-1]["test_loss"],
        "test_acc": rows[-1]["test_acc"],
        "job_wall_s": wall,
        "to_first_round_logged_s": stamps[0] - t0,
        # trace + lower + backend compile of the round program, as
        # memscope times it; the line's "compile" object splits it
        "round_program_compile_s": compile_s,
        "steady_round_s": steady,
        "round_then_block_until_ready_s": bur,
        "device_get_after_block_until_ready_s": get_after,
        "round_then_device_get_s": get_only,
        "block_until_ready_waits": min(get_after) < 0.25 * min(bur),
        "scalar_fetch_s": fetch,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "round_program_bytes": {
            slug: {f: p.get(f) for f in
                   ("temp_bytes", "argument_bytes", "output_bytes")}
            for slug, p in programs.items()
            if p["family"] == "sim_round"
        },
        "state_platform": sorted(_platforms_of(state)),
    }


# ---------------------------------------------------------------------------
# phase c
# ---------------------------------------------------------------------------


def _one_step_config(cohort: int):
    """The job in f32 with full-batch clients: one SGD step each."""
    cfg = _smoke_config(num_rounds=1, clients_per_round=cohort)
    return dataclasses.replace(
        cfg,
        data=dataclasses.replace(cfg.data, full_batch=True),
        train=dataclasses.replace(cfg.train, compute_dtype="float32"),
    )


def _run_one_round(sim):
    """(initial variables, new variables, metrics) on the host, and
    where the new state lives."""
    import jax

    state = sim.init()
    initial = jax.device_get(state.variables)
    state, m = sim.run_round(state)
    return (initial, *jax.device_get((state.variables, m))), state


def phase_parity(chips: int) -> dict:
    """One one-step f32 round, 2-client cohort: chip vs the in-process
    CPU device, same code, same seed. The chip side runs with matmul
    precision "highest" so both sides compute in f32."""
    import jax

    from fedml_tpu.experiments.harness import build_sim

    cfg = _one_step_config(cohort=2)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        chip, chip_state = _run_one_round(build_sim(cfg))
    t_chip = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        cpu, cpu_state = _run_one_round(build_sim(cfg))
    t_cpu = time.perf_counter() - t0
    on = (_platforms_of(chip_state), _platforms_of(cpu_state))
    require(on == ({PLATFORM}, {"cpu"}), on)
    rec = dict(_compare_rounds(cpu, chip), chip_s=t_chip, cpu_s=t_cpu)
    require(rec["within"], f"chip round differs from the CPU round: {rec}")
    return rec


# ---------------------------------------------------------------------------
# phase d
# ---------------------------------------------------------------------------


def phase_kernel(chips: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.flash_attention import flash_attention
    from fedml_tpu.ops.ring_attention import full_attention

    b, h, t, d = 4, 8, 2048, 64
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q, k, v = (
        jax.random.normal(key, (b, t, h, d), jnp.bfloat16)
        for key in (kq, kk, kv)
    )
    flash = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    )
    compiled = flash.lower(q, k, v).compile()
    require("tpu_custom_call" in compiled.as_text(), (
        "the Pallas kernel is not in the compiled program"
    ))
    got = np.asarray(compiled(q, k, v), np.float32)
    want = np.asarray(
        jax.jit(lambda q, k, v: full_attention(q, k, v, causal=True))(
            q, k, v),
        np.float32,
    )
    require(got.shape == (b, t, h, d) and np.all(np.isfinite(got)),
            f"kernel output shape {got.shape} or non-finite values")
    err = float(np.max(np.abs(got - want)))
    require(err <= KERNEL_TOL, f"flash vs full attention: {err}")
    return {"shape": [b, t, h, d], "dtype": "bfloat16", "causal": True,
            "max_abs_diff": err, "tol": KERNEL_TOL,
            "compiled_kernel": True}


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------


def _placement(tree) -> dict:
    """How many devices hold ``tree`` and how many of its bytes sit on
    each (a replicated leaf counts once per device)."""
    import jax

    per_dev: dict[str, int] = {}
    n_dev = 0
    for leaf in jax.tree.leaves(tree):
        n_dev = max(n_dev, len(leaf.sharding.device_set))
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            per_dev[key] = per_dev.get(key, 0) + int(shard.data.nbytes)
    return {"devices": n_dev, "bytes_per_device": per_dev,
            "bytes_total": _tree_bytes(tree)}


def phase_sharded(chips: int) -> dict:
    """ShardedFedAvg and ShardedAggregator on real chips against their
    single-device results (tests/test_sharded.py and
    tests/test_compress.py hold the CPU forms)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.algorithms.fedavg import (
        FedAvgSim,
        local_reducer,
        server_update,
    )
    from fedml_tpu.core import random as R
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import (
        ShardedAggregator,
        ShardedFedAvg,
        make_client_mesh,
        make_mesh,
    )

    cohort = 8
    out = {"cohort": cohort}

    def mesh_round(cfg, n_clients, n_data):
        """One round on the mesh, then a second one that takes the
        first one's output and the banks as they lie (no compile, no
        re-placement): where everything lives, and what each cost."""
        sharded = ShardedFedAvg(
            create_model(cfg.model), load_dataset(cfg.data), cfg,
            make_mesh(client_axis=n_clients, data_axis=n_data),
        )
        rec = {"banks": _placement(sharded.banks),
               "state_in": _placement(sharded.init())}
        t0 = time.perf_counter()
        rounds, state = _run_one_round(sharded)
        rec["first_round_s"] = time.perf_counter() - t0
        rec["state_out"] = _placement(state)
        rec["banks_after"] = _placement(sharded.banks)
        t0 = time.perf_counter()
        state, _ = sharded.run_round(state)
        jax.block_until_ready(state)
        rec["second_round_s"] = time.perf_counter() - t0
        require(int(state.round) == 2, int(state.round))
        for what in ("banks", "banks_after", "state_out"):
            require(rec[what]["devices"] == 4, (what, rec[what]))
        return rounds, rec

    # 4 x 1: the clients mesh, exact semantics — the one-step round
    # must equal the single-device round under the stratified sampler.
    # Matmul precision "highest" on both sides, as in phase c: at the
    # chip's default precision an f32 convolution multiplies in bf16,
    # and two differently fused programs then differ by that rounding
    # (the first four-chip run: loss within 1.5e-4, one BatchNorm
    # scale's update off by 0.52 of its size).
    cfg = _one_step_config(cohort)
    with jax.default_matmul_precision("highest"):
        mesh, rec = mesh_round(cfg, 4, 1)
        single, state = _run_one_round(FedAvgSim(
            create_model(cfg.model), load_dataset(cfg.data), cfg,
            sampler=lambda k, n, c:
                R.sample_clients_stratified(k, n, c, 4),
        ))
    rec["single_state_out"] = _placement(state)
    rec.update(_compare_rounds(single, mesh))
    out["mesh_4x1"] = rec
    require(rec["within"], f"sharded round differs from one device: {rec}")

    # 2 x 2: clients x data, the job's own batch 32 in f32. With plain
    # BatchNorm each data shard normalises its half of a batch (only
    # the running statistics are averaged), so this layout is not
    # equal to one device by design — tests/test_sharded.py pins the
    # data axis's gradient psum on a model without BatchNorm. Here:
    # it runs, lives on 4 chips and gives finite values.
    cfg = _smoke_config(num_rounds=1, clients_per_round=cohort)
    cfg = dataclasses.replace(
        cfg,
        train=dataclasses.replace(
            cfg.train, compute_dtype="float32", scan_unroll=1
        ),
    )
    (_, new_vars, m), rec = mesh_round(cfg, 2, 2)
    _assert_finite(new_vars, "2x2 round")
    rec["train_loss"] = float(m["train_loss"])
    require(np.isfinite(rec["train_loss"]), rec)
    out["mesh_2x2"] = rec

    # the sharded server aggregation against the stacked
    # single-device server_update on the same [C, ...] operand
    agg = ShardedAggregator(
        cfg, 1, cfg.data.batch_size, mesh=make_client_mesh(4)
    )
    state = FedAvgSim(
        create_model(cfg.model), load_dataset(cfg.data), cfg
    ).init()
    keys = iter(jax.random.split(jax.random.key(1), 10_000))
    stacked = jax.tree.map(
        lambda v: v[None] + 0.01 * jax.random.normal(
            next(keys), (cohort,) + v.shape, v.dtype),
        state.variables,
    )
    w = jnp.arange(1.0, cohort + 1.0)
    rkey = jax.random.key(99)
    want = jax.jit(
        lambda st, s, ww, k: server_update(
            cfg.fed, cfg.train, 1, cfg.data.batch_size, st, s, ww, k,
            local_reducer(),
        )
    )(state, stacked, w, rkey)
    got = agg.update(state, stacked, w, rkey)
    leaf, used = _worst_leaf(
        jax.device_get(want.variables), jax.device_get(got.variables),
        lambda a, b: np.max(np.abs(a - b)) / (1e-6 + 1e-5 * np.abs(a).max()),
    )
    out["sharded_aggregator"] = {
        "stacked_bytes": _tree_bytes(stacked),
        "state_out": _placement(got),
        "worst_leaf": leaf,
        # a weighted mean of the same rows, reassociated over 4 shards
        "err_over_band_1e-5": used,
    }
    require(out["sharded_aggregator"]["state_out"]["devices"] == 4,
            out["sharded_aggregator"]["state_out"])
    require(used <= 1.0,
            f"ShardedAggregator differs: {out['sharded_aggregator']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path (ShardedFedAvg and "
                         "ShardedAggregator against their single-device "
                         "results) on four chips")
    args = ap.parse_args(argv)
    phases = [("a_device", phase_device)]
    if args.chips == 4:
        phases.append(("mesh_sharded_round", phase_sharded))
    else:
        phases += [("b_resnet56_job", phase_job),
                   ("c_chip_vs_cpu_round", phase_parity),
                   ("d_pallas_flash_attention", phase_kernel)]
    import jax

    # the device as JAX reports it, on the last line whatever happens
    device = {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    failed = []
    compiles = CompileLog()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            rec = fn(args.chips)
        except Exception as err:  # a failed phase fails the run
            traceback.print_exc()
            say(phase=name, ok=False, error=repr(err)[:2000])
            failed.append(name)
            if name == "a_device":
                break  # not the device: nothing after phase a can run
            continue  # the later phases still say what they find
        say(phase=name, ok=True, seconds=time.perf_counter() - t0,
            compile=compiles.take(), **rec)
    if failed:
        say(ok=False, failed=failed, device=device)
        return 1
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
