"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: build the population from the seed,
build the sim the way ``fedml_tpu.experiments.harness`` does, warm up
the cell's own programs (set-up), let ``FedAvgSim.run`` /
``ShardedFedAvg.run`` drive rounds for ``--seconds`` seconds, check the
result against the plain reference, print one JSON object as the last
line. No chip, or fewer chips than the cell asks for: exit code 2 and
no result line. There is no CPU fallback.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own that this program finds by the
name in ``BENCHMARK.json``:

    benchmarks/configs/<config>.json   sizes, source, reduced, assumed
    benchmarks/configs/<config>.py     ARCH of its plain reference
    benchmarks/traffic/<traffic>.json  population, client-size law, cohort,
                                       eval cadence, chips, sim class, and
                                       any FedConfig / TrainConfig override
    benchmarks/layer_metrics/<metric>.py   read(ctx) -> number or None
                                       (``<quantity>.<suffix>`` is read
                                       by ``<quantity>.py`` where it has
                                       no file of its own)

``--trace 0`` reports the cell's end-to-end metrics with the profiler
off. ``--trace 1`` profiles a short steady part early in the window,
lets the window run on until it holds ``--seconds`` of rounds the
profiler did not touch, and reports the per-layer metrics and nothing
end to end.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CHECK_ROUNDS = 3  # rounds the reference follows
HUGE = 10 ** 9  # num_rounds: the window, not the count, ends the run
TRACE_MIN_S = 2.0  # a traced part lasts at least this long ...
TRACE_SKIP_S = 1.0  # ... and starts this far into the window
LOSS_ROUNDS_MIN = 8  # rounds a window needs for its loss to be judged


def say(**rec) -> None:
    print(json.dumps(rec, default=float), flush=True)


class NoChip(RuntimeError):
    pass


class _WindowClosed(Exception):
    """Raised by the sink to end ``run``'s loop when the window is over."""


# ---------------------------------------------------------------------------
# files by name
# ---------------------------------------------------------------------------


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_py(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(bench_dir: str, metric: str) -> str:
    """A per-layer metric's reader: ``layer_metrics/<metric>.py``, or,
    for a quantity split by the end-to-end metric it moves
    (``eval_ms.mesh4``), the quantity's own ``layer_metrics/eval_ms.py``."""
    own = os.path.join(bench_dir, "layer_metrics", metric + ".py")
    if os.path.exists(own):
        return own
    return os.path.join(
        bench_dir, "layer_metrics", metric.split(".")[0] + ".py")


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything the files say about one cell."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    here = os.path.dirname(os.path.join(root, cfg_entry["file"]))
    arch = _load_py(os.path.join(here, config["reference"]),
                    "bench_reference").ARCH
    traffic = _load_json(os.path.join(
        os.path.dirname(here), "traffic", cell["traffic"] + ".json"))
    if int(traffic.get("chips", 1)) != int(cell["chips"]):
        raise SystemExit(
            f"{workload}: BENCHMARK.json says {cell['chips']} chips, "
            f"traffic file says {traffic.get('chips', 1)}")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "name": workload, "cell": cell, "config": config, "arch": arch,
        "traffic": traffic, "bench_dir": os.path.dirname(here),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def experiment_config(config: dict, traffic: dict):
    """The program's own typed configuration for this cell. Keys under
    ``fed`` / ``train`` / ``data`` in the traffic file override the
    configuration's, so a mix can switch on any option the program has
    (``client_block_size``, ``compress``, ``robust_method``, ``peft``,
    ``fuse_rounds``, ...) without new code here."""
    from fedml_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, TrainConfig,
    )

    m = config["model"]
    data = {"dataset": "bench:" + config["dataset"]["name"],
            "num_clients": int(traffic["population"]),
            "batch_size": int(config["batch_size"]),
            **traffic.get("data", {})}
    fed = {**config.get("fed", {}), "num_rounds": HUGE,
           "clients_per_round": int(traffic["clients_per_round"]),
           "eval_every": int(traffic["eval_every"]),
           **traffic.get("fed", {})}
    train = {**config.get("train", {}), **traffic.get("train", {})}
    return ExperimentConfig(
        data=DataConfig(**data),
        model=ModelConfig(name=m["name"], num_classes=int(m["num_classes"]),
                          input_shape=tuple(m["input_shape"])),
        train=TrainConfig(**train),
        fed=FedConfig(**fed),
        # the program bakes its seed into the compiled round (a constant
        # of the HLO), so a new seed would be a new program and a cold
        # compile: the cell fixes it; --seed drives data and weights
        seed=int(traffic.get("program_seed", 0)),
    )


def build_sim(cfg, traffic: dict, pop: dict):
    """``FedAvgSim(create_model(cfg.model), data, cfg)`` as
    ``experiments.harness._fedavg_family`` builds it — with the
    benchmark's population in place of ``load_dataset`` (whose stand-in
    is fixed at 6,000 samples) — or ``ShardedFedAvg`` over a mesh."""
    from fedml_tpu.data.federated import FederatedData
    from fedml_tpu.models import create_model

    data = FederatedData(
        pop["x_train"], pop["y_train"], pop["x_test"], pop["y_test"],
        pop["train_map"], pop["test_map"], pop["classes"])
    model = create_model(cfg.model)
    kind = traffic.get("sim", "FedAvgSim")
    if kind == "FedAvgSim":
        from fedml_tpu.algorithms.fedavg import FedAvgSim

        return FedAvgSim(model, data, cfg)
    if kind == "ShardedFedAvg":
        from fedml_tpu.parallel import ShardedFedAvg, make_mesh

        mesh = traffic["mesh"]
        return ShardedFedAvg(model, data, cfg, make_mesh(
            client_axis=int(mesh["clients"]), data_axis=int(mesh["data"])))
    raise SystemExit(f"unknown sim class {kind!r} in the traffic file")


def seed_key(seed: int):
    """``--seed`` may be a little over 2**31: fold it in two halves."""
    import jax

    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _data_operand(sim):
    return ("banks", sim.banks) if hasattr(sim, "banks") else (
        "arrays", sim.arrays)


def one_batch_operand(sim, batch: int):
    """The sim's data operand with every client cut to its first batch
    (one optimizer step a client); slots past a client's samples repeat
    its first sample with weight 0, as the program pads short clients.
    Same shapes and placement, so the compiled round is the timed one."""
    import jax
    import numpy as np

    _, op = _data_operand(sim)
    mask = np.asarray(op.mask)
    idx = np.asarray(op.idx)
    keep = np.arange(mask.shape[-1]) < np.minimum(
        mask.sum(-1, keepdims=True), batch)
    new_idx = np.where(keep, idx, idx[..., :1])
    put = lambda new, old: (
        jax.device_put(new.astype(old.dtype), old.sharding)
        if isinstance(old, jax.Array) else new.astype(old.dtype))
    changes = {"idx": put(new_idx, op.idx),
               "mask": put(keep.astype(np.float32), op.mask)}
    if hasattr(op, "counts"):
        changes["counts"] = put(keep.sum(-1), op.counts)
    return op.replace(**changes)


def first_gradient(before, state, fed):
    """What the server optimizer was handed in its first step, worked
    out from the state after it: FedAvg moves the parameters by
    ``-server_lr * g``; Adam's first moment is ``(1 - b1) * g``."""
    import jax
    import numpy as np

    from lib.fedref import ADAM_B1

    if fed.server_optimizer == "adam":
        mus = [s.mu for s in jax.tree.leaves(
            state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu")]
        return jax.tree.map(
            lambda m: np.asarray(m, np.float64) / (1 - ADAM_B1),
            jax.device_get(mus[0]))
    if fed.server_optimizer == "sgd" and not fed.server_momentum:
        after = jax.device_get(state.variables["params"])
        return jax.tree.map(
            lambda a, b: (np.asarray(b, np.float64) - np.asarray(a, np.float64))
            / fed.server_lr, after, before["params"])
    raise SystemExit(
        f"no rule to read the first gradient under {fed.server_optimizer}")


def sampled_clients(cell, round_idx):
    """The clients round ``round_idx`` of this cell samples (the
    benchmark's copy of the sampling rule, ``lib/fedref.cohort_ids``)."""
    from lib.fedref import cohort_ids

    t = cell["traffic"]
    return cohort_ids(
        int(t.get("program_seed", 0)), round_idx, int(t["population"]),
        int(t["clients_per_round"]),
        int(t["mesh"]["clients"]) if t.get("mesh") else 1)


def reference_batches(pop, cell, batch, rounds):
    """Host batches of the rounds the reference follows: for each round
    ``(x [C,B,...], y [C,B], w [C,B])`` of the sampled clients' first
    batch, padded the way the program pads."""
    import numpy as np

    out = []
    for r in range(rounds):
        rows, ws = [], []
        for c in sampled_clients(cell, r):
            own = pop["train_map"][int(c)][:batch]
            rows.append(np.concatenate(
                [own, np.repeat(own[:1], batch - len(own))]))
            ws.append((np.arange(batch) < len(own)).astype(np.float32))
        rows = np.stack(rows)
        out.append((pop["x_train"][rows], pop["y_train"][rows],
                    np.stack(ws)))
    return out


def client_steps(pop, cell, cfg, batch, rounds) -> int:
    """Optimizer steps the sampled clients of ``rounds`` really take:
    ``ceil(n_k / batch)`` a local epoch each."""
    import numpy as np

    return cfg.train.epochs * sum(
        int(np.ceil(pop["sizes"][sampled_clients(cell, r)] / batch).sum())
        for r in rounds)


def seed_state(sim, arch, seed):
    """The program's own initial state with its variables replaced by
    weights made from ``--seed`` (one jitted program on the device).
    -> host copy; ``fresh_state(host)`` places a new one."""
    import jax

    from lib import refnet

    seed_vars = jax.jit(lambda k: refnet.init(arch, k))(seed_key(seed))
    template = sim.init()  # the program's own state, for its structure
    if (jax.tree.structure(template.variables)
            != jax.tree.structure(seed_vars)):
        raise SystemExit("the reference's variable tree is not the program's")
    return jax.device_get(template._replace(variables=seed_vars))


def fresh_state(host_state):
    """Uncommitted device arrays, as the program's own init() returns."""
    import jax

    return jax.tree.map(jax.numpy.asarray, host_state)


def drive_check_rounds(sim, cfg, batch, host_state):
    """Drive the compiled round — the object the window then uses —
    from the seed's weights through ``CHECK_ROUNDS`` one-step rounds.
    -> (what the comparison reads, state after them, seconds of the
    first call, which compiles)."""
    import jax

    operand_name, real_operand = _data_operand(sim)
    t0 = time.perf_counter()
    setattr(sim, operand_name, one_batch_operand(sim, batch))
    try:
        state = fresh_state(host_state)
        program = {"losses": []}
        for r in range(CHECK_ROUNDS):
            state, m = sim.run_round(state)
            program["losses"].append(float(jax.device_get(m["train_loss"])))
            if r == 0:
                first_s = time.perf_counter() - t0
                program["first_grad"] = first_gradient(
                    host_state.variables, state, cfg.fed)
        program["final"] = jax.device_get(state.variables)
    finally:
        setattr(sim, operand_name, real_operand)
    return program, state, first_s


def reference_rounds(cell, cfg, pop, batch, initial, quant=None):
    from lib import fedref

    server = {"optimizer": cfg.fed.server_optimizer, "lr": cfg.fed.server_lr}
    return fedref.run_rounds(
        cell["arch"], cfg.train.lr, server, initial,
        reference_batches(pop, cell, batch, CHECK_ROUNDS), quant)



# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class WindowSink:
    """The metrics sink handed to ``run``: stamps every round record as
    it arrives and ends the window from here. In a traced run it also
    starts and stops the profiler around a steady part; the window then
    runs on to its end, so the numbers the profiler distorts (the tail,
    an evaluation) are read off the rounds it did not see."""

    def __init__(self, seconds: float, tracer=None):
        self.seconds = seconds
        self.tracer = tracer
        self.records, self.stamps = [], []
        self.t_start = None

    def open(self):
        self.t_start = time.perf_counter()

    def log(self, record: dict) -> None:
        now = time.perf_counter()
        self.records.append(dict(record))
        self.stamps.append(now)
        lost = 0.0
        if self.tracer is not None:
            self.tracer.step(now - self.t_start, record)
            if self.tracer.tracing():
                return
            lost = self.tracer.on_s()
        # writing a trace takes the profiler tens of seconds (43 s for
        # 4.5 s of four chips): a traced run's window is as long again
        # as that, so it still holds ``seconds`` of untouched rounds
        if now - self.t_start - lost >= self.seconds:
            raise _WindowClosed


class Tracer:
    """Profiles rounds ``[first, last]`` of the window: starts at the
    first record boundary after ``TRACE_SKIP_S`` that follows an
    evaluating round, and stops once it has covered two evaluation
    periods and ``TRACE_MIN_S`` seconds."""

    def __init__(self, out_dir: str, eval_every: int):
        self.out_dir, self.eval_every = out_dir, eval_every
        self.first = self.last = None
        self.t_on = None
        self.clock = None  # perf_counter() from switching on to written

    def step(self, elapsed: float, record: dict) -> None:
        import jax

        r = int(record["round"])
        if self.first is None:
            if elapsed >= TRACE_SKIP_S and (r + 1) % self.eval_every == 0:
                t0 = time.perf_counter()
                shutil.rmtree(self.out_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.out_dir, profiler_options=opts)
                self.first, self.t_on, self.clock = r + 1, elapsed, [t0, None]
            return
        if self.last is not None:
            return
        covered = r - self.first + 1
        if (covered >= 2 * self.eval_every and covered % self.eval_every == 0
                and elapsed - self.t_on >= TRACE_MIN_S):
            jax.profiler.stop_trace()
            self.last, self.clock[1] = r, time.perf_counter()

    def tracing(self) -> bool:
        return self.first is not None and self.last is None

    def on_s(self) -> float:
        """Seconds from switching the profiler on to its trace written
        (0 before the traced part)."""
        return self.clock[1] - self.clock[0] if self.last is not None else 0.0

    def saw(self, t0: float, t1: float) -> bool:
        """Did ``[t0, t1]`` on the host's clock touch the traced part
        (switching the profiler on and writing its trace included)?"""
        return (self.clock is not None and t1 >= self.clock[0]
                and (self.clock[1] is None or t0 <= self.clock[1]))


def annotate(sim, log: list):
    """Traced runs only: the benchmark's own host spans around the two
    calls ``run`` makes into the layers below it, written into the
    profiler's trace (same clock as the device) and kept on the host."""
    import jax

    def wrap(name, fn):
        def wrapped(*a, **k):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench." + name):
                out = fn(*a, **k)
            log.append((name, t0, time.perf_counter()))
            return out
        return wrapped

    sim.run_round = wrap("run_round", sim.run_round)
    sim.evaluate_global = wrap("evaluate_global", sim.evaluate_global)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def quantile95(values):
    """95th percentile, linear between order statistics."""
    xs = sorted(values)
    pos = 0.95 * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def loss_row(losses) -> dict:
    """Training through the window trains: the median train loss of the
    window's last quarter of rounds lies under that of its first. This
    is what holds the steps past a client's first, which the rounds the
    reference follows do not take. Under ``LOSS_ROUNDS_MIN`` rounds a
    quarter is one or two cohorts' luck, and the row is not judged."""
    n = len(losses)
    quarter = max(1, n // 4)
    first = statistics.median(losses[:quarter]) if n else float("nan")
    last = statistics.median(losses[-quarter:]) if n else float("nan")
    judged = n >= LOSS_ROUNDS_MIN
    return {"number": "train_loss_last_minus_first_quarter",
            "value": last - first, "limit": 0, "judged": judged,
            "ok": bool(last - first < 0) or not judged,
            "first_quarter": first, "last_quarter": last, "rounds": n}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, break_path=None) -> tuple[dict, int]:
    """-> (result object, exit code). ``break_path(sim)`` is for the
    tests that show ``correct`` can come out false."""
    import jax
    import numpy as np

    from fedml_tpu.core.compile_cache import enable_compile_cache
    from lib import fedref, refnet, traffic as TR
    from lib.compile_log import CompileLog
    from lib.peaks import peaks_for

    config, traffic, arch = cell["config"], cell["traffic"], cell["arch"]
    chips = int(cell["cell"]["chips"])
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_chip:
        if device["platform"] != "tpu":
            raise NoChip(f"this benchmark measures on a TPU; JAX found "
                         f"{device['platform']} ({device['kind']})")
        if len(devs) < chips:
            raise NoChip(f"{cell['name']} needs {chips} chips, JAX found "
                         f"{len(devs)}")
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else None
    compiles = CompileLog()
    say(phase="start", workload=cell["name"], seed=seed, seconds=seconds,
        trace=int(trace), device=device, compile_cache_dir=cache_dir)

    # -- set-up: data ------------------------------------------------------
    t0 = time.perf_counter()
    pop = TR.make_population(config["dataset"], traffic, seed)
    cfg = experiment_config(config, traffic)
    sim = build_sim(cfg, traffic, pop)
    _, real_operand = _data_operand(sim)
    jax.block_until_ready(jax.tree.leaves(real_operand))
    data_build_s = time.perf_counter() - t0
    batch = sim.batch_size
    say(phase="data", device=device, data_build_s=data_build_s,
        population=int(traffic["population"]),
        client_sizes={"min": int(pop["sizes"].min()),
                      "median": float(np.median(pop["sizes"])),
                      "max": int(pop["sizes"].max())},
        train_samples=int(pop["sizes"].sum()), batch=batch)

    # -- set-up: weights from the seed; the compiled round driven through
    # -- the rounds the reference follows, then handed to the window ------
    host_state = seed_state(sim, arch, seed)
    initial = host_state.variables
    sim.init = lambda: fresh_state(host_state)  # run() starts from them
    if break_path is not None:
        break_path(sim)
    t0 = time.perf_counter()
    program, state, first_round_s = drive_check_rounds(
        sim, cfg, batch, host_state)
    check_round_counter = int(state.round)
    state, m = sim.run_round(state)  # one round on the real population
    jax.block_until_ready(m)
    sim.evaluate_global(state)
    del state
    warm = compiles.snapshot()
    say(phase="warmup", device=device, first_round_s=first_round_s,
        warmup_s=time.perf_counter() - t0, compile=warm)

    spans: list = []
    tracer = None
    trace_dir = os.path.join(cell["bench_dir"], ".trace", cell["name"])
    if trace:
        annotate(sim, spans)
        tracer = Tracer(trace_dir, cfg.fed.eval_every)
    last = {}
    inner = sim.run_round

    def keep_state(state):
        out = inner(state)
        last["state"] = out[0]
        return out

    sim.run_round = keep_state
    sink = WindowSink(seconds, tracer)
    setup_s = time.perf_counter() - T_PROCESS

    # -- the window: the program's own round loop ---------------------------
    raised = 0
    sink.open()
    try:
        sim.run(metrics_sink=sink)
    except _WindowClosed:
        pass
    except Exception as err:  # a round that raises is a failed round
        import traceback

        traceback.print_exc()
        say(phase="window", device=device, error=repr(err)[:500])
        raised = 1
    window_s = (sink.stamps[-1] if sink.stamps else time.perf_counter()
                ) - sink.t_start
    in_window = compiles.snapshot()
    compiled_in_window = (
        in_window["backend_compile_s"] - warm["backend_compile_s"])

    records, stamps = sink.records, sink.stamps
    n = len(records)
    losses = [rec["train_loss"] for rec in records]
    failed = raised + sum(not math.isfinite(x) for x in losses)
    edges = [sink.t_start] + stamps
    if tracer is not None and tracer.first is not None and tracer.last is None:
        jax.profiler.stop_trace()  # the window closed inside the traced part
    # round intervals and the benchmark's spans that the profiler did not
    # touch (all of them in an untraced run)
    clean = [i for i in range(n) if tracer is None
             or not tracer.saw(edges[i], edges[i + 1])]
    intervals_ms = [1e3 * (edges[i + 1] - edges[i]) for i in clean]
    spans = [sp for sp in spans if tracer is None or not tracer.saw(*sp[1:])]
    final_round = int(last["state"].round) if "state" in last else -1
    # the allocator keeps two books: arrays that live on the chip
    # ("in use") and the scratch memory a running program reserves for
    # itself ("reserved", XLA's temp). The chip held both at once.
    stats = [d.memory_stats() or {} for d in devs[:chips]]
    memory = max(
        ({"peak_bytes_in_use": s.get("peak_bytes_in_use", 0),
          "peak_bytes_reserved": s.get("peak_bytes_reserved", 0)}
         for s in stats), key=lambda m: sum(m.values()))
    memory_peak = sum(memory.values())
    trained = loss_row(losses)
    accs = [(rec["round"], rec["test_acc"]) for rec in records
            if "test_acc" in rec]
    say(phase="window", device=device, rounds=n, window_s=window_s,
        round_interval_samples=len(intervals_ms),
        round_ms_median=(statistics.median(intervals_ms)
                         if intervals_ms else None),
        train_loss_first_quarter=trained["first_quarter"],
        train_loss_last_quarter=trained["last_quarter"],
        test_acc_trajectory=accs[:: max(1, len(accs) // 12)],
        state_round=final_round, compiled_in_window_s=compiled_in_window,
        memory_peak_bytes=memory_peak, **memory)

    # -- the reference, once the program's state is gone --------------------
    del sim, real_operand, last, inner
    gc.collect()
    t0 = time.perf_counter()
    reference = reference_rounds(cell, cfg, pop, batch, initial)
    rows, ok = fedref.compare(
        program, reference, initial, config["correct_limits"])
    rows.append(trained)
    rows.append({"number": "rounds_failed", "value": failed, "limit": 0,
                 "ok": failed == 0})
    rows.append({"number": "state_round_minus_rounds_counted",
                 "value": final_round - n, "limit": 0,
                 "ok": final_round == n})
    rows.append({"number": "check_rounds_counted",
                 "value": check_round_counter - CHECK_ROUNDS, "limit": 0,
                 "ok": check_round_counter == CHECK_ROUNDS})
    rows.append({"number": "compiled_in_window_s",
                 "value": compiled_in_window, "limit": 0,
                 "ok": compiled_in_window == 0})
    for row in rows:
        say(phase="check", device=device, **row)
    correct = bool(ok and all(r["ok"] for r in rows) and n > 0)
    say(phase="reference", device=device,
        reference_s=time.perf_counter() - t0, correct=correct)

    # -- metrics --------------------------------------------------------------
    ctx = {
        "cell": cell, "device": device, "peaks": peaks, "chips": chips,
        "records": records, "stamps": stamps, "t_start": sink.t_start,
        "intervals_ms": intervals_ms, "window_s": window_s,
        "round_p95_ms": quantile95(intervals_ms) if intervals_ms else None,
        "setup_s": setup_s, "data_build_s": data_build_s,
        "compile": warm, "memory_peak_bytes": memory_peak,
        "spans": spans, "trace": None,
    }
    result = {"correct": correct, "attempted": n + raised, "failed": failed}
    dev_out = dict(device, memory_peak_bytes=memory_peak, **memory)
    if not trace:
        values = {
            "rounds_per_s": n / window_s if window_s > 0 else 0.0,
            "round_p95_ms": ctx["round_p95_ms"],
            "setup_s": setup_s,
        }
        say(phase="end_to_end", device=device, samples=n, **values)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]
        }
    else:
        from lib import xplane

        if tracer.last is None:
            raise SystemExit("the window closed before the traced part did: "
                             "give the run more --seconds")
        say(phase="traced_part", device=device, rounds_in_window=n,
            rounds_outside_it=len(clean),
            profiler_on_s=tracer.on_s(), window_s=window_s)
        traced = list(range(tracer.first, tracer.last + 1))
        if device["platform"] == "tpu":  # no device numbers off the chip
            ctx["trace"] = xplane.reduce_trace(
                xplane.find_xplane(trace_dir), chips=chips,
                rounds=len(traced))
        ctx["traced_rounds"] = traced
        ctx["client_steps"] = client_steps(pop, cell, cfg, batch, traced)
        ctx["step_flops"] = refnet.step_flops(
            arch, batch, int(config["dataset"]["input_shape"][0]))
        say(phase="trace", device=device, traced_rounds=len(traced),
            client_steps=ctx["client_steps"], step_flops=ctx["step_flops"],
            **{k: v for k, v in (ctx["trace"] or {}).items()
               if k not in ("device_ops", "idle_gaps")})
        metrics = {}
        for m in cell["per_layer"]:
            reader = _load_py(
                reader_path(cell["bench_dir"], m["name"]), "bench_metric")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if device["platform"] == "tpu":
            dev_out["busy_s"] = ctx["trace"]["busy_s"]
            dev_out["window_s"] = ctx["trace"]["window_s"]
            result["breakdown"] = {
                "device_ops": ctx["trace"]["device_ops"][:10],
                "idle_gaps": ctx["trace"]["idle_gaps"][:10],
            }
    result.update(metrics=metrics, device=dev_out)
    return result, 0


def main(argv=None, root: str = ROOT, require_chip: bool = True,
         break_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)
    try:
        result, rc = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              require_chip, break_path)
    except NoChip as err:
        print(f"benchmarks/run.py: {err}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
