"""Experiment harness: algorithm registry + repetition runner.

TPU-native equivalent of the fork's ``ExperimentBase``
(``fedml_experiments/standalone/utils/experiment.py:16``: repetition loop
with group ids ``:27-39``, per-repetition seeding ``:69-76``) and the
per-algorithm ``main_<algo>.py`` entry scripts. One registry maps algorithm
names to sim builders; :class:`Experiment` runs N seeded repetitions and
writes JSONL metrics + a summary per repetition.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable

from fedml_tpu.config import ExperimentConfig
from fedml_tpu.core import fuse as F
from fedml_tpu.core import telemetry
from fedml_tpu.core.tracing import span
from fedml_tpu.data.loaders import load_dataset
from fedml_tpu.metrics.sink import MetricsSink
from fedml_tpu.models import create_model


def _fedavg_family(algorithm: str):
    def build(cfg: ExperimentConfig):
        from fedml_tpu.algorithms.fedavg import FedAvgSim

        cfg = dataclasses.replace(
            cfg, fed=dataclasses.replace(cfg.fed, algorithm=algorithm)
        )
        data = load_dataset(cfg.data)
        return FedAvgSim(create_model(cfg.model), data, cfg)

    return build


def _build_decentralized(method):
    def build(cfg: ExperimentConfig):
        from fedml_tpu.algorithms.decentralized import DecentralizedSim

        data = load_dataset(cfg.data)
        return DecentralizedSim(
            create_model(cfg.model), data, cfg, method=method
        )

    return build


def _build_hierarchical(cfg: ExperimentConfig):
    from fedml_tpu.algorithms.hierarchical import HierarchicalFedAvg

    data = load_dataset(cfg.data)
    return HierarchicalFedAvg(create_model(cfg.model), data, cfg)


def _build_gan(name):
    def build(cfg: ExperimentConfig):
        from fedml_tpu.algorithms import gan_core as G
        from fedml_tpu.algorithms.gan_family import (
            FedDTGSim, FedGANSim, FedGDKDSim,
        )
        from fedml_tpu.algorithms.sgan import FedSSGANSim, FedUAGANSim
        from fedml_tpu.models.gan import (
            ACGANDiscriminator, generator_from_config,
        )

        data = load_dataset(cfg.data)
        shape = cfg.model.input_shape
        gen = generator_from_config(
            cfg.gan, cfg.model.num_classes, shape[0], shape[-1]
        )
        if name == "fedgdkd":
            return FedGDKDSim(gen, create_model(cfg.model), data, cfg)
        disc = G.DiscHandle(
            module=ACGANDiscriminator(num_classes=cfg.model.num_classes),
            has_validity_head=True,
        )
        if name == "fedgan":
            return FedGANSim(gen, disc, data, cfg)
        if name == "feddtg":
            return FedDTGSim(gen, disc, create_model(cfg.model), data, cfg)
        if name == "fedssgan":
            return FedSSGANSim(
                gen,
                G.DiscHandle(
                    module=ACGANDiscriminator(
                        num_classes=cfg.model.num_classes
                    )
                ),
                data, cfg,
            )
        if name == "feduagan":
            return FedUAGANSim(gen, disc, data, cfg)
        raise ValueError(name)

    return build


def _build_distill(name):
    def build(cfg: ExperimentConfig):
        from fedml_tpu.algorithms.distill import FDSim, FedArjunSim, FedMDSim

        data = load_dataset(cfg.data)
        if name == "fedmd":
            return FedMDSim(create_model(cfg.model), data, cfg)
        if name == "fd_faug":
            return FDSim(create_model(cfg.model), data, cfg)
        if name == "fedarjun":
            local = dataclasses.replace(cfg.model, name="lr")
            return FedArjunSim(
                create_model(cfg.model), create_model(local), data, cfg
            )
        raise ValueError(name)

    return build


def _build_fedgkt(cfg: ExperimentConfig):
    from fedml_tpu.algorithms.split import FedGKTSim
    from fedml_tpu.models.gkt import GKTClientResNet, GKTServerResNet

    data = load_dataset(cfg.data)
    nc = cfg.model.num_classes
    return FedGKTSim(
        GKTClientResNet(num_classes=nc),
        GKTServerResNet(num_classes=nc),
        data, cfg,
    )


def _build_splitnn(cfg: ExperimentConfig):
    from fedml_tpu.algorithms.split import SplitNNSim
    from fedml_tpu.models.gkt import SplitClientNet, SplitServerNet

    data = load_dataset(cfg.data)
    return SplitNNSim(
        SplitClientNet(), SplitServerNet(num_classes=cfg.model.num_classes),
        data, cfg,
    )


def _build_vfl(cfg: ExperimentConfig):
    """Two-party classical vertical FL (reference
    ``standalone/classical_vertical_fl/vfl_fixture.py``): guest holds the
    labels, both parties contribute logit components from their feature
    slice. Datasets: ``nus_wide`` / ``lending_club`` real files under
    ``data_dir``, else ``fake_vfl`` — a seeded linearly-separable
    two-party set so offline smoke runs converge."""
    import numpy as np

    from fedml_tpu.algorithms.split import VFLSim
    from fedml_tpu.models.gkt import VFLDenseModel, VFLLocalModel

    ds = cfg.data.dataset
    if ds == "nus_wide":
        from fedml_tpu.data.vertical import load_nus_wide_two_party

        # VFLSim is a binary sigmoid-BCE model (reference vfl.py), so the
        # multi-concept labels must be binarized; "person"-vs-rest is the
        # reference experiments' usual positive concept
        d = load_nus_wide_two_party(
            cfg.data.data_dir, binary_positive="person"
        )
    elif ds == "lending_club":
        from fedml_tpu.data.vertical import load_lending_club_two_party

        d = load_lending_club_two_party(cfg.data.data_dir)
    else:  # fake_vfl / any offline name
        rng = np.random.default_rng(cfg.data.seed)
        n, dim = 512, 24
        w = rng.normal(size=(dim,))
        x = rng.normal(size=(n, dim)).astype(np.float32)
        xt = rng.normal(size=(n // 4, dim)).astype(np.float32)
        d = {
            "train": (x, (x @ w > 0).astype(np.float32)),
            "test": (xt, (xt @ w > 0).astype(np.float32)),
            "splits": [(0, dim // 2), (dim // 2, dim)],
        }
    return VFLSim(
        party_models=[
            (VFLLocalModel(out_dim=8, hidden=16), VFLDenseModel())
            for _ in d["splits"]
        ],
        feature_splits=d["splits"],
        x_train=d["train"][0],
        y_train=d["train"][1],
        x_test=d["test"][0],
        y_test=d["test"][1],
        cfg=cfg,
    )


def _build_turboaggregate(cfg: ExperimentConfig):
    """FedAvg with TurboAggregate secure aggregation as the server rule
    (reference ``distributed/turboaggregate``)."""
    from fedml_tpu.algorithms.mpc import SecureFedAvgSim

    data = load_dataset(cfg.data)
    return SecureFedAvgSim(create_model(cfg.model), data, cfg)


def _build_fednas(cfg: ExperimentConfig):
    from fedml_tpu.algorithms.fednas import FedNASSim
    from fedml_tpu.models.darts import DARTSNetwork

    data = load_dataset(cfg.data)
    return FedNASSim(
        DARTSNetwork(num_classes=cfg.model.num_classes), data, cfg
    )


def _build_baseline(cfg: ExperimentConfig):
    from fedml_tpu.algorithms.local_baselines import BaselineSim

    data = load_dataset(cfg.data)
    return BaselineSim(create_model(cfg.model), data, cfg)


def _build_centralized(cfg: ExperimentConfig):
    from fedml_tpu.algorithms.local_baselines import CentralizedTrainer

    data = load_dataset(cfg.data)
    return CentralizedTrainer(create_model(cfg.model), data, cfg)


def _build_dol(method):
    """Decentralized ONLINE learning (regret metric; reference
    ``main_dol.py``): dataset in {susy, ro} reads the UCI files under
    data_dir; anything else uses the procedural SUSY-shaped stream.
    ``comm_round`` doubles as the iteration count T. The adversarial
    ``beta`` fraction is taken from ``partition_alpha`` ONLY when
    ``partition_method == "hetero"`` was explicitly requested — the
    default run is fully stochastic (beta=0), matching the reference
    ``main_dol.py`` default."""

    def build(cfg: ExperimentConfig):
        from fedml_tpu.algorithms.decentralized import OnlineDecentralizedSim
        from fedml_tpu.data import streaming as S

        name = cfg.data.dataset.lower()
        n, t = cfg.data.num_clients, cfg.fed.num_rounds
        beta = (
            cfg.data.partition_alpha
            if cfg.data.partition_method == "hetero"
            else 0.0
        )
        if name in ("susy", "ro"):
            xs, ys = S.load_uci_stream(
                name, cfg.data.data_dir, n, t, beta=beta,
                seed=cfg.data.seed,
            )
        else:
            xs, ys = S.make_susy_like_stream(
                n, t, beta=beta, seed=cfg.data.seed
            )
        sim = OnlineDecentralizedSim(
            xs, ys, method=method, lr=cfg.train.lr,
            weight_decay=cfg.train.weight_decay, seed=cfg.seed,
        )
        sim.log_every = cfg.fed.eval_every  # harness eval cadence
        return sim

    return build


ALGORITHMS: dict[str, Callable[[ExperimentConfig], Any]] = {
    # FedAvg family: one compiled round, configured per variant
    "fedavg": _fedavg_family("fedavg"),
    "fedopt": _fedavg_family("fedopt"),
    "fedprox": _fedavg_family("fedavg"),  # prox_mu in TrainConfig
    "fednova": _fedavg_family("fednova"),
    "fedavg_robust": _fedavg_family("fedavg"),  # robust_* in FedConfig
    "fedavg_multiclient": _fedavg_family("fedavg"),
    "fedseg": _fedavg_family("fedavg"),  # segmentation task via dataset
    "decentralized_dsgd": _build_decentralized("dsgd"),
    "decentralized_pushsum": _build_decentralized("pushsum"),
    "dol_dsgd": _build_dol("dsgd"),
    "dol_pushsum": _build_dol("pushsum"),
    "hierarchical": _build_hierarchical,
    "fedgan": _build_gan("fedgan"),
    "fedgdkd": _build_gan("fedgdkd"),
    "feddtg": _build_gan("feddtg"),
    "fedssgan": _build_gan("fedssgan"),
    "feduagan": _build_gan("feduagan"),
    "fedmd": _build_distill("fedmd"),
    "fd_faug": _build_distill("fd_faug"),
    "fedarjun": _build_distill("fedarjun"),
    "fedgkt": _build_fedgkt,
    "splitnn": _build_splitnn,
    "vfl": _build_vfl,
    "classical_vertical_fl": _build_vfl,
    "turboaggregate": _build_turboaggregate,
    "fednas": _build_fednas,
    "baseline": _build_baseline,
    "centralized": _build_centralized,
}


def build_sim(cfg: ExperimentConfig):
    algo = cfg.fed.algorithm
    if algo not in ALGORITHMS:
        raise ValueError(
            f"unknown algorithm: {algo}; known: {sorted(ALGORITHMS)}"
        )
    return ALGORITHMS[algo](cfg)


class Experiment:
    """Seeded repetition runner (fork ``ExperimentBase``)."""

    def __init__(self, cfg: ExperimentConfig, repetitions: int = 1):
        self.cfg = cfg
        self.repetitions = repetitions

    def run(self) -> list[dict]:
        summaries = []
        for rep in range(self.repetitions):
            cfg = dataclasses.replace(
                self.cfg,
                seed=self.cfg.seed + rep,
                data=dataclasses.replace(
                    self.cfg.data, seed=self.cfg.data.seed + rep
                ),
                run_name=f"{self.cfg.run_name}_rep{rep}",
            )
            out_dir = os.path.join(cfg.out_dir, cfg.run_name)
            sink = MetricsSink(path=os.path.join(out_dir, "metrics.jsonl"))
            with open(
                _ensure(os.path.join(out_dir, "config.json")), "w"
            ) as f:
                f.write(cfg.to_json())
            sim = build_sim(cfg)
            self._run_sim(sim, cfg, sink)
            sink.close()
            summaries.append(dict(sink.summary, run_name=cfg.run_name))
        return summaries

    @staticmethod
    def _run_sim(sim, cfg: ExperimentConfig, sink: MetricsSink):
        """Drive any sim shape: prefer its own ``run``; else the
        run_round/evaluate protocol. With ``cfg.checkpoint_every`` > 0
        the generic loop takes over for sims exposing the
        init/run_round state protocol, so round state checkpoints
        atomically every N rounds and a restarted run resumes from the
        latest step. Sims without device-resident round state
        (host-driven or run-only shapes) cannot checkpoint — the flag
        warns and falls back to a plain run. On resume after a
        mid-interval crash, rounds after the last checkpoint re-run and
        re-log: metrics.jsonl may carry a duplicate round record — the
        later one is authoritative, machine-checkably so: every row a
        resumed incarnation logs carries ``resumed: true`` (consumers
        keep the ``resumed`` row when a round number appears twice)."""
        ckpt = None
        start_round = 0
        checkpointable = (
            cfg.checkpoint_every > 0
            and hasattr(sim, "init")
            and hasattr(sim, "run_round")
        )
        if cfg.checkpoint_every > 0 and not checkpointable:
            import warnings

            warnings.warn(
                f"checkpoint_every={cfg.checkpoint_every} ignored: "
                f"{type(sim).__name__} does not expose the "
                "init/run_round state protocol",
                stacklevel=2,
            )
        if not checkpointable:
            if (hasattr(sim, "run") and not isinstance(sim, type)
                    and _run_accepts_sink(sim)):
                try:
                    # run-shaped sims drive their own loop: one span
                    # covers the whole trajectory (round-level spans
                    # come from the generic loop below otherwise)
                    with span("fedml.run", sim=type(sim).__name__):
                        sim.run(metrics_sink=sink)
                    return
                except TypeError:
                    pass
        state = sim.init() if hasattr(sim, "init") else None
        if checkpointable and state is not None:
            from fedml_tpu.utils.checkpoint import RoundCheckpointer

            ckpt = RoundCheckpointer(
                os.path.join(
                    os.path.dirname(sink.path) if sink.path else
                    cfg.out_dir, "ckpt"
                )
            )
            state, start_round = Experiment._restore_state(
                ckpt, sim, state
            )
            if start_round:
                sink.log({"resumed_from": start_round})
        elif checkpointable:
            import warnings

            warnings.warn(
                "checkpoint_every ignored: sim has no device-resident "
                "round state (init() returned None)",
                stacklevel=2,
            )
        try:
            Experiment._round_loop(sim, cfg, sink, state, start_round,
                                   ckpt)
        finally:
            if ckpt is not None:
                ckpt.close()

    @staticmethod
    def _round_loop(sim, cfg, sink, state, start_round, ckpt):
        """The generic protocol, as hooks of the ONE round loop
        (``core.fuse.run_loop`` — the loop ``FedAvgSim.run`` calls, so
        a checkpointed run is timed and traced like any other): a round
        is ``run_round()`` for host-driven sims without state
        (HeteroFedGDKD), ``run_round(state, r)`` or ``run_round(state)``;
        the evaluator is the first of the known protocol names; a
        checkpoint is the after-round hook and a resume the start state
        and round. ``fuse_rounds`` > 1 drives ``run_block`` sims in
        blocks that end on the eval / checkpoint rounds."""
        fuse = int(getattr(cfg.fed, "fuse_rounds", 1) or 1)
        fusable = hasattr(sim, "run_block") and state is not None
        if fuse > 1 and not fusable:
            import warnings

            warnings.warn(
                f"fuse_rounds={fuse} ignored: {type(sim).__name__} "
                "does not expose the run_block state protocol (round "
                "fusion covers the FedAvg-family compiled sims); "
                "running per-round",
                stacklevel=2,
            )
        total = cfg.fed.num_rounds
        wants_round = _wants_round(sim)

        def step(state, r):
            if state is None:
                return None, sim.run_round()
            return (sim.run_round(state, r) if wants_round
                    else sim.run_round(state))

        def after_round(r, state):
            if telemetry.METRICS.enabled:
                # /statusz "run" block (core/export.py): the sim loop
                # has no actor to register, so the live round rides
                # the cheap run-state dict instead
                from fedml_tpu.core import export as _export

                _export.set_run_state(
                    round=r, num_rounds=total, run_name=cfg.run_name,
                )
            if ckpt is not None and (
                (r + 1) % cfg.checkpoint_every == 0 or r == total - 1
            ):
                Experiment._save_state(ckpt, sim, r, state)

        F.run_loop(
            sim, state, sink,
            step=step,
            run_block=sim.run_block if fusable else None,
            evaluate=lambda state: Experiment._evaluate(sim, state),
            path=getattr(sim, "_anatomy_path", lambda: "stacked")(),
            start=start_round,
            total=total,
            eval_every=cfg.fed.eval_every,
            fuse=fuse,
            checkpoint_every=(
                cfg.checkpoint_every if ckpt is not None else 0
            ),
            after_round=after_round,
        )

    @staticmethod
    def _save_state(ckpt, sim, r, state):
        """Checkpoint one round: sims carrying client-state banks
        (docs/FAULT_TOLERANCE.md "Client-state banks" — the compress
        error-feedback residual, the PEFT private adapter bank) save
        the ``{"server": state, "bank": {name: rows}}`` composite so a
        SIGKILLed run restores every client's row bitwise; bankless
        sims keep the bare-state layout unchanged."""
        banks = sim.bank_state() if hasattr(sim, "bank_state") else {}
        if banks:
            ckpt.save(r, {"server": state, "bank": banks})
        else:
            ckpt.save(r, state)

    @staticmethod
    def _restore_state(ckpt, sim, state):
        """The restore half of :meth:`_save_state`. Bank-aware sims
        restore through the raw (template-free) path so the composite's
        variable bank payload never has to match a shape template; a
        legacy bare-state checkpoint (or a composite from a config
        without this sim's banks) restores the server state and leaves
        the lazily-initialized fresh banks in place — exactly what the
        pre-bank checkpoint encoded."""
        if not (hasattr(sim, "restore_banks")
                and hasattr(sim, "bank_state")):
            return ckpt.restore_or(state)
        raw, nxt = ckpt.restore_raw()
        if raw is None:
            return state, 0
        from fedml_tpu.utils.checkpoint import from_savable

        bank_blob = None
        if isinstance(raw, dict) and "server" in raw:
            bank_blob = raw.get("bank")
            raw = raw["server"]
        restored = from_savable(state, raw)
        sim.restore_banks(restored, bank_blob)
        return restored, nxt

    @staticmethod
    def _evaluate(sim, state) -> dict:
        """Run the sim's evaluator (first of the known protocol names);
        ``core.fuse.eval_record`` names its fields for the record."""
        for ev_name in ("evaluate_global", "evaluate_clients",
                        "evaluate_consensus", "evaluate"):
            if hasattr(sim, ev_name):
                ev = getattr(sim, ev_name)
                return ev(state) if state is not None else ev()
        return {}


def _wants_round(sim) -> bool:
    import inspect

    try:
        return len(inspect.signature(sim.run_round).parameters) >= 2
    except (TypeError, ValueError):
        return False


def _run_accepts_sink(sim) -> bool:
    """Signature gate for the ``sim.run(metrics_sink=...)`` fast path —
    checked up front so a sim without the kwarg falls through to the
    generic loop WITHOUT a probe call (which would record a phantom
    error-tagged sim_run span when tracing is on)."""
    import inspect

    try:
        params = inspect.signature(sim.run).parameters
    except (TypeError, ValueError):
        return True  # unintrospectable: fall back to the call probe
    return "metrics_sink" in params or any(
        p.kind == inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


def _ensure(path: str) -> str:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    return path
