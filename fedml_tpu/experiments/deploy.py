"""Process-separated deployment: one OS process per rank, over a socket.

The reference's canonical deployment is N separate OS processes —
``mpirun -np $PROCESS_NUM`` launching a per-rank ``main_fedavg.py``
(``fedml_experiments/distributed/fedavg/run_fedavg_distributed_pytorch.sh:1-20``)
and the cross-silo shell launchers that start a server role and client
roles on separate machines
(``fedml_experiments/distributed/fedavg_cross_silo/run_server.sh``,
``run_client.sh``). This module is that surface for the TPU framework:
``python -m fedml_tpu.experiments.run --role server|client --rank N
--world_size W --backend grpc|tcp|trpc|pubsub|pubsub_blob ...`` runs ONE
rank; ``scripts/run_distributed.sh`` is the mpirun-shaped localhost
launcher.

Equality contract: every process derives its data partition, model init,
and rng keys from the shared seeded config, and the actors are the same
:mod:`fedml_tpu.algorithms.distributed_fedavg` /
:mod:`fedml_tpu.algorithms.split_actors` classes whose loopback runs are
equality-pinned against the compiled sims — so an N-process run over real
sockets matches the compiled simulator to float round-off
(``tests/test_deploy.py`` pins it cross-process).

Readiness: socket transports have no MPI-style barrier, and the pub/sub
path drops publishes with no subscriber (MQTT QoS-0 semantics). Clients
therefore re-announce ``MSG_TYPE_C2S_READY`` every 0.5 s until the
server ACKs (``MSG_TYPE_S2C_ACK`` reply to each READY) or any other
server message arrives; the server starts round 0 once all
``world_size - 1`` distinct ranks have announced. The ACK matters:
liveness must not be inferred from WORK traffic — a later-rank SplitNN
client legitimately idles for the whole of its predecessors' epochs, and
before the ACK existed it would hit ``ready_timeout`` and kill a healthy
run. Send failures during announcement (server socket not yet bound) are
retried, which makes process launch order irrelevant — the reference
gets the same property from MQTT broker buffering + its client
"register" message.

Liveness (docs/FAULT_TOLERANCE.md): once the run is underway both sides
heartbeat (``MSG_TYPE_HEARTBEAT``) and watch per-peer last-seen times.
The server routes dead peers into the actor's straggler logic
(``FedAvgServerActor.on_peer_dead`` — quorum/deadline rounds) instead of
blocking forever on its inbox; clients detect a dead server and exit
loudly. Deterministic fault injection for all of this lives in
:mod:`fedml_tpu.core.transport.chaos` and is threaded here via
``DeployConfig.fault``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import threading
import time

import jax
import numpy as np

from fedml_tpu.config import ExperimentConfig
from fedml_tpu.core import telemetry
from fedml_tpu.core.manager import Manager, ServerManager, create_transport
from fedml_tpu.core.message import (
    MSG_TYPE_C2S_JOIN,
    MSG_TYPE_C2S_READY,
    MSG_TYPE_S2C_ACK,
    Message,
)
from fedml_tpu.core.transport.base import BaseTransport
from fedml_tpu.core.transport.chaos import ChaosTransport, FaultPolicy

FEDAVG_FAMILY = ("fedavg", "fedopt", "fednova")
DEPLOY_ALGORITHMS = FEDAVG_FAMILY + ("splitnn",)


@dataclasses.dataclass(frozen=True)
class DeployConfig:
    """One rank's deployment coordinates (the reference passes these as
    ``--client_id/--server_ip`` flags + ``ip_config`` CSV tables,
    ``ip_config_utils.py``)."""

    role: str  # "server" | "client"
    rank: int  # 0 = server, >=1 = client
    world_size: int
    backend: str = "grpc"
    ip_config: dict[int, tuple[str, int]] | None = None
    broker: tuple[str, int] | None = None  # pubsub* backends
    blob_dir: str | None = None  # pubsub_blob file-backed store
    ready_timeout: float = 120.0
    # -- fault tolerance (docs/FAULT_TOLERANCE.md) -------------------------
    heartbeats: bool = True  # arm the liveness protocol once underway
    heartbeat_interval_s: float = 2.0
    heartbeat_timeout_s: float = 30.0
    # straggler-tolerant rounds (fedavg family): fraction of live workers
    # whose results close a round at the deadline; None deadline = wait
    # for every live worker (dead ones are still skipped via heartbeats)
    quorum_fraction: float = 1.0
    round_deadline_s: float | None = None
    # -- crash recovery (docs/FAULT_TOLERANCE.md "Recovery") ---------------
    # server rank: checkpoint ServerState every N closed rounds under
    # <run_dir>/ckpt and resume from the latest checkpoint on restart
    # (0 = off; the same flag drives the simulator path)
    checkpoint_every: int = 0
    # deadline-under-quorum re-arms before the quorum-lost abort fires —
    # under a supervisor a crashed rank is seconds from rejoining
    recovery_extensions: int = 0
    # seeded fault injection for THIS rank (None/disabled = real traffic)
    fault: FaultPolicy | None = None
    # -- Byzantine defense (docs/FAULT_TOLERANCE.md "Threat model") --------
    # server rank: quarantine clients whose cross-round EWMA anomaly
    # score exceeds the threshold (0 = off); they stay served but their
    # results are excluded from aggregation, and the reputation state
    # rides the round checkpoint so it survives server restarts
    quarantine_threshold: float = 0.0
    quarantine_decay: float = 0.7
    # rounds a rank may sit in quarantine before it is PERMANENTLY
    # evicted from the membership ledger (0 = never escalate)
    quarantine_evict_after: int = 0
    # -- elastic membership (docs/FAULT_TOLERANCE.md "Elastic
    # membership"): client rank — after submitting the result for this
    # round, announce a graceful LEAVE and wind down (None = stay for
    # the whole run)
    leave_after_round: int | None = None
    # server rank, set by the SUPERVISOR on a restart: ranks whose final
    # summary reported a graceful LEAVE (or eviction) — they are never
    # respawned, so even if the restored checkpoint predates the
    # departure the barrier must not wait for them (the ledger is
    # brought up to date before the required set is computed)
    presumed_left: tuple[int, ...] = ()
    # like presumed_left but for ranks whose summary said "evicted":
    # the restored ledger must mark them EVICTED, not LEFT — a LEFT
    # rank may JOIN back, a ban must survive the restart
    presumed_evicted: tuple[int, ...] = ()
    # -- async + tiered aggregation (docs/FAULT_TOLERANCE.md "Async +
    # tiered worlds"): the tier topology this world runs under
    # (``root:<L>`` — one root, L leaf aggregators; None = flat).
    # Roles "server" (the root) and "leaf" consume it; clients are
    # topology-blind (they only ever talk to rank 0 of THEIR world).
    tier_spec: str | None = None
    # leaf rank only: the ROOT world's rank table (the leaf's own
    # ``ip_config`` is its leaf world, where it is rank 0)
    uplink_ip_config: dict[int, tuple[str, int]] | None = None
    # leaf rank only: global client id of this leaf's slot 0 (None =
    # the TierSpec default — contiguous equal-size blocks)
    tier_client_base: int | None = None
    # -- telemetry (docs/OBSERVABILITY.md) ---------------------------------
    # directory for THIS rank's artifacts: trace_rank<r>.json span dump,
    # metrics_rank<r>.json snapshot, flight_rank<r>_*.json crash rings;
    # None + trace=False keeps the telemetry plane fully disabled
    telemetry_dir: str | None = None
    trace: bool = False  # span tracing without (or in addition to) a dir
    # periodic metrics time-series flush: seconds between snapshot rows
    # appended to metrics_rank<r>.jsonl (None = off; the round-latency
    # SLO surface of a long-lived server — histograms carry p50/p95/p99
    # — docs/OBSERVABILITY.md "Performance observability")
    metrics_interval: float | None = None
    # live OpenMetrics exporter (core/export.py, docs/OBSERVABILITY.md
    # "Live export and SLOs"): serve /metrics + /statusz + /healthz on
    # this port (0 = ephemeral; None = no socket, the default — the
    # zero-cost-when-off rule). SLO specs ride FedConfig.slos. The
    # endpoints are unauthenticated; metrics_host restricts the bind
    # (default any-interface so a remote Prometheus can scrape).
    metrics_port: int | None = None
    metrics_host: str = "0.0.0.0"


def load_ip_config(path: str) -> dict[int, tuple[str, int]]:
    """JSON ``{"0": ["host", port], ...}`` -> rank table (the reference
    uses CSV ``ip_config`` files; JSON keeps the one-file shape)."""
    with open(path) as f:
        raw = json.load(f)
    return {int(r): (str(h), int(p)) for r, (h, p) in raw.items()}


def _make_transport(dep: DeployConfig) -> BaseTransport:
    backend = dep.backend.upper()
    if backend in ("PUBSUB", "MQTT", "PUBSUB_BLOB", "MQTT_S3"):
        from fedml_tpu.core.transport.broker import RemoteTopicBus
        from fedml_tpu.core.transport.pubsub import BlobStore

        assert dep.broker is not None, f"{dep.backend} needs --broker"
        bus = RemoteTopicBus(*dep.broker)
        store = None
        if backend in ("PUBSUB_BLOB", "MQTT_S3"):
            assert dep.blob_dir is not None, (
                "pubsub_blob needs --blob_dir (file-backed cross-process "
                "blob store)"
            )
            store = BlobStore(root=dep.blob_dir)
        transport = create_transport(
            dep.backend, dep.rank, bus=bus, store=store,
            size=dep.world_size,
        )
    else:
        assert dep.ip_config is not None, f"{dep.backend} needs --ip_config"
        transport = create_transport(
            dep.backend, dep.rank, ip_config=dep.ip_config
        )
    if dep.fault is not None and dep.fault.enabled():
        if dep.fault.corrupt_prob and backend not in (
                "TCP", "PUBSUB", "MQTT", "PUBSUB_BLOB", "MQTT_S3"):
            import sys as _sys

            print(
                "warning: --fault_corrupt flips bits in the sealed "
                "tcp/pubsub frame codecs; the "
                f"{dep.backend} backend does not seal frames, so the "
                "corrupt fault is inert here",
                file=_sys.stderr,
            )
        transport = ChaosTransport(transport, dep.fault)
    return transport


# ---------------------------------------------------------------------------
# readiness handshake + liveness
# ---------------------------------------------------------------------------


def _server_dead_peer_cb(server: ServerManager):
    """Route heartbeat-detected client deaths into the actor.

    Actors with straggler-tolerant rounds (``on_peer_dead``) absorb the
    death — the round closes over the survivors or aborts with a quorum
    diagnostic. Actors without it (SplitNN's strictly-sequential
    round-robin cannot skip a rank) record the failure and stop the
    transport: fail loudly instead of hanging."""

    def on_dead(rank: int) -> None:
        handler = getattr(server, "on_peer_dead", None)
        if handler is not None:
            handler(rank)  # dumps its own flight artifact
            return
        server._liveness_failure = (
            f"client rank {rank} became unreachable mid-run "
            "(heartbeats stopped)"
        )
        telemetry.flight_dump(
            "dead_peer", peer=rank, detail=server._liveness_failure
        )
        server.transport.stop()

    return on_dead


class _AliveObserver:
    """Second transport observer on the server: counts the SENDER of
    every inbound message toward the readiness barrier. In a fresh run
    this is inert (a fresh client's first message IS its JOIN); after a
    supervised server restart it is what completes the barrier — the
    surviving clients are blocked mid-run waiting for the next sync and
    only emit heartbeats, which prove they are up and reachable."""

    def __init__(self, note):
        self._note = note

    def receive_message(self, msg_type: int, msg: Message) -> None:
        self._note(msg.sender)


def _serve_with_ready_barrier(
    server: ServerManager, dep: DeployConfig, kickoff
) -> None:
    """ACK every READY/JOIN, start (or resume) the run once all clients
    have announced or otherwise proven liveness, arm the dead-client
    watchdog, then drain until the actor finishes. A JOIN arriving AFTER
    kickoff is a rejoin: it is routed to the actor's ``on_peer_rejoin``
    (docs/FAULT_TOLERANCE.md "Recovery")."""
    ready: set[int] = set()
    started = threading.Event()
    # the barrier's required set: normally the launch world, but a
    # server RESTORED from an elastic checkpoint serves the ledger's
    # world — a rank that gracefully LEFT before the crash must not be
    # waited on (it is never coming back), and a mid-run admission that
    # outlived the crash completes the barrier like any member
    for r in dep.presumed_left:
        # the supervisor SAW these ranks depart (their final summary
        # said "left") and will never respawn them; if the restored
        # checkpoint predates the departure the ledger still lists
        # them ACTIVE and the barrier would wait forever — bring the
        # ledger up to date first
        leave = getattr(server, "on_peer_leave", None)
        if leave is not None:
            leave(r)
    for r in dep.presumed_evicted:
        # same, but the departure was a PERMANENT ban: replaying it as
        # a LEAVE would let the banned (possibly adversarial) rank
        # JOIN back in — re-evict so the restored ledger rejects it.
        # notify=False: the rank's process already exited with
        # status "evicted"; a FINISH to its gone endpoint would only
        # sit out the transport retry budget and delay the barrier
        evict = getattr(server, "evict_rank", None)
        if evict is not None:
            evict(r, notify=False)
    required = (set(server.client_ranks())
                - set(dep.presumed_left)
                - set(dep.presumed_evicted))
    # an empty required set normally means an actor without a ledger —
    # fall back to waiting for the launch world. But when departures
    # EXPLAIN the emptiness (every restored member departed by design)
    # the launch ranks are never respawned: falling back would wedge
    # the relaunch forever — wait for the next admission instead
    # (note_alive grows the set as JOINs are admitted)
    all_departed = (not required
                    and bool(dep.presumed_left or dep.presumed_evicted))
    if not required and not all_departed:
        required = set(range(1, dep.world_size))

    def note_alive(sender: int) -> None:
        # observers run on the single dispatch thread — no lock needed
        if started.is_set():
            return
        if sender not in required:
            if all_departed and sender in server.client_ranks():
                # admitted after the all-departed barrier was computed:
                # this rank IS the world now — it completes the barrier
                required.add(sender)
            else:
                return
        ready.add(sender)
        if len(ready) >= len(required):
            started.set()
            if dep.heartbeats:
                server.enable_liveness(
                    # re-read: ranks admitted DURING the barrier window
                    # are watched from kickoff too
                    server.client_ranks(),
                    interval_s=dep.heartbeat_interval_s,
                    timeout_s=dep.heartbeat_timeout_s,
                    on_dead=_server_dead_peer_cb(server),
                )
            kickoff()

    def on_ready(msg: Message) -> None:
        # ACK unconditionally (duplicates arrive by design — clients
        # re-announce until acknowledged): the ACK tells a client the
        # control channel works BOTH ways, independent of when its
        # first work message will come (a later-rank SplitNN client may
        # idle for the whole of its predecessors' epochs)
        try:
            server.send_message(
                Message(MSG_TYPE_S2C_ACK, 0, msg.sender, {})
            )
        except Exception:
            pass  # client endpoint flapped; it will re-announce
        note_alive(msg.sender)

    def on_join(msg: Message) -> None:
        join = getattr(server, "on_peer_join",
                       getattr(server, "on_peer_rejoin", None))
        membership = getattr(server, "membership", None)
        if (membership is not None
                and msg.sender in membership.get("evicted", ())):
            # a restored ledger may ban a rank INSIDE the launch world
            # (--quarantine_evict_after before the restart): its JOIN is
            # never ACKed, pre-kickoff included — ACKing would park the
            # banned client waiting forever for a sync it will never
            # get, masquerading as a healthy member
            return
        if started.is_set():
            if join is not None:
                # unified membership entry: rejoin for active members
                # (WELCOMEd with the current round's sync), mid-run
                # ADMISSION for ranks beyond the launch world, silent
                # rejection for evicted ranks
                # (docs/FAULT_TOLERANCE.md "Elastic membership")
                if join(msg.sender) == "admitted":
                    # an admission is not synced until the NEXT round
                    # boundary: ACK now so the joiner's announce loop
                    # stops waiting instead of racing ready_timeout
                    # against an in-flight round that may outlast it
                    # (without heartbeats the ACK is its only contact)
                    try:
                        server.send_message(
                            Message(MSG_TYPE_S2C_ACK, 0, msg.sender, {})
                        )
                    except Exception:
                        pass  # joiner endpoint flapped; it re-JOINs
                return
            # actor without mid-run membership (SplitNN's strictly
            # sequential rounds): ACK so the client stops announcing
            on_ready(msg)
            return
        returning = (membership is not None
                     and msg.sender in membership.get("left", ()))
        if join is not None and (
                not (1 <= msg.sender < dep.world_size) or returning):
            # a beyond-world rank announcing BEFORE kickoff — or an
            # in-world rank a RESTORED ledger marks LEFT (departed
            # before the server was SIGKILLed, relaunched now): admit
            # it into the ledger (first cohort slot at the next round
            # boundary); without the re-admission the LEFT rank would
            # be ACKed but never served — parked forever outside
            # client_ranks(). It neither counts toward nor blocks the
            # launch barrier, which still waits for the configured
            # world. An EVICTED rank is never ACKed — its announce
            # loop times out loudly on its side.
            if join(msg.sender) == "rejected":
                return
        on_ready(msg)

    # NOTE: no per-deploy heartbeat handler anymore. A client's liveness
    # view must be satisfiable BEFORE the barrier completes (its watchdog
    # arms at ACK time, but the server's own beats only start at kickoff)
    # — the Manager's default handler covers this: every beat carrying
    # ``hb_ts`` is echoed back, which both refreshes the client's
    # last-seen table and closes its RTT gauge loop.
    server.register_message_receive_handler(MSG_TYPE_C2S_READY, on_ready)
    server.register_message_receive_handler(MSG_TYPE_C2S_JOIN, on_join)
    server.transport.add_observer(_AliveObserver(note_alive))
    server.transport.start()
    server.run()  # blocks until the actor's finish path stops the transport


def _announce_until_first_message(
    mgr: Manager, dep: DeployConfig
) -> tuple[threading.Event, list[str]]:
    """Client side: re-send JOIN until the server's ACK (fresh run), its
    WELCOME (mid-run rejoin), or any other server message arrives, then
    arm the server-liveness watchdog. A fresh start and a supervised
    restart are deliberately indistinguishable here — the SERVER decides
    (pre-kickoff JOIN counts toward the barrier like READY; post-kickoff
    JOIN is a rejoin, docs/FAULT_TOLERANCE.md "Recovery").

    Returns ``(first-inbound event, failure log)``. If ``ready_timeout``
    expires before any server message, the loop STOPS the transport so
    the caller's ``run()`` unblocks — the caller must then check the
    event and fail loudly (a silently-hung client would wedge the whole
    launcher run). Once the server HAS been heard from, the heartbeat
    monitor takes over: a server that goes silent mid-run (crashed
    endpoint, dead broker) stops the transport and records the failure
    for the caller to raise. Pub/sub caveat: a publish to a dead peer
    succeeds silently (MQTT QoS-0), so there the staleness detector is
    the only signal — which is why BOTH sides beat."""
    got = threading.Event()
    failures: list[str] = []

    class _FirstInbound:
        def receive_message(self, msg_type: int, msg: Message) -> None:
            got.set()

    mgr.transport.add_observer(_FirstInbound())

    def on_server_dead(rank: int) -> None:
        failures.append(
            "server became unreachable mid-run (no inbound traffic for "
            f"{dep.heartbeat_timeout_s}s)"
        )
        telemetry.flight_dump("dead_peer", peer=rank, detail=failures[0])
        mgr.transport.stop()

    def loop() -> None:
        deadline = time.monotonic() + dep.ready_timeout
        while not got.is_set() and time.monotonic() < deadline:
            try:
                mgr.send_message(
                    Message(MSG_TYPE_C2S_JOIN, mgr.rank, 0, {})
                )
            except Exception:
                pass  # server endpoint not up yet — retry
            got.wait(0.5)
        if not got.is_set():
            mgr.transport.stop()  # unblock run() -> caller raises
            return
        if dep.heartbeats:
            mgr.enable_liveness(
                [0],
                interval_s=dep.heartbeat_interval_s,
                timeout_s=dep.heartbeat_timeout_s,
                on_dead=on_server_dead,
            )

    threading.Thread(target=loop, daemon=True).start()
    return got, failures


def _check_contacted(got: threading.Event, dep: DeployConfig) -> None:
    if not got.is_set():
        raise RuntimeError(
            f"server never contacted this client within "
            f"--ready_timeout {dep.ready_timeout}s — is the server rank "
            "up and reachable?"
        )


def _run_client(mgr: Manager, dep: DeployConfig) -> None:
    """Client main loop: announce, drain until FINISH (or a detected
    server death / readiness timeout), fail loudly on either."""
    mgr.transport.start()
    got, failures = _announce_until_first_message(mgr, dep)
    mgr.run()
    _check_contacted(got, dep)
    if failures:
        raise RuntimeError(failures[0])


# ---------------------------------------------------------------------------
# rank entrypoints
# ---------------------------------------------------------------------------


def _params_digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def _run_dir(cfg: ExperimentConfig) -> str:
    d = os.path.join(cfg.out_dir, cfg.run_name)
    os.makedirs(d, exist_ok=True)
    return d


def _write_final(cfg: ExperimentConfig, tag: str, tree) -> str:
    """Persist final variables (numpy pytree pickle — the cross-process
    equality artifact the tests and the launcher compare)."""
    path = os.path.join(_run_dir(cfg), f"{tag}.pkl")
    host = jax.tree.map(np.asarray, tree)
    with open(path, "wb") as f:
        pickle.dump(host, f, protocol=5)
    return path


def _run_tier_leaf_rank(cfg: ExperimentConfig, dep: DeployConfig) -> dict:
    """Run ONE leaf aggregator (docs/FAULT_TOLERANCE.md "Async +
    tiered worlds"): rank 0 of its own leaf world toward its clients
    (``--ip_config``), member rank ``dep.rank`` of the root world
    toward the root (``--uplink_ip_config``). The leaf waits for its
    OWN clients' readiness barrier first, then announces JOIN upstream
    — so the root's barrier completes exactly when every leaf's
    subtree is servable."""
    from fedml_tpu.algorithms.async_actors import TierAggregatorActor
    from fedml_tpu.algorithms.distributed_fedavg import (
        QuorumLostError,
        RoundPolicy,
    )
    from fedml_tpu.core.reputation import QuarantinePolicy
    from fedml_tpu.core.tier import TierSpec
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    tier = TierSpec.parse(dep.tier_spec)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    # downlink: this leaf IS rank 0 of its leaf world. uplink: member
    # rank of the root world — chaos flags stay on the client-facing
    # edge only (a faulted uplink would punish every client at once).
    downlink = _make_transport(dataclasses.replace(dep, rank=0))
    uplink_t = _make_transport(dataclasses.replace(
        dep, ip_config=dep.uplink_ip_config, fault=None,
    ))
    uplink = Manager(dep.rank, tier.root_world_size, uplink_t)
    base = (
        dep.tier_client_base
        if dep.tier_client_base is not None
        else tier.client_base(dep.rank, dep.world_size - 1)
    )
    leaf = TierAggregatorActor(
        dep.world_size, downlink, uplink, model, cfg,
        client_base=base,
        num_clients=cfg.data.num_clients, data=data,
        round_policy=RoundPolicy(
            quorum_fraction=dep.quorum_fraction,
            round_deadline_s=dep.round_deadline_s,
            recovery_extensions=dep.recovery_extensions,
        ),
        quarantine=QuarantinePolicy(
            threshold=dep.quarantine_threshold,
            decay=dep.quarantine_decay,
            evict_after=dep.quarantine_evict_after,
        ),
    )
    up_state: dict = {"got": None, "failures": []}

    def kickoff() -> None:
        # this leaf's subtree is ready: surface upstream. The announce
        # helper re-sends JOIN until the root answers and then arms
        # the uplink liveness watchdog — a dead root stops the uplink,
        # and the bridge below stops the downlink so the leaf fails
        # loudly instead of serving a headless subtree forever.
        uplink_t.start()
        got, failures = _announce_until_first_message(uplink, dep)
        up_state["got"], up_state["failures"] = got, failures
        threading.Thread(target=uplink.run, daemon=True,
                         name=f"leaf{dep.rank}-uplink").start()

        def bridge() -> None:
            uplink_t._stopped.wait()
            if not leaf.done.is_set():
                leaf.transport.stop()

        threading.Thread(target=bridge, daemon=True,
                         name=f"leaf{dep.rank}-uplink-bridge").start()

    _serve_with_ready_barrier(leaf, dep, kickoff)
    if leaf.failure is not None:
        raise QuorumLostError(
            f"leaf {dep.rank} aborted: {leaf.failure}"
        )
    if up_state["failures"]:
        raise RuntimeError(up_state["failures"][0])
    if up_state["got"] is not None:
        _check_contacted(up_state["got"], dep)
    if not leaf.done.is_set():
        raise RuntimeError(
            f"leaf {dep.rank} stopped before the root finished the "
            f"run (version {leaf.round_idx})"
        )
    return {
        "role": "leaf",
        "rank": dep.rank,
        "status": "finished",
        "tier_spec": dep.tier_spec,
        "client_base": base,
        "partials": leaf.partials_sent,
        "membership": leaf.membership,
        "quarantined": leaf.quarantined_ranks,
        "dead_peers": sorted(leaf.dead_peers),
    }


def _run_fedavg_rank(cfg: ExperimentConfig, dep: DeployConfig) -> dict:
    from fedml_tpu.algorithms.distributed_fedavg import (
        FedAvgClientActor,
        FedAvgServerActor,
    )
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    if dep.role == "leaf":
        return _run_tier_leaf_rank(cfg, dep)
    # every rank rebuilds the identical seeded dataset + partition (the
    # reference ships the same data path to every MPI rank too,
    # main_fedavg.py load_data before FedML_FedAvg_distributed)
    data = load_dataset(cfg.data)
    model = create_model(cfg.model)
    transport = _make_transport(dep)

    if dep.role == "server":
        from fedml_tpu.algorithms.distributed_fedavg import (
            QuorumLostError,
            RoundPolicy,
        )

        ckpt = None
        if dep.checkpoint_every > 0:
            from fedml_tpu.utils.checkpoint import RoundCheckpointer

            # <run_dir>/ckpt — the same layout the simulator harness
            # uses, so a deploy run and a sim run of one config share
            # the resume story (docs/FAULT_TOLERANCE.md "Recovery")
            ckpt = RoundCheckpointer(os.path.join(_run_dir(cfg), "ckpt"))
        from fedml_tpu.core.reputation import QuarantinePolicy

        # actor-class selection (docs/FAULT_TOLERANCE.md "Async +
        # tiered worlds"): async and/or tiered servers are strictly
        # opt-in subclasses — with both knobs off this constructs the
        # untouched FedAvgServerActor, byte-identical to every prior
        # release (pinned in tests/test_async.py)
        from fedml_tpu.core.async_agg import AsyncConfig
        from fedml_tpu.core.tier import TierSpec

        acfg = AsyncConfig.from_fed(cfg.fed)
        extra = {}
        if dep.tier_spec is not None:
            from fedml_tpu.algorithms.async_actors import (
                AsyncTierRootActor,
                TierRootActor,
            )

            tier = TierSpec.parse(dep.tier_spec)
            if dep.world_size != tier.root_world_size:
                raise ValueError(
                    f"--tier_spec {dep.tier_spec} implies a root "
                    f"world of {tier.root_world_size} (root + "
                    f"{tier.n_leaves} leaves), got --world_size "
                    f"{dep.world_size}"
                )
            cls = AsyncTierRootActor if acfg.enabled() else TierRootActor
            extra["tier_spec"] = tier
        elif acfg.enabled():
            from fedml_tpu.algorithms.async_actors import (
                AsyncFedAvgServerActor,
            )

            cls = AsyncFedAvgServerActor
        else:
            cls = FedAvgServerActor
        server = cls(
            dep.world_size, transport, model, cfg,
            num_clients=cfg.data.num_clients, data=data,
            round_policy=RoundPolicy(
                quorum_fraction=dep.quorum_fraction,
                round_deadline_s=dep.round_deadline_s,
                recovery_extensions=dep.recovery_extensions,
            ),
            checkpointer=ckpt,
            checkpoint_every=dep.checkpoint_every or 1,
            quarantine=QuarantinePolicy(
                threshold=dep.quarantine_threshold,
                decay=dep.quarantine_decay,
                evict_after=dep.quarantine_evict_after,
            ),
            **extra,
        )
        try:
            if server.resumed_from >= cfg.fed.num_rounds:
                # restored AT the end (crash between the final round
                # closing and the summary): nothing to run, and the
                # clients that finished the run may be gone for good —
                # don't wait on a readiness barrier that can never
                # complete; just finish and emit the summary
                server.done.set()
                server.finish_all()
            else:
                _serve_with_ready_barrier(server, dep, server.kickoff)
        finally:
            if ckpt is not None:
                ckpt.close()
        if server.failure is not None:
            raise QuorumLostError(
                f"run aborted (straggler tolerance exhausted): "
                f"{server.failure}"
            )
        if not server.done.is_set():
            raise RuntimeError(
                f"server stopped before completing {cfg.fed.num_rounds} "
                f"rounds (round_idx={server.round_idx})"
            )
        path = _write_final(cfg, "final_params", server.variables)
        # global test metrics on the final model (reference
        # test_on_server_for_all_clients, FedAVGAggregator.py:110-164)
        from fedml_tpu.algorithms.base import build_evaluator, make_task

        arrays = data.to_arrays(pad_multiple=cfg.data.batch_size)
        ev = build_evaluator(model, make_task(data.task))
        metrics = {
            k: float(v)
            for k, v in ev(server.variables, arrays.test_x,
                           arrays.test_y).items()
        }
        return {
            "role": "server",
            "algorithm": cfg.fed.algorithm,
            "backend": dep.backend,
            "world_size": dep.world_size,
            "rounds": server.round_idx,
            # first round executed by THIS incarnation (0 = fresh start;
            # > 0 = restored from <run_dir>/ckpt after a crash)
            "resumed_from": server.resumed_from,
            "final_params": path,
            "params_digest": _params_digest(server.variables),
            "dead_peers": sorted(server.dead_peers),
            # the Byzantine-defense plane's verdicts (docs/
            # FAULT_TOLERANCE.md "Threat model"): the defense rule in
            # force and which ranks ended the run quarantined
            "defense": cfg.fed.robust_method,
            "quarantined": server.quarantined_ranks,
            # the elastic-membership verdicts (docs/FAULT_TOLERANCE.md
            # "Elastic membership"): who ended the run active / left /
            # evicted — mid-run admissions show up as active ranks
            # beyond the launch world
            "membership": server.membership,
            "elastic": bool(cfg.fed.elastic_buckets),
            # the wire codec + aggregation layout in force
            # (docs/PERFORMANCE.md "Wire compression"): reduction
            # claims must be checkable against what actually ran
            "compress": cfg.fed.compress,
            "shard_aggregation": bool(cfg.fed.shard_aggregation),
            # the async/tier plane in force (docs/FAULT_TOLERANCE.md
            # "Async + tiered worlds"): 0 / None == the synchronous
            # flat path ran, byte-identical to prior releases
            "async_buffer_k": cfg.fed.async_buffer_k,
            "async_restored_folds": getattr(server, "restored_folds",
                                            0),
            "tier_spec": dep.tier_spec,
            # the live-observability plane in force (docs/
            # OBSERVABILITY.md "Live export and SLOs"): the SLO specs
            # evaluated this run (verdicts in slo_rank<r>.json) and
            # the exporter's bound port (None = no listener)
            "slos": list(cfg.fed.slos),
            "metrics_port": getattr(telemetry.exporter(), "port",
                                    None),
            **metrics,
        }

    client = FedAvgClientActor(
        dep.rank, dep.world_size, transport, model, data, cfg,
        leave_after_round=dep.leave_after_round,
    )
    _run_client(client, dep)
    return {
        "role": "client",
        "rank": dep.rank,
        # "left": announced a graceful LEAVE; "evicted": the server
        # FINISHed it out of the world permanently; either way the
        # Supervisor must never respawn or reactivate this rank
        "status": (
            "left" if client.left.is_set()
            else "evicted" if client.finish_reason == "evicted"
            else "finished"
        ),
    }


def _run_splitnn_rank(cfg: ExperimentConfig, dep: DeployConfig) -> dict:
    from fedml_tpu.algorithms.split import SplitNNSim
    from fedml_tpu.algorithms.split_actors import (
        SplitNNClientActor,
        SplitNNServerActor,
    )
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models.gkt import SplitClientNet, SplitServerNet

    if dep.world_size != cfg.data.num_clients + 1:
        raise ValueError(
            "splitnn deployment: world_size must be num_clients+1 "
            f"(got {dep.world_size} vs {cfg.data.num_clients}+1)"
        )
    if dep.checkpoint_every:
        import sys as _sys

        # only the fedavg-family server checkpoints rounds; saying so
        # loudly beats letting the user believe a splitnn run is
        # durable (it restarts from round 0 after a crash)
        print(
            "warning: --checkpoint_every is ignored for splitnn "
            "deployments (round checkpointing covers the fedavg "
            "family only)",
            file=_sys.stderr,
        )
    if cfg.adversary.enabled():
        import sys as _sys

        print(
            "warning: --adversary_* flags are ignored by splitnn "
            "ranks (adversary injection covers the fedavg-family "
            "client actor only)",
            file=_sys.stderr,
        )
    if cfg.fed.compress != "none" or cfg.fed.shard_aggregation:
        import sys as _sys

        print(
            "warning: --compress / --shard_aggregation are ignored by "
            "splitnn ranks (the compressed + sharded weight-update "
            "path covers the fedavg family only)",
            file=_sys.stderr,
        )
    data = load_dataset(cfg.data)
    client_model = SplitClientNet()
    server_model = SplitServerNet(num_classes=cfg.model.num_classes)
    # the seeded sim init is the shared starting point: each rank takes
    # only its own piece (the reference distributes initial weights by
    # broadcast; here init is deterministic so no round-0 broadcast of
    # the lower stacks is needed)
    sim = SplitNNSim(client_model, server_model, data, cfg)
    state0 = sim.init()
    transport = _make_transport(dep)

    if dep.role == "server":
        server = SplitNNServerActor(
            dep.world_size, transport, server_model,
            state0.server_vars, cfg,
        )
        _serve_with_ready_barrier(server, dep, server.start_round)
        if not server.done.is_set():
            liveness = getattr(server, "_liveness_failure", None)
            raise RuntimeError(
                liveness
                if liveness is not None
                else f"splitnn server stopped before completing "
                     f"{cfg.fed.num_rounds} rounds (round_idx="
                     f"{server.round_idx})"
            )
        path = _write_final(cfg, "final_server_params", server.server_vars)
        return {
            "role": "server",
            "algorithm": "splitnn",
            "backend": dep.backend,
            "world_size": dep.world_size,
            "rounds": len(server.metrics_history),
            "final_params": path,
            "params_digest": _params_digest(server.server_vars),
            "metrics_history": server.metrics_history,
        }

    client = SplitNNClientActor(
        dep.rank, dep.world_size, transport, client_model,
        jax.tree.map(lambda s: s[dep.rank - 1], state0.client_stack),
        data, cfg,
    )
    _run_client(client, dep)
    path = _write_final(
        cfg, f"final_client{dep.rank}_params", client.c_vars
    )
    return {
        "role": "client",
        "rank": dep.rank,
        "status": "finished",
        "final_params": path,
        "params_digest": _params_digest(client.c_vars),
    }


# ---------------------------------------------------------------------------
# supervised deployment: spawn, watch, restart
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RankSpec:
    """One rank's launch recipe for the :class:`Supervisor`.

    ``restart_argv`` (default: ``argv``) is what a RESTARTED incarnation
    runs — the CLI supervise path strips ``--fault_*`` chaos flags here,
    so an injected ``--fault_crash_round`` kills the first incarnation
    exactly once and the replacement runs clean (otherwise the restart
    would re-crash on the same round's sync, forever)."""

    rank: int
    argv: list[str]
    restart_argv: list[str] | None = None


class SupervisorError(RuntimeError):
    """A rank exhausted its restart budget (or the run timed out); the
    message carries the rank, exit code, and last log path."""


class Supervisor:
    """Process supervisor for a deployment world: spawns every rank,
    watches exit codes, and restarts crashed ranks with capped
    exponential backoff (the same :class:`RetryPolicy` schedule the
    transports use), turning a SIGKILL of any rank into a
    kill -> restart -> rejoin -> converge loop instead of a dead run
    (docs/FAULT_TOLERANCE.md "Recovery").

    Exit-code semantics: nonzero — including signal deaths (negative
    returncodes) and chaos's
    :data:`~fedml_tpu.core.transport.chaos.CHAOS_EXIT_CODE` — is a
    crash, restarted until ``max_restarts`` per rank is spent. The run
    succeeds when the SERVER (rank 0) exits 0; its last stdout line is
    the run summary. A CLIENT exiting 0 is a genuine end-of-run
    wind-down when the server is alive and has never crashed (the
    normal case — the server exits moments later); but when the server
    has crashed or is mid-restart, a clean client exit means it obeyed
    a doomed incarnation's FINISH broadcast, so it is respawned after
    ``finish_grace_s`` — and a server crash likewise *reactivates*
    clients that were already marked finished. These respawns spend
    their own ``respawns`` cap, never the crash budget. Each attempt's
    output goes to ``<log_dir>/rank<r>_try<n>.log`` (a crashed rank's
    log is named in the failure diagnostic)."""

    def __init__(
        self,
        specs: list[RankSpec],
        *,
        max_restarts: int = 3,
        backoff=None,
        env: dict | None = None,
        cwd: str | None = None,
        log_dir: str | None = None,
        poll_interval_s: float = 0.1,
        # delay before respawning a client whose clean exit was judged
        # premature (server crashed / mid-restart); a genuine
        # end-of-run never schedules one
        finish_grace_s: float = 5.0,
    ):
        import tempfile

        from fedml_tpu.core.transport.retry import RetryPolicy

        self.specs = {s.rank: s for s in specs}
        assert 0 in self.specs, "the supervisor needs a server (rank 0)"
        self.max_restarts = max_restarts
        self.backoff = backoff or RetryPolicy(
            max_attempts=max_restarts + 1, base_delay_s=0.5,
            max_delay_s=10.0, jitter=0.25, deadline_s=float("inf"),
        )
        self.env = env
        self.cwd = cwd
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="fedml_sup_")
        os.makedirs(self.log_dir, exist_ok=True)
        self.poll_interval_s = poll_interval_s
        self.finish_grace_s = finish_grace_s
        self.procs: dict[int, "subprocess.Popen"] = {}
        self.restarts: dict[int, int] = {r: 0 for r in self.specs}
        self.respawns: dict[int, int] = {r: 0 for r in self.specs}
        self.exited: dict[int, int] = {}  # rank -> rc for clean exits
        # ranks whose clean exit was a graceful LEAVE (summary status
        # "left"): departed BY DESIGN, never respawned or reactivated —
        # the ledger keeps the departure across server restarts and the
        # restored barrier will not wait for them
        self.departed: set[int] = set()
        # the subset of departed whose status was "evicted": a restarted
        # server must re-EVICT them (not mark them LEFT) so the ban
        # survives a checkpoint that predates it
        self.evicted: set[int] = set()
        self.log_paths: dict[int, list[str]] = {r: [] for r in self.specs}
        self._fhs: list = []
        self._pending: dict[int, float] = {}  # rank -> respawn-at time
        import random as _random

        self._rng = _random.Random(0)

    def _spawn(self, rank: int, argv: list[str]) -> None:
        import subprocess

        from fedml_tpu.analysis.flags import check_rank_argv

        # one registration contract across run.py and this
        # supervisor (fedml_tpu/analysis/flags.py): a client argv
        # carrying a rank-0-only bind flag (--metrics_port) means the
        # caller built its RankSpecs without run.py's strip — fail at
        # spawn, not at N clients fighting over one port
        check_rank_argv(argv, rank)
        n = len(self.log_paths[rank])
        path = os.path.join(self.log_dir, f"rank{rank}_try{n}.log")
        fh = open(path, "w")
        self._fhs.append(fh)
        self.log_paths[rank].append(path)
        self.procs[rank] = subprocess.Popen(
            argv, env=self.env, cwd=self.cwd, stdout=fh,
            stderr=subprocess.STDOUT,
        )

    def _terminate_all(self) -> None:
        for p in self.procs.values():
            try:
                p.terminate()
            except Exception:
                pass
        deadline = time.monotonic() + 5
        for p in self.procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except Exception:
                try:
                    p.kill()
                except Exception:
                    pass
        self.procs.clear()
        self._pending.clear()
        for fh in self._fhs:
            try:
                fh.close()
            except Exception:
                pass

    def _server_healthy(self) -> bool:
        """True while rank 0 is alive RIGHT NOW (not crashed, not
        awaiting respawn). Prior crashes don't matter: a client exiting
        0 under a live server incarnation is a genuine wind-down even
        after a recovery (the restarted server's own post-run work can
        take tens of seconds), and the one mis-classification this
        allows — a doomed server broadcasting FINISH moments before its
        own death — is repaired by the rank-0 crash handler, which
        reactivates every already-finished client."""
        proc = self.procs.get(0)
        return (
            0 not in self._pending
            and proc is not None
            and proc.poll() is None
        )

    def _client_departed(self, rank: int) -> str | None:
        """The rank's departure status if its last incarnation reported
        a departure BY DESIGN — its final stdout line is the run.py
        summary JSON with ``status: "left"`` (graceful LEAVE) or
        ``"evicted"`` (the server permanently banned it and FINISHed it
        out of the world); either way the rank must stay gone
        (docs/FAULT_TOLERANCE.md "Elastic membership"). None for an
        ordinary finish (or no readable summary)."""
        try:
            with open(self.log_paths[rank][-1], "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - 4096))
                tail = f.read().decode("utf-8", "replace")
        except Exception:
            return None
        for line in reversed(tail.strip().splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            # stderr rides the same stream (_spawn merges it): a
            # '{'-prefixed fragment AFTER the summary (interpreter-
            # shutdown noise, dict reprs) must not mask the summary —
            # keep scanning earlier lines past anything that is not a
            # status-carrying JSON object
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            status = (
                obj.get("status") if isinstance(obj, dict) else None
            )
            if status is not None:
                return (
                    status if status in ("left", "evicted") else None
                )
        return None

    def _respawn_finished_client(self, rank: int) -> None:
        """Schedule a respawn for a client whose clean exit was judged
        premature (it obeyed a doomed server incarnation's FINISH).
        Spends the respawn cap, not the crash budget."""
        if self.respawns[rank] >= max(3, self.max_restarts):
            self._terminate_all()
            raise SupervisorError(
                f"rank {rank} kept finishing prematurely "
                f"({self.respawns[rank]} respawns) while the "
                f"server never completed; last log: "
                f"{self.log_paths[rank][-1]}"
            )
        self.respawns[rank] += 1
        telemetry.RECORDER.record(
            "premature_finish", rank=rank,
            respawn=self.respawns[rank],
        )
        self._pending[rank] = time.monotonic() + self.finish_grace_s

    def _on_exit(self, rank: int, rc: int) -> None:
        if rc == 0:
            status = (
                self._client_departed(rank) if rank != 0 else None
            )
            if status is not None:
                # graceful LEAVE or eviction: this clean exit is a
                # mid-run departure BY DESIGN, not an obeyed FINISH —
                # stays gone even if the server is mid-restart (the
                # rank-0 respawn argv carries the departure so the
                # restored barrier will not wait for it)
                self.departed.add(rank)
                if status == "evicted":
                    self.evicted.add(rank)
                self.exited[rank] = 0
                return
            if rank == 0 or self._server_healthy():
                # the server completing, or a client winding down while
                # a never-crashed server finishes its post-run work
                # (eval + summary can take tens of seconds cold) — a
                # genuine finish, not a failure
                self.exited[rank] = 0
                return
            # server crashed / mid-restart: this client's FINISH came
            # from a doomed incarnation — bring it back so the
            # restarted server's barrier can complete
            self._respawn_finished_client(rank)
            return
        if self.restarts[rank] >= self.max_restarts:
            self._terminate_all()
            raise SupervisorError(
                f"rank {rank} exited rc={rc} with its restart budget "
                f"({self.max_restarts}) spent; last log: "
                f"{self.log_paths[rank][-1]}"
            )
        pause = self.backoff.delay(self.restarts[rank], self._rng)
        self.restarts[rank] += 1
        telemetry.METRICS.inc("recovery.restarts")
        # every restart is a flight-recorder trigger: the artifact names
        # the rank, the exit code, and the backoff it sat out
        telemetry.flight_dump(
            "restart", rank=rank, code=rc,
            attempt=self.restarts[rank], delay_s=pause,
        )
        self._pending[rank] = time.monotonic() + pause
        if rank == 0:
            # the dying server may have FINISHed clients into clean
            # exits moments before it crashed — reactivate them; its
            # restarted incarnation needs them back at the barrier.
            # Gracefully-LEFT ranks stay gone: the ledger says so.
            for r in [r for r in self.exited
                      if r != 0 and r not in self.departed]:
                del self.exited[r]
                self._respawn_finished_client(r)

    def run(self, timeout: float | None = None) -> dict:
        """Supervise until the server completes (returns the run
        summary parsed from its stdout) or a budget is exhausted
        (raises :class:`SupervisorError`)."""
        import json as _json

        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        try:
            for rank in sorted(self.specs, reverse=True):  # clients 1st
                self._spawn(rank, self.specs[rank].argv)
            while True:
                now = time.monotonic()
                if deadline is not None and now > deadline:
                    raise SupervisorError(
                        f"run exceeded its {timeout}s budget "
                        f"(restarts so far: {self.restarts})"
                    )
                for rank, at in list(self._pending.items()):
                    if now >= at:
                        del self._pending[rank]
                        spec = self.specs[rank]
                        argv = list(spec.restart_argv or spec.argv)
                        if rank == 0 and self.departed:
                            # the restored checkpoint may predate a
                            # departure: tell the restarted server
                            # which ranks are gone BY DESIGN so its
                            # barrier does not wait forever for ranks
                            # this supervisor will never respawn —
                            # evictions separately, so the ledger
                            # re-bans instead of marking merely LEFT
                            left = sorted(self.departed - self.evicted)
                            if left:
                                argv += ["--presumed_left",
                                         *(str(r) for r in left)]
                            if self.evicted:
                                argv += ["--presumed_evicted", *(
                                    str(r) for r in sorted(self.evicted)
                                )]
                        self._spawn(rank, argv)
                for rank, proc in list(self.procs.items()):
                    rc = proc.poll()
                    if rc is None:
                        continue
                    del self.procs[rank]
                    self._on_exit(rank, rc)
                if self.exited.get(0) == 0:
                    break
                if not self.procs and not self._pending:
                    raise SupervisorError(
                        "every rank exited but the server never "
                        f"completed (clean exits: {self.exited})"
                    )
                time.sleep(self.poll_interval_s)
            # server done: clients received FINISH — give them a grace
            # window to unwind, then stop any leftovers
            grace = time.monotonic() + 15
            for p in self.procs.values():
                try:
                    p.wait(timeout=max(0.1, grace - time.monotonic()))
                except Exception:
                    pass
        finally:
            self._terminate_all()
        with open(self.log_paths[0][-1]) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        summary = None
        for ln in reversed(lines):  # stderr shares the file: take the
            try:                    # last line that IS the summary JSON
                cand = _json.loads(ln)
            except ValueError:
                continue
            # json.loads also accepts bare scalars ('1.0', 'true',
            # quoted strings) that a trailing library/log line can
            # produce — the rank summary is always an object
            if isinstance(cand, dict):
                summary = cand
                break
        if summary is None:
            raise SupervisorError(
                f"server completed but its log carries no summary "
                f"JSON ({self.log_paths[0][-1]})"
            )
        return {
            "summary": summary,
            "restarts": dict(self.restarts),
            "respawns": dict(self.respawns),
            "logs": {r: list(p) for r, p in self.log_paths.items()},
        }


def run_role(cfg: ExperimentConfig, dep: DeployConfig) -> dict:
    """Run THIS process's rank to completion; returns the rank summary."""
    if (dep.telemetry_dir or dep.trace
            or dep.metrics_interval or dep.metrics_port is not None
            or cfg.fed.slos or cfg.fed.anatomy
            or cfg.fed.profile_on_breach):
        telemetry.configure(
            # --trace without a dir still gets dumps, in the run dir
            telemetry_dir=dep.telemetry_dir
            or telemetry.default_dir(cfg.out_dir, cfg.run_name),
            rank=dep.rank,
            metrics_interval=dep.metrics_interval,
            metrics_port=dep.metrics_port,
            metrics_host=dep.metrics_host,
            slos=cfg.fed.slos,
            slo_scope=cfg.run_name,
        )
        if cfg.fed.anatomy or cfg.fed.profile_on_breach:
            # the round-anatomy plane (core/anatomy.py) rides the
            # telemetry dir configured above; the knobs travel in
            # FedConfig so every rank of a world shares ONE config —
            # the supervisor strips --profile_on_breach from client
            # argv (rank-0-only), explicit --role launches honor what
            # each rank's own command line says
            from fedml_tpu.core import anatomy

            anatomy.configure(
                anatomy=cfg.fed.anatomy,
                profile_on_breach=cfg.fed.profile_on_breach,
                profile_window_s=cfg.fed.profile_window_s,
                profile_max_captures=cfg.fed.profile_max_captures,
            )
    algo = cfg.fed.algorithm
    if algo in FEDAVG_FAMILY:
        return _run_fedavg_rank(cfg, dep)
    if dep.role == "leaf":
        raise ValueError(
            f"--role leaf covers the fedavg family only (tier "
            f"aggregation has no {algo!r} path)"
        )
    if algo == "splitnn":
        return _run_splitnn_rank(cfg, dep)
    raise ValueError(
        f"algorithm {algo!r} has no deployment path; deployable: "
        f"{DEPLOY_ALGORITHMS} (every other algorithm runs via the "
        "compiled simulator, python -m fedml_tpu.experiments.run without "
        "--role)"
    )
