"""Actor-based distributed FedAvg over the message-passing runtime.

Redesign of ``fedml_api/distributed/fedavg`` (5-file pattern:
``FedAvgAPI.py`` init + rank split, ``FedAVGAggregator``, ``FedAVGTrainer``,
``FedAvgServerManager``/``FedAvgClientManager``, ``message_define.py``).
The actor shell is for TRUE cross-process deployments (multi-host DCN);
compute inside each actor is the same jitted local update as the compiled
simulator, so the math is identical to :class:`FedAvgSim` by construction.

Topology (reference ``FedAvgAPI.py:36-66``): rank 0 = server, rank i>=1
trains the partition of client ``cohort[i-1]`` each round.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.config import ExperimentConfig
from fedml_tpu.core import adversary as A
from fedml_tpu.core.anatomy import ANATOMY
from fedml_tpu.core import compress as CMP
from fedml_tpu.core import elastic as E
from fedml_tpu.core import export as EXPORT
from fedml_tpu.core import memscope as MEMSCOPE
from fedml_tpu.core import robust, telemetry
from fedml_tpu.core import tree as T
from fedml_tpu.core.tracing import span
from fedml_tpu.core.membership import MembershipLedger
from fedml_tpu.core.reputation import QuarantinePolicy, ReputationTracker
from fedml_tpu.core.manager import ClientManager, ServerManager
from fedml_tpu.core.message import (
    KEY_CLIENT_INDEX,
    KEY_COMPRESSED,
    KEY_MODEL_PARAMS,
    KEY_NUM_SAMPLES,
    KEY_ROUND,
    MSG_TYPE_C2S_JOIN,
    MSG_TYPE_C2S_LEAVE,
    MSG_TYPE_C2S_RESULT,
    MSG_TYPE_FINISH,
    MSG_TYPE_S2C_SYNC_MODEL,
    MSG_TYPE_S2C_WELCOME,
    Message,
)
from fedml_tpu.core.transport.base import BaseTransport
from fedml_tpu.data.federated import FederatedData, arrays_and_batch
from fedml_tpu.algorithms.base import build_local_update, make_task
from fedml_tpu.algorithms.fedavg import (
    ServerState,
    local_reducer,
    make_server_optimizer,
    server_update,
)
from fedml_tpu.core import random as RND
from fedml_tpu.models.base import FedModel


@dataclasses.dataclass(frozen=True)
class RoundPolicy:
    """Straggler tolerance for the actor-based server (Server Averaging
    for FL, arxiv 2103.11619: a server that makes progress from whatever
    subset of updates actually arrives).

    - ``quorum_fraction``: fraction of the round's LIVE workers whose
      results suffice to close the round once the deadline fires
      (aggregation weights renormalize over the survivors — the weighted
      mean divides by the survivors' sample mass). 1.0 + no deadline ==
      the strict everyone-reports behavior, byte-identical to the
      compiled simulator.
    - ``round_deadline_s``: wall-clock budget per round. When it expires
      with quorum met, the round closes without the stragglers; without
      quorum, the run aborts with a diagnostic instead of hanging.
      ``None`` disables the deadline (crashed peers are still handled
      via the heartbeat dead-peer callback).
    - ``recovery_extensions``: how many times a deadline that fires
      UNDER quorum re-arms for the same round instead of aborting —
      under a supervisor a crashed rank is typically seconds from being
      restarted and rejoining, so the hard quorum-lost abort only fires
      once recovery has had its chance (docs/FAULT_TOLERANCE.md
      "Recovery"). 0 (the default) keeps the PR-1 abort-at-first-expiry
      behavior.
    """

    quorum_fraction: float = 1.0
    round_deadline_s: float | None = None
    recovery_extensions: int = 0

    def __post_init__(self):
        if not (0.0 < self.quorum_fraction <= 1.0):
            raise ValueError(
                f"quorum_fraction must be in (0, 1], "
                f"got {self.quorum_fraction}"
            )
        if self.round_deadline_s is not None and self.round_deadline_s <= 0:
            raise ValueError(
                f"round_deadline_s must be positive or None, "
                f"got {self.round_deadline_s}"
            )
        if self.recovery_extensions < 0:
            raise ValueError(
                f"recovery_extensions must be >= 0, "
                f"got {self.recovery_extensions}"
            )
        if self.recovery_extensions and self.round_deadline_s is None:
            raise ValueError(
                "recovery_extensions requires round_deadline_s: "
                "extensions re-arm the round deadline, so without one "
                "there is nothing to extend and the quorum-lost abort "
                "would still fire immediately"
            )


class QuorumLostError(RuntimeError):
    """The server could not assemble a quorum of client results (too many
    crashed/straggling ranks). Carries the server's diagnostic."""


def _result_is_finite(params, n_k: float) -> bool:
    """True iff a client result carries only finite values (floating
    leaves checked; integer leaves are finite by construction)."""
    if not math.isfinite(n_k):
        return False
    for leaf in jax.tree.leaves(params):
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating) and not np.all(
            np.isfinite(a)
        ):
            return False
    return True


class FedAvgServerActor(ServerManager):
    """Rank-0 aggregator (reference ``FedAVGServerManager`` +
    ``FedAVGAggregator``) with straggler-tolerant rounds: the round
    closes when every live worker reports, when the deadline fires with
    a quorum of results in hand, or aborts loudly when the quorum is
    unreachable — the server never blocks forever on a crashed client."""

    def __init__(
        self,
        size: int,
        transport: BaseTransport,
        model: FedModel,
        cfg: ExperimentConfig,
        num_clients: int,
        on_round_done: Callable[[int, dict], None] | None = None,
        initial_variables=None,
        steps_per_epoch: int | None = None,
        batch_size: int | None = None,
        data: FederatedData | None = None,
        round_policy: RoundPolicy | None = None,
        checkpointer=None,
        checkpoint_every: int = 1,
        quarantine: QuarantinePolicy | None = None,
    ):
        super().__init__(0, size, transport)
        self.cfg = cfg
        self.num_clients = num_clients
        self.model = model
        variables = (
            initial_variables
            if initial_variables is not None
            else model.init(jax.random.key(cfg.seed))
        )
        opt = make_server_optimizer(
            cfg.fed.server_optimizer, cfg.fed.server_lr,
            cfg.fed.server_momentum,
        )
        # full ServerState so EVERY server rule the compiled sim supports
        # (FedOpt adam/adagrad/yogi pseudo-gradients, FedNova
        # tau-normalization + gmf momentum, robust clip/noise/median/
        # trimmed-mean) runs over the actor runtime too — the transport
        # zoo's second consumer (ref fedopt/FedOptAggregator.py)
        self.state = ServerState(
            variables=variables,
            opt_state=opt.init(variables["params"]),
            momentum=jax.tree.map(jnp.zeros_like, variables["params"]),
            round=jnp.asarray(0, jnp.int32),
        )
        # FedNova's tau normalization needs the RESOLVED batch size and
        # steps_per_epoch (arrays_and_batch handles full-batch mode and
        # batch > max_n clamping) — pass `data` or the explicit values;
        # raw cfg.data.batch_size would silently skew tau.
        if data is not None and (steps_per_epoch is None
                                 or batch_size is None):
            arrays, rbatch = arrays_and_batch(data, cfg.data)
            batch_size = rbatch if batch_size is None else batch_size
            if steps_per_epoch is None:
                steps_per_epoch = arrays.max_client_samples // rbatch
        if cfg.fed.algorithm == "fednova" and (
            steps_per_epoch is None or batch_size is None
        ):
            raise ValueError(
                "fednova server rule needs BOTH steps_per_epoch and "
                "batch_size (the RESOLVED values — full-batch mode and "
                "batch > max_n clamping change them): pass data= to "
                "resolve automatically, or both values explicitly"
            )
        # explicit 0 is a caller bug (would silently skew FedNova tau if
        # coerced to 1) — reject rather than repair
        if steps_per_epoch is not None and steps_per_epoch < 1:
            raise ValueError(
                f"steps_per_epoch must be >= 1, got {steps_per_epoch}"
            )
        if batch_size is not None and batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.steps_per_epoch = 1 if steps_per_epoch is None else steps_per_epoch
        self.batch_size = cfg.data.batch_size if batch_size is None else batch_size
        self.root_key = jax.random.key(cfg.seed)
        self.round_idx = 0
        self._round_t0 = time.monotonic()
        # perf observability (core/perf.py, docs/OBSERVABILITY.md
        # "Performance observability"): the idle-gap signal fires its
        # flight-recorder event once per process, not once per round
        self._idle_gap_flagged = False
        self._results: dict[int, tuple[dict, float]] = {}
        self._lock = threading.Lock()
        self.on_round_done = on_round_done
        self.done = threading.Event()
        self.round_policy = (
            round_policy if round_policy is not None else RoundPolicy()
        )
        # -- elastic membership (docs/FAULT_TOLERANCE.md "Elastic
        # membership"): the ledger — not the launch world_size — is the
        # source of truth for who is served. JOINs from ranks beyond
        # the launch world are admitted mid-run with a stable client
        # id; MSG_TYPE_C2S_LEAVE marks a graceful departure (no restart
        # budget, no suspicion); eviction is permanent. The ledger
        # rides the round checkpoint so a SIGKILLed server restores the
        # grown/shrunk world, not the launch flag's.
        self._ledger = MembershipLedger(size, num_clients)
        self.dead_peers: set[int] = set()
        self.failure: str | None = None  # quorum-lost diagnostic
        self._deadline_timer: threading.Timer | None = None
        # generation stamp carried by every armed deadline timer:
        # Timer.cancel() is a no-op once the callback has STARTED (it
        # may already be blocked on self._lock), so a superseded timer
        # is also invalidated by its stale generation — without this, a
        # timer racing the recovery-extension re-arm could abort (or
        # burn an extra extension) inside the freshly-opened window
        self._deadline_gen = 0
        # deadline-under-quorum re-arms already spent on the current
        # round (RoundPolicy.recovery_extensions); reset per round
        self._extensions_used = 0
        # the CURRENT round's broadcast payload ``(round_idx, host_vars,
        # cohort)``, stashed by start_round so a mid-round rejoiner gets
        # the EXACT sync its cohort-mates got (a WELCOME built from live
        # state could race a round close and ship the next round's model
        # under this round's tag)
        self._round_sync: tuple[int, dict, np.ndarray] | None = None
        # rank -> round of its last WELCOME: a rejoiner re-announces
        # JOIN every 0.5 s until its first inbound, and with a large
        # model the WELCOME can take longer than that — the duplicates
        # must refresh its watchdog, not re-serialize the full model
        # (or re-count the rejoin)
        self._welcomed: dict[int, int] = {}
        # -- durable rounds (docs/FAULT_TOLERANCE.md "Recovery"): with a
        # RoundCheckpointer the server persists ServerState (which
        # carries the round counter the RNG folding derives from) every
        # ``checkpoint_every`` closed rounds, and a restarted rank 0
        # resumes from the last completed round instead of round 0.
        self._ckpt = checkpointer
        self.checkpoint_every = checkpoint_every
        self.resumed_from = 0
        # -- Byzantine defense plane (docs/FAULT_TOLERANCE.md "Threat
        # model"): the per-round defense rule rides cfg.fed.robust_*
        # through server_update; the cross-round reputation tracker
        # accumulates anomaly scores and quarantines repeat offenders —
        # excluded from aggregation but still served, so a false
        # positive can earn its way back. Its state persists through
        # the round checkpointer below: a restarted server does not
        # forget who it banned.
        self._pipeline = robust.DefensePipeline.from_fed(cfg.fed)
        # surface the contradiction at construction, before the
        # readiness barrier — not at the first round close, where a
        # supervised deployment would crash-loop its restart budget
        robust.check_fednova_compat(cfg.fed.algorithm,
                                    self._pipeline.method)
        self._quarantine = quarantine or QuarantinePolicy()
        self._reputation = ReputationTracker(size, self._quarantine)
        self._diag_fn = None  # lazily-jitted anomaly scorer
        # -- shape-bucketed compiled rounds (core/elastic.py): with
        # cfg.fed.elastic_buckets the aggregation pass is compiled once
        # per power-of-two bucket (cohort padded with zero-weight /
        # zero-delta rows every defense rule masks out) and held in an
        # LRU of executables — membership churn costs a cache hit, not
        # an XLA recompile. Off by default: the eager aggregation path
        # below stays byte-identical to its pre-elastic self.
        self._elastic = bool(cfg.fed.elastic_buckets)
        # NOTE deliberately NOT donated: on the CPU backend
        # ``np.asarray`` of a jax array is zero-copy, so the
        # ``_round_sync`` host snapshot a mid-round WELCOME replays can
        # ALIAS the live ServerState buffers — donating the state would
        # let the compiled update overwrite the snapshot under a
        # concurrent rejoin (the same aliasing class PR 1's checkpoint
        # zero-copy SIGSEGV fix documents). The sim round donates
        # instead, where the state has exactly one owner.
        self._agg_cache = (
            E.CompiledRoundCache(self._bucketed_update,
                                 family="deploy_update")
            if self._elastic else None
        )
        self._diag_cache = (
            E.CompiledRoundCache(self._bucketed_diag,
                                 family="deploy_diag")
            if self._elastic else None
        )
        # -- compressed weight-update wire (core/compress.py,
        # docs/PERFORMANCE.md "Wire compression"): clients ship typed
        # quantized/sparsified delta payloads instead of dense
        # variables; the server validates them at the receive edge,
        # stores the (small) payloads, and decompresses the stacked
        # round inside a compiled — optionally client-axis-sharded —
        # program at close. Off by default: the dense path is
        # byte-identical on the wire and in here.
        self._cspec = CMP.CompressionSpec.from_fed(cfg.fed,
                                                   seed=cfg.seed)
        self._payload_template = (
            CMP.payload_template(self._cspec, self.state.variables)
            if self._cspec.enabled() else None
        )
        if self._cspec.enabled():
            telemetry.METRICS.gauge(
                "compress.ratio",
                CMP.wire_ratio(self._cspec, self.state.variables),
            )
        self._decomp_cache = (
            E.CompiledRoundCache(self._decompress_prog,
                                 family="deploy_decompress")
            if self._cspec.enabled() else None
        )
        # memory-plane knobs (core/memscope.py): the monitor samples at
        # every round close below; --mem_headroom_warn tunes its alarm
        MEMSCOPE.MONITOR.headroom_warn = float(
            getattr(cfg.fed, "mem_headroom_warn", 0.9) or 0.9
        )
        # -- mesh-sharded server update (parallel/sharded_agg.py,
        # ROADMAP item 2): shard decompress -> clip -> defense-reduce
        # -> optimizer step over the client axis of a mesh spanning
        # this host's devices, all-gathering only the final params.
        # Off by default: the replicated paths above stay untouched.
        self._sharded = None
        if cfg.fed.shard_aggregation:
            from fedml_tpu.parallel.sharded_agg import ShardedAggregator

            self._sharded = ShardedAggregator(
                cfg, self.steps_per_epoch, self.batch_size,
                spec=self._cspec,
            )
        if checkpointer is not None:
            if checkpoint_every < 1:
                raise ValueError(
                    f"checkpoint_every must be >= 1 with a checkpointer, "
                    f"got {checkpoint_every}"
                )
            from fedml_tpu.utils.checkpoint import from_savable

            raw, start = checkpointer.restore_raw()
            if raw is not None:
                if isinstance(raw, dict) and "server" in raw:
                    # composite payload (PR 4+): server state + the
                    # reputation plane, + the membership ledger once
                    # the world went elastic. Reputation/membership
                    # arrays adapt to a DIFFERENT relaunch world size
                    # — the checkpoint is authoritative.
                    self.state = from_savable(self.state, raw["server"])
                    self._reputation.load_arrays(raw["reputation"])
                    if "membership" in raw:
                        self._ledger.load_arrays(raw["membership"])
                else:
                    # checkpoint written before the reputation plane:
                    # a bare ServerState. Restore it and start with a
                    # clean reputation — an upgraded server must
                    # resume, not crash-loop the Supervisor's restart
                    # budget away.
                    self.state = from_savable(self.state, raw)
                    import warnings

                    warnings.warn(
                        "restored a pre-reputation checkpoint (bare "
                        "ServerState); quarantine state starts fresh",
                        stacklevel=2,
                    )
            if start:
                if int(self.state.round) != start:
                    raise ValueError(
                        f"checkpoint at step {start - 1} carries "
                        f"round={int(self.state.round)}; expected "
                        f"{start} — wrong run directory?"
                    )
                self.round_idx = start
                self.resumed_from = start
                telemetry.METRICS.inc("recovery.resumes")
                telemetry.METRICS.gauge("recovery.resumed_from_round",
                                        start)
                telemetry.RECORDER.record("resume", round=start)
        self.register_message_receive_handler(
            MSG_TYPE_C2S_RESULT, self._handle_result
        )
        # library-path membership entries; the deployment barrier
        # re-registers JOIN with its pre-kickoff-aware wrapper
        # (deploy.py)
        self.register_message_receive_handler(
            MSG_TYPE_C2S_JOIN, lambda msg: self.on_peer_join(msg.sender)
        )
        self.register_message_receive_handler(
            MSG_TYPE_C2S_LEAVE,
            lambda msg: self.on_peer_leave(msg.sender),
        )
        # live run introspection (core/export.py ``/statusz``): the
        # actor is a WEAKLY-held status source — registration costs
        # nothing while the exporter is off, and a dead actor is
        # pruned at snapshot time instead of being kept alive
        EXPORT.register_status_source("server", self)

    def status(self) -> dict:
        """One ``/statusz`` snapshot: scalars copied under the
        existing round lock (briefly), membership/quarantine read from
        their own thread-safe planes — no new lock is held across
        serialization (the HTTP handler json-encodes the returned
        plain dict outside every lock)."""
        with self._lock:
            pending = len(self._results)
            dead = sorted(self.dead_peers)
            failure = self.failure
            round_idx = self.round_idx
        mem = self._ledger.summary()
        return {
            "actor": type(self).__name__,
            "round": round_idx,
            "num_rounds": self.cfg.fed.num_rounds,
            "results_pending": pending,
            "membership": {k: len(v) for k, v in mem.items()},
            "quarantined": self._reputation.quarantined(),
            "dead_peers": dead,
            "resumed_from": self.resumed_from,
            "done": self.done.is_set(),
            "failure": failure,
        }

    @property
    def variables(self):
        return self.state.variables

    def _sample(self) -> np.ndarray:
        """Seeded cohort sampling (reference ``client_sampling``,
        ``FedAVGAggregator.py:90-98``). In the distributed path the cohort
        size is the worker count, as in the reference (one MPI rank per
        sampled client, ``FedAvgAPI.py:36-66``); if there are more workers
        than clients the assignment wraps so every worker gets a client.
        The worker count is the CURRENT membership (elastic worlds grow
        and shrink it); in a static world it equals the launch
        ``size - 1`` and the draw is unchanged."""
        n_workers = max(1, len(self._member_workers()))
        if n_workers >= self.num_clients:
            return np.arange(self.num_clients)
        rng = np.random.default_rng(self.round_idx)
        return rng.choice(self.num_clients, n_workers, replace=False)

    # -- straggler accounting (all under self._lock) -----------------------

    def client_ranks(self) -> list[int]:
        """Every currently-ACTIVE member (broadcast / FINISH targets) —
        including admissions whose first round is still ahead, and
        excluding departed ranks."""
        return self._ledger.active_ranks()

    def _member_workers(self) -> list[int]:
        """Members participating in the CURRENT round: ACTIVE, and
        admitted at or before this round's boundary (a mid-round
        admission must not raise the in-flight round's quorum bar for a
        sync it never received)."""
        return self._ledger.active_ranks(self.round_idx)

    def _live_workers(self) -> list[int]:
        return [
            r for r in self._member_workers()
            if r not in self.dead_peers
        ]

    def _quorum(self) -> int:
        """Results required to close the round at the deadline: a
        fraction of the CURRENTLY live workers, never below 1 (a death
        detected mid-round shrinks the quorum with the cohort)."""
        live = len(self._live_workers())
        return max(1, math.ceil(self.round_policy.quorum_fraction * live))

    def kickoff(self) -> None:
        """Deployment-barrier entry: start the (possibly resumed) run
        unless a round is already underway. After a server restart the
        barrier can complete on the very message that closed the
        resumed round (the Manager's handler runs before the barrier
        observer), whose ``_close_round`` already started the next one
        — a second ``start_round`` here would re-broadcast it and make
        every client compute the round twice."""
        with self._lock:
            sync = self._round_sync
            if sync is not None and sync[0] == self.round_idx:
                return  # a round is already in flight
        self.start_round()

    def start_round(self) -> None:
        # a server restored from its FINAL checkpoint has nothing left
        # to run — finish immediately instead of broadcasting a sync
        # for a round past the end
        if self.round_idx >= self.cfg.fed.num_rounds:
            self.done.set()
            self.finish_all()
            return
        # reconcile rejoin/death races at the round boundary: a rank in
        # dead_peers that the liveness monitor does NOT consider dead
        # was revived by a JOIN that interleaved with an in-flight
        # death callback (the callback re-added it after the rejoin's
        # removal, stranding a live rank outside the cohort forever).
        # The monitor is the live-ness source of truth — a truly-down
        # peer lands (back) in monitor.dead within a heartbeat timeout,
        # so healing here converges instead of flapping.
        mon = self.liveness
        if mon is not None:
            mon_dead = mon.dead_snapshot()
            with self._lock:
                stranded = sorted(self.dead_peers - mon_dead)
                self.dead_peers -= set(stranded)
            if stranded:
                telemetry.METRICS.inc("recovery.rejoins_reconciled",
                                      len(stranded))
                telemetry.RECORDER.record(
                    "rejoin_reconciled", peers=stranded,
                    round=self.round_idx,
                )
        cohort = self._sample()
        self._round_t0 = time.monotonic()
        if ANATOMY.enabled:
            # the anatomy plane (core/anatomy.py): every deploy
            # timestamp below is passed explicitly on the actor's own
            # monotonic clock, so arrivals and the sync origin compare
            ANATOMY.begin_round(self.round_idx, path="deploy")
        tr = telemetry.TRACER
        if tr is not None:
            # one trace id per round: every sync this broadcast ships
            # (and every result it provokes) correlates under it
            telemetry.set_current_trace(telemetry.new_trace_id())
            tr.log_round_start(self.round_idx)
        host_vars = jax.tree.map(np.asarray, self.variables)
        # slot = the rank's position among this round's MEMBER workers:
        # in a static launch world that is exactly rank-1 (the historic
        # assignment); in an elastic world it stays dense as ranks
        # beyond the launch world join and others leave
        slots = {r: i for i, r in enumerate(self._member_workers())}
        with self._lock:
            ranks = self._live_workers()
            self._extensions_used = 0
            self._deadline_gen += 1
            gen = self._deadline_gen
            # one consistent (round, model, cohort, slots) snapshot:
            # WELCOME replies to mid-round rejoiners replay exactly
            # this sync
            self._round_sync = (self.round_idx, host_vars, cohort, slots)
        self.broadcast(
            MSG_TYPE_S2C_SYNC_MODEL,
            lambda r: {
                KEY_MODEL_PARAMS: host_vars,
                KEY_CLIENT_INDEX: int(
                    cohort[slots.get(r, r - 1) % len(cohort)]
                ),
                KEY_ROUND: self.round_idx,
            },
            ranks=ranks,
            on_send_error=self._on_sync_send_failed,
        )
        if self.round_policy.round_deadline_s is not None:
            t = threading.Timer(
                self.round_policy.round_deadline_s,
                self._on_round_deadline,
                args=(self.round_idx, gen),
            )
            t.daemon = True
            self._deadline_timer = t
            t.start()

    def _on_sync_send_failed(self, rank: int, err: Exception) -> None:
        """A model sync that cannot be shipped == a crashed worker; the
        round proceeds without it rather than aborting the broadcast."""
        self.on_peer_dead(rank)

    def on_peer_join(self, rank: int) -> str | None:
        """Unified JOIN entry (docs/FAULT_TOLERANCE.md "Elastic
        membership"): dispatches on the membership ledger's verdict —

        - an ACTIVE member's JOIN is the crash-recovery REJOIN
          (:meth:`on_peer_rejoin`, unchanged) — unless its admission
          has not taken effect yet (a just-admitted rank's announce
          loop re-sends JOIN until the next round's sync arrives; a
          WELCOME now would pull it into the CURRENT round, whose
          quorum and cohort slots were fixed without it);
        - an unknown or previously-LEFT rank is ADMITTED: stable client
          id assigned, liveness armed, first cohort slot at the next
          round boundary — or THIS round's, when no round is in flight
          yet (a restored all-departed world admitting its next member
          pre-kickoff must serve it in the round it is about to
          broadcast, not one past it);
        - an EVICTED rank is rejected silently — never ACKed, so the
          banned client's announce loop times out loudly on its side
          instead of idling against a world that will not serve it.
        """
        with self._lock:
            if self.done.is_set() or self.failure is not None:
                return None
            if not self._elastic and self._ledger.status(rank) is None:
                # static world, never-seen rank: drop un-ACKed — the
                # pre-elastic contract (run.py's client-side guard
                # says "a static server drops it", and admitting here
                # would shift every member's cohort slot in a world
                # the operator configured as fixed)
                telemetry.METRICS.inc("membership.rejected_joins")
                return None
            sync = self._round_sync
            in_flight = sync is not None and sync[0] == self.round_idx
            verdict = self._ledger.admit(rank, self.round_idx,
                                         immediate=not in_flight)
            effective = rank in self._ledger.active_ranks(self.round_idx)
        if verdict == "rejected":
            return verdict
        if verdict == "member":
            if effective:
                self.on_peer_rejoin(rank)
            return verdict
        # newly admitted (or returning after a graceful LEAVE): a crash
        # while LEFT is impossible, so there is no dead-peer state to
        # reverse — just arm liveness and grow the per-rank planes
        self._reputation.ensure_size(rank + 1)
        with self._lock:
            self.dead_peers.discard(rank)
        if self.liveness is not None:
            self.liveness.watch(rank)
        return verdict

    def on_peer_leave(self, rank: int) -> None:
        """Graceful departure (``MSG_TYPE_C2S_LEAVE``): the rank is
        marked LEFT in the ledger — NOT dead. No restart budget is
        spent, no dead-peer flight dump fires, and its reputation is
        frozen, not laundered (a later rejoin resumes the same score).
        A result it already submitted this round stays valid (it
        contributed, then left). The round re-evaluates its close
        condition immediately: the departed rank no longer counts
        toward quorum."""
        left = self._ledger.leave(rank, self.round_idx)
        if not left:
            return
        if self.liveness is not None:
            self.liveness.unwatch(rank)
        with self._lock:
            self.dead_peers.discard(rank)
            self._welcomed.pop(rank, None)
        self._maybe_close_round(deadline_fired=False)

    def evict_rank(self, rank: int, notify: bool = True) -> None:
        """Permanent eviction: future JOINs from this rank are rejected
        (the one transition nothing undoes short of a fresh run dir).
        Used by operators via the library API and by the quarantine
        plane's ``evict_after`` policy. ``notify=False`` skips the
        FINISH to the banned rank — the restart replay path uses it,
        where the rank's process already exited and a send would only
        sit out the transport's full retry budget."""
        self._ledger.evict(rank, self.round_idx)
        if self.liveness is not None:
            self.liveness.unwatch(rank)
        with self._lock:
            self.dead_peers.discard(rank)
            self._results.pop(rank, None)
            self._welcomed.pop(rank, None)
        # tell the banned rank to wind down cleanly: under a supervisor
        # an evicted client left idling would otherwise crash-loop its
        # restart budget (its JOINs are never ACKed) and take the whole
        # world down with it — a FINISH carrying the reason lets it
        # exit 0 with status "evicted", which the Supervisor treats
        # like a graceful LEAVE (gone by design, never respawned)
        if notify:
            try:
                self.send_message(Message(
                    MSG_TYPE_FINISH, self.rank, rank,
                    {"reason": "evicted"},
                ))
            except Exception:
                pass  # peer unreachable; announce loop times out loudly
        self._maybe_close_round(deadline_fired=False)

    def on_peer_rejoin(self, rank: int) -> None:
        """Rejoin entry (``MSG_TYPE_C2S_JOIN`` mid-run, docs/
        FAULT_TOLERANCE.md "Recovery"): reverse the dead-peer removal,
        re-arm the rank's liveness watchdog, and reply ``WELCOME`` with
        the CURRENT round's sync payload — the same (model, round,
        client assignment) its cohort-mates received, so a rejoiner's
        result is byte-identical to the one the original sync would
        have produced. Safe from any thread; a duplicate JOIN from an
        already-live rank only refreshes its watchdog (the duplicate
        result its WELCOME provokes is discarded by the keep-first
        dedup)."""
        with self._lock:
            if self.done.is_set() or self.failure is not None:
                return
            was_dead = rank in self.dead_peers
            self.dead_peers.discard(rank)
            sync = self._round_sync
            if sync is not None and sync[0] != self.round_idx:
                # the snapshot's round is mid-close (round_idx already
                # advanced): a WELCOME for it would only provoke a
                # local update whose result is guaranteed stale —
                # skip; the rank is live again, so the imminent
                # start_round broadcast covers it
                sync = None
            if sync is not None:
                if not was_dead and self._welcomed.get(rank) == sync[0]:
                    # duplicate announce (the WELCOME is still in
                    # flight): refresh the watchdog, send nothing
                    sync = None
                    duplicate = True
                else:
                    self._welcomed[rank] = sync[0]
                    duplicate = False
            else:
                duplicate = not was_dead
        if self.liveness is not None:
            self.liveness.revive(rank)
        if duplicate:
            return
        telemetry.METRICS.inc("recovery.rejoins")
        telemetry.RECORDER.record("rejoin", peer=rank, was_dead=was_dead)
        if sync is None:
            return  # no round underway; the next broadcast covers it
        round_idx, host_vars, cohort, slots = sync
        try:
            self.send_message(
                Message(
                    MSG_TYPE_S2C_WELCOME,
                    self.rank,
                    rank,
                    {
                        KEY_MODEL_PARAMS: host_vars,
                        KEY_CLIENT_INDEX: int(
                            cohort[slots.get(rank, rank - 1)
                                   % len(cohort)]
                        ),
                        KEY_ROUND: round_idx,
                    },
                )
            )
        except Exception:
            self.on_peer_dead(rank)  # flapped again mid-welcome

    def on_peer_dead(self, rank: int) -> None:
        """Dead-peer callback (heartbeat monitor / failed sends). Safe to
        call from any thread, idempotent per rank."""
        with self._lock:
            if rank in self.dead_peers or self.done.is_set():
                return
            self.dead_peers.add(rank)
            self._results.pop(rank, None)  # a dead rank's result is void
            dead = sorted(self.dead_peers)  # snapshot under the lock
        telemetry.METRICS.inc("round.dead_peers")
        # a dead worker is a flight-recorder trigger: the artifact names
        # the peer and carries the recent event ring + metrics snapshot
        telemetry.flight_dump(
            "dead_peer", peer=rank, round=self.round_idx,
            dead_peers=dead,
        )
        self._maybe_close_round(deadline_fired=False)

    def _on_round_deadline(self, round_idx: int, gen: int) -> None:
        self._maybe_close_round(deadline_fired=True,
                                deadline_round=round_idx,
                                deadline_gen=gen)

    def _abort_locked(self, why: str) -> None:
        """Record the abort decision. Must run under ``self._lock`` so a
        straggler result racing the deadline cannot both close the round
        and see the run aborted; the FINISH broadcast happens after the
        lock is released (it takes no shared state)."""
        self.failure = why

    def _maybe_close_round(
        self,
        deadline_fired: bool,
        deadline_round: int | None = None,
        deadline_gen: int | None = None,
    ) -> None:
        """Close the round if its exit condition holds: every live worker
        reported (zero-fault path — byte-identical to the strict
        behavior), or the deadline fired with >= quorum results. Aborts
        when no live worker remains or the deadline passes under quorum.
        The round index advances under the SAME lock that claims the
        result set, so a result racing the close is correctly classified
        as a stale straggler rather than leaking into the next round; a
        deadline timer carries its own round (``deadline_round``) and is
        re-validated under that lock, so a timer firing just as its round
        closes cannot apply deadline semantics to the NEXT round."""
        extended = None
        with self._lock:
            if self.done.is_set() or self.failure is not None:
                return
            if deadline_round is not None and (
                deadline_round != self.round_idx
                or (deadline_gen is not None
                    and deadline_gen != self._deadline_gen)
            ):
                # stale timer: its round already closed, or a recovery
                # extension superseded it (cancel() cannot stop a timer
                # whose callback is already blocked on this lock)
                return
            live = self._live_workers()
            n_results = len(self._results)
            # the fast-path close means "every LIVE worker reported":
            # a graceful leaver's booked result stays valid for quorum
            # and aggregation, but must not stand in for a still-
            # computing live member's
            n_live_results = sum(1 for r in live if r in self._results)
            quorum = self._quorum()
            abort = results = None
            closed_idx = self.round_idx
            dead = sorted(self.dead_peers)  # snapshot under the lock
            if live and (n_live_results >= len(live) or (
                deadline_fired and n_results >= quorum
            )):
                results, self._results = self._results, {}
                self.round_idx += 1
                if self._deadline_timer is not None:
                    self._deadline_timer.cancel()
                    self._deadline_timer = None
            elif deadline_fired or not live:
                sync = self._round_sync
                if not deadline_fired and (
                        sync is None or sync[0] != self.round_idx):
                    # no-live-workers check with NO round in flight: a
                    # restored server replaying presumed departures
                    # before kickoff (every member departed by design).
                    # There is nothing to abort — the ready barrier is
                    # waiting for the next admission to BE the world
                    return
                # under quorum (or out of workers entirely): abort only
                # after recovery is exhausted — each extension re-arms
                # the deadline so a supervised restart can rejoin and
                # deliver the missing results
                if (self._extensions_used
                        < self.round_policy.recovery_extensions
                        and self.round_policy.round_deadline_s
                        is not None):
                    self._extensions_used += 1
                    extended = self._extensions_used
                    # supersede the timer already covering this round
                    # (the all-dead path gets here with the ORIGINAL
                    # deadline timer still armed — left valid it would
                    # fire at the unextended time, see extensions
                    # exhausted, and abort inside the window the
                    # extension opened). cancel() handles the not-yet-
                    # fired case; the generation bump invalidates a
                    # timer already blocked on this lock.
                    if self._deadline_timer is not None:
                        self._deadline_timer.cancel()
                    self._deadline_gen += 1
                    t = threading.Timer(
                        self.round_policy.round_deadline_s,
                        self._on_round_deadline,
                        args=(self.round_idx, self._deadline_gen),
                    )
                    t.daemon = True
                    self._deadline_timer = t
                    t.start()
                elif not live:
                    spent = (
                        f" ({self._extensions_used} recovery "
                        f"extensions spent)"
                        if self.round_policy.recovery_extensions
                        else ""
                    )
                    # the MEMBER count, not the launch world: an
                    # elastic run may have grown/shrunk — and "no live
                    # workers" covers graceful departures too, not just
                    # deaths
                    abort = (
                        f"no live workers left before round "
                        f"{self.round_idx} closed "
                        f"({len(self._member_workers())} members, "
                        f"dead peers {sorted(self.dead_peers)}{spent})"
                    )
                else:
                    abort = (
                        f"round {self.round_idx} deadline "
                        f"({self.round_policy.round_deadline_s}s) "
                        f"expired with {n_results}/{len(live)} live "
                        f"results (quorum {quorum}; dead peers "
                        f"{sorted(self.dead_peers)}; "
                        f"{self._extensions_used} recovery extensions "
                        f"spent)"
                    )
            else:
                return  # stragglers may still arrive before the deadline
            if abort is not None:
                self._abort_locked(abort)
        if extended is not None:
            telemetry.METRICS.inc("recovery.deadline_extensions")
            telemetry.RECORDER.record(
                "deadline_extended", round=closed_idx,
                extension=extended, results=n_results, quorum=quorum,
            )
            return
        if abort is not None:
            # a quorum-lost abort is a flight-recorder trigger: PR 1
            # made it loud, this makes it debuggable
            telemetry.METRICS.inc("round.quorum_lost_aborts")
            telemetry.flight_dump(
                "quorum_lost", detail=abort, round=closed_idx,
                dead_peers=dead,
            )
            self.finish_all()  # done unset: deploy raises the diagnostic
        else:
            self._close_round(results, closed_idx, n_live=len(live),
                              dead=dead)

    def _discard_locked(self, msg: Message) -> bool:
        """Cheap drop checks, under ``self._lock``: finished/aborted
        run, stale round tag (a straggler's result from an already-
        closed round must not leak into the current aggregate; untagged
        results predate round-tagging and are accepted for
        compatibility), dead sender, and duplicate ``(round, rank)``
        results — chaos dup / retry resend / rejoin recompute — where
        the FIRST is kept so sample mass is never double-counted in the
        renormalized survivor aggregation."""
        if self.done.is_set() or self.failure is not None:
            return True
        msg_round = msg.get(KEY_ROUND)
        if msg_round is not None and int(msg_round) != self.round_idx:
            telemetry.METRICS.inc("round.stale_results")
            return True
        if msg.sender in self.dead_peers:
            return True  # declared dead; its late result is void
        if self._ledger.status(msg.sender) == "evicted":
            # evict_rank voided this rank's pending result; a copy
            # still in flight must not be re-accepted into the round
            # (a LEFT rank's result stays valid — it contributed,
            # then departed — but a BAN is authoritative)
            return True
        if msg.sender in self._results:
            telemetry.METRICS.inc("round.duplicate_results")
            return True
        return False

    def _screen_compressed(self, msg: Message):
        """Receive-edge screen for a compressed result: the typed
        payload must match the spec's expected structure (codec tag,
        per-leaf shapes/dtypes, in-range top-k indices) and carry only
        finite floats — a malformed or poisoned payload is counted
        ``compress.decode_errors`` and dropped, never stacked into the
        compiled decompress. Returns the payload or None."""
        comp = msg.get(KEY_COMPRESSED)
        err = None
        if not isinstance(comp, dict) or "payload" not in comp:
            err = (
                "dense result on a compressed wire"
                if msg.get(KEY_MODEL_PARAMS) is not None
                else "missing compressed payload"
            )
        elif comp.get("codec") != self._cspec.method:
            err = (
                f"codec {comp.get('codec')!r} != configured "
                f"{self._cspec.method!r}"
            )
        else:
            err = CMP.validate_payload(self._payload_template,
                                       comp["payload"])
        if err is not None:
            telemetry.METRICS.inc("compress.decode_errors")
            telemetry.RECORDER.record(
                "compress_decode_error", peer=msg.sender,
                round=msg.get(KEY_ROUND), detail=err,
            )
            return None
        return comp["payload"]

    def _handle_result(self, msg: Message) -> None:
        # cheap checks FIRST: a duplicate or post-close straggler must
        # not pay the full-pytree scan below
        with self._lock:
            if self._discard_locked(msg):
                return
        n_k = float(msg.get(KEY_NUM_SAMPLES))
        if self._cspec.enabled():
            params = self._screen_compressed(msg)
            if params is None:
                return
            if not math.isfinite(n_k):
                # mirror the dense screen's accounting: a poisoned
                # sample count must be as visible on the compressed
                # wire as on the dense one
                telemetry.METRICS.inc("robust.nonfinite_rejected")
                telemetry.RECORDER.record(
                    "nonfinite_rejected", peer=msg.sender,
                    round=msg.get(KEY_ROUND),
                )
                return
        else:
            params = msg.get(KEY_MODEL_PARAMS)
            if params is None:
                # a compressed result against a dense-configured
                # server (config skew between ranks): unusable
                telemetry.METRICS.inc("compress.decode_errors")
                telemetry.RECORDER.record(
                    "compress_decode_error", peer=msg.sender,
                    round=msg.get(KEY_ROUND),
                    detail="compressed result on a dense wire",
                )
                return
            # non-finite screening (outside the lock — it touches
            # every leaf): a single NaN/Inf delta defeats the weighted
            # mean AND norm-clip (NaN * 0-scale is still NaN), so a
            # poisoned result never enters the aggregate. The screened
            # rank stays live and simply has no result this round — it
            # counts against quorum like a straggler.
            if not _result_is_finite(params, n_k):
                telemetry.METRICS.inc("robust.nonfinite_rejected")
                telemetry.RECORDER.record(
                    "nonfinite_rejected", peer=msg.sender,
                    round=msg.get(KEY_ROUND),
                )
                return
        with self._lock:
            # re-validate: the round can close, or the sender can die
            # or deliver via another path, while the scan ran unlocked
            if self._discard_locked(msg):
                return
            self._results[msg.sender] = (params, n_k)
        if ANATOMY.enabled:
            # straggler attribution (core/anatomy.py): first ACCEPTED
            # result per rank, on the same monotonic clock as
            # _round_t0 — screened/duplicate results never count
            ANATOMY.note_arrival(msg.sender, ts=time.monotonic())
        self._maybe_close_round(deadline_fired=False)

    @property
    def quarantined_ranks(self) -> list[int]:
        return self._reputation.quarantined()

    @property
    def membership(self) -> dict:
        """Rank lists per membership status (run-summary view)."""
        return self._ledger.summary()

    def _bucketed_update(self, state, stacked_vars, n_k, valid, rkey):
        """The bucket-compiled aggregation body: exactly the eager
        path's ``server_update`` with the padding mask threaded through
        (zero-weight, zero-delta pad rows cannot perturb any rule —
        core/elastic.py)."""
        return server_update(
            self.cfg.fed,
            self.cfg.train,
            self.steps_per_epoch,
            self.batch_size,
            state,
            stacked_vars,
            n_k,
            rkey,
            local_reducer(),
            valid=valid,
        )

    def _decompress_prog(self, stacked_payload, gvars):
        """Bucket-compiled decompress: stacked payloads -> stacked
        dense VARIABLES (``global + delta``). A padded zero payload
        row decompresses to a delta of exactly zero — the healed-row
        convention every downstream mask-aware rule expects."""
        delta = CMP.decompress_stacked(self._cspec, stacked_payload,
                                       gvars)
        return jax.tree.map(
            lambda g, d: (g[None] + d).astype(g.dtype), gvars, delta
        )

    def _decompress_results(
        self, results: dict[int, tuple[dict, float]]
    ) -> dict:
        """Inflate one closed round's compressed payloads into dense
        variables through ONE compiled decompress over the stacked
        round — client-axis-sharded when the mesh is on, bucket-padded
        so membership churn stays a compile-cache hit. Returns the
        dense stacked tree in sorted-rank order; downstream
        (reputation scoring, aggregation) consumes the STACK directly
        — rows are sliced out only on the rare quarantine-exclusion
        path."""
        ranks = sorted(results)
        stacked = T.tree_stack([
            jax.tree.map(jnp.asarray, results[r][0]) for r in ranks
        ])
        n = len(ranks)
        if self._sharded is not None:
            return self._sharded.decompress(stacked,
                                            self.state.variables, n)
        bucket = E.bucket_for(n) if self._elastic else n
        padded = CMP.pad_stacked_payload(stacked, bucket)
        dense = self._decomp_cache(bucket, padded,
                                   self.state.variables)
        return jax.tree.map(lambda x: x[:n], dense)

    @staticmethod
    def _bucketed_diag(stacked_params, gp, valid):
        deltas = jax.tree.map(
            lambda s, g: s - g[None], stacked_params, gp
        )
        return robust.anomaly_scores(deltas, valid)

    def _diagnose(self, stacked_vars,
                  n_rows: int | None = None) -> dict[str, np.ndarray]:
        """Per-client anomaly scores over this round's results (one
        jitted flatten + gram matmul, core/robust.anomaly_scores).
        Static path: recompiles per distinct result count, which a
        quorum-shrunk round changes rarely. Elastic path
        (``n_rows``): the stack is padded to its bucket and scored by
        a bucket-compiled executable, so membership churn never
        retraces the scorer; rows past ``n_rows`` are padding debris
        and are sliced off before anything host-side sees them."""
        gp = self.state.variables["params"]
        if self._elastic and n_rows is not None:
            bucket = E.bucket_for(n_rows)
            padded, _, valid = E.pad_stacked(
                stacked_vars["params"],
                np.ones((n_rows,), np.float32),
                gp,
                bucket,
            )
            out = self._diag_cache(bucket, padded, gp, valid)
            return {k: np.asarray(v)[:n_rows] for k, v in out.items()}
        if self._diag_fn is None:
            # same pipeline as the bucketed scorer, no padding mask
            # (anomaly_scores treats valid=None as all-valid)
            self._diag_fn = jax.jit(
                lambda s, gp: self._bucketed_diag(s, gp, None)
            )
        out = self._diag_fn(stacked_vars["params"], gp)
        return {k: np.asarray(v) for k, v in out.items()}

    def _score_and_exclude(
        self, results: dict[int, tuple[dict, float]], closed_idx: int,
        stacked_all: dict | None = None,
    ) -> tuple[list[int], dict | None]:
        """The reputation pass over one closed round's results: score
        every reporter, fold into the cross-round tracker, and return
        ``(included ranks, stacked tree or None)`` — the stack built
        for scoring rides back to the caller when every reporter
        survived, so the cohort's params cross to device ONCE per
        round, not once for scoring and again for aggregation.
        Quarantined reporters are scored (they can earn their way
        back) but excluded. Skipped entirely on the zero-defense path
        (mean rule, no quarantine, metrics off), which therefore pays
        nothing."""
        ranks = sorted(results)
        m = telemetry.METRICS
        score_now = self._quarantine.enabled() or (
            self._pipeline.method != "mean" and m.enabled
        )
        if not score_now or not ranks:
            # the caller may already hold the stacked round (the
            # compressed path's decompress output) — pass it back so
            # it is never rebuilt from rows
            return ranks, stacked_all
        self._reputation.ensure_size(max(ranks) + 1)
        if stacked_all is None:
            stacked_all = T.tree_stack([results[r][0] for r in ranks])
        diag = self._diagnose(stacked_all, len(ranks))
        events = self._reputation.observe(closed_idx, ranks,
                                          diag["score"])
        if self._quarantine.evict_after > 0:
            # quarantine -> eviction escalation: a rank that has sat in
            # quarantine for evict_after FULL rounds without earning
            # release is permanently banned (docs/FAULT_TOLERANCE.md
            # "Elastic membership"). Strictly more than: the round that
            # TRIPPED the quarantine (closed_idx == q_at) is not a
            # round "sat without release" — evict_after=1 promises one
            # recoverable round, not an instant ban
            for r in list(self._reputation.quarantined()):
                q_at = int(self._reputation.quarantined_at[r])
                if (closed_idx - q_at >= self._quarantine.evict_after
                        and self._ledger.status(r) != "evicted"):
                    self.evict_rank(r)
        excluded = [r for r in ranks
                    if self._reputation.is_quarantined(r)]
        included = [r for r in ranks if r not in excluded]
        if not included:
            # every reporter is quarantined: refusing to aggregate
            # would stall the run forever — degrade to the full set
            # and let the per-round defense rule carry the round
            telemetry.RECORDER.record(
                "quarantine_overruled", round=closed_idx, ranks=ranks
            )
            included, excluded = ranks, []
        if m.enabled:
            if events["suspected"]:
                m.inc("defense.suspected", len(events["suspected"]))
            if events["quarantined"]:
                m.inc("defense.quarantines", len(events["quarantined"]))
            if events["released"]:
                m.inc("defense.releases", len(events["released"]))
            if excluded:
                m.inc("defense.excluded", len(excluded))
            sel_excluded = self._pipeline.excluded_count(len(included))
            if sel_excluded:
                # results the krum-family selection rule drops inside
                # the aggregation pass by construction
                m.inc("defense.excluded", sel_excluded)
            if self._pipeline.method == "fltrust":
                m.inc("defense.reweighted", len(included))
            m.gauge("defense.quarantined",
                    len(self._reputation.quarantined()))
            m.gauge("defense.anomaly_score_max",
                    float(diag["score"].max()))
            for r in ranks:
                # label-capped family: a 10k-client cohort folds ranks
                # beyond the cap into defense.score_rank.other instead
                # of growing the registry per peer
                m.gauge_labeled("defense.score_rank", str(r),
                                self._reputation.score(r), sep="")
        if events["released"]:
            telemetry.RECORDER.record(
                "quarantine_released", round=closed_idx,
                peers=events["released"],
            )
        if events["quarantined"]:
            # a quarantine trip is a flight-recorder trigger, like a
            # dead peer: the artifact names the peers and their scores
            telemetry.RECORDER.record(
                "quarantine", round=closed_idx,
                peers=events["quarantined"],
            )
            telemetry.flight_dump(
                "quarantine", round=closed_idx,
                peers=events["quarantined"],
                scores={r: self._reputation.score(r) for r in ranks},
                quarantined=self._reputation.quarantined(),
            )
        return included, (stacked_all if included == ranks else None)

    def _close_round(
        self,
        results: dict[int, tuple[dict, float]],
        closed_idx: int,
        n_live: int | None = None,
        dead: list[int] | None = None,
    ) -> None:
        """Aggregate ``results`` through the SAME server_update as the
        compiled sim (reference handle_message_receive_model_from_client,
        FedAvgServerManager.py:45-82 + fedopt/FedOptAggregator.py) — the
        two paths cannot drift. With a partial cohort the weighted mean
        renormalizes over the survivors' sample counts by construction.
        ``round_idx`` was already advanced by the caller under the lock;
        ``closed_idx`` is the round these results belong to."""
        tr = telemetry.TRACER
        if tr is not None:
            tr.log_round_end(closed_idx)
        anat = ANATOMY.enabled
        t_close = time.monotonic()
        if anat:
            # everything from sync broadcast to round close is client
            # compute + transport from the server's seat: `wire`
            ANATOMY.phase("wire", t_close - self._round_t0)
        m = telemetry.METRICS
        if m.enabled:
            wall = time.monotonic() - self._round_t0
            m.observe("round.wall_s", wall)
            # the SLO surface (core/slo.py, docs/OBSERVABILITY.md "Live
            # export and SLOs"): the deploy server shares the sims'
            # perf.round_wall_s histogram name, so one --slo spec
            # covers both drivers
            m.observe("perf.round_wall_s", wall)
            m.gauge("round.results", len(results))
            if n_live is not None and n_live > len(results):
                # live workers whose results the deadline cut out
                m.inc("round.stragglers", n_live - len(results))
            if len(results) < len(self._ledger.active_ranks(closed_idx)):
                # fewer results than the CLOSED round's members (the
                # elastic world's count, not the launch world_size —
                # round_idx has already advanced here): the weighted
                # mean below renormalizes over the survivors' sample
                # mass
                m.inc("round.quorum_renormalizations")
        telemetry.RECORDER.record(
            "round_close", round=closed_idx, results=len(results),
            dead_peers=dead if dead is not None else [],
        )
        t_agg0 = time.monotonic()
        stacked_all = None
        if self._cspec.enabled() and results:
            # inflate the round's compressed payloads first (ONE
            # compiled decompress over the stacked round; sharded over
            # the client axis when the mesh is on) — scoring and every
            # aggregation path below consume the dense STACK directly,
            # built exactly once; results keep the (small) payloads
            stacked_all = self._decompress_results(results)
        included, stacked = self._score_and_exclude(
            results, closed_idx, stacked_all
        )
        if stacked is None:
            if stacked_all is not None:
                # quarantine dropped ranks from a compressed round:
                # gather the kept rows out of the decompressed stack
                # (results still hold payloads, not dense rows)
                ranks = sorted(results)
                keep = jnp.asarray(
                    [ranks.index(r) for r in included], jnp.int32
                )
                stacked = jax.tree.map(lambda x: x[keep], stacked_all)
            else:
                stacked = T.tree_stack(
                    [results[r][0] for r in included]
                )
        weights = jnp.asarray([results[r][1] for r in included])
        t_def_end = time.monotonic() if anat else 0.0
        if anat:
            # decompress + robust scoring + stack build
            ANATOMY.phase("defense_agg", t_def_end - t_agg0)
        rkey = RND.round_key(self.root_key, self.state.round)
        if self._sharded is not None:
            # mesh-sharded update (parallel/sharded_agg.py): pads the
            # cohort to the mesh bucket itself and returns the new
            # replicated state — elastic or not, churn costs a
            # compile-cache hit in ITS executable LRU
            self.state = self._sharded.update(
                self.state, stacked, weights, rkey
            )
        elif self._elastic:
            # shape-bucketed aggregation (core/elastic.py): pad the
            # cohort to its power-of-two bucket and run the
            # bucket-compiled executable — a cohort-size change between
            # rounds (membership churn, quorum-shrunk closes) is a
            # compile-cache hit, not an XLA recompile
            bucket = E.bucket_for(len(included))
            padded, w, valid = E.pad_stacked(
                jax.tree.map(jnp.asarray, stacked), weights,
                self.variables, bucket,
            )
            self.state = self._agg_cache(
                bucket, self.state, padded, w, valid, rkey
            )
        else:
            self.state = server_update(
                self.cfg.fed,
                self.cfg.train,
                self.steps_per_epoch,
                self.batch_size,
                self.state,
                jax.tree.map(jnp.asarray, stacked),
                weights,
                rkey,
                local_reducer(),
            )
        agg_s = 0.0
        if m.enabled:
            # server-side device-time accounting (core/perf.py; the
            # accounting Smart-NIC FL serving work optimizes against,
            # arxiv 2307.06561): how much of the round the server's
            # chip actually worked vs sat waiting on the wire. The
            # block_until_ready makes agg time mean execution, not
            # dispatch — metrics-enabled runs only; the off path stays
            # async exactly as before.
            jax.block_until_ready(jax.tree.leaves(self.state.variables))
            agg_s = time.monotonic() - t_agg0
            if anat:
                # optimizer step + device wait, net of defense_agg
                ANATOMY.phase(
                    "server_update", time.monotonic() - t_def_end
                )
            wall_s = max(time.monotonic() - self._round_t0, 1e-9)
            m.observe("perf.agg_wall_s", agg_s)
            m.gauge("perf.host_wait_s", max(0.0, wall_s - agg_s))
            agg_frac = min(1.0, agg_s / wall_s)
            m.gauge("perf.agg_frac", agg_frac)
            if agg_frac < 0.005:
                # the deploy-path twin of the sims' dispatch-bound
                # detector: >99.5% of the round is client/transport
                # wait — the aggregator's device is idle-gapped
                m.inc("perf.idle_gap_rounds")
                if not self._idle_gap_flagged:
                    self._idle_gap_flagged = True
                    telemetry.RECORDER.record(
                        "perf_idle_gap", round=closed_idx,
                        agg_s=round(agg_s, 6), wall_s=round(wall_s, 6),
                        note="aggregation occupies <0.5% of the round; "
                             "the server device is idle waiting on "
                             "clients/transport",
                    )
            # round-close device-memory sample (core/memscope.py):
            # live/peak bytes + headroom gauges at the same boundary
            # the wall-time accounting uses
            MEMSCOPE.MONITOR.sample(tag=f"round{closed_idx}")
        t_ck = time.monotonic() if anat else 0.0
        if self._ckpt is not None and (
            (closed_idx + 1) % self.checkpoint_every == 0
            or closed_idx + 1 >= self.cfg.fed.num_rounds
        ):
            # atomic orbax save of the FULL ServerState — variables,
            # server-optimizer state, momentum, and the round counter
            # every RNG fold derives from — plus the reputation plane
            # (quarantine must survive a server SIGKILL) and the
            # membership ledger (a restarted server must serve the
            # grown/shrunk world, not the launch flag's), keyed by the
            # closed round, so a restart resumes here, not round 0
            self._ckpt.save(closed_idx, {
                "server": self.state,
                "reputation": self._reputation.state_arrays(),
                "membership": self._ledger.state_arrays(),
            })
            telemetry.METRICS.inc("recovery.checkpoints")
            telemetry.RECORDER.record("checkpoint", round=closed_idx)
            # counters ride the checkpoint cadence to disk: a SIGKILLed
            # server's metrics (rejoins, dedups, ...) survive the crash
            # instead of dying with the exit-time flush
            telemetry.flush_metrics()
            if anat:
                ANATOMY.phase("checkpoint", time.monotonic() - t_ck)
        if anat:
            # stragglers BEFORE end_round (end_round seals the ring
            # entry) and both BEFORE start_round below, which opens
            # the next round and clears the arrival table
            ANATOMY.attribute_stragglers(
                closed_idx, t_sync=self._round_t0, t_close=t_close,
                t_agg_s=agg_s,
            )
            ANATOMY.end_round(wall_s=time.monotonic() - self._round_t0)
        if self.on_round_done is not None:
            self.on_round_done(
                self.round_idx,
                {
                    "num_results": len(results),
                    "dead_peers": sorted(self.dead_peers),
                },
            )
        if self.round_idx >= self.cfg.fed.num_rounds:
            self.done.set()
            self.finish_all()
        else:
            self.start_round()


class FedAvgClientActor(ClientManager):
    """Rank>=1 worker (reference ``FedAVGClientManager`` +
    ``FedAVGTrainer``)."""

    def __init__(
        self,
        rank: int,
        size: int,
        transport: BaseTransport,
        model: FedModel,
        data: FederatedData,
        cfg: ExperimentConfig,
        leave_after_round: int | None = None,
    ):
        super().__init__(rank, size, transport)
        self.cfg = cfg
        self.model = model
        # elastic membership (docs/FAULT_TOLERANCE.md "Elastic
        # membership"): after submitting the result for this round the
        # client announces a GRACEFUL departure and winds down — the
        # server marks it LEFT (no dead-peer suspicion, no restart
        # budget), and a supervisor sees a clean exit
        self.leave_after_round = leave_after_round
        self.left = threading.Event()
        self.last_round = -1  # last round this rank worked (/statusz)
        self.arrays, batch = arrays_and_batch(data, cfg.data)
        max_n = self.arrays.max_client_samples
        task = make_task(data.task)
        self._local_update = jax.jit(
            build_local_update(model, task, cfg.train, batch, max_n)
        )
        self.root_key = jax.random.key(cfg.seed)
        # seeded Byzantine injection (core/adversary.py): when THIS
        # rank is a policy member it corrupts its own delta before
        # sending — the deploy-path mirror of the simulator's stacked
        # injection (docs/FAULT_TOLERANCE.md "Threat model")
        adv = cfg.adversary
        self._adversary = (
            adv
            if adv.enabled() and adv.is_member(rank, size - 1, base=1)
            else None
        )
        # -- compressed weight-update wire (core/compress.py): this
        # rank deltas its trained variables against the round's sync,
        # folds in the error-feedback residual it carries across
        # rounds, and ships the typed quantized/sparsified payload
        # instead of dense variables. Off by default (dense wire,
        # byte-identical).
        self._cspec = CMP.CompressionSpec.from_fed(cfg.fed,
                                                   seed=cfg.seed)
        self._residual = None  # lazy zero carry, shaped like variables
        self._compress_fn = None
        self._comp_cache: tuple[int, dict] | None = None
        if self._cspec.enabled():
            spec = self._cspec

            def _compress(delta, residual, key):
                payload, _, new_res = CMP.apply_with_feedback(
                    spec, delta, residual, key
                )
                return payload, new_res

            # the carried residual is donated: new carry aliases old
            self._compress_fn = jax.jit(_compress,
                                        donate_argnums=(1,))
        self.register_message_receive_handler(
            MSG_TYPE_S2C_SYNC_MODEL, self._handle_sync
        )
        # a WELCOME (rejoin reply) carries the same payload as the
        # round's sync and is worked identically — the server's
        # keep-first dedup absorbs the case where both arrive
        self.register_message_receive_handler(
            MSG_TYPE_S2C_WELCOME, self._handle_sync
        )
        EXPORT.register_status_source("client", self)

    def status(self) -> dict:
        """The client rank's ``/statusz`` contribution."""
        return {
            "actor": type(self).__name__,
            "rank": self.rank,
            "last_round": self.last_round,
            "left": self.left.is_set(),
        }

    def _compress_result(self, synced_vars, new_vars,
                         round_idx: int) -> dict:
        """Delta, fold in the error-feedback carry, compress, and
        advance the carry — ONCE per round: a duplicate sync for the
        same round (WELCOME racing the broadcast, chaos dup) re-sends
        the cached payload, and a delayed duplicate of an OLDER round
        — whose result the server's round-tag check is guaranteed to
        discard — is compressed against an empty carry WITHOUT
        touching the live residual (re-consuming it would mark its
        error as transmitted when the server never books it)."""
        if (self._comp_cache is not None
                and round_idx == self._comp_cache[0]):
            return self._comp_cache[1]
        key = CMP.slot_key(
            self._cspec,
            RND.round_key(self.root_key,
                          jnp.asarray(round_idx, jnp.int32)),
            self.rank - 1,
        )
        delta = jax.tree.map(jnp.subtract, new_vars, synced_vars)
        if (self._comp_cache is not None
                and round_idx < self._comp_cache[0]):
            payload = CMP.compress_tree(self._cspec, delta, key)
            return {
                "codec": self._cspec.method,
                "payload": jax.tree.map(np.asarray, payload),
            }
        if self._residual is None:
            self._residual = jax.tree.map(jnp.zeros_like, synced_vars)
        payload, self._residual = self._compress_fn(
            delta, self._residual, key
        )
        m = telemetry.METRICS
        if m.enabled:
            m.gauge("compress.residual_norm",
                    float(T.tree_l2_norm(self._residual)))
        wire = {
            "codec": self._cspec.method,
            "payload": jax.tree.map(np.asarray, payload),
        }
        self._comp_cache = (round_idx, wire)
        return wire

    def _handle_sync(self, msg: Message) -> None:
        t0 = time.monotonic()
        client_idx = int(msg.get(KEY_CLIENT_INDEX))
        round_idx = int(msg.get(KEY_ROUND))
        self.last_round = round_idx
        variables = jax.tree.map(jnp.asarray, msg.get(KEY_MODEL_PARAMS))
        rng = jax.random.fold_in(
            jax.random.fold_in(self.root_key, round_idx), client_idx
        )
        # the np.asarray conversion blocks on the async dispatch, so the
        # span covers the real device work, not just the enqueue
        t_loc = time.monotonic()
        with span(
            "local_update", rank=self.rank, round=round_idx,
            client=client_idx,
        ):
            new_vars, n_k, _ = self._local_update(
                variables,
                self.arrays.idx[client_idx],
                self.arrays.mask[client_idx],
                self.arrays.x,
                self.arrays.y,
                rng,
            )
            if self._adversary is not None:
                new_vars = A.corrupt_client_vars(
                    self._adversary, variables, new_vars, round_idx,
                    self.rank,
                )
                telemetry.METRICS.inc("adversary.corrupted_results")
            if self._cspec.enabled():
                result_payload = {
                    KEY_COMPRESSED: self._compress_result(
                        variables, new_vars, round_idx
                    ),
                }
            else:
                result_payload = {
                    KEY_MODEL_PARAMS: jax.tree.map(np.asarray,
                                                   new_vars),
                }
        t_send = time.monotonic()
        self.send_message(
            Message(
                MSG_TYPE_C2S_RESULT,
                self.rank,
                0,
                {
                    **result_payload,
                    KEY_NUM_SAMPLES: float(n_k),
                    # round tag: lets the server discard a straggler's
                    # result that arrives after its round already closed
                    KEY_ROUND: round_idx,
                },
            )
        )
        m = telemetry.METRICS
        if m.enabled:
            # the client's own round wall (sync received -> result
            # shipped): the fleet-federation whitelist forwards this
            # histogram's bucket deltas on the heartbeat uplink, so
            # rank 0's fleet.perf.round_wall_s answers "p95 client
            # round time across the cohort" from one scrape
            m.observe("perf.round_wall_s", time.monotonic() - t0)
            if ANATOMY.enabled:
                # client-side phase attribution: local compute (incl.
                # compression) as its own fleet-federated histogram —
                # rank 0's fleet.perf.phase.local_s splits the cohort's
                # round wall into compute vs wire from one scrape
                m.observe("perf.phase.local_s", t_send - t_loc)
        if (self.leave_after_round is not None
                and round_idx >= self.leave_after_round):
            # contribute this round's result, THEN depart gracefully:
            # LEAVE after RESULT on the same ordered channel, so the
            # server books the contribution before the departure
            try:
                self.send_message(
                    Message(MSG_TYPE_C2S_LEAVE, self.rank, 0, {})
                )
            except Exception:
                pass  # server gone; heartbeat staleness covers it
            self.left.set()
            telemetry.RECORDER.record("leave", rank=self.rank,
                                      round=round_idx)
            self.finish()
