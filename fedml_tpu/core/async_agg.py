"""Asynchronous (FedBuff-style) aggregation: staleness-weighted buffer.

Every aggregation path before this module is a synchronous round funneled
into one rank-0 aggregator — the ceiling for the ROADMAP north-star
("millions of users") is the server's inbox. This module removes the
round barrier itself (ROADMAP item 1): the server folds each arriving
(decompressed, screened, defense-preprocessed) client delta into a
staleness-weighted buffer tagged with the model VERSION the client
trained against, and emits a new model every K arrivals. Clients are
re-synced individually the moment their result lands, so a slow client
never blocks a fast one; its late result is folded with a reduced
staleness weight instead of being dropped.

Grounding: "Server Averaging for Federated Learning" (arxiv 2103.11619
— staleness-weighted server-side folding of whatever updates actually
arrive) and the FedBuff buffered-async scheme (buffer K arrivals, one
server step per emission). The polynomial staleness discount
``(1 + lag)^-alpha`` is the standard FedAsync/FedBuff family weighting.

The buffer is a plain pytree accumulator::

    sum   += w(lag) * n_k * delta_k        # weighted delta mass
    mass  += w(lag) * n_k                  # total weight
    count += 1                             # arrivals since last emit

and an emission hands ``sum / mass`` (one weighted-mean delta row) to
the SAME ``server_update`` body every synchronous path uses, so the
server rule (FedOpt optimizers, clip/noise postprocessing) cannot
drift between the sync and async worlds. State is checkpointable
(:meth:`AsyncBuffer.state_arrays` / :meth:`AsyncBuffer.load_arrays`)
and rides the server's :class:`~fedml_tpu.utils.checkpoint.
RoundCheckpointer` composite payload under the ``"async"`` key — a
SIGKILLed async server resumes its buffer, not just its params
(docs/FAULT_TOLERANCE.md "Async + tiered worlds").
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

STALENESS_FNS = ("poly", "const")


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Knobs for the buffered-async server (rides ``FedConfig``).

    - ``buffer_k``: emit a new model every K folded arrivals; 0 (the
      default) disables the async path entirely — the synchronous
      round machinery stays byte-identical.
    - ``staleness_fn``: ``"poly"`` discounts a result that trained
      against a model ``lag`` versions old by ``(1 + lag)^-alpha``;
      ``"const"`` folds every arrival at full weight (plain FedBuff).
    - ``staleness_alpha``: the poly exponent (0.5 is the FedAsync
      default; higher forgets stale work faster).
    """

    buffer_k: int = 0
    staleness_fn: str = "poly"
    staleness_alpha: float = 0.5

    def __post_init__(self):
        if self.buffer_k < 0:
            raise ValueError(
                f"async_buffer_k must be >= 0, got {self.buffer_k}"
            )
        if self.staleness_fn not in STALENESS_FNS:
            raise ValueError(
                f"staleness_fn must be one of {STALENESS_FNS}, "
                f"got {self.staleness_fn!r}"
            )
        if self.staleness_alpha < 0:
            raise ValueError(
                f"staleness_alpha must be >= 0, "
                f"got {self.staleness_alpha}"
            )

    @staticmethod
    def from_fed(fed) -> "AsyncConfig":
        return AsyncConfig(
            buffer_k=getattr(fed, "async_buffer_k", 0),
            staleness_fn=getattr(fed, "staleness_fn", "poly"),
            staleness_alpha=getattr(fed, "staleness_alpha", 0.5),
        )

    def enabled(self) -> bool:
        return self.buffer_k > 0

    def weight(self, lag: int | float) -> float:
        """The staleness discount for a result that trained against a
        model ``lag`` versions behind the current one. ``lag`` is the
        version-lag (current emit counter minus the version tag the
        result carries); a fresh result (lag 0) always weighs 1.0."""
        lag = float(lag)
        if lag < 0:
            raise ValueError(f"version lag must be >= 0, got {lag}")
        if self.staleness_fn == "const":
            return 1.0
        return (1.0 + lag) ** (-self.staleness_alpha)


class AsyncBuffer:
    """The staleness-weighted fold buffer.

    NOT thread-safe by itself — the owning actor serializes folds under
    its own lock (results arrive on the transport's single dispatch
    thread anyway). All arithmetic is plain jax ops over the delta
    pytree, so the fold runs on whatever backend the server state lives
    on and a fold is O(model size), never O(cohort)."""

    def __init__(self, cfg: AsyncConfig, template_vars: Pytree):
        self.cfg = cfg
        self._template = template_vars
        self.sum = jax.tree.map(jnp.zeros_like, template_vars)
        self.mass = 0.0
        self.count = 0
        self.version = 0  # emit counter == the model version clients see

    # -- fold / emit -------------------------------------------------------

    def fold(self, delta: Pytree, n_k: float, lag: int) -> float:
        """Fold one screened delta (trained ``lag`` versions ago) into
        the buffer. Returns the staleness weight applied, so the caller
        can gauge it without recomputing."""
        w = self.cfg.weight(lag)
        wn = w * float(n_k)
        self.sum = jax.tree.map(
            lambda s, d: s + wn * d.astype(s.dtype), self.sum, delta
        )
        self.mass += wn
        self.count += 1
        return w

    def ready(self) -> bool:
        return self.count >= self.cfg.buffer_k > 0

    def emit(self) -> tuple[Pytree, float]:
        """Drain the buffer: returns ``(weighted-mean delta, mass)``
        and resets the accumulator. Advances ``version`` — the caller
        applies the delta through ``server_update`` and re-syncs
        clients with the new version."""
        if self.count == 0:
            raise RuntimeError("emit() on an empty async buffer")
        inv = 1.0 / self.mass
        mean_delta = jax.tree.map(lambda s: s * inv, self.sum)
        mass = self.mass
        self.sum = jax.tree.map(jnp.zeros_like, self._template)
        self.mass = 0.0
        self.count = 0
        self.version += 1
        return mean_delta, mass

    # -- checkpoint persistence (utils/checkpoint.py) ----------------------

    def state_arrays(self) -> dict:
        """Checkpoint payload: the accumulated sum tree plus the three
        scalars, all as host arrays (rides the server's composite
        checkpoint under the ``"async"`` key)."""
        return {
            "sum": jax.tree.map(np.asarray, self.sum),
            "mass": np.asarray(self.mass, np.float64),
            "count": np.asarray(self.count, np.int64),
            "version": np.asarray(self.version, np.int64),
        }

    def load_arrays(self, blob: dict) -> None:
        """Restore a SIGKILLed server's pending folds: the buffer
        resumes mid-accumulation, so the arrivals folded before the
        crash still count toward the next emission."""
        self.sum = jax.tree.map(
            lambda t, b: jnp.asarray(np.asarray(b), dtype=t.dtype),
            self._template, blob["sum"],
        )
        self.mass = float(np.asarray(blob["mass"]))
        self.count = int(np.asarray(blob["count"]))
        self.version = int(np.asarray(blob["version"]))


# ---------------------------------------------------------------------------
# open-loop world simulation (tests/test_async.py's acceptance shape)
# ---------------------------------------------------------------------------


def _serial_completion(arrivals: np.ndarray, t_free: float,
                       service_s: float) -> tuple[np.ndarray, float]:
    """Completion times of jobs served one-at-a-time in arrival order
    by a server free at ``t_free`` (the leaf/root aggregator model:
    folds are serialized on the aggregator's dispatch thread)."""
    out = np.empty_like(arrivals)
    for i, a in enumerate(arrivals):
        t_free = max(float(a), t_free) + service_s
        out[i] = t_free
    return out, t_free


def simulate_open_loop(
    *,
    n_clients: int = 10_000,
    n_leaves: int = 1,
    buffer_k: int = 32,
    flush_every: int | None = None,
    horizon_s: float = 20.0,
    seed: int = 0,
    fold_cost_s: float = 4e-4,
    emit_cost_s: float = 2e-3,
    mean_latency_s: float = 1.0,
    sigma: float = 0.8,
    sync: bool = False,
) -> dict:
    """Deterministic discrete-event simulation of an open-loop
    federated world: ``n_clients`` clients each cycle train->report->
    re-sync forever, with seeded lognormal per-result latencies
    (``sigma`` controls the straggler tail). Aggregators are SERIAL
    resources — a fold occupies the aggregator for ``fold_cost_s``
    (the per-arrival cost of the live AsyncBuffer code) and an
    emission for ``emit_cost_s``.

    Topology: clients are dealt round-robin over ``n_leaves`` leaf
    aggregators; each leaf forwards one partial upstream every
    ``flush_every`` folds (default 8 — the wire-reduction factor the
    leaf buys the root), and the root folds partials and emits every
    ``buffer_k`` partials. One emission therefore costs
    ``flush_every * buffer_k`` client arrivals in EVERY configuration,
    so emits/sec across fan-ins compares like-for-like and scales with
    the world's total fold throughput — which is the leaf tier's
    aggregate capacity once the single aggregator saturates.

    ``sync=True`` models the synchronous FedAvg baseline on the SAME
    world: a round closes only when every client's result has been
    folded (the barrier), so the round rate is pinned by the straggler
    maximum of ``n_clients`` latency draws plus the serial fold
    backlog — which is why it saturates flat as fan-in grows while
    async emit throughput keeps scaling (the acceptance shape of
    ROADMAP item 1).

    This is a MODEL of the control plane, not a wall-clock
    measurement: the aggregation costs are the caller's, the
    client latencies are a seeded synthetic population, and virtual
    time makes the result exactly reproducible — it gives the
    scaling SHAPE (emits/sec vs fan-in), never absolute device time.
    """
    if n_clients < 1 or n_leaves < 1 or buffer_k < 1:
        raise ValueError("n_clients, n_leaves, buffer_k must be >= 1")
    rng = np.random.default_rng(seed)
    mu = math.log(mean_latency_s) - sigma * sigma / 2.0  # mean-preserving
    per_leaf = [n_clients // n_leaves + (1 if l < n_clients % n_leaves
                                         else 0)
                for l in range(n_leaves)]

    if sync:
        # round-at-a-time: all clients draw a latency, every result is
        # folded serially at its leaf, the round closes at the LAST
        # fold (the barrier), then partials hit the root and the model
        # emits. No overlap across rounds — that is the point.
        t = 0.0
        rounds = 0
        # at least 3 rounds regardless of horizon: one synchronous
        # round of a heavy-tailed 10k-client world can outlast any
        # sensible horizon by itself — which is exactly the point the
        # record makes, but a rate of 0/anything carries no shape
        while t < horizon_s or rounds < 3:
            close = t
            for c in per_leaf:
                lat = rng.lognormal(mu, sigma, size=c)
                arrivals = np.sort(t + lat)
                done, _ = _serial_completion(arrivals, t, fold_cost_s)
                close = max(close, float(done[-1]))
            # root: one partial per leaf, then the emission
            close += n_leaves * fold_cost_s + emit_cost_s
            t = close
            rounds += 1
        return {
            "mode": "sync",
            "n_clients": n_clients,
            "n_leaves": n_leaves,
            "rounds": rounds,
            "sim_wall_s": round(t, 6),
            "rounds_per_sec": rounds / t,
        }

    flush = flush_every if flush_every is not None else 8
    if flush < 1:
        raise ValueError(f"flush_every must be >= 1, got {flush}")
    # event heap of (result_ready_time, seq, client_id); each client's
    # next cycle is scheduled when its previous fold completes (the
    # immediate individual re-sync — open loop, no barrier)
    heap: list[tuple[float, int, int]] = []
    seq = 0
    for cid in range(n_clients):
        heapq.heappush(
            heap, (float(rng.lognormal(mu, sigma)), seq, cid)
        )
        seq += 1
    leaf_free = [0.0] * n_leaves
    leaf_folds = [0] * n_leaves
    root_free = 0.0
    partials = 0
    partials_arrived = 0
    emits = 0
    folds = 0
    last_emit_t = 0.0
    while heap:
        t, _, cid = heapq.heappop(heap)
        if t >= horizon_s:
            continue  # drain without scheduling successors
        leaf = cid % n_leaves
        start = max(t, leaf_free[leaf])
        done = start + fold_cost_s
        leaf_free[leaf] = done
        leaf_folds[leaf] += 1
        # only work that COMPLETES inside the horizon counts: a
        # saturated aggregator's backlog drains long after the window
        # and crediting it would overstate the steady-state rate
        if done <= horizon_s:
            folds += 1
        if leaf_folds[leaf] % flush == 0:
            # one partial frame upstream per flush; the root is its
            # own serial resource
            r_start = max(done, root_free)
            root_free = r_start + fold_cost_s
            partials_arrived += 1
            if root_free <= horizon_s:
                partials += 1
            if partials_arrived % buffer_k == 0:
                root_free += emit_cost_s
                if root_free <= horizon_s:
                    emits += 1
                    last_emit_t = root_free
        # the client re-syncs the moment its fold lands and starts the
        # next local update — nobody waits for anybody
        heapq.heappush(
            heap,
            (done + float(rng.lognormal(mu, sigma)), seq, cid),
        )
        seq += 1
    return {
        "mode": "async",
        "n_clients": n_clients,
        "n_leaves": n_leaves,
        "buffer_k": buffer_k,
        "flush_every": flush,
        "folds": folds,
        "partials": partials,
        "emits": emits,
        "emits_per_sec": emits / horizon_s,
        "folds_per_sec": folds / horizon_s,
        "last_emit_t": round(last_emit_t, 6),
    }
