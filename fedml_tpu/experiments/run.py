"""CLI entry: ``python -m fedml_tpu.experiments.run ...``.

Replaces the reference's per-algorithm ``main_<algo>.py`` argparse scripts
(``fedml_experiments/{standalone,distributed}/*/main_*.py``) with one typed
entry over the algorithm registry. Config precedence: ``--config`` JSON
(the full :class:`ExperimentConfig` shape) overridden by explicit flags.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

from fedml_tpu.config import ExperimentConfig
from fedml_tpu.experiments.harness import ALGORITHMS, Experiment

# the FedAvg-family simulators whose compiled round wires in adversary
# injection, the wire codec, round fusion, and bulk streaming — every
# other sim ignores those knobs (main() warns per flag), so their
# compatibility matrices must neither be enforced nor reported there
_ADVERSARY_SIMS = {"fedavg", "fedopt", "fedprox", "fednova",
                   "fedavg_robust", "fedavg_multiclient", "fedseg"}


def parse_args(argv=None) -> tuple[ExperimentConfig, argparse.Namespace]:
    p = argparse.ArgumentParser(
        prog="fedml_tpu.experiments.run",
        description="TPU-native federated learning experiment runner",
    )
    p.add_argument("--config", type=str, default=None,
                   help="JSON file with the full ExperimentConfig")
    p.add_argument("--algorithm", type=str, default=None,
                   choices=sorted(ALGORITHMS))
    p.add_argument("--dataset", type=str, default=None)
    p.add_argument("--data_dir", type=str, default=None)
    p.add_argument("--model", type=str, default=None)
    p.add_argument("--num_classes", type=int, default=None)
    p.add_argument("--input_shape", type=int, nargs="+", default=None)
    p.add_argument("--client_num_in_total", type=int, default=None)
    p.add_argument("--client_num_per_round", type=int, default=None)
    p.add_argument("--comm_round", type=int, default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--client_optimizer", type=str, default=None)
    # -- server-side optimization (FedOpt family; fedavg.py
    # make_server_optimizer). Previously settable ONLY by hand-editing
    # a --config JSON, which bypassed parse-time validation — the
    # fedlint parse-time-validation rule flagged the gap
    # (docs/STATIC_ANALYSIS.md).
    p.add_argument("--server_optimizer", type=str, default=None,
                   choices=["sgd", "adam", "adagrad", "yogi"],
                   help="server-side optimizer applied to the "
                        "aggregated delta (FedOpt; 'sgd' with "
                        "--server_lr 1.0 == plain FedAvg)")
    p.add_argument("--server_lr", type=float, default=None,
                   help="server optimizer learning rate (> 0)")
    p.add_argument("--server_momentum", type=float, default=None,
                   help="server SGD momentum (in [0, 1))")
    p.add_argument("--gmf", type=float, default=None,
                   help="FedNova global momentum factor (in [0, 1); "
                        "0 disables the momentum buffer)")
    p.add_argument("--compute_dtype", type=str, default=None,
                   choices=["float32", "bfloat16"],
                   help="mixed-precision compute dtype (params stay f32)")
    p.add_argument("--no_cohort_fused", action="store_true",
                   help="disable the cohort-grouped fast path (always "
                        "vmap the per-client local update)")
    p.add_argument("--partition_method", type=str, default=None)
    p.add_argument("--partition_alpha", type=float, default=None)
    p.add_argument("--frequency_of_the_test", type=int, default=None)
    _DEFENSES = ["mean", "median", "trimmed_mean", "krum", "multikrum",
                 "fltrust"]
    p.add_argument("--robust_method", type=str, default=None,
                   choices=_DEFENSES)
    p.add_argument("--defense", type=str, default=None,
                   choices=_DEFENSES,
                   help="aggregation defense rule (alias of "
                        "--robust_method, taking precedence; composes "
                        "with --robust_norm_clip / "
                        "--robust_noise_stddev — see "
                        "docs/FAULT_TOLERANCE.md 'Threat model')")
    p.add_argument("--defense_num_adversaries", type=int, default=None,
                   help="assumed adversary count f for the Krum-family "
                        "defenses (selection keeps the C-f-2 nearest "
                        "neighbors per score)")
    p.add_argument("--defense_multikrum_m", type=int, default=None,
                   help="multi-Krum keep count m (0 = auto: C - f)")
    p.add_argument("--defense_trim_frac", type=float, default=None,
                   help="trimmed_mean per-side trim fraction (raise it "
                        "for small cohorts: floor(0.1*C) trims nobody "
                        "below C=10)")
    p.add_argument("--robust_norm_clip", type=float, default=None)
    p.add_argument("--robust_noise_stddev", type=float, default=None)
    # -- compressed + sharded weight-update path (core/compress.py,
    # parallel/sharded_agg.py; docs/PERFORMANCE.md) -------------------------
    p.add_argument("--compress", type=str, default=None,
                   choices=["none", "int8", "topk", "topk_int8"],
                   help="wire codec for the client->server delta "
                        "payload: int8 absmax quantization, top-k "
                        "sparsification, or both — with client-side "
                        "error feedback so compression error is "
                        "telescoping carry, not bias. 'none' (default) "
                        "keeps the dense wire byte-identical. Applies "
                        "to the fedavg-family sim and --role paths; "
                        "set it identically on EVERY rank of a world")
    p.add_argument("--compress_topk_frac", type=float, default=None,
                   help="fraction of each leaf's entries the topk "
                        "family keeps (>= 1 entry per leaf)")
    p.add_argument("--shard_aggregation", action="store_true",
                   help="server rank: shard the aggregation pass "
                        "(decompress -> clip -> defense-reduce -> "
                        "optimizer step) over the client axis of a "
                        "mesh spanning this host's devices, "
                        "all-gathering only the final params "
                        "(parallel/sharded_agg.py; the sims' sharded "
                        "runtime is ShardedFedAvg)")
    # -- async + tiered aggregation (core/async_agg.py, core/tier.py;
    # docs/FAULT_TOLERANCE.md "Async + tiered worlds") ---------------------
    p.add_argument("--async_buffer_k", type=int, default=None,
                   help="server rank: FedBuff-style buffered-async "
                        "aggregation — fold every arriving screened "
                        "delta into a staleness-weighted buffer and "
                        "emit a new model every K arrivals, re-syncing "
                        "each client individually the moment its "
                        "result lands (no round barrier; a slow "
                        "client never blocks a fast one). 0 (default) "
                        "keeps the synchronous rounds byte-identical")
    p.add_argument("--staleness_fn", type=str, default=None,
                   choices=["poly", "const"],
                   help="staleness discount for async folds: poly = "
                        "(1+lag)^-alpha over the version lag, const = "
                        "full weight for every arrival")
    p.add_argument("--staleness_alpha", type=float, default=None,
                   help="exponent of the poly staleness discount "
                        "(0.5 = the FedAsync default)")
    p.add_argument("--tier_spec", type=str, default=None,
                   help="tier topology, e.g. root:2 — one root "
                        "aggregator serving 2 leaf aggregators, each "
                        "leaf terminating its own clients' transports "
                        "in its own world and forwarding one partial "
                        "[sum, n, count] upstream per flush. Set on "
                        "the root (--role server) and every leaf "
                        "(--role leaf); clients are topology-blind")
    p.add_argument("--uplink_ip_config", type=str, default=None,
                   help="leaf rank: the ROOT world's rank table "
                        "(--ip_config stays this leaf's own world, "
                        "where it is rank 0)")
    p.add_argument("--tier_client_base", type=int, default=None,
                   help="leaf rank: global client id of this leaf's "
                        "slot 0 (default: contiguous equal-size "
                        "blocks per leaf rank)")
    # -- parameter-efficient fine-tuning (fedml_tpu.peft;
    # docs/PERFORMANCE.md "Parameter-efficient federated
    # fine-tuning") --------------------------------------------------------
    p.add_argument("--peft", type=str, default=None,
                   choices=["none", "lora"],
                   help="parameter-efficient fine-tuning: 'lora' "
                        "wraps the transformer's targeted Dense "
                        "projections with zero-init low-rank "
                        "branches and trains/aggregates ONLY the "
                        "adapter + LM-head subtree — the frozen base "
                        "takes no optimizer state, builds no delta, "
                        "and ships no wire bytes (composes "
                        "multiplicatively with --compress). "
                        "Transformer models + FedAvg-family sims "
                        "only; round 0 is byte-identical to the base "
                        "model")
    p.add_argument("--lora_rank", type=int, default=None,
                   help="LoRA rank r (>= 1); the adapter branch is "
                        "(alpha/r) * x A B with A [in, r] seeded and "
                        "B [r, out] zero-init")
    p.add_argument("--lora_alpha", type=float, default=None,
                   help="LoRA scale alpha (> 0)")
    p.add_argument("--lora_targets", type=str, nargs="+", default=None,
                   help="which named TransformerLM projections get "
                        "adapters (subset of q_proj k_proj v_proj "
                        "attn_out mlp_up mlp_down; default: the "
                        "classic q_proj v_proj pair); resolved "
                        "against the model's Dense names at parse "
                        "time")
    p.add_argument("--peft_personalize", action="store_true",
                   help="keep each client's adapters in a PRIVATE "
                        "per-client bank — only the shared LM head "
                        "aggregates; client i's adapters never reach "
                        "the server or client j "
                        "(fedml_tpu.peft.personal). The bank is a "
                        "client-state bank (core/statebank.py), so it "
                        "composes with --client_block_size, "
                        "--elastic, --fuse_rounds, the sharded "
                        "runtime, and --checkpoint_every; compress / "
                        "defended robust_method / adversary combos "
                        "are rejected at parse time")
    # -- seeded Byzantine adversary injection (core/adversary.py) ----------
    p.add_argument("--adversary_mode", type=str, default=None,
                   choices=["none", "sign_flip", "scale_boost", "gauss",
                            "zero", "constant", "collude"],
                   help="make selected clients emit malicious deltas "
                        "(simulator: client ids; deployment: worker "
                        "ranks). Deterministic given --adversary_seed")
    p.add_argument("--adversary_seed", type=int, default=None,
                   help="seed for the adversary stream (selection + "
                        "corruption draws)")
    p.add_argument("--adversary_ranks", type=int, nargs="+",
                   default=None,
                   help="explicit adversarial identities (client ids "
                        "on the simulator path, ranks >= 1 under "
                        "--role); overrides --adversary_num")
    p.add_argument("--adversary_num", type=int, default=None,
                   help="seeded choice of this many adversaries when "
                        "--adversary_ranks is not given")
    p.add_argument("--adversary_scale", type=float, default=None,
                   help="attack magnitude (sign_flip/scale_boost "
                        "multiplier, constant fill, collude delta norm)")
    p.add_argument("--adversary_noise", type=float, default=None,
                   help="gauss-mode perturbation stddev")
    # -- cross-round reputation / quarantine (server rank) -----------------
    p.add_argument("--quarantine_threshold", type=float, default=0.0,
                   help="EWMA anomaly score above which a client is "
                        "quarantined — excluded from aggregation but "
                        "still served, so a false positive can earn "
                        "its way back (0 = off; server rank, fedavg "
                        "family; survives server restarts via "
                        "--checkpoint_every)")
    p.add_argument("--quarantine_decay", type=float, default=0.7,
                   help="EWMA memory for the reputation score "
                        "(higher = slower to trip and to forgive)")
    p.add_argument("--quarantine_evict_after", type=int, default=0,
                   help="rounds a rank may sit in quarantine without "
                        "earning release before it is PERMANENTLY "
                        "evicted from the membership ledger (0 = "
                        "never; docs/FAULT_TOLERANCE.md 'Elastic "
                        "membership')")
    # -- elastic membership / shape bucketing ------------------------------
    p.add_argument("--elastic", action="store_true",
                   help="elastic world: pad cohorts to power-of-two "
                        "buckets so membership churn (mid-run client "
                        "admission via JOIN from ranks >= world_size, "
                        "graceful --leave_after_round departures) "
                        "costs a compile-cache hit instead of an XLA "
                        "recompile; rides config.json as "
                        "fed.elastic_buckets")
    p.add_argument("--leave_after_round", type=int, default=None,
                   help="client rank: after submitting the result for "
                        "this round, announce a graceful LEAVE and "
                        "exit 0 (no dead-peer suspicion, no restart "
                        "budget spent)")
    p.add_argument("--presumed_left", type=int, nargs="*", default=(),
                   help="server rank, set by the supervisor on a "
                        "restart: ranks whose final summary reported a "
                        "departure — marked LEFT before the ready "
                        "barrier even when the restored checkpoint "
                        "predates the LEAVE (they are never respawned, "
                        "so waiting would hang the relaunch)")
    p.add_argument("--presumed_evicted", type=int, nargs="*",
                   default=(),
                   help="server rank, set by the supervisor on a "
                        "restart: ranks whose final summary reported "
                        "an EVICTION — re-evicted before the ready "
                        "barrier even when the restored checkpoint "
                        "predates the ban (marking them merely LEFT "
                        "would let the banned rank JOIN back in)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--repetitions", type=int, default=1)
    p.add_argument("--run_name", type=str, default=None)
    p.add_argument("--out_dir", type=str, default=None)
    p.add_argument("--checkpoint_every", type=int, default=None,
                   help="checkpoint round state every N rounds into "
                        "<out_dir>/<run>/ckpt and resume from the "
                        "latest checkpoint on restart (0 = off; works "
                        "for the simulator AND the fedavg-family "
                        "--role server deployment path; splitnn "
                        "deployments do not checkpoint)")
    # -- telemetry (docs/OBSERVABILITY.md) ---------------------------------
    p.add_argument("--telemetry_dir", type=str, default=None,
                   help="enable the telemetry plane and write THIS "
                        "rank's artifacts here: trace_rank<r>.json span "
                        "dump, metrics_rank<r>.json snapshot, "
                        "flight_rank<r>_*.json crash rings; merge the "
                        "span dumps with scripts/merge_trace.py")
    p.add_argument("--trace", action="store_true",
                   help="enable span tracing + metrics without naming a "
                        "directory (dumps to <out_dir>/<run>/telemetry; "
                        "implied by --telemetry_dir)")
    # -- round fusion (core/fuse.py; docs/PERFORMANCE.md "Round
    # fusion") --------------------------------------------------------------
    p.add_argument("--fuse_rounds", type=int, default=None,
                   help="simulator: run K complete rounds as ONE "
                        "compiled program (a lax.scan over the round "
                        "body, state + error-feedback residual as "
                        "donated carries) with per-block host metric "
                        "consumption — the MFU-recovery path. Cohort "
                        "sampling inside the fused block is bitwise-"
                        "identical to the unfused loop; eval/"
                        "checkpoint rounds force a block boundary. 1 "
                        "(default) keeps the per-round loop byte-"
                        "identical. FedAvg-family sims only")
    # -- bulk-client streaming (core/bulk.py; docs/PERFORMANCE.md
    # "Bulk-client execution") ---------------------------------------------
    p.add_argument("--client_block_size", type=int, default=None,
                   help="simulator: stream the sampled cohort through "
                        "the device in fixed-size blocks of B clients "
                        "(the device-resident bulk-client engine): "
                        "each block runs the vmapped local update and "
                        "is folded into an O(model) partial-sum scan "
                        "carry, so round memory is O(B + model) "
                        "instead of O(cohort) — the 10k-client-real-"
                        "training path. Composes with --elastic "
                        "(block-count buckets), --fuse_rounds (nested "
                        "scans), --compress (client-id-keyed error-"
                        "feedback bank, core/statebank.py), "
                        "--peft_personalize (streamed adapter bank), "
                        "every --robust_method (streamed defense "
                        "sketches, core/streamdef.py), and every "
                        "adversary mode. 0/unset = the stacked "
                        "[C, ...] round")
    # -- performance observability (docs/OBSERVABILITY.md) -----------------
    p.add_argument("--profile_rounds", type=int, default=None,
                   help="capture a jax.profiler window around each of "
                        "the first K compiled rounds and parse it into "
                        "a per-round device-time breakdown (compute/"
                        "collective/host/idle) under "
                        "<telemetry_dir>/jax_profile/, plus live "
                        "perf.* gauges (round rate, MFU, dispatch-"
                        "bound detector) for the whole run; the "
                        "captures hold the fedml.* host spans and "
                        "device scopes. Implies telemetry.")
    p.add_argument("--metrics_interval", type=float, default=None,
                   help="seconds between periodic metrics snapshots "
                        "appended to metrics_rank<r>.jsonl in the "
                        "telemetry dir (round-latency SLO time "
                        "series: histograms carry p50/p95/p99); "
                        "implies telemetry")
    # -- memory observability (core/memscope.py; docs/OBSERVABILITY.md
    # "Memory & compilation") ----------------------------------------------
    p.add_argument("--mem_headroom_warn", type=float, default=None,
                   help="used fraction of device HBM capacity at which "
                        "the memory monitor leaves its one "
                        "mem_headroom flight-recorder event (default "
                        "0.9). The monitor itself rides the telemetry "
                        "plane: per-device mem.bytes_in_use/"
                        "mem.peak_bytes gauges at round boundaries, "
                        "per-program mem.program.* accounting at every "
                        "compile, RSS fallback on backends without "
                        "memory_stats")
    # -- live observability plane (core/export.py, core/slo.py;
    # docs/OBSERVABILITY.md "Live export and SLOs") -------------------------
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve THIS rank's live metrics over HTTP: "
                        "/metrics (OpenMetrics text a stock Prometheus "
                        "scrape parses, with real histogram buckets "
                        "and the fleet.* aggregates federated from "
                        "client heartbeats), /statusz (JSON run "
                        "introspection: round, membership, async "
                        "buffer, SLO verdicts), /healthz — all on one "
                        "stdlib listener. 0 binds an ephemeral port "
                        "(read it back from export_rank<r>.json in "
                        "the telemetry dir); unset (default) opens no "
                        "socket. Implies telemetry")
    p.add_argument("--metrics_host", type=str, default="0.0.0.0",
                   help="interface the metrics listener binds "
                        "(default 0.0.0.0 so a remote Prometheus can "
                        "scrape; the endpoints are unauthenticated "
                        "and /statusz exposes run introspection — on "
                        "a shared network bind 127.0.0.1)")
    p.add_argument("--slo", action="append", default=None,
                   metavar="SPEC",
                   help="declarative SLO (repeatable), e.g. "
                        "'perf.round_wall_s:p99<2.0@60s': metric, "
                        "statistic (p50/p95/p99/mean/max/min over the "
                        "window, 'value' for gauges, 'rate' for "
                        "counters), healthy relation, threshold, "
                        "window. Evaluated on the metrics time-series "
                        "cadence; exports slo.ok/slo.breach_seconds/"
                        "slo.burn_rate gauges, records ONE flight "
                        "event per breach transition, and writes "
                        "slo_rank<r>.json verdicts at shutdown. "
                        "Implies telemetry")
    # -- round anatomy + breach-triggered deep profiling
    # (core/anatomy.py; docs/OBSERVABILITY.md "Round anatomy") -------------
    p.add_argument("--anatomy", action="store_true",
                   help="enable the round-anatomy plane: per-phase "
                        "wall-time attribution (perf.phase.* "
                        "histograms + dominant-phase gauge) timed at "
                        "the sync points each round path already has, "
                        "a last-N-rounds /tracez ring on the "
                        "--metrics_port listener, and cross-rank "
                        "straggler/critical-path accounting on the "
                        "deploy server. Off (default) costs one "
                        "attribute check per round and keeps results "
                        "byte-identical. Implies telemetry")
    p.add_argument("--profile_on_breach", action="store_true",
                   help="arm a one-shot jax.profiler deep-profile "
                        "window fired on an SLO breach TRANSITION or "
                        "the mem_headroom crossing, written under "
                        "<telemetry_dir>/profiles/ with a flight "
                        "event linking breach -> artifact path. "
                        "Requires an armed breach source (--slo or "
                        "--mem_headroom_warn); rank 0 only under "
                        "--supervise (like --metrics_port). Capture "
                        "never extends a round deadline. Implies "
                        "telemetry")
    p.add_argument("--profile_window_s", type=float, default=None,
                   help="breach-profile capture window in seconds "
                        "(> 0; default 5)")
    p.add_argument("--profile_max_captures", type=int, default=None,
                   help="lifetime cap on breach-profile captures "
                        "(>= 1; default 3) — re-armed breaches after "
                        "the cap are counted in profile.skipped, "
                        "never captured")
    # -- process-separated deployment (reference mpirun/run_server.sh
    # surface: one OS process per rank; scripts/run_distributed.sh is the
    # localhost launcher) --------------------------------------------------
    p.add_argument("--role", type=str, default=None,
                   choices=["server", "client", "leaf"],
                   help="run ONE deployment rank instead of the local "
                        "simulator (requires --world_size; clients and "
                        "leaf aggregators also --rank)")
    p.add_argument("--rank", type=int, default=None,
                   help="this process's rank (server=0, clients>=1)")
    p.add_argument("--world_size", type=int, default=None,
                   help="total process count (1 server + N clients)")
    p.add_argument("--backend", type=str, default="grpc",
                   choices=["tcp", "grpc", "trpc", "pubsub", "pubsub_blob"],
                   help="deployment transport backend")
    p.add_argument("--ip_config", type=str, default=None,
                   help='JSON file {"rank": ["host", port], ...} '
                        "(tcp/grpc/trpc backends)")
    p.add_argument("--broker", type=str, default=None,
                   help="host:port of the pub/sub broker daemon "
                        "(pubsub/pubsub_blob backends; start one with "
                        "python -m fedml_tpu.core.transport.broker)")
    p.add_argument("--blob_dir", type=str, default=None,
                   help="shared directory for the file-backed blob store "
                        "(pubsub_blob backend)")
    p.add_argument("--ready_timeout", type=float, default=120.0,
                   help="seconds a client re-announces readiness before "
                        "giving up")
    # -- fault tolerance (docs/FAULT_TOLERANCE.md) -------------------------
    p.add_argument("--no_heartbeats", action="store_true",
                   help="disable the liveness protocol (heartbeats + "
                        "dead-peer detection)")
    p.add_argument("--heartbeat_interval", type=float, default=2.0,
                   help="seconds between liveness beacons")
    p.add_argument("--heartbeat_timeout", type=float, default=30.0,
                   help="seconds of peer silence before it is declared "
                        "dead")
    p.add_argument("--quorum_fraction", type=float, default=1.0,
                   help="fraction of live workers whose results close a "
                        "round once --round_deadline expires (server "
                        "rank; fedavg family)")
    p.add_argument("--round_deadline", type=float, default=None,
                   help="per-round wall-clock budget in seconds: at "
                        "expiry the round closes with >= quorum results "
                        "or the run aborts (0/unset = no deadline)")
    # -- crash recovery (docs/FAULT_TOLERANCE.md "Recovery") ---------------
    p.add_argument("--recovery_extensions", type=int, default=0,
                   help="times a round deadline that expires UNDER "
                        "quorum re-arms (waiting for restarted ranks "
                        "to rejoin) before the quorum-lost abort fires")
    p.add_argument("--supervise", action="store_true",
                   help="launch ALL ranks of the deployment on this "
                        "host under a Supervisor that restarts crashed "
                        "processes with capped backoff (requires "
                        "--world_size; do not pass --role/--rank)")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="per-rank restart budget under --supervise")
    # -- seeded fault injection for THIS rank (chaos testing) --------------
    p.add_argument("--fault_seed", type=int, default=0,
                   help="seed for the deterministic fault stream")
    p.add_argument("--fault_drop", type=float, default=0.0,
                   help="per-message send drop probability")
    p.add_argument("--fault_delay", type=float, default=0.0,
                   help="per-message send delay probability")
    p.add_argument("--fault_delay_max", type=float, default=0.05,
                   help="max injected delay in seconds")
    p.add_argument("--fault_dup", type=float, default=0.0,
                   help="per-message duplication probability")
    p.add_argument("--fault_reorder", type=float, default=0.0,
                   help="per-message reorder probability")
    p.add_argument("--fault_corrupt", type=float, default=0.0,
                   help="per-message payload bit-flip probability "
                        "(seeded; the CRC32 frame checksum on the "
                        "tcp/pubsub codecs detects and drops the "
                        "frame — transport.corrupt_frames — and the "
                        "retry/straggler machinery heals the loss)")
    p.add_argument("--fault_crash_round", type=int, default=None,
                   help="crash this rank on the first message tagged "
                        "with round_idx >= N")
    p.add_argument("--fault_crash_mode", type=str, default="silent",
                   choices=["silent", "exit"],
                   help="silent: the rank stops communicating; exit: "
                        "the process dies (os._exit) like kill -9")
    # the shared registration checker (fedml_tpu/analysis/flags.py):
    # run.py OWNS the reserved --slo/--metrics_port names, so owner
    # mode asserts they are registered AND nothing is duplicated —
    # the supervisor runs the non-owner side of the same contract
    from fedml_tpu.analysis.flags import check_flag_registry

    check_flag_registry(p, owner=True,
                        entrypoint="fedml_tpu.experiments.run")
    a = p.parse_args(argv)

    if a.config:
        with open(a.config) as f:
            cfg = ExperimentConfig.from_dict(json.load(f))
    else:
        cfg = ExperimentConfig()

    def rep(obj, **kw):
        kw = {k: v for k, v in kw.items() if v is not None}
        return dataclasses.replace(obj, **kw) if kw else obj

    cfg = rep(
        cfg,
        data=rep(
            cfg.data,
            dataset=a.dataset,
            data_dir=a.data_dir,
            num_clients=a.client_num_in_total,
            # batch_size=-1 == the reference's full-batch `combine_batches`
            # mode (fedml_experiments/standalone/utils/dataset.py:158-164)
            batch_size=None if a.batch_size == -1 else a.batch_size,
            full_batch=True if a.batch_size == -1 else None,
            partition_method=a.partition_method,
            partition_alpha=a.partition_alpha,
        ),
        model=rep(
            cfg.model,
            name=a.model,
            num_classes=a.num_classes,
            input_shape=tuple(a.input_shape) if a.input_shape else None,
        ),
        train=rep(
            cfg.train, lr=a.lr, epochs=a.epochs,
            optimizer=a.client_optimizer,
            compute_dtype=a.compute_dtype,
            cohort_fused=False if a.no_cohort_fused else None,
        ),
        fed=rep(
            cfg.fed,
            algorithm=a.algorithm,
            num_rounds=a.comm_round,
            clients_per_round=a.client_num_per_round,
            eval_every=a.frequency_of_the_test,
            server_optimizer=a.server_optimizer,
            server_lr=a.server_lr,
            server_momentum=a.server_momentum,
            gmf=a.gmf,
            robust_method=a.defense or a.robust_method,
            robust_norm_clip=a.robust_norm_clip,
            robust_noise_stddev=a.robust_noise_stddev,
            robust_num_adversaries=a.defense_num_adversaries,
            robust_multikrum_m=a.defense_multikrum_m,
            robust_trim_frac=a.defense_trim_frac,
            elastic_buckets=True if a.elastic else None,
            async_buffer_k=a.async_buffer_k,
            staleness_fn=a.staleness_fn,
            staleness_alpha=a.staleness_alpha,
            compress=a.compress,
            compress_topk_frac=a.compress_topk_frac,
            shard_aggregation=True if a.shard_aggregation else None,
            profile_rounds=a.profile_rounds,
            mem_headroom_warn=a.mem_headroom_warn,
            client_block_size=a.client_block_size,
            fuse_rounds=a.fuse_rounds,
            slos=tuple(a.slo) if a.slo else None,
            anatomy=True if a.anatomy else None,
            profile_on_breach=True if a.profile_on_breach else None,
            profile_window_s=a.profile_window_s,
            profile_max_captures=a.profile_max_captures,
            peft=a.peft,
            lora_rank=a.lora_rank,
            lora_alpha=a.lora_alpha,
            lora_targets=(
                tuple(a.lora_targets) if a.lora_targets else None
            ),
            peft_personalize=True if a.peft_personalize else None,
        ),
        adversary=rep(
            cfg.adversary,
            mode=a.adversary_mode,
            seed=a.adversary_seed,
            ranks=tuple(a.adversary_ranks) if a.adversary_ranks else None,
            num_adversaries=a.adversary_num,
            scale=a.adversary_scale,
            noise_stddev=a.adversary_noise,
        ),
        seed=a.seed,
        run_name=a.run_name,
        out_dir=a.out_dir,
        checkpoint_every=a.checkpoint_every,
    )
    # surface defense/quarantine/adversary config errors at argument
    # time (unconditionally — e.g. a bad --quarantine_decay with the
    # threshold off would otherwise crash the server actor at
    # construction): under --supervise a construction-time ValueError
    # would crash-loop the server through its whole restart budget
    from fedml_tpu.core.compress import CompressionSpec
    from fedml_tpu.core.reputation import QuarantinePolicy
    from fedml_tpu.core.robust import DefensePipeline, check_fednova_compat

    from fedml_tpu.core.async_agg import AsyncConfig
    from fedml_tpu.core.tier import TierSpec

    if cfg.fed.fuse_rounds < 1:
        raise SystemExit(
            f"--fuse_rounds must be >= 1, got {cfg.fed.fuse_rounds}"
        )
    try:
        # server-optimizer plane: validate HERE, not at first round
        # close where a supervised server would crash-loop its restart
        # budget (the fednova+defense lesson; fedlint
        # parse-time-validation)
        from fedml_tpu.algorithms.fedavg import make_server_optimizer

        make_server_optimizer(cfg.fed.server_optimizer,
                              cfg.fed.server_lr,
                              cfg.fed.server_momentum)
        if cfg.fed.server_lr <= 0:
            raise ValueError(
                f"--server_lr must be > 0, got {cfg.fed.server_lr}"
            )
        if not (0.0 <= cfg.fed.server_momentum < 1.0):
            raise ValueError(
                f"--server_momentum must be in [0, 1), got "
                f"{cfg.fed.server_momentum}"
            )
        if not (0.0 <= cfg.fed.gmf < 1.0):
            raise ValueError(
                f"--gmf must be in [0, 1), got {cfg.fed.gmf}"
            )
        DefensePipeline.from_fed(cfg.fed)
        CompressionSpec.from_fed(cfg.fed)
        QuarantinePolicy(threshold=a.quarantine_threshold,
                         decay=a.quarantine_decay,
                         evict_after=a.quarantine_evict_after)
        check_fednova_compat(cfg.fed.algorithm, cfg.fed.robust_method)
        AsyncConfig.from_fed(cfg.fed)
        # bulk-client streaming: the PR-14 composition walls (selection
        # defenses, compress, the gauss adversary) have fallen — the
        # client-state banks and streamed defense sketches carry them —
        # so check_bulk_compat accepts everything; it stays called as
        # the parse-time seam (fedlint parse-time-validation
        # discipline) for any future wall. Only for processes that
        # will actually RUN a simulator: under --role/--supervise the
        # flag is inert (warned below).
        from fedml_tpu.core.bulk import BulkSpec, check_bulk_compat

        bulk = BulkSpec.from_fed(cfg.fed)
        if bulk.enabled() and a.role is None and not a.supervise \
                and cfg.fed.algorithm in _ADVERSARY_SIMS:
            check_bulk_compat(cfg.fed, cfg.adversary)
            if bulk.block_size >= cfg.fed.clients_per_round:
                print(
                    f"warning: --client_block_size "
                    f"{bulk.block_size} >= clients_per_round "
                    f"{cfg.fed.clients_per_round}: the whole cohort "
                    "fits one block — the stacked round "
                    "(client_block_size=0) compiles the same work "
                    "without the streaming wrapper and wins",
                    file=sys.stderr,
                )
        # PEFT/LoRA: the whole spec (rank >= 1, alpha > 0, targets
        # resolved against the model's Dense names) and the
        # personalization compatibility matrix fail HERE, not at
        # simulator construction (fedlint parse-time-validation
        # discipline). Algorithm families outside the FedAvg-family
        # round program would silently fine-tune the FULL model under
        # a 'lora' label — rejected, not warned. Like the bulk gate
        # above, the matrix applies only to processes that will RUN a
        # simulator: under --role/--supervise the flag is inert
        # (warned below, keyed on the merged config) and a shared
        # sim-oriented config must not hard-fail a rank PEFT cannot
        # affect.
        from fedml_tpu.config import FedConfig as _FC

        _fd = _FC()  # field defaults, to detect MERGED-config drift
        if cfg.fed.peft == "none" and not cfg.fed.peft_personalize \
                and (cfg.fed.lora_rank != _fd.lora_rank
                     or cfg.fed.lora_alpha != _fd.lora_alpha
                     or cfg.fed.lora_targets != _fd.lora_targets):
            # lora_* knobs without peft='lora' — keyed on the MERGED
            # config (a --config JSON carrying lora_* but no peft key
            # is the same footgun as the bare flags): say so loudly
            # rather than letting the user think a LoRA run was
            # configured
            print(
                "warning: lora_rank/lora_alpha/lora_targets are "
                "inert without peft='lora' — this run fine-tunes the "
                "FULL model",
                file=sys.stderr,
            )
        if cfg.fed.peft != "none" or cfg.fed.peft_personalize:
            from fedml_tpu.peft import (
                LoRASpec, check_model_supported, check_peft_compat,
            )

            LoRASpec.from_fed(cfg.fed)
            if a.role is not None or a.supervise:
                # PEFT covers the compiled simulators only; the deploy
                # actors ship full deltas. Keyed on the MERGED config
                # (not the bare CLI flag) so a --config JSON carrying
                # fed.peft cannot silently measure full fine-tuning
                # under a 'lora' label.
                print(
                    "warning: peft covers the compiled simulators "
                    "(FedAvgSim/ShardedFedAvg) and is inert under "
                    "--role/--supervise — this deployment trains and "
                    "ships the FULL model (docs/PERFORMANCE.md "
                    "'Parameter-efficient federated fine-tuning')",
                    file=sys.stderr,
                )
            else:
                check_peft_compat(cfg.fed, cfg.adversary,
                                  checkpoint_every=cfg.checkpoint_every)
                check_model_supported(cfg.model.name)
                if cfg.fed.algorithm not in _ADVERSARY_SIMS:
                    raise ValueError(
                        f"--peft covers the FedAvg-family compiled "
                        f"round ({sorted(_ADVERSARY_SIMS)}); the "
                        f"{cfg.fed.algorithm!r} simulator would "
                        "silently fine-tune the full model under a "
                        "'lora' label"
                    )
        if cfg.fed.slos:
            from fedml_tpu.core.slo import parse_specs

            parse_specs(cfg.fed.slos)
        if a.metrics_port is not None and not (
                0 <= a.metrics_port < 65536):
            raise ValueError(
                f"--metrics_port must be in [0, 65535] (0 = "
                f"ephemeral), got {a.metrics_port}"
            )
        if not (0.0 < cfg.fed.mem_headroom_warn <= 1.0):
            raise ValueError(
                f"--mem_headroom_warn is a used FRACTION of device "
                f"memory in (0, 1], got {cfg.fed.mem_headroom_warn}"
            )
        # breach profiling (core/anatomy.py BreachProfiler): keyed on
        # the MERGED config so a --config JSON carrying the knobs gets
        # the same parse-time gate as the bare flags (fedlint
        # parse-time-validation discipline)
        if cfg.fed.profile_window_s <= 0:
            raise ValueError(
                f"--profile_window_s must be > 0, got "
                f"{cfg.fed.profile_window_s}"
            )
        if cfg.fed.profile_max_captures < 1:
            raise ValueError(
                f"--profile_max_captures must be >= 1, got "
                f"{cfg.fed.profile_max_captures}"
            )
        if cfg.fed.profile_on_breach and not cfg.fed.slos \
                and a.mem_headroom_warn is None:
            # without a breach SOURCE the armed profiler can never
            # fire — the operator thinks deep profiles are coming and
            # none ever do
            raise ValueError(
                "--profile_on_breach needs an armed breach source: "
                "add --slo spec(s) and/or an explicit "
                "--mem_headroom_warn threshold"
            )
        if (cfg.fed.profile_window_s != 5.0
                or cfg.fed.profile_max_captures != 3) \
                and not cfg.fed.profile_on_breach:
            print(
                "warning: --profile_window_s/--profile_max_captures "
                "are inert without --profile_on_breach",
                file=sys.stderr,
            )
        if a.tier_spec is not None:
            TierSpec.parse(a.tier_spec)
        from fedml_tpu.algorithms.async_actors import check_async_compat

        check_async_compat(cfg)
    except ValueError as err:
        raise SystemExit(str(err))
    return cfg, a


def _parse_broker(value: str) -> tuple[str, int]:
    """``host:port`` -> tuple, with a clear SystemExit on malformed input
    (a bare ``--broker localhost`` used to crash with a ValueError
    traceback from ``int('localhost')``)."""
    host, sep, port = value.rpartition(":")
    if not sep or not host or not port.isdigit():
        raise SystemExit(
            f"--broker expects host:port (e.g. 127.0.0.1:29950), "
            f"got {value!r}"
        )
    port_num = int(port)
    if not (0 < port_num < 65536):
        raise SystemExit(
            f"--broker port must be in [1, 65535], got {port_num}"
        )
    return host, port_num


def _fault_policy(a) -> "FaultPolicy | None":
    from fedml_tpu.core.transport.chaos import FaultPolicy

    policy = FaultPolicy(
        seed=a.fault_seed,
        drop_prob=a.fault_drop,
        delay_prob=a.fault_delay,
        delay_max_s=a.fault_delay_max,
        dup_prob=a.fault_dup,
        reorder_prob=a.fault_reorder,
        corrupt_prob=a.fault_corrupt,
        crash_at_round=a.fault_crash_round,
        crash_mode=a.fault_crash_mode,
    )
    return policy if policy.enabled() else None


def _deploy_config(a) -> "DeployConfig":
    from fedml_tpu.experiments.deploy import DeployConfig, load_ip_config

    if a.world_size is None:
        raise SystemExit("--role requires --world_size")
    if a.world_size < 2:
        raise SystemExit(
            "--world_size must be >= 2 (1 server + at least 1 client); "
            "for a single-process run drop --role and use the simulator"
        )
    rank = a.rank if a.rank is not None else (0 if a.role == "server" else None)
    if rank is None:
        raise SystemExit(f"--role {a.role} requires --rank >= 1")
    if a.role == "server" and rank != 0:
        raise SystemExit("server is always rank 0")
    if a.role == "client" and rank < 1:
        raise SystemExit("client rank must be >= 1")
    if a.role == "leaf":
        # a leaf aggregator lives in TWO worlds: rank 0 of its own
        # leaf world (--ip_config) and member rank of the root world
        # (--uplink_ip_config) — docs/FAULT_TOLERANCE.md "Async +
        # tiered worlds"
        if not a.tier_spec:
            raise SystemExit("--role leaf requires --tier_spec")
        if not a.uplink_ip_config:
            raise SystemExit(
                "--role leaf requires --uplink_ip_config (the root "
                "world's rank table; --ip_config is this leaf's own "
                "client-facing world)"
            )
        from fedml_tpu.core.tier import TierSpec

        try:
            spec = TierSpec.parse(a.tier_spec)
        except ValueError as err:
            raise SystemExit(str(err))
        if not (1 <= rank <= spec.n_leaves):
            raise SystemExit(
                f"leaf rank must be in 1..{spec.n_leaves} of the root "
                f"world ({a.tier_spec}), got {rank}"
            )
        if a.backend not in ("tcp", "grpc", "trpc"):
            raise SystemExit(
                "tier worlds need a rank-addressed backend "
                "(tcp/grpc/trpc): the pub/sub topic space cannot host "
                "two overlapping rank worlds on one broker"
            )
    if (a.role == "client" and rank >= a.world_size
            and not a.elastic):
        # a rank beyond the launch world is a mid-run ADMISSION — it
        # only makes sense against an elastic server, whose membership
        # ledger will admit the JOIN (docs/FAULT_TOLERANCE.md "Elastic
        # membership"); a static server drops it and this client would
        # time out
        raise SystemExit(
            f"client rank {rank} is outside the launch world "
            f"[1, {a.world_size}); joining a running world mid-run "
            "requires --elastic (on BOTH the server and this client)"
        )
    # simulator-only knobs are silently inert under --role — say so
    # loudly rather than letting the user think they took effect
    if a.profile_rounds:
        print(
            "warning: --profile_rounds capture windows cover the "
            "simulator paths; under --role the aggregation path "
            "reports perf.agg_wall_s / perf.host_wait_s / idle-gap "
            "signals instead (docs/OBSERVABILITY.md 'Performance "
            "observability')",
            file=sys.stderr,
        )
    if a.fuse_rounds and a.fuse_rounds > 1:
        # rounds on the deploy path close on the transport barrier —
        # there is no compiled multi-round program to fuse
        print(
            "warning: --fuse_rounds covers the compiled simulator "
            "round loop and is inert under --role (deploy rounds "
            "close on the transport barrier; docs/PERFORMANCE.md "
            "'Round fusion')",
            file=sys.stderr,
        )
    if a.repetitions != 1:
        print(
            "warning: --repetitions is a simulator flag and is ignored "
            "under --role (each deployment process runs exactly one rank)",
            file=sys.stderr,
        )
    if a.client_block_size:
        # deploy clients are one process each — there is no stacked
        # cohort on a rank to stream in blocks
        print(
            "warning: --client_block_size covers the compiled "
            "simulators (FedAvgSim/ShardedFedAvg) and is inert under "
            "--role (docs/PERFORMANCE.md 'Bulk-client execution')",
            file=sys.stderr,
        )
    # (peft inertness under --role/--supervise is warned at parse
    # time, keyed on the MERGED config so --config JSON is covered)
    if a.recovery_extensions and not a.round_deadline:
        # fail at argument time with the pairing rule, not per-rank
        # (under a supervisor the server would otherwise crash-loop on
        # RoundPolicy's ValueError until the restart budget is spent)
        raise SystemExit(
            "--recovery_extensions requires --round_deadline: "
            "extensions re-arm the round deadline, so without one "
            "there is nothing to extend"
        )
    broker = _parse_broker(a.broker) if a.broker is not None else None
    return DeployConfig(
        role=a.role,
        rank=rank,
        world_size=a.world_size,
        telemetry_dir=a.telemetry_dir,
        trace=a.trace,
        metrics_interval=a.metrics_interval,
        metrics_port=a.metrics_port,
        metrics_host=a.metrics_host,
        backend=a.backend,
        ip_config=load_ip_config(a.ip_config) if a.ip_config else None,
        broker=broker,
        blob_dir=a.blob_dir,
        ready_timeout=a.ready_timeout,
        heartbeats=not a.no_heartbeats,
        heartbeat_interval_s=a.heartbeat_interval,
        heartbeat_timeout_s=a.heartbeat_timeout,
        quorum_fraction=a.quorum_fraction,
        round_deadline_s=(
            a.round_deadline if a.round_deadline else None
        ),
        checkpoint_every=a.checkpoint_every or 0,
        recovery_extensions=a.recovery_extensions,
        fault=_fault_policy(a),
        quarantine_threshold=a.quarantine_threshold,
        quarantine_decay=a.quarantine_decay,
        quarantine_evict_after=a.quarantine_evict_after,
        leave_after_round=a.leave_after_round,
        presumed_left=tuple(a.presumed_left),
        presumed_evicted=tuple(a.presumed_evicted),
        tier_spec=a.tier_spec,
        uplink_ip_config=(
            load_ip_config(a.uplink_ip_config)
            if a.uplink_ip_config else None
        ),
        tier_client_base=a.tier_client_base,
    )


def _strip_flags(
    argv: list[str], bare=(), valued=(), prefixes=()
) -> list[str]:
    """Remove flags from a raw argv list: ``bare`` take no value,
    ``valued`` (and any flag matching a ``prefixes`` entry) consume the
    next token unless given as ``--flag=value``."""
    out, i = [], 0
    while i < len(argv):
        tok = argv[i]
        name = tok.split("=", 1)[0]
        if name in bare:
            i += 1
            continue
        if name in valued or any(name.startswith(p) for p in prefixes):
            i += 1 if "=" in tok else 2
            continue
        out.append(tok)
        i += 1
    return out


def _run_supervised(a, argv: list[str]) -> int:
    """``--supervise``: launch the whole world (server + clients) on
    this host under a :class:`~fedml_tpu.experiments.deploy.Supervisor`.
    Every rank runs this same CLI with ``--role``/``--rank`` appended;
    restarted incarnations run WITHOUT the ``--fault_*`` chaos flags,
    so an injected crash happens once and its replacement runs clean
    (the kill -> restart -> rejoin -> converge loop,
    docs/FAULT_TOLERANCE.md "Recovery")."""
    from fedml_tpu.experiments.deploy import RankSpec, Supervisor

    if a.role is not None:
        raise SystemExit(
            "--supervise launches every rank itself; drop --role/--rank"
        )
    if a.world_size is None or a.world_size < 2:
        raise SystemExit("--supervise requires --world_size >= 2")
    if a.tier_spec:
        raise SystemExit(
            "--supervise launches one flat world (server + clients); "
            "tier worlds span several worlds — start the root, "
            "leaves, and clients explicitly (scripts/async_smoke.py "
            "shows the shape)"
        )
    if a.no_heartbeats:
        raise SystemExit(
            "--supervise requires the liveness protocol: after a "
            "server restart the readiness barrier completes via the "
            "surviving clients' heartbeats — with --no_heartbeats the "
            "restarted server would wait forever"
        )
    if a.recovery_extensions and not a.round_deadline:
        raise SystemExit(
            "--recovery_extensions requires --round_deadline: "
            "extensions re-arm the round deadline, so without one "
            "there is nothing to extend"
        )
    if a.telemetry_dir:
        from fedml_tpu.core import telemetry

        # the supervisor is its own telemetry process; rank world_size
        # (one past the last client) keeps its artifacts from
        # colliding with the server's rank-0 files
        telemetry.configure(telemetry_dir=a.telemetry_dir,
                            rank=a.world_size)
    base = _strip_flags(argv, bare={"--supervise"},
                        valued={"--max_restarts"})
    clean = _strip_flags(base, prefixes=("--fault_",))
    # --metrics_port names ONE port: the server keeps it (its /metrics
    # carries the federated fleet.* view anyway); clients would all
    # collide on the same bind, so the flag is stripped from their
    # argv. --profile_on_breach is rank-0-only the same way (one deep
    # profiler per world, armed where rounds close); its window/cap
    # companions go with it so the clients don't warn about inert
    # knobs. --anatomy stays on every rank: the clients' phase
    # histograms are what fleet federation forwards.
    _c_bare = {"--profile_on_breach"}
    _c_valued = {"--metrics_port", "--profile_window_s",
                 "--profile_max_captures"}
    c_base = _strip_flags(base, bare=_c_bare, valued=_c_valued)
    c_clean = _strip_flags(clean, bare=_c_bare, valued=_c_valued)
    entry = [sys.executable, "-m", "fedml_tpu.experiments.run"]
    specs = [
        RankSpec(
            rank=0,
            argv=[*entry, *base, "--role", "server"],
            restart_argv=[*entry, *clean, "--role", "server"],
        )
    ]
    for r in range(1, a.world_size):
        specs.append(
            RankSpec(
                rank=r,
                argv=[*entry, *c_base, "--role", "client",
                      "--rank", str(r)],
                restart_argv=[*entry, *c_clean, "--role", "client",
                              "--rank", str(r)],
            )
        )
    sup = Supervisor(
        specs, max_restarts=a.max_restarts, env=dict(os.environ)
    )
    result = sup.run()
    print(json.dumps(
        {**result["summary"], "restarts": result["restarts"]},
        default=float,
    ))
    return 0


def main(argv=None) -> int:
    cfg, a = parse_args(argv)
    if a.supervise:
        # the supervisor parent stays off jax: each rank it starts
        # is the one process that may own a chip
        return _run_supervised(
            a, list(sys.argv[1:] if argv is None else argv)
        )
    from fedml_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    if a.role is not None:
        from fedml_tpu.experiments.deploy import run_role

        # telemetry for the role path is configured inside run_role
        # (DeployConfig carries the knobs, so library callers get the
        # same wiring as the CLI)
        print(json.dumps(run_role(cfg, _deploy_config(a)), default=float))
        return 0
    if a.quarantine_threshold:
        # the reputation plane lives in the server ACTOR; the compiled
        # simulator applies per-round defenses (--defense) but has no
        # per-client identity to quarantine across rounds
        print(
            "warning: --quarantine_threshold is a deployment flag and "
            "is ignored by the simulator (use --role/--supervise; "
            "--defense still applies here)",
            file=sys.stderr,
        )
    if a.leave_after_round is not None:
        # departure is an actor-protocol event (MSG_TYPE_C2S_LEAVE);
        # the compiled simulator has no per-rank processes to depart
        print(
            "warning: --leave_after_round is a deployment flag and is "
            "ignored by the simulator (use --role client; "
            "set_cohort_size drives churn in the simulator)",
            file=sys.stderr,
        )
    if cfg.fed.async_buffer_k:
        # the async buffer lives in the deploy server actor: the
        # compiled simulator IS one synchronous program — there is no
        # arrival stream to fold without a barrier
        print(
            "warning: --async_buffer_k is a deployment flag and is "
            "ignored by the simulator (use --role/--supervise; "
            "docs/FAULT_TOLERANCE.md 'Async + tiered worlds')",
            file=sys.stderr,
        )
    if a.tier_spec:
        print(
            "warning: --tier_spec is a deployment flag and is ignored "
            "by the simulator (tier worlds are --role server/leaf/"
            "client processes)",
            file=sys.stderr,
        )
    if cfg.fed.shard_aggregation:
        # the sharded server update lives in the deploy server actor;
        # the sims' sharded runtime is ShardedFedAvg (library API)
        print(
            "warning: --shard_aggregation covers the --role server "
            "aggregation path and is ignored by the simulator "
            "(parallel.ShardedFedAvg is the sims' sharded runtime)",
            file=sys.stderr,
        )
    # adversary injection is wired into the FedAvgSim round program;
    # other sims (mpc/secure-agg, GAN family, splitnn, ...) aggregate
    # elsewhere and would silently run a vacuous Byzantine experiment
    # (_ADVERSARY_SIMS is module-level: parse_args gates the bulk
    # compatibility matrix on the same family)
    if (cfg.adversary.enabled()
            and cfg.fed.algorithm not in _ADVERSARY_SIMS):
        print(
            f"warning: --adversary_* flags are ignored by the "
            f"{cfg.fed.algorithm!r} simulator (adversary injection "
            "covers the FedAvg-family round program: "
            f"{sorted(_ADVERSARY_SIMS)})",
            file=sys.stderr,
        )
    if (cfg.fed.fuse_rounds > 1
            and cfg.fed.algorithm not in _ADVERSARY_SIMS):
        # the fused block scans the FedAvg-family round body; other
        # sims fall back to the per-round loop (the harness warns too,
        # but say it at launch where the flag was typed)
        print(
            f"warning: --fuse_rounds is ignored by the "
            f"{cfg.fed.algorithm!r} simulator (round fusion covers "
            "the FedAvg-family compiled round: "
            f"{sorted(_ADVERSARY_SIMS)}); this run executes per-round",
            file=sys.stderr,
        )
    if (cfg.fed.client_block_size
            and cfg.fed.algorithm not in _ADVERSARY_SIMS):
        # same honesty rule as fuse_rounds: the block scan wraps the
        # FedAvg-family round body only
        print(
            f"warning: --client_block_size is ignored by the "
            f"{cfg.fed.algorithm!r} simulator (bulk streaming covers "
            "the FedAvg-family compiled round: "
            f"{sorted(_ADVERSARY_SIMS)}); this run executes stacked",
            file=sys.stderr,
        )
    if (cfg.fed.compress != "none"
            and cfg.fed.algorithm not in _ADVERSARY_SIMS):
        # same honesty rule as the adversary gate: only the
        # FedAvg-family round wires the codec in — a summary labeled
        # topk_int8 must not have measured a dense run
        print(
            f"warning: --compress is ignored by the "
            f"{cfg.fed.algorithm!r} simulator (the wire codec covers "
            "the FedAvg-family round program: "
            f"{sorted(_ADVERSARY_SIMS)}); results here are DENSE",
            file=sys.stderr,
        )
    if (a.telemetry_dir or a.trace
            or cfg.fed.profile_rounds or a.metrics_interval
            or a.metrics_port is not None or cfg.fed.slos
            or cfg.fed.anatomy or cfg.fed.profile_on_breach):
        from fedml_tpu.core import telemetry

        telemetry.configure(
            telemetry_dir=a.telemetry_dir
            or telemetry.default_dir(cfg.out_dir, cfg.run_name),
            rank=0,
            metrics_interval=a.metrics_interval,
            metrics_port=a.metrics_port,
            metrics_host=a.metrics_host,
            slos=cfg.fed.slos,
            slo_scope=cfg.run_name,
        )
        if cfg.fed.anatomy or cfg.fed.profile_on_breach:
            # the anatomy plane rides the telemetry dir configured
            # above (breach profiles land under <dir>/profiles/)
            from fedml_tpu.core import anatomy

            anatomy.configure(
                anatomy=cfg.fed.anatomy,
                profile_on_breach=cfg.fed.profile_on_breach,
                profile_window_s=cfg.fed.profile_window_s,
                profile_max_captures=cfg.fed.profile_max_captures,
            )
    summaries = Experiment(cfg, a.repetitions).run()
    for s in summaries:
        print(json.dumps(s, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
