"""Merge per-rank tracer dumps into ONE Chrome-trace-event JSON.

Each deployment rank (or a shared-process loopback world) dumps its
:class:`~fedml_tpu.core.tracing.Tracer` events to
``<telemetry_dir>/trace_rank<r>.json``. This tool folds any number of
those dumps into a single Chrome trace-event file — load it at
https://ui.perfetto.dev (or chrome://tracing) and every rank appears as
its own process (pid = rank), with threads as tracks and cross-process
flow arrows connecting a message's ``msg_send`` on the sending rank to
its ``msg_deliver`` on the receiving rank (matched by the span id the
:class:`~fedml_tpu.core.message.Message` envelope carried over the
wire; docs/OBSERVABILITY.md).

Usage::

    python scripts/merge_trace.py RUN_TELEMETRY_DIR [--out merged.json]
    python scripts/merge_trace.py trace_rank0.json trace_rank1.json ...
    python scripts/merge_trace.py RUN_TELEMETRY_DIR --jax-profile

Timestamps are wall-clock (epoch) microseconds rebased to the earliest
event, so ranks on the same host line up; ``X`` complete events carry
span durations, instant events render as markers.

jax-profiler captures (``--profile_rounds``, core/perf.py) live in
their own files by design — ``<telemetry_dir>/jax_profile/round<k>/``,
one session per profiled round — so they can never clobber the host
span dumps, and the ``fedml.*`` span annotations (core/tracing.py)
land INSIDE the capture they belong to. ``--jax-profile`` optionally folds those captures into
the merged timeline: each profiled round becomes its own Perfetto
process (``jax profile round <k>``) holding the XLA op events, rebased
onto the host timeline via the epoch anchor in each capture's
``capture.json`` manifest (written at ``start_trace`` time — alignment
is anchor-accurate to ~ms, good enough to see which host span a device
burst belongs to; within-capture relative timing is exact).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def load_rank_events(path: str) -> list[dict]:
    """Read one tracer dump; tolerates both the current
    ``{"rank": r, "events": [...]}`` shape and a bare legacy list."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, list):
        events, default_rank = data, None
    else:
        events, default_rank = data.get("events", []), data.get("rank")
    out = []
    for ev in events:
        ev = dict(ev)
        if ev.get("rank") is None:
            ev["rank"] = default_rank if default_rank is not None else 0
        out.append(ev)
    return out


def _flow_id(span_id: str) -> int:
    try:
        return int(span_id, 16) & 0x7FFFFFFF
    except (TypeError, ValueError):
        return hash(span_id) & 0x7FFFFFFF


_STRUCTURAL = ("kind", "ts", "seconds", "rank", "tid", "name")


def merge(paths: list[str]) -> dict:
    """Fold tracer dumps into a Chrome trace-event dict.

    A supervised deployment (docs/FAULT_TOLERANCE.md "Recovery") leaves
    MULTIPLE dumps per rank — ``trace_rank<r>.json`` from the first
    incarnation, ``trace_rank<r>_i<n>.json`` from each restart. All of a
    rank's incarnations fold into the same pid (events carry their
    rank), so the timeline shows the crash gap and the resumed work on
    one track. A dump a SIGKILLed process left unreadable is skipped
    with a warning rather than sinking the merge."""
    events: list[dict] = []
    for p in paths:
        try:
            events.extend(load_rank_events(p))
        except (json.JSONDecodeError, OSError, KeyError, TypeError) as e:
            print(f"warning: skipping unreadable dump {p!r}: {e}",
                  file=sys.stderr)
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "_epoch0": None}
    ts0 = min(float(ev.get("ts", 0.0)) for ev in events)

    trace_events: list[dict] = []
    ranks: set[int] = set()
    sends: dict[str, dict] = {}
    delivers: dict[str, dict] = {}

    cp_events: list[dict] = []

    for ev in events:
        rank = int(ev["rank"] or 0)
        ranks.add(rank)
        ts_us = (float(ev.get("ts", ts0)) - ts0) * 1e6
        dur_us = float(ev.get("seconds", 0.0)) * 1e6
        name = ev.get("name") or ev["kind"]
        if name == "critical_path":
            # round-anatomy critical path (core/anatomy.py): the
            # instant event carries the closed round's segment
            # durations — rendered as contiguous spans on a dedicated
            # track below, not a zero-width marker buried in rank 0's
            # stream
            cp_events.append(ev)
            continue
        args = {k: v for k, v in ev.items() if k not in _STRUCTURAL}
        base = {
            "name": name,
            "cat": ev["kind"],
            "pid": rank,
            "tid": int(ev.get("tid", 0)),
            "ts": ts_us,
            "args": args,
        }
        if dur_us > 0:
            trace_events.append({**base, "ph": "X", "dur": dur_us})
        else:
            trace_events.append({**base, "ph": "i", "s": "t"})
        span_id = ev.get("span_id")
        if span_id:
            if name == "msg_send":
                sends[span_id] = base
            elif name == "msg_deliver":
                delivers[span_id] = base

    # flow arrows: one per message observed on BOTH sides
    for span_id, send in sends.items():
        recv = delivers.get(span_id)
        if recv is None:
            continue
        fid = _flow_id(span_id)
        common = {"name": "msg", "cat": "msg_flow", "id": fid}
        trace_events.append({
            **common, "ph": "s", "pid": send["pid"], "tid": send["tid"],
            "ts": send["ts"],
        })
        trace_events.append({
            **common, "ph": "f", "bp": "e", "pid": recv["pid"],
            "tid": recv["tid"],
            # a deliver observed at (or clock-skewed before) its send
            # still needs flow ts >= the start or the arrow is dropped
            "ts": max(recv["ts"], send["ts"] + 1.0),
        })

    trace_events.extend(_critical_path_track(cp_events, ts0))

    for r in sorted(ranks):
        label = f"rank {r}" + (" (server)" if r == 0 else "")
        trace_events.append({
            "ph": "M", "name": "process_name", "pid": r, "tid": 0,
            "args": {"name": label},
        })
        trace_events.append({
            "ph": "M", "name": "process_sort_index", "pid": r, "tid": 0,
            "args": {"sort_index": r},
        })

    # _epoch0 (the epoch-seconds base every ts was rebased against) is
    # internal plumbing for fold_jax_profiles; stripped before writing
    return {"traceEvents": trace_events, "displayTimeUnit": "ms",
            "_epoch0": ts0}


#: synthetic pid for the round-anatomy critical-path track (above any
#: real rank, below the jax-profile block)
_CRITICAL_PATH_PID = 8000


def _critical_path_track(cp_events: list[dict], ts0: float) -> list[dict]:
    """Per-round critical-path spans (core/anatomy.py
    ``attribute_stragglers``): each ``critical_path`` instant event is
    emitted at round close and carries the closed round's segment
    durations, so the track reconstructs the dependent chain backwards
    from the close timestamp — ``sync -> slowest result (rank r)``
    followed by ``aggregate`` — as contiguous ``X`` spans on one
    synthetic process. Empty input (anatomy off, sim-only worlds)
    yields no track at all."""
    out: list[dict] = []
    for ev in cp_events:
        close_ts = float(ev.get("ts", ts0))
        closed_after = float(ev.get("closed_after_s", 0.0))
        sync_to_result = float(ev.get("sync_to_result_s", 0.0))
        agg = float(ev.get("aggregate_s", 0.0))
        # the event fires at close; the round's sync broadcast was
        # closed_after_s earlier
        start_us = (close_ts - ts0 - closed_after) * 1e6
        rnd = ev.get("round")
        rank_path = ev.get("rank_path")
        out.append({
            "name": f"r{rnd} sync->result rank{rank_path}",
            "cat": "critical_path",
            "ph": "X",
            "pid": _CRITICAL_PATH_PID,
            "tid": 0,
            "ts": start_us,
            "dur": sync_to_result * 1e6,
            "args": {
                "round": rnd,
                "rank_path": rank_path,
                "straggler_wait_s": ev.get("straggler_wait_s"),
                "total_s": ev.get("total_s"),
            },
        })
        if agg > 0:
            out.append({
                "name": f"r{rnd} aggregate",
                "cat": "critical_path",
                "ph": "X",
                "pid": _CRITICAL_PATH_PID,
                "tid": 0,
                "ts": start_us + sync_to_result * 1e6,
                "dur": agg * 1e6,
                "args": {"round": rnd},
            })
    if out:
        out.append({
            "ph": "M", "name": "process_name",
            "pid": _CRITICAL_PATH_PID, "tid": 0,
            "args": {"name": "critical path (round anatomy)"},
        })
        out.append({
            "ph": "M", "name": "process_sort_index",
            "pid": _CRITICAL_PATH_PID, "tid": 0,
            "args": {"sort_index": _CRITICAL_PATH_PID},
        })
    return out


#: pid block for folded jax-profile rounds (far above any real rank)
_JAX_PID_BASE = 9000


def fold_jax_profiles(merged: dict, dirs: list[str]) -> int:
    """Fold ``jax_profile/round<k>/`` captures (core/perf.py
    RoundProfiler) into an already-merged Chrome trace, one synthetic
    process per profiled round. Only XLA op events (those carrying an
    ``hlo_op`` arg or living on a ``/device:*`` plane) are folded — the
    captures also hold thousands of threadpool bookkeeping events that
    would bury the timeline. Returns the number of folded events."""
    try:
        from fedml_tpu.core.perf import load_trace_events
    except ImportError:
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from fedml_tpu.core.perf import load_trace_events

    evs = merged["traceEvents"]
    host_ts0_us = min(
        (e["ts"] for e in evs if e.get("ph") in ("X", "i")),
        default=None,
    )
    # the host events were rebased to their earliest epoch; recover the
    # epoch base from the merge (merge() rebased by ts0 — stash it)
    epoch0 = merged.get("_epoch0")
    folded = 0
    for d in dirs:
        for rdir in sorted(glob.glob(os.path.join(d, "jax_profile",
                                                  "round*"))):
            manifest_path = os.path.join(rdir, "capture.json")
            try:
                with open(manifest_path) as f:
                    manifest = json.load(f)
            except (OSError, json.JSONDecodeError):
                print(f"warning: no capture manifest in {rdir!r}; "
                      "skipping", file=sys.stderr)
                continue
            rnd = manifest.get("round", 0)
            pid = _JAX_PID_BASE + int(rnd)
            # rebase: event ts is session-relative; the manifest's
            # t_start anchors the session on the epoch timeline
            if epoch0 is not None:
                base_us = (manifest["t_start"] - epoch0) * 1e6
            else:
                base_us = host_ts0_us or 0.0
            n = 0
            for ev in load_trace_events(rdir):
                if ("hlo_op" not in ev["args"]
                        and not ev["process"].startswith("/device:")):
                    continue
                evs.append({
                    "name": ev["name"],
                    "cat": "jax_op",
                    "ph": "X",
                    "pid": pid,
                    "tid": ev["tid"],
                    "ts": base_us + ev["ts"],
                    "dur": ev["dur"],
                    "args": ev["args"],
                })
                n += 1
            if n:
                evs.append({
                    "ph": "M", "name": "process_name", "pid": pid,
                    "tid": 0,
                    "args": {"name": f"jax profile round {rnd}"},
                })
                evs.append({
                    "ph": "M", "name": "process_sort_index", "pid": pid,
                    "tid": 0, "args": {"sort_index": pid},
                })
            folded += n
    return folded


def resolve_inputs(inputs: list[str]) -> list[str]:
    paths: list[str] = []
    for inp in inputs:
        if os.path.isdir(inp):
            found = sorted(glob.glob(os.path.join(inp, "trace_rank*.json")))
            if not found:
                raise SystemExit(f"no trace_rank*.json dumps in {inp!r}")
            paths.extend(found)
        else:
            paths.append(inp)
    return paths


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="merge per-rank tracer dumps into one Perfetto-"
                    "loadable Chrome trace (pid = rank)"
    )
    p.add_argument("inputs", nargs="+",
                   help="telemetry dir(s) and/or trace_rank*.json files")
    p.add_argument("--out", default=None,
                   help="output path (default: merged_trace.json next to "
                        "the first input)")
    p.add_argument("--jax-profile", action="store_true",
                   help="also fold jax-profiler captures "
                        "(<dir>/jax_profile/round*/ from "
                        "--profile_rounds) into the timeline, one "
                        "Perfetto process per profiled round")
    a = p.parse_args(argv)
    paths = resolve_inputs(a.inputs)
    merged = merge(paths)
    if a.jax_profile:
        dirs = [d for d in a.inputs if os.path.isdir(d)]
        n_jax = fold_jax_profiles(merged, dirs)
        print(f"folded {n_jax} jax-profile op events", file=sys.stderr)
    merged.pop("_epoch0", None)
    out = a.out
    if out is None:
        anchor = a.inputs[0]
        base = anchor if os.path.isdir(anchor) else os.path.dirname(anchor)
        out = os.path.join(base or ".", "merged_trace.json")
    with open(out, "w") as f:
        json.dump(merged, f)
    n = len(merged["traceEvents"])
    print(f"wrote {out}: {n} trace events from {len(paths)} dump(s)",
          file=sys.stderr)
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
