"""Per-op time ledger for the STANDARD-layout ResNet-56 round.

VERDICT r4 weak #1: the reference-parity line (18.99 r/s, MFU 0.052)
explains its gap to peak qualitatively ("grouped-conv dense expansion")
but never itemizes it. This script produces the ledger:

- every distinct conv shape the cohort-grouped standard ResNet-56
  executes (stem, 3 stages x 9 blocks x 2 convs, stride-2 entries,
  1x1 projections), microbenched fwd+bwd in bf16 with inner-scan
  amortization (ONLY on an idle chip, see docs/PERFORMANCE.md round-4
  negative result);
- each op's XLA-executed FLOPs (cost_analysis) vs its USEFUL FLOPs
  (the grouped math the semantics require) -> dense-expansion factor;
- composition: sum(op time x per-round count) vs the measured compiled
  round -> residual (BN/glue/latency);
- two bounds: the EXECUTED-op bound (the round cannot run faster than
  its constituent convs at this lowering) and the USEFUL-FLOP ideal
  (what de-expansion would buy at MXU peak).

Writes docs/ledger_resnet56_std.md (markdown table + bounds) and prints
the same. Run on an IDLE TPU: python scripts/ledger_resnet56_std.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

INNER = 20  # amortize the per-call dispatch over an inner scan


def conv_shapes(cpg=(16, 32, 64), blocks=9, group=2, batch=32, hw=32):
    """Distinct conv invocations of one fwd pass of cohort-grouped
    standard ResNet-56 (reference model/cv/resnet.py:113 layout:
    conv3x3 stem, 3 stages x 9 basic blocks, channels 16/32/64,
    stride-2 at stage entries, 1x1 projection shortcuts), with
    per-round occurrence counts. Channels are x``group`` (clients
    concatenated), feature_group_count=``group``."""
    shapes = []  # (label, B, H, Cin, Cout, k, stride, fgc, count)
    shapes.append(("stem 3->16", batch, hw, 3 * group, cpg[0] * group,
                   3, 1, group, 1))
    h = hw
    for s, c in enumerate(cpg):
        C = c * group
        if s == 0:
            shapes.append((f"stage{s} 3x3 {c}->{c}", batch, h, C, C,
                           3, 1, group, 2 * blocks))
        else:
            prev = cpg[s - 1] * group
            shapes.append((f"stage{s} entry 3x3 {cpg[s-1]}->{c} /2",
                           batch, h, prev, C, 3, 2, group, 1))
            shapes.append((f"stage{s} proj 1x1 {cpg[s-1]}->{c} /2",
                           batch, h, prev, C, 1, 2, group, 1))
            h //= 2
            shapes.append((f"stage{s} 3x3 {c}->{c}", batch, h, C, C,
                           3, 1, group, 2 * blocks - 1))
    return shapes


def timed(fn, *args, n=10):
    """Best-of-3 amortized seconds per single op call."""
    out = fn(*args)  # compile+warm
    leaf = jax.tree.leaves(out)[0]
    float(np.asarray(jax.device_get(jnp.sum(leaf))))
    fetches = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(np.asarray(jax.device_get(jnp.sum(leaf))))
        fetches.append(time.perf_counter() - t0)
    fetch = min(fetches)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        leaf = jax.tree.leaves(out)[0]
        float(np.asarray(jax.device_get(jnp.sum(leaf))))
        dt = time.perf_counter() - t0
        wall = max(dt - fetch, dt / 2)  # fetch-corrected, capped at 2x
        best = wall if best is None else min(best, wall)
    return best / n / INNER


def bench_conv(B, H, Cin, Cout, k, stride, fgc):
    """fwd+bwd time and executed FLOPs of ONE grouped conv in bf16."""
    x = jnp.zeros((B, H, H, Cin), jnp.bfloat16)
    w = jnp.zeros((k, k, Cin // fgc, Cout), jnp.bfloat16)
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    pad = "SAME" if stride == 1 else [(k // 2, k // 2)] * 2

    def one(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), pad, dimension_numbers=dn,
            feature_group_count=fgc,
        )

    def fwd_bwd(x, w):
        def body(carry, _):
            xx, ww = carry
            loss, (dx, dw) = jax.value_and_grad(
                lambda a, b: jnp.sum(one(a, b).astype(jnp.float32)),
                argnums=(0, 1),
            )(xx, ww)
            return (xx + dx.astype(xx.dtype) * 0,
                    ww + dw.astype(ww.dtype) * 0), loss

        (xo, _), losses = lax.scan(body, (x, w), None, length=INNER)
        return xo, losses

    f = jax.jit(fwd_bwd)
    # executed FLOPs from the SINGLE-op grad program (HLO cost analysis
    # counts a scan body once, so costing the scan version would be
    # ambiguous across XLA versions)
    single = jax.jit(jax.grad(
        lambda a, b: jnp.sum(one(a, b).astype(jnp.float32)),
        argnums=(0, 1),
    ))
    try:
        ca = single.lower(x, w).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        executed = float(ca.get("flops") or 0) or None
    except Exception:
        executed = None
    t = timed(f, x, w)
    # useful fwd+bwd FLOPs: 3x the forward conv MACs x2 (fwd, dgrad,
    # wgrad), grouped semantics (Cin/fgc per output channel)
    Ho = H // stride
    useful = 3 * 2.0 * B * Ho * Ho * k * k * (Cin // fgc) * Cout
    return t, executed, useful


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})", flush=True)
    if dev.platform == "cpu":
        print("WARNING: CPU run — times are structural only, publish "
              "numbers from an idle TPU run", flush=True)

    # the bench --std config: 10-client cohort, cohort_groups=5 ->
    # grouped ops carry 2 clients; mean steps/round from the bench sim
    sys.argv = ["bench.py"]
    import bench

    sim = bench.build_sim(num_clients=100, model_name="resnet56")
    counts = np.asarray(sim.arrays.counts)
    mean_steps = float(np.mean(np.ceil(counts / sim.batch_size)))
    n_groups = sim.cfg.train.cohort_groups  # sequential sub-group passes
    group = sim.cfg.fed.clients_per_round // n_groups

    rows = []
    total_t = total_useful = total_executed = 0.0
    for (label, B, H, Cin, Cout, k, stride, fgc,
         per_pass) in conv_shapes(group=group, batch=sim.batch_size):
        t, executed, useful = bench_conv(B, H, Cin, Cout, k, stride, fgc)
        per_round = per_pass * mean_steps * n_groups
        expansion = (executed / useful) if executed and useful else None
        rows.append((label, B, H, fgc, t * 1e6, per_round,
                     t * per_round * 1e3, useful * per_round / 1e9,
                     (executed or 0) * per_round / 1e9, expansion))
        total_t += t * per_round
        total_useful += useful * per_round
        total_executed += (executed or 0) * per_round
        print(f"  {label}: {t*1e6:.0f} us/call x {per_round:.0f}", flush=True)

    # measured full round for the residual
    rps, _, _ = bench.rate_bench(sim, 6)
    round_s = 1.0 / rps
    peak = bench.device_peaks(dev)[0]

    lines = [
        "# Standard-layout ResNet-56 round: per-op ledger",
        "",
        f"Device: {dev.device_kind}; cohort 10 clients in {n_groups} "
        f"sub-groups of {group}; batch {sim.batch_size}; mean "
        f"{mean_steps:.1f} steps/client/round; measured round "
        f"{round_s*1e3:.1f} ms ({rps:.2f} r/s).",
        "",
        "| conv op | B | H | fgc | us/call | calls/round | ms/round | "
        "useful GFLOP | executed GFLOP | expansion |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for (label, B, H, fgc, us, cnt, ms, ugf, egf, exp) in rows:
        lines.append(
            f"| {label} | {B} | {H} | {fgc} | {us:.0f} | {cnt:.0f} | "
            f"{ms:.2f} | {ugf:.1f} | {egf:.1f} | "
            f"{exp:.1f}x |" if exp else
            f"| {label} | {B} | {H} | {fgc} | {us:.0f} | {cnt:.0f} | "
            f"{ms:.2f} | {ugf:.1f} | — | — |"
        )
    conv_ms = total_t * 1e3
    resid_ms = round_s * 1e3 - conv_ms
    lines += [
        "",
        f"- conv ops account for **{conv_ms:.1f} ms** of the "
        f"{round_s*1e3:.1f} ms round ({100*conv_ms/round_s:.0f}%); "
        f"residual {resid_ms:.1f} ms = BN/elementwise/glue + per-round "
        "lowering latency.",
        f"- useful conv FLOPs {total_useful/1e9:.1f} GFLOP vs executed "
        f"{total_executed/1e9:.1f} GFLOP -> mean dense-expansion "
        f"{total_executed/max(total_useful,1):.1f}x.",
    ]
    if peak:
        ideal_ms = total_useful / peak * 1e3
        lines.append(
            f"- bounds: executed-op bound {conv_ms:.1f} ms/round "
            f"(= {1000/conv_ms:.1f} r/s ceiling at this lowering); "
            f"useful-FLOP ideal {ideal_ms:.2f} ms "
            f"(= {1000/ideal_ms:.0f} r/s) — unreachable without "
            "de-expanding 16-channel-per-client convs, which neither "
            "XLA nor a Pallas kernel can tile on a 128x128 MXU "
            "(docs/PERFORMANCE.md)."
        )
    out = "\n".join(lines) + "\n"
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "ledger_resnet56_std.md")
    with open(path, "w") as f:
        f.write(out)
    print(out)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
