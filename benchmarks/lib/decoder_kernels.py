"""What the decoder stack's two kernels have to do, counted from
shapes, and what a traced part says of the model's own scopes and
counters — for the per-layer metrics of the ``decoder`` configurations
(``attn_ms``, ``moe_*``, ``head_loss_ms``, the two roofline shares).

The blockwise attention and the grouped expert products are timed by
their scopes (``fedml.model.attn.kernel``, ``fedml.model.moe.experts``);
operations and bytes are those of the CALLS the traced rounds made: a
layer is recomputed in the backward pass, so every step calls a
forward kernel twice and a backward kernel once. Attention is counted
by the key blocks a layer really visits under its mask (a block that
the causal or window mask empties is never loaded), the grouped
products by the rows the traced rounds' own ``moe_rows_held`` counter
reports — never by the expectation — so that neither share can count
more than was run.

A program without the scopes or the counters (the parent of the PR
that added them) gives nothing to read: every function returns None.
"""

from __future__ import annotations

import numpy as np

from lib import program_spans

LOG = program_spans.PREFIX + "log"
SPARSE, SLIDING = "sparse", "sliding_attention"
BF16 = 2  # bytes of the compute dtype


def scope_ms(ctx, *scopes):
    """Busy ms a traced round under ``scopes`` together (each op counts
    under its innermost ``fedml.*`` scope), mean over chips; None
    without a trace, a scope map, or any op under them."""
    t = program_spans.analyse(ctx)
    if t is None or not t["scopes"]:
        return None
    busy = [t["scope_busy_s"].get(s) for s in scopes]
    if all(b is None for b in busy):
        return None
    return 1e3 * sum(b or 0.0 for b in busy) / t["rounds"]


def round_counters(ctx, *names):
    """``{name: total over the traced rounds}`` of counters the round
    program reported: attrs of the traced rounds' ``fedml.log`` spans,
    the last traced round's (the profiler stops inside its ``log()``)
    from its record. None where any traced round lacks one."""
    t = program_spans.analyse(ctx)
    if t is None:
        return None
    seen = {}
    for _, _, name, st in t["spans"]:
        if name == LOG and "round" in st and all(n in st for n in names):
            seen[int(st["round"])] = {n: float(st[n]) for n in names}
    for rec in ctx.get("records", ()):
        if all(n in rec for n in names):
            seen.setdefault(int(rec["round"]),
                            {n: float(rec[n]) for n in names})
    traced = ctx["traced_rounds"]
    if not traced or any(r not in seen for r in traced):
        return None
    return {n: sum(seen[r][n] for r in traced) for n in names}


def decoder_sizes(ctx):
    """-> (the configuration's ``model.extra``, sequence length), or
    None where the cell's model is no decoder stack."""
    model = ctx["cell"]["config"]["model"]
    if model.get("name") != "decoder":
        return None
    return model["extra"], int(model["input_shape"][0])


def attention_block() -> int | None:
    """The kernel's block edge, the program's own constant."""
    try:
        from fedml_tpu.ops.attention import BLOCK
    except ImportError:
        return None
    return int(BLOCK)


def blocks_visited(seq: int, block: int, window: int | None) -> int:
    """(query block, key block) pairs in which some query may read some
    key: what a blockwise kernel loads."""
    block = min(block, seq)
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    n = seq // block
    return int(seen.reshape(n, block, n, block).any(axis=(1, 3)).sum())


def attention_work(extra: dict, seq: int, batch: int, block: int):
    """-> (operations, bytes) of one optimizer step's attention kernel
    calls: per layer and head, forward (scores and mix: 2 products a
    visited block pair) twice and backward (the scores again, then dV,
    dP, dQ, dK: 5 products) once; bytes: q, k, v read and the output
    written a forward call, those and dO read and dq, dk, dv written a
    backward call, keys and values once a query-head GROUP."""
    d, kv = extra["head_dim"], extra["num_key_value_heads"]
    block = min(block, seq)
    flops = nbytes = 0.0
    for kind, heads in zip(extra["layer_types"], extra["heads_per_layer"]):
        window = extra.get("sliding_window") if kind == SLIDING else None
        pairs = blocks_visited(seq, block, window)
        product = 2.0 * block * block * d
        flops += batch * heads * pairs * product * (2 * 2 + 5)
        q_rows, kv_rows = batch * heads * seq * d, batch * kv * seq * d
        forward = 2 * q_rows + 2 * kv_rows
        backward = 4 * q_rows + 4 * kv_rows
        nbytes += BF16 * (2 * forward + backward)
    return flops, nbytes


def experts_work(extra: dict, rows_held: float, layer_calls: float):
    """-> (operations, bytes) of the grouped expert products for
    ``rows_held`` assignments over ``layer_calls`` (sparse layers x
    optimizer steps): three products a row (width ``hidden x expert
    width``), each forward twice and twice backward (by the rows, by
    the weights); bytes: every call reads the held experts' matrices
    (or writes their gradient) and reads and writes its rows."""
    hidden, width = extra["hidden_size"], extra["moe_intermediate_size"]
    held = extra["experts_held"][1]
    flops = rows_held * 3 * 2.0 * hidden * width * 4
    matrices = held * hidden * width * BF16  # one product's, all held
    rows = rows_held * (hidden + width) * BF16  # one product's in and out
    # 12 product calls a layer call: 3 products x (2 forward + 2 backward)
    nbytes = layer_calls * 12 * matrices + 4 * 3 * rows
    return flops, nbytes


def sparse_layers(extra: dict) -> int:
    return sum(k == SPARSE for k in extra["mlp_layer_types"])


def roofline_pct(ctx, scope: str, work) -> float | None:
    """100 x the least seconds the chip could take for ``work`` =
    (operations, bytes) of the traced rounds — the larger of operations
    over peak FLOP/s and bytes over peak bytes/s — over the device
    seconds under ``scope``."""
    ms = scope_ms(ctx, scope)
    if not ms or ctx.get("peaks") is None or work is None:
        return None
    flops, nbytes = work
    least_s = max(flops / ctx["peaks"]["flops_per_s"],
                  nbytes / ctx["peaks"]["bytes_per_s"])
    seconds = 1e-3 * ms * len(ctx["traced_rounds"])
    return 100.0 * least_s / seconds
