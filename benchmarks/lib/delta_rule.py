"""What the gated delta rule's recurrence has to do, counted from the
``delta_attention`` record and from shapes alone — for the per-layer
metrics of a ``decoder`` configuration with ``delta_attention`` layers
(``delta_mixer_ms``, ``delta_scan_ms``, ``delta_scan_roofline_pct``).

The recurrence is timed by its scope (``fedml.model.delta.scan``, from
the decays' running sums to ``o``, inside ``fedml.model.delta``; the
convolutions, norms and gates under ``fedml.model.delta.mix``). Work is
that of the CHUNKED form at the record's ``chunk_size``, whatever
implements it — not the calls the program makes, so that the count
reads the same when a kernel replaces the plain products: per chunk of
``Q`` tokens and head of ``K = V = head_dim``, the two decayed Gram
products over the causal pairs (``A``: ``Q (Q - 1) / 2`` pairs, ``P``:
``Q (Q + 1) / 2``, each ``K`` wide), the unit-lower-triangular solve
against ``K + V`` columns (``Q (Q - 1) / 2`` pairs), the two reads of
the entering state and the write of the leaving one (``Q x K x V``
each) and ``P`` against ``V`` columns; forward ONCE (a rematerialised
layer keeps entering states and the result; what it makes again of a
chunk is not counted as work it has to do) and twice that backward. Bytes, the least the passes could move: forward reads q, k, v
(compute dtype), the decays and the write strengths (float32) and
writes ``o`` and a state a chunk; backward reads those and ``o``'s
cotangent and writes the five inputs'.

A model without such layers gives nothing to read: every function
returns None.
"""

from __future__ import annotations

from lib import decoder_kernels as K

DELTA, SCAN, MIX = (
    "fedml.model.delta", "fedml.model.delta.scan", "fedml.model.delta.mix")
DELTA_ATTENTION = "delta_attention"
F32 = 4  # bytes of a decay, a write strength and a state


def delta_sizes(ctx):
    """-> (``model.extra``, sequence length, delta-rule layers), or None
    where the cell's model has no such layer."""
    sizes = K.decoder_sizes(ctx)
    if sizes is None or DELTA_ATTENTION not in sizes[0].get(
            "layer_types", ()):
        return None
    extra, seq = sizes
    return extra, seq, [l for l, kind in enumerate(extra["layer_types"])
                        if kind == DELTA_ATTENTION]


def heads_held(extra: dict, layer: int) -> int:
    return (extra.get("query_heads_held")
            or [0, extra["heads_per_layer"][layer]])[1]


def chunk_macs(q: int, d: int) -> float:
    """Multiply-accumulates of one chunk of ``q`` tokens and one head of
    ``d``-wide keys and values, forward (module docstring)."""
    below, upto = q * (q - 1) // 2, q * (q + 1) // 2
    return float(below * d + upto * d + below * 2 * d + 3 * q * d * d
                 + upto * d)


def scan_work(extra: dict, seq: int, layer: int):
    """-> (operations, bytes) of ONE delta-rule layer's recurrence over
    one sequence in one training step."""
    s = extra[DELTA_ATTENTION]
    heads, d = heads_held(extra, layer), s["head_dim"]
    q = min(s["chunk_size"], seq)
    chunks = seq // q
    flops = 2.0 * 3 * chunks * heads * chunk_macs(q, d)
    rows = seq * heads * d * K.BF16  # q, k, v, o, or a cotangent of one
    decays, writes = seq * heads * d * F32, seq * heads * F32
    states = chunks * heads * d * d * F32
    forward = 4 * rows + decays + writes + states
    backward = 4 * rows + decays + writes + states + (
        3 * rows + decays + writes)
    return flops, float(forward + backward)


def scan_roofline_pct(ctx):
    sizes = delta_sizes(ctx)
    if sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq, layers = sizes
    work = [scan_work(extra, seq, l) for l in layers]
    calls = ctx["client_steps"] * int(ctx["cell"]["config"]["batch_size"])
    return K.roofline_pct(ctx, SCAN, (calls * sum(w[0] for w in work),
                                      calls * sum(w[1] for w in work)))
