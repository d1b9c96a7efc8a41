"""What the chunked state-space scan and the latent squared-ReLU
experts have to do, counted from shapes and from the round program's
own counters — for the per-layer metrics of a ``decoder`` configuration
with ``state_space`` layers and a latent expert layer (``ssm_ms``,
``ssm_scan_ms``, ``moe_latent_ms``, ``ssm_scan_roofline_pct``,
``latent_experts_roofline_pct``).

The scan is timed by its scope (``fedml.model.ssm.scan``, inside
``fedml.model.ssm``), the grouped expert products by theirs
(``fedml.model.moe.experts``), the two latent projections by
``fedml.model.moe.latent``. Work is that of the passes the program
really makes. A state-space layer is recomputed in the backward pass
but for the states entering each chunk and the scan's result, which it
keeps (``ops/ssm.KEPT``): of the scan's four products a chunk — ``C
B^T`` of a group; the masked mix, a chunk's own state and the read of
the entering state of a head — a training step runs each once forward
and twice backward (by either operand), and ``C B^T`` and the read once
more, between the two. The experts' two products a held row run forward
twice (a sparse layer keeps nothing) and backward twice, over the rows
the traced rounds' own ``moe_rows_held`` counts — never the expectation.

A program without the scopes or the counters, or a model without such
layers, gives nothing to read: every function returns None.
"""

from __future__ import annotations

from lib import decoder_kernels as K

SSM, SCAN, LATENT = (
    "fedml.model.ssm", "fedml.model.ssm.scan", "fedml.model.moe.latent")
STATE_SPACE = "state_space"
F32 = 4  # bytes of a decay, a step and a state

# calls of each product in one training step of one layer: forward,
# made again before the backward pass, backward (module docstring)
PASSES = {"cb": 1 + 1 + 2, "mix": 1 + 0 + 2, "own": 1 + 0 + 2,
          "read": 1 + 1 + 2}


def state_space_sizes(ctx):
    """-> (``model.extra``, sequence length, state-space layers), or
    None where the cell's model has no such layer."""
    sizes = K.decoder_sizes(ctx)
    if sizes is None or STATE_SPACE not in sizes[0].get("layer_types", ()):
        return None
    extra, seq = sizes
    return extra, seq, sum(k == STATE_SPACE for k in extra["layer_types"])


def held(s: dict) -> tuple[int, int]:
    """(heads, groups) of the share ``state_space`` record ``s`` holds."""
    heads = (s.get("heads_held") or [0, s["num_heads"]])[1]
    return heads, heads // (s["num_heads"] // s["n_groups"])


def scan_work(extra: dict, seq: int):
    """-> (operations, bytes) of ONE state-space layer's scan over one
    sequence in one training step. Operations: per chunk of ``Q``
    tokens a group's ``C B^T`` (``Q x Q x N``) and, a head, the mix
    (``Q x Q x P``), the chunk's own state and the read of the entering
    one (``Q x P x N`` each), each :data:`PASSES` times, two operations
    a multiply-accumulate. Bytes, the least the passes could move:
    forward reads ``x``, ``B``, ``C`` and the steps and writes ``y`` and
    the entering states; backward reads those and ``dy`` and writes the
    four gradients."""
    s = extra["state_space"]
    heads, groups = held(s)
    p, n = s["head_dim"], s["state_size"]
    q = min(s["chunk_size"], seq)
    chunks = seq // q
    flops = 2.0 * chunks * (
        groups * q * q * n * PASSES["cb"]
        + heads * (q * q * p * PASSES["mix"]
                   + q * p * n * (PASSES["own"] + PASSES["read"])))
    x = seq * heads * p * K.BF16  # also y, dy, dx
    bc = 2 * seq * groups * n * K.BF16  # B and C, or their gradients
    steps = seq * heads * F32
    states = chunks * heads * p * n * F32
    forward = 2 * x + bc + steps + states
    backward = 3 * x + 2 * bc + 2 * steps + states
    return flops, float(forward + backward)


def latent_experts_work(extra: dict, rows_held: float, layer_calls: float):
    """-> (operations, bytes) of the grouped expert products for
    ``rows_held`` assignments over ``layer_calls`` (sparse layers x
    optimizer steps): TWO products a row (``latent x expert width``),
    each forward twice and twice backward (by the rows, by the
    weights); bytes: every product call reads the held experts' matrix
    (or writes its gradient) and reads and writes its rows."""
    latent, width = extra["moe_latent_size"], extra["moe_intermediate_size"]
    count = extra["experts_held"][1]
    flops = rows_held * 2 * 2.0 * latent * width * 4
    matrix = count * latent * width * K.BF16  # one product's, all held
    rows = rows_held * (latent + width) * K.BF16  # one product's in and out
    # 8 product calls a layer call: 2 products x (2 forward + 2 backward)
    return flops, layer_calls * 8 * matrix + 4 * 2 * rows


def scan_roofline_pct(ctx):
    sizes = state_space_sizes(ctx)
    if sizes is None or not ctx.get("client_steps"):
        return None
    extra, seq, layers = sizes
    flops, nbytes = scan_work(extra, seq)
    calls = (ctx["client_steps"] * layers
             * int(ctx["cell"]["config"]["batch_size"]))
    return K.roofline_pct(ctx, SCAN, (calls * flops, calls * nbytes))


def latent_experts_roofline_pct(ctx):
    c, sizes = K.round_counters(ctx, "moe_rows_held"), K.decoder_sizes(ctx)
    if (not c or sizes is None or not sizes[0].get("moe_latent_size")
            or not ctx.get("client_steps")):
        return None
    extra, _ = sizes
    calls = ctx["client_steps"] * K.sparse_layers(extra)
    return K.roofline_pct(ctx, "fedml.model.moe.experts",
                          latent_experts_work(extra, c["moe_rows_held"],
                                              calls))
