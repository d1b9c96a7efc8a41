"""The plain reference of a one-step federated round, and the
comparison that decides ``correct``.

A round here is what the reference algorithm describes (McMahan et al.
2017; Reddi et al. 2021 for the server optimizer): every sampled client
starts from the global variables, takes ONE minibatch-SGD step on its
batch in float32, and the server moves the global parameters by the
sample-weighted mean of the clients' changes — directly (FedAvg) or
through Adam (FedOpt). Normalisation statistics are averaged with the
same weights. Clients run one after the other (``lax.map``): no
widening, no vmap. Nothing here imports the program.

``quant=FP8`` puts the reference in a lower precision (the control):
every convolution and matrix product, forward and backward, then sees
its inputs rounded to float8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lib import refnet

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # optax.adam defaults


def cohort_ids(program_seed, round_idx, population, per_round, strata=1):
    """Which clients round ``round_idx`` samples: the reference rule
    (seeded choice without replacement, ``FedAVGAggregator.
    client_sampling``) on the key chain the program documents —
    ``fold_in(fold_in(key(seed), round), 0)``, per stratum
    ``fold_in(.., s)`` on a clients mesh. A copy, so that the reference
    can follow the program's rounds without importing it; if the two
    ever disagree the losses below disagree and ``correct`` is false."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(program_seed), round_idx), 0
    )
    if strata == 1:
        if per_round >= population:
            return np.arange(population)
        return np.asarray(jax.random.choice(
            key, population, shape=(per_round,), replace=False))
    size, per = population // strata, per_round // strata
    return np.concatenate([
        np.asarray(jax.random.choice(
            jax.random.fold_in(key, s), size, shape=(per,), replace=False))
        + s * size
        for s in range(strata)
    ])


def _round_to(t, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / top
    return (t / scale).astype(dtype).astype(t.dtype) * scale


@jax.custom_vjp
def _fp8_inputs(t):
    return _round_to(t, jnp.float8_e4m3fn, 240.0)


_fp8_inputs.defvjp(lambda t: (_fp8_inputs(t), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(t):
    return t


_fp8_cotangent.defvjp(
    lambda t: (t, None),
    lambda _, g: (_round_to(g, jnp.float8_e5m2, 28672.0),))

# The control's precision: what a float8 training path does. Both
# inputs of every forward product are rounded to e4m3 and the cotangent
# that enters both backward products to e5m2, one scale a tensor;
# products accumulate in float32 and everything between them stays
# float32. The nearest precision below the cells' bfloat16.
FP8 = (_fp8_inputs, _fp8_cotangent)


def make_round(arch, lr, server, quant=None):
    """-> jitted ``round(variables, opt, xb, yb, wb)`` with ``xb``
    ``[C, B, H, W, c]``, labels ``[C, B]``, weights ``[C, B]`` (0 marks a
    padded row): ``(new variables, new opt, train loss, pseudo-gradient)``.
    ``server``: ``{"optimizer": "sgd" | "adam", "lr": float}``."""

    def client(variables, x, y, w):
        def loss_fn(params):
            logits, stats = refnet.forward(
                arch, {**variables, "params": params}, x, True, quant)
            logp = jax.nn.log_softmax(logits)
            ce = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
            return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0), (
                stats, jnp.sum(ce * w))

        (_, (stats, loss_sum)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(variables["params"])
        new = {"params": jax.tree.map(
            lambda p, g: p - lr * g, variables["params"], grads)}
        if stats:
            new["batch_stats"] = stats
        return new, loss_sum

    def fed_round(variables, opt, xb, yb, wb):
        with jax.default_matmul_precision("highest"):
            new, loss_sums = jax.lax.map(
                lambda b: client(variables, *b), (xb, yb, wb))
        n_k = jnp.sum(wb, axis=1)
        mean = lambda t: jnp.tensordot(n_k, t, axes=1) / jnp.sum(n_k)
        avg = jax.tree.map(mean, new)
        # what the server optimizer is handed: old - mean(new)
        grad = jax.tree.map(
            lambda g, a: g - a, variables["params"], avg["params"])
        if server["optimizer"] == "sgd":
            params = jax.tree.map(
                lambda p, g: p - server["lr"] * g, variables["params"], grad)
        elif server["optimizer"] == "adam":
            t = opt["count"] + 1
            mu = jax.tree.map(
                lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, opt["mu"], grad)
            nu = jax.tree.map(
                lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                opt["nu"], grad)
            params = jax.tree.map(
                lambda p, m, v: p - server["lr"] * (m / (1 - ADAM_B1 ** t))
                / (jnp.sqrt(v / (1 - ADAM_B2 ** t)) + ADAM_EPS),
                variables["params"], mu, nu)
            opt = {"mu": mu, "nu": nu, "count": t}
        else:
            raise ValueError(server["optimizer"])
        out = {**avg, "params": params}
        return out, opt, jnp.sum(loss_sums) / jnp.sum(n_k), grad

    return jax.jit(fed_round)


def init_opt(variables, server):
    if server["optimizer"] != "adam":
        return {}
    zeros = jax.tree.map(jnp.zeros_like, variables["params"])
    return {"mu": zeros, "nu": zeros, "count": jnp.zeros((), jnp.float32)}


def run_rounds(arch, lr, server, variables, batches, quant=None):
    """Follow ``len(batches)`` rounds. -> host dict ``losses``,
    ``first_grad`` (round 1's pseudo-gradient), ``final`` variables.

    Runs where JAX's default device is (the chip, in a benchmark run),
    after the program's state is freed."""
    step = make_round(arch, lr, server, quant)
    opt = init_opt(variables, server)
    losses, first = [], None
    for xb, yb, wb in batches:
        variables, opt, loss, grad = step(variables, opt, xb, yb, wb)
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(grad)
    return {"losses": losses, "first_grad": first,
            "final": jax.device_get(variables)}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def norm_gap(got, ref):
    """``| |got| - |ref| | / |ref|`` with the whole tree as one vector:
    the fault of scale (an update missed, halved or taken twice). Taken
    leaf by leaf the same gap swings between 0.13 and 0.92 from seed to
    seed on ResNet-56 in bfloat16 (the first blocks' BatchNorm scales
    and biases have gradients that are sums of cancelling terms), so a
    limit on the worst leaf could not tell a sound run from a state
    returned unchanged (1.0); ``calibrate.py`` still reads the worst
    leaf, for the record."""
    g = sum(float(np.sum(np.asarray(x, np.float64) ** 2))
            for x in jax.tree.leaves(got)) ** 0.5
    r = sum(float(np.sum(np.asarray(x, np.float64) ** 2))
            for x in jax.tree.leaves(ref)) ** 0.5
    return abs(g - r) / max(r, 1e-300)


def rel_err(got, ref):
    """``|got - ref| / |ref|`` over the whole tree as one vector: what
    rounding in a lower precision moves first (norms barely feel random
    error, which adds in quadrature; the difference feels all of it)."""
    num = den = 0.0
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        num += float(np.sum((g - r) ** 2))
        den += float(np.sum(r ** 2))
    return (num / max(den, 1e-300)) ** 0.5


def tree_sub(a, b):
    return jax.tree.map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
        a, b)


def numbers(program, reference, initial):
    """The numbers the comparison reads, ``[(name, value, note)]``.

    Forward quantities (the losses, the running statistics, the last
    layer's gradient) are what a lower precision moves steadily; the
    norm gaps are there for faults of scale (a gradient missed or taken
    twice, a state returned unchanged)."""
    out = []
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out.append((f"loss_rel_gap.round{i + 1}", abs(a - b) / abs(b),
                    {"program": a, "reference": b}))
    pg, rg = program["first_grad"], reference["first_grad"]
    out.append(("head_grad_rel_err", rel_err(pg["head"], rg["head"]), {}))
    out.append(("first_grad_norm_gap", norm_gap(pg, rg), {}))
    moved = tree_sub(program["final"], initial)
    moved_ref = tree_sub(reference["final"], initial)
    if "batch_stats" in moved_ref:
        out.append(("stats_change_rel_err", rel_err(
            moved["batch_stats"], moved_ref["batch_stats"]), {}))
    out.append(("change_norm_gap", norm_gap(
        moved["params"], moved_ref["params"]), {}))
    return out


def compare(program, reference, initial, limits):
    """``program`` / ``reference``: ``{"losses", "first_grad", "final"}``
    from the same initial variables. Compares the numbers ``limits``
    names (``loss_rel_gap`` stands for every round's). -> (rows, ok):
    one row ``{"number", "value", "limit", "ok", ...}`` a number; with
    ``limits=None`` every number is read and none is judged
    (``calibrate.py``)."""
    rows = []
    for name, value, note in numbers(program, reference, initial):
        key = name.split(".")[0]
        if limits is None:
            rows.append({"number": name, "value": value, **note})
        elif key in limits:
            rows.append({"number": name, "value": value,
                         "limit": limits[key],
                         "ok": bool(value <= limits[key]), **note})
    return rows, all(r.get("ok", True) for r in rows)
