"""Plain float32 residual networks for the benchmark's reference.

Straightforward ``jax.numpy``/``lax``: every convolution written as the
matrix product it is, normalisation written out, no cohort widening,
no kernels, no batching over clients. Every matrix product runs at
precision "highest" (on a TPU a float32 product otherwise multiplies
in bfloat16). Nothing here imports the program.

An architecture is a plain dict (kept in the configuration's reference
file beside its JSON)::

    {"norm": "bn" | "gn", "stem": 16, "classes": 10,
     "stages": [[width, blocks, first_stride], ...]}

Variables are a nested dict in the layout of the program's checkpoints
(``Conv2D_i`` / ``BatchNorm_i`` / ``GroupNorm_i`` / ``BasicBlock_i`` /
``head``), so the same tree is handed to the program as its initial
weights and compared with what it returns, leaf for leaf.

Departures from the published descriptions (He et al. 2016; the
reference fork's ``resnet.py`` / ``resnet_gn.py``), all inherited from
the program so that the two compute the same function: projection
shortcuts are 1x1 convolution + normalisation (the CIFAR paper's
option B); the ResNet-18 stem is one 3x3 stride-1 convolution without
max-pooling (CIFAR-sized inputs); GroupNorm uses 2 groups and epsilon
1e-6, BatchNorm momentum 0.9 and epsilon 1e-5 with the biased batch
variance in the running average; weights are random from the seed
(He-normal kernels, perturbed norm scales and biases), not trained.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_MOMENTUM, BN_EPS, GN_EPS, GN_GROUPS = 0.9, 1e-5, 1e-6, 2


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def conv(x, w, stride, quant=None):
    """2-D convolution, ``SAME`` padding, written as the matrix product
    it is: every output pixel's receptive field laid out as a row
    (shifted slices of the padded input), times the kernel as a
    ``[kh*kw*cin, cout]`` matrix. One float32 ``dot`` at precision
    "highest" a layer (the TPU compiler takes a quarter of an hour over
    the same network written with ``lax.conv`` at that precision).

    ``quant`` (the control only): ``(round_inputs, round_cotangent)`` put
    every product of this layer — forward and both backward ones — on
    inputs rounded to a lower precision."""
    if quant is not None:
        x, w = quant[0](x), quant[0](w)
    kh, kw, cin, cout = w.shape
    b, h, wd, _ = x.shape
    oh, ow = -(-h // stride), -(-wd // stride)
    ph = max((oh - 1) * stride + kh - h, 0)
    pw = max((ow - 1) * stride + kw - wd, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    cols = [
        xp[:, i:i + (oh - 1) * stride + 1:stride,
           j:j + (ow - 1) * stride + 1:stride, :]
        for i in range(kh) for j in range(kw)
    ]
    patches = jnp.concatenate(cols, axis=-1).reshape(b * oh * ow, kh * kw * cin)
    y = jnp.dot(patches, w.reshape(kh * kw * cin, cout), precision=HIGHEST)
    y = y.reshape(b, oh, ow, cout)
    return y if quant is None else quant[1](y)


def dense(x, w, b, quant=None):
    if quant is not None:
        x, w = quant[0](x), quant[0](w)
    y = jnp.dot(x, w, precision=HIGHEST)
    return (y if quant is None else quant[1](y)) + b


def batch_norm(x, p, stats, train):
    """-> (y, new running stats). Train mode normalises by the batch's
    own moments (biased variance, as E[x^2] - E[x]^2) and moves the
    running average by ``1 - BN_MOMENTUM``."""
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.maximum(jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean, 0.0)
        stats = {
            "mean": BN_MOMENTUM * stats["mean"] + (1 - BN_MOMENTUM) * mean,
            "var": BN_MOMENTUM * stats["var"] + (1 - BN_MOMENTUM) * var,
        }
    else:
        mean, var = stats["mean"], stats["var"]
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    return y, stats


def group_norm(x, p):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, GN_GROUPS, c // GN_GROUPS)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.maximum(
        jnp.mean(g * g, axis=(1, 2, 4), keepdims=True) - mean * mean, 0.0
    )
    g = (g - mean) * lax.rsqrt(var + GN_EPS)
    return g.reshape(b, h, w, c) * p["scale"] + p["bias"]


# ---------------------------------------------------------------------------
# the network
# ---------------------------------------------------------------------------


def _blocks(arch):
    """(block index, in width, out width, stride) in network order."""
    out, cin, i = [], arch["stem"], 0
    for width, n, first in arch["stages"]:
        for b in range(n):
            out.append((i, cin, width, first if b == 0 else 1))
            cin, i = width, i + 1
    return out


def _norm_name(arch):
    return "BatchNorm" if arch["norm"] == "bn" else "GroupNorm"


def _block(arch, y, p, old, stride, project, train, quant):
    """One basic block: conv-norm-relu, conv-norm, shortcut, relu.
    -> (y, new running stats of its norms, ``{}`` under GroupNorm)."""
    nn, new = _norm_name(arch), {}

    def norm(z, name):
        if arch["norm"] == "gn":
            return group_norm(z, p[name])
        z, new[name] = batch_norm(z, p[name], old[name], train)
        return z

    z = conv(y, p["Conv2D_0"]["kernel"], stride, quant)
    z = jax.nn.relu(norm(z, f"{nn}_0"))
    z = conv(z, p["Conv2D_1"]["kernel"], 1, quant)
    z = norm(z, f"{nn}_1")
    if project:
        y = conv(y, p["Conv2D_2"]["kernel"], stride, quant)
        y = norm(y, f"{nn}_2")
    return jax.nn.relu(z + y), new


def forward(arch, variables, x, train, quant=None):
    """-> (logits [B, classes], new ``batch_stats`` or ``{}``).

    Blocks of one stage that have the same shapes (all but a stage's
    first) run as one ``lax.scan`` over their stacked weights: the same
    arithmetic in the same order, written once — which keeps the
    compile of a 56-layer network at precision "highest" to minutes."""
    params = variables["params"]
    old = variables.get("batch_stats", {})
    nn, new = _norm_name(arch), {}
    bn = arch["norm"] == "bn"

    y = conv(x, params["Conv2D_0"]["kernel"], 1, quant)
    if bn:
        y, new[f"{nn}_0"] = batch_norm(
            y, params[f"{nn}_0"], old[f"{nn}_0"], train)
    else:
        y = group_norm(y, params[f"{nn}_0"])
    y = jax.nn.relu(y)

    blocks = _blocks(arch)
    i = 0
    while i < len(blocks):
        idx, cin, width, stride = blocks[i]
        key = f"BasicBlock_{idx}"
        project = stride != 1 or cin != width
        # the run of blocks after this one with identical shapes
        j = i + 1
        while (not project and j < len(blocks)
               and blocks[j][1:] == (width, width, 1)):
            j += 1
        if j - i >= 2:
            keys = [f"BasicBlock_{blocks[k][0]}" for k in range(i, j)]
            stack = lambda trees: jax.tree.map(
                lambda *xs: jnp.stack(xs), *trees)
            ps = stack([params[k] for k in keys])
            ss = stack([old.get(k, {}) for k in keys])

            def body(y, p_s):
                return _block(arch, y, p_s[0], p_s[1], 1, False, train,
                              quant)

            y, stats = lax.scan(body, y, (ps, ss))
            if bn:
                for n, k in enumerate(keys):
                    new[k] = jax.tree.map(lambda a: a[n], stats)
            i = j
            continue
        y, stats = _block(arch, y, params[key], old.get(key, {}), stride,
                          project, train, quant)
        if bn:
            new[key] = stats
        i += 1
    y = jnp.mean(y, axis=(1, 2))
    logits = dense(y, params["head"]["kernel"], params["head"]["bias"], quant)
    return logits, new


def init(arch, key):
    """All weights from one key. Call it under ``jax.jit`` so they are
    made on the device in one program."""
    nn = _norm_name(arch)
    counter = iter(range(1 << 20))
    sub = lambda: jax.random.fold_in(key, next(counter))

    def kernel(kh, cin, cout):
        std = (2.0 / (kh * kh * cin)) ** 0.5
        return {"kernel": std * jax.random.normal(sub(), (kh, kh, cin, cout))}

    def affine(c):
        return {
            "scale": 1.0 + 0.1 * jax.random.normal(sub(), (c,)),
            "bias": 0.1 * jax.random.normal(sub(), (c,)),
        }

    def stat(c):
        return {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}

    params = {"Conv2D_0": kernel(3, arch["in_channels"], arch["stem"]),
              f"{nn}_0": affine(arch["stem"])}
    stats = {f"{nn}_0": stat(arch["stem"])}
    for i, cin, width, stride in _blocks(arch):
        p = {"Conv2D_0": kernel(3, cin, width), f"{nn}_0": affine(width),
             "Conv2D_1": kernel(3, width, width), f"{nn}_1": affine(width)}
        s = {f"{nn}_0": stat(width), f"{nn}_1": stat(width)}
        if stride != 1 or cin != width:
            p["Conv2D_2"] = kernel(1, cin, width)
            p[f"{nn}_2"] = affine(width)
            s[f"{nn}_2"] = stat(width)
        params[f"BasicBlock_{i}"], stats[f"BasicBlock_{i}"] = p, s
    last = arch["stages"][-1][0]
    params["head"] = {
        "kernel": (1.0 / last) ** 0.5
        * jax.random.normal(sub(), (last, arch["classes"])),
        "bias": jnp.zeros((arch["classes"],)),
    }
    variables = {"params": params}
    if arch["norm"] == "bn":
        variables["batch_stats"] = stats
    return variables


def step_flops(arch, batch: int, hw: int = 32) -> float:
    """Floating-point operations ONE client optimizer step needs at
    ``batch`` samples of ``hw`` x ``hw`` pixels: forward plus the two
    backward products of every convolution and of the head, two
    operations a multiply-accumulate. Normalisation, ReLU, the loss and
    the SGD update are left out (they are not matrix work), so a share
    of the matrix unit's peak built on this count cannot be flattered
    by them."""
    macs, res = hw * hw * 9 * arch["in_channels"] * arch["stem"], hw
    for _, cin, width, stride in _blocks(arch):
        res //= stride
        macs += res * res * 9 * (cin + width) * width
        if stride != 1 or cin != width:
            macs += res * res * cin * width
    macs += arch["stages"][-1][0] * arch["classes"]
    return 3.0 * 2.0 * macs * batch
