"""EXACT space-to-depth execution layout for the standard CIFAR ResNet.

``resnet56_s2d`` (models/vision.py) is a different parameterization —
fast, but not weight-compatible with reference checkpoints. This module
is the missing parity bridge: the SAME function as the standard
``resnet56``, re-laid-out so stage 1 (the TPU-hostile 16-channel 32x32
stage) runs in space-to-depth space with 4x wider channels, computed
from a standard checkpoint by a pure weight transformation.

The embedding (classic TPU trick, e.g. the ResNet-50 s2d stem; derived
independently here for the CIFAR stage-1 case):

- input [B, 32, 32, c] -> s2d -> [B, 16, 16, 4c], channel order
  (phase-major): (u, v, ci) for phase (u, v) in {0,1}^2.
- a 3x3 stride-1 conv on the original grid equals a 3x3 conv on the s2d
  grid with kernel K'[di, dj, (u,v,ci), (a,b,co)]: output pixel
  (2i+a, 2j+b) reads original pixel (2i+a+s, 2j+b+t), which lives at s2d
  offset di = floor((a+s)/2) phase u = (a+s) mod 2 — each original tap
  (s, t) scatters to exactly one (di, u, dj, v) slot, so K' is 25% dense
  (the 4x FLOP inflation is the price of 4x wider, MXU-tileable
  channels).
- stage-1 BatchNorm needs PHASE-POOLED statistics: original per-channel
  moments pool over all spatial positions == over all 4 phases of the
  s2d layout (:class:`PhasePooledBatchNorm`); scale/bias/running stats
  replicate 4x on conversion, so eval-mode normalization is exactly the
  original affine.
- the stage-2 entry (3x3 stride-2 conv + 1x1 stride-2 shortcut) maps to
  a 2x2 (resp. 1x1) conv on the s2d grid that also RETURNS to the
  natural layout — stages 2-3 and the head then run the ORIGINAL
  weights unchanged.

``convert_resnet_checkpoint_to_s2d(variables, depth)`` maps a standard
``ResNetCIFAR`` variables tree to :class:`ResNetCIFARS2DExact`'s tree;
outputs match to f32 round-off in both eval and train mode
(tests/test_models.py::test_s2d_exact_*). Reference context: checkpoints
trained with ``fedml_api/model/cv/resnet.py`` port through
``models/gkt.py``'s torch mapping into ``resnet56`` and from there
through this converter into the TPU layout.

Measured on v5e: ~1.2x faster than the standard layout for SINGLE-model
training/eval (stage-1 channels 4x wider); in the cohort-grouped
federated round it is a wash (~49 vs 48 ms headline) — the grouped
convs dense-expand either way, so the 4x stage-1 FLOP inflation cancels
the width win. Use it for parity-preserving single-model work
(centralized training, evaluation, GKT-style warm starts).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models.cohort import dense as _cohort_dense
from fedml_tpu.ops.cohort_conv import Conv2D


def s2d_rearrange(x: jax.Array, cohort: int = 1) -> jax.Array:
    """[B, H, W, C*c] -> [B, H/2, W/2, C*4c]: per-client channel blocks
    stay outermost (client-major), phases phase-major (u, v, ci) within
    each client — the layout the converted kernels expect."""
    b, h, w, cc = x.shape
    c = cc // cohort
    x = x.reshape(b, h // 2, 2, w // 2, 2, cohort, c)
    return x.transpose(0, 1, 3, 5, 2, 4, 6).reshape(
        b, h // 2, w // 2, cohort * 4 * c
    )


def convert_conv3x3_to_s2d(w: np.ndarray) -> np.ndarray:
    """[3, 3, ci, co] stride-1 SAME -> [3, 3, 4ci, 4co] on the s2d grid
    (exact; 25% dense)."""
    w = np.asarray(w)
    _, _, ci, co = w.shape
    out = np.zeros((3, 3, 4 * ci, 4 * co), w.dtype)
    for a in (0, 1):
        for b in (0, 1):
            for s in (-1, 0, 1):
                for t in (-1, 0, 1):
                    di, u = divmod(a + s, 2)
                    dj, v = divmod(b + t, 2)
                    out[
                        di + 1, dj + 1,
                        (2 * u + v) * ci:(2 * u + v + 1) * ci,
                        (2 * a + b) * co:(2 * a + b + 1) * co,
                    ] = w[s + 1, t + 1]
    return out


def convert_conv3x3_stride2_to_s2d(w: np.ndarray) -> np.ndarray:
    """[3, 3, ci, co] stride-2 SAME (32->16) -> [2, 2, 4ci, co] on the
    s2d grid, stride 1, output in the NATURAL (non-s2d) layout.

    XLA's SAME padding for kernel 3 stride 2 on even extent pads only at
    the high edge, so output pixel i reads original pixels 2i..2i+2:
    offset s in {0, 1, 2} -> s2d offset di = s // 2, phase u = s % 2."""
    w = np.asarray(w)
    _, _, ci, co = w.shape
    out = np.zeros((2, 2, 4 * ci, co), w.dtype)
    for s in (0, 1, 2):
        for t in (0, 1, 2):
            di, u = divmod(s, 2)
            dj, v = divmod(t, 2)
            out[di, dj, (2 * u + v) * ci:(2 * u + v + 1) * ci] += w[s, t]
    return out


def convert_conv1x1_stride2_to_s2d(w: np.ndarray) -> np.ndarray:
    """[1, 1, ci, co] stride-2 -> [1, 1, 4ci, co] stride-1 on the s2d
    grid (only phase (0, 0) contributes)."""
    w = np.asarray(w)
    _, _, ci, co = w.shape
    out = np.zeros((1, 1, 4 * ci, co), w.dtype)
    out[0, 0, :ci] = w[0, 0]
    return out


class PhasePooledBatchNorm(nn.Module):
    """BatchNorm whose batch statistics pool the ``phases`` s2d phase
    groups of each original channel — exactly the original per-channel
    moments. Parameters/stats are stored at the widened size (phase-
    replicated on conversion) so eval mode is a plain affine. With
    ``cohort`` > 1 channels are client-major blocks of ``phases * c``
    and stats pool phases WITHIN each client (per-client batch norm, as
    the cohort-grouped layout requires)."""

    phases: int = 4
    cohort: int = 1
    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        cw = x.shape[-1]  # cohort * phases * c
        c = cw // (self.phases * self.cohort)
        scale = self.param("scale", nn.initializers.ones, (cw,))
        bias = self.param("bias", nn.initializers.zeros, (cw,))
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((cw,))
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((cw,))
        )
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xs = x.reshape(
                x.shape[:-1] + (self.cohort, self.phases, c)
            )
            red = tuple(range(xs.ndim - 3)) + (xs.ndim - 2,)
            mean_c = jnp.mean(xs.astype(jnp.float32), axis=red)
            var_c = jnp.mean(
                jnp.square(xs.astype(jnp.float32)), axis=red
            ) - jnp.square(mean_c)  # [cohort, c]
            rep = lambda m: jnp.broadcast_to(
                m[:, None, :], (self.cohort, self.phases, c)
            ).reshape(cw)
            mean, var = rep(mean_c), rep(var_c)
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value
                    + (1.0 - self.momentum) * mean
                )
                ra_var.value = (
                    self.momentum * ra_var.value
                    + (1.0 - self.momentum) * var
                )
        y = (x - mean.astype(x.dtype)) * jax.lax.rsqrt(
            var.astype(x.dtype) + jnp.asarray(self.epsilon, x.dtype)
        )
        return y * scale.astype(x.dtype) + bias.astype(x.dtype)


def _bn(train: bool, phases: int | None, cohort: int = 1):
    if phases:
        return PhasePooledBatchNorm(
            phases=phases, cohort=cohort, use_running_average=not train
        )
    return nn.BatchNorm(use_running_average=not train, momentum=0.9)


class _S2DBasicBlock(nn.Module):
    """Stage-1 basic block in s2d space (channels constant, stride 1)."""

    widened: int  # 4 * original channels (per client)
    cohort: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        co = self.cohort
        residual = x
        y = Conv2D(self.widened * co, (3, 3), padding="SAME",
                   use_bias=False, feature_group_count=co)(x)
        y = _bn(train, 4, co)(y)
        y = nn.relu(y)
        y = Conv2D(self.widened * co, (3, 3), padding="SAME",
                   use_bias=False, feature_group_count=co)(y)
        y = _bn(train, 4, co)(y)
        return nn.relu(y + residual)


class _TransitionBlock(nn.Module):
    """The stage-2 entry block: consumes s2d stage-1 output, produces
    the natural-layout stage-2 activation (conv kernels are the
    converted stride-2 forms; see module docstring)."""

    channels: int
    cohort: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        co = self.cohort
        # converted 3x3-stride2 kernel: 2x2 VALID after a (0,1) pad on
        # the s2d grid (original SAME pads only the high edge)
        xp = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
        y = Conv2D(self.channels * co, (2, 2), padding="VALID",
                   use_bias=False, feature_group_count=co)(xp)
        y = _bn(train, None)(y)
        y = nn.relu(y)
        y = Conv2D(self.channels * co, (3, 3), padding="SAME",
                   use_bias=False, feature_group_count=co)(y)
        y = _bn(train, None)(y)
        residual = Conv2D(self.channels * co, (1, 1), padding="VALID",
                          use_bias=False, feature_group_count=co)(x)
        residual = _bn(train, None)(residual)
        return nn.relu(y + residual)


class _BasicBlock(nn.Module):
    """Standard basic block (stages 2-3 past the transition)."""

    channels: int
    stride: int = 1
    cohort: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        co = self.cohort
        residual = x
        y = Conv2D(self.channels * co, (3, 3),
                   (self.stride, self.stride), padding="SAME",
                   use_bias=False, feature_group_count=co)(x)
        y = _bn(train, None)(y)
        y = nn.relu(y)
        y = Conv2D(self.channels * co, (3, 3), padding="SAME",
                   use_bias=False, feature_group_count=co)(y)
        y = _bn(train, None)(y)
        if residual.shape != y.shape:
            residual = Conv2D(self.channels * co, (1, 1),
                              (self.stride, self.stride),
                              use_bias=False, feature_group_count=co)(x)
            residual = _bn(train, None)(residual)
        return nn.relu(y + residual)


class ResNetCIFARS2DExact(nn.Module):
    """The standard CIFAR ResNet, stage 1 executed in s2d space.

    Same function as ``ResNetCIFAR(depth, norm="bn")`` under the weight
    conversion below; a different (TPU-friendlier) execution layout."""

    depth: int = 56
    num_classes: int = 10
    width: int = 16
    # cohort > 1 = cohort-grouped mode (see fedml_tpu.models.cohort)
    cohort: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        n = (self.depth - 2) // 6
        w = self.width
        co = self.cohort
        x = s2d_rearrange(x, co)  # [B,16,16,C*4c_in]
        # stem conv (3x3 stride 1) in s2d space
        x = Conv2D(4 * w * co, (3, 3), padding="SAME", use_bias=False,
                   feature_group_count=co)(x)
        x = _bn(train, 4, co)(x)
        x = nn.relu(x)
        for _ in range(n):
            x = _S2DBasicBlock(4 * w, co)(x, train)
        x = _TransitionBlock(2 * w, co)(x, train)
        for _ in range(n - 1):
            x = _BasicBlock(2 * w, cohort=co)(x, train)
        for blk in range(n):
            x = _BasicBlock(4 * w, 2 if blk == 0 else 1, co)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        y = _cohort_dense(self.num_classes, co, "head")(x)
        return y.transpose(1, 0, 2) if co > 1 else y


def _tile4(v):
    return np.tile(np.asarray(v), 4)


def _bn_scopes(src_p, src_s, scope, pooled):
    p = {k: np.asarray(v) for k, v in src_p[scope].items()}
    s = {k: np.asarray(v) for k, v in src_s[scope].items()}
    if pooled:
        p = {k: _tile4(v) for k, v in p.items()}
        s = {k: _tile4(v) for k, v in s.items()}
    return (
        {k: jnp.asarray(v) for k, v in p.items()},
        {k: jnp.asarray(v) for k, v in s.items()},
    )


def convert_resnet_checkpoint_to_s2d(variables: dict,
                                     depth: int = 56) -> dict:
    """Standard ``ResNetCIFAR(depth, norm='bn')`` variables ->
    :class:`ResNetCIFARS2DExact` variables (exact; see module
    docstring). Scope mapping (both modules are @nn.compact, so flax
    auto-names follow call order deterministically):

    - stem ``Conv2D_0``/``BatchNorm_0`` -> s2d-converted stem
      (phase-pooled BN);
    - ``BasicBlock_0..n-1`` (stage 1) -> ``_S2DBasicBlock_i``;
    - ``BasicBlock_n`` (stage-2 entry, has shortcut) ->
      ``_TransitionBlock_0`` with stride-2 kernel conversions;
    - remaining blocks and the head copy through unchanged."""
    n = (depth - 2) // 6
    src_p = variables["params"]
    src_s = variables.get("batch_stats", {})
    out_p: dict = {}
    out_s: dict = {}

    # stem
    out_p["Conv2D_0"] = {
        "kernel": jnp.asarray(
            convert_conv3x3_to_s2d(src_p["Conv2D_0"]["kernel"])
        )
    }
    p, s = _bn_scopes(src_p, src_s, "BatchNorm_0", pooled=True)
    out_p["PhasePooledBatchNorm_0"] = p
    out_s["PhasePooledBatchNorm_0"] = s

    # stage 1: BasicBlock_0..n-1 -> _S2DBasicBlock_i
    for i in range(n):
        sb = src_p[f"BasicBlock_{i}"]
        ss = src_s[f"BasicBlock_{i}"]
        dst_p: dict = {}
        dst_s: dict = {}
        for j in (0, 1):
            dst_p[f"Conv2D_{j}"] = {
                "kernel": jnp.asarray(
                    convert_conv3x3_to_s2d(sb[f"Conv2D_{j}"]["kernel"])
                )
            }
            bp = {k: jnp.asarray(_tile4(v))
                  for k, v in sb[f"BatchNorm_{j}"].items()}
            bs = {k: jnp.asarray(_tile4(v))
                  for k, v in ss[f"BatchNorm_{j}"].items()}
            dst_p[f"PhasePooledBatchNorm_{j}"] = bp
            dst_s[f"PhasePooledBatchNorm_{j}"] = bs
        out_p[f"_S2DBasicBlock_{i}"] = dst_p
        out_s[f"_S2DBasicBlock_{i}"] = dst_s

    # stage-2 entry block -> transition
    sb = src_p[f"BasicBlock_{n}"]
    ss = src_s[f"BasicBlock_{n}"]
    out_p["_TransitionBlock_0"] = {
        "Conv2D_0": {
            "kernel": jnp.asarray(
                convert_conv3x3_stride2_to_s2d(sb["Conv2D_0"]["kernel"])
            )
        },
        "BatchNorm_0": {k: jnp.asarray(v)
                        for k, v in sb["BatchNorm_0"].items()},
        "Conv2D_1": {"kernel": jnp.asarray(sb["Conv2D_1"]["kernel"])},
        "BatchNorm_1": {k: jnp.asarray(v)
                        for k, v in sb["BatchNorm_1"].items()},
        "Conv2D_2": {
            "kernel": jnp.asarray(
                convert_conv1x1_stride2_to_s2d(sb["Conv2D_2"]["kernel"])
            )
        },
        "BatchNorm_2": {k: jnp.asarray(v)
                        for k, v in sb["BatchNorm_2"].items()},
    }
    out_s["_TransitionBlock_0"] = {
        k: {kk: jnp.asarray(vv) for kk, vv in v.items()}
        for k, v in ss.items()
    }

    # remaining blocks copy verbatim: BasicBlock_{n+1}.. -> _BasicBlock_i
    rest = [f"BasicBlock_{i}" for i in range(n + 1, 3 * n)]
    for i, scope in enumerate(rest):
        out_p[f"_BasicBlock_{i}"] = jax.tree.map(
            jnp.asarray, src_p[scope]
        )
        out_s[f"_BasicBlock_{i}"] = jax.tree.map(
            jnp.asarray, src_s[scope]
        )

    out_p["head"] = jax.tree.map(jnp.asarray, src_p["head"])
    return {"params": out_p, "batch_stats": out_s}
