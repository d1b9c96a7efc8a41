"""Cohort-grouped convolution: fast ``vmap`` over per-client kernels.

The FL hot loop vmaps client local SGD over the cohort, so every conv runs
with a *batched kernel* (one kernel per client). XLA's stock lowering for
batched-kernel convolutions on TPU is poor at CIFAR-class shapes — measured
on v5e, a vmapped 3x3/16ch conv fwd+bwd is ~10x slower than the same math
with shared kernels, and the kernel-gradient is the dominant term. The
entire gap is a lowering artifact: reshaping the cohort into *feature
groups* — activations ``[C,B,H,W,ci] -> [B,H,W,C*ci]``, kernels
``[C,kh,kw,ci,co] -> [kh,kw,ci,C*co]`` — turns the batched conv into ONE
grouped ``lax.conv_general_dilated`` with ``feature_group_count=C`` that is
bit-identical to the vmapped form and ~2.6x faster end-to-end through the
backward pass (the grouped kernel-grad tiles the MXU properly).

This module packages that rewrite as a JAX primitive triple, so models keep
ordinary per-example code and ``vmap``/``grad`` compose as usual:

- ``conv_fwd_p`` (y from x,w), ``conv_dx_p`` (dL/dx from dy,w),
  ``conv_dw_p`` (dL/dw from x,dy) — a set closed under transposition, each
  bilinear, mirroring how ``lax.conv`` itself is wired into autodiff.
- Unbatched, each lowers to the stock ``lax`` computation (no regression
  for single-model paths like evaluation or ``entry()``).
- Under ``vmap`` (the cohort axis), each lowers to the grouped form. The
  dx/dw grouped lowerings are derived from the ONE grouped forward by
  ``jax.linear_transpose``, so the three can never drift apart.

Because ``vmap(grad(f))`` applies AD rules before batching rules, the
backward ops that batching sees ARE these primitives — which is exactly why
a plain ``jax.custom_vjp``/``custom_vmap`` wrapper is not enough and a
primitive is required.

:class:`Conv2D` is the drop-in flax module used by the model zoo in place
of ``nn.Conv`` — parameter leaf names ("kernel"/"bias"), shapes, and
initializers match ``nn.Conv``. Module *scope* names differ from an
``nn.Conv``-based tree (flax auto-names by class: ``Conv2D_N`` vs
``Conv_N``), so variable trees are consistent within this zoo but not
with checkpoints written by a pre-Conv2D build.

Reference context: the reference trains clients serially in torch
(``fedml_api/standalone/fedavg/fedavg_api.py:40-81``), so it never meets
this problem; it is created by the TPU-native "whole cohort in one XLA
program" design and solved here at the compiler-lowering level.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.core import ShapedArray
from jax.extend import core as jex_core
from jax.interpreters import ad, batching, mlir

DN = ("NHWC", "HWIO", "NHWC")


def _resolve_padding(
    padding, in_spatial, kernel_spatial, strides, rhs_dilation,
    lhs_dilation=(1, 1),
) -> tuple[tuple[int, int], ...]:
    """Resolve "SAME"/"VALID"/explicit padding to explicit (lo, hi) pairs
    (primitive params must not depend on operand shapes at rule time).
    With input (lhs) dilation, SAME is resolved against the dilated
    extent — transposed convs compute their own explicit pairs instead
    (see :func:`_conv_transpose_pads`)."""
    if isinstance(padding, str):
        pad = padding.upper()
        if pad == "VALID":
            return tuple((0, 0) for _ in in_spatial)
        if pad == "SAME":
            out = []
            for i, k, s, d, ld in zip(
                in_spatial, kernel_spatial, strides, rhs_dilation,
                lhs_dilation,
            ):
                i = (i - 1) * ld + 1
                eff_k = (k - 1) * d + 1
                o = -(-i // s)  # ceil
                total = max((o - 1) * s + eff_k - i, 0)
                out.append((total // 2, total - total // 2))
            return tuple(out)
        raise ValueError(
            f"unknown padding {padding!r} (supported: 'SAME', 'VALID', "
            "int, per-dim ints, or explicit (lo, hi) pairs; nn.Conv's "
            "'CIRCULAR' is not implemented here)"
        )
    # nn.Conv also accepts a single int or a per-dimension sequence of
    # ints; normalize them to (lo, hi) pairs to keep the drop-in contract
    if isinstance(padding, int):
        return tuple((padding, padding) for _ in in_spatial)
    return tuple(
        (int(p), int(p)) if isinstance(p, int) else (int(p[0]), int(p[1]))
        for p in padding
    )


def _conv_transpose_pads(k, s, padding):
    """Explicit (lo, hi) padding of the fractionally-strided conv that
    realizes a transposed conv — same rule as ``lax.conv_transpose``
    (SAME: out = in*s; VALID: out = in*s + max(k-s, 0))."""
    if padding == "SAME":
        pad_len = k + s - 2
        pad_a = k - 1 if s > k - 1 else -(-pad_len // 2)
    elif padding == "VALID":
        pad_len = k + s - 2 + max(k - s, 0)
        pad_a = k - 1
    else:
        raise ValueError(f"unknown transpose padding {padding!r}")
    return (pad_a, pad_len - pad_a)


def _out_spatial(i, pad, k, s, d, ld=1):
    i = (i - 1) * ld + 1
    eff_k = (k - 1) * d + 1
    return (i + pad[0] + pad[1] - eff_k) // s + 1


# ---------------------------------------------------------------------------
# Stock (unbatched) lowerings
# ---------------------------------------------------------------------------


def _lax_fwd(x, w, *, strides, padding, fgc, rhs_dilation,
             lhs_dilation=(1, 1), **_):
    return lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=padding,
        lhs_dilation=lhs_dilation,
        rhs_dilation=rhs_dilation,
        dimension_numbers=DN,
        feature_group_count=fgc,
    )


def _lax_dx(dy, w, *, lhs_shape, **params):
    x_aval = jax.ShapeDtypeStruct(lhs_shape, dy.dtype)
    f = lambda xx: _lax_fwd(xx, w, **params)
    return jax.linear_transpose(f, x_aval)(dy)[0]


def _lax_dw(x, dy, *, rhs_shape, **params):
    w_aval = jax.ShapeDtypeStruct(rhs_shape, dy.dtype)
    f = lambda ww: _lax_fwd(x, ww, **params)
    return jax.linear_transpose(f, w_aval)(dy)[0]


# ---------------------------------------------------------------------------
# Cohort-grouped (batched) lowerings
# ---------------------------------------------------------------------------


def _cohort_fwd(x_b, w_b, *, strides, padding, fgc, rhs_dilation,
                lhs_dilation=(1, 1), **_):
    """Batched-over-(x, w) conv as ONE grouped conv: clients become channel
    groups. Bit-identical to ``vmap(conv)`` — group c of the grouped conv
    sees exactly client c's channels and kernel."""
    C, B, H, W, ci = x_b.shape
    _, kh, kw, cig, co = w_b.shape
    xg = x_b.transpose(1, 2, 3, 0, 4).reshape(B, H, W, C * ci)
    wg = w_b.transpose(1, 2, 3, 0, 4).reshape(kh, kw, cig, C * co)
    yg = _lax_fwd(
        xg, wg, strides=strides, padding=padding, fgc=C * fgc,
        rhs_dilation=rhs_dilation, lhs_dilation=lhs_dilation,
    )
    Ho, Wo = yg.shape[1], yg.shape[2]
    return yg.reshape(B, Ho, Wo, C, co).transpose(3, 0, 1, 2, 4)


def _lift(operand, bdim, size):
    """Bring the batch dim to axis 0, broadcasting unbatched operands —
    every batching rule then only handles the both-batched case."""
    if bdim is None:
        return jnp.broadcast_to(operand[None], (size,) + operand.shape)
    return jnp.moveaxis(operand, bdim, 0)


def _batch_size(args, dims):
    for a, d in zip(args, dims):
        if d is not None:
            return a.shape[d]
    raise AssertionError("no batched operand")


def _fwd_batch(args, dims, **params):
    x, w = args
    xd, wd = dims
    if wd is None:
        # kernels shared: fold the extra axis into the conv batch (strictly
        # better than the grouped form — no kernel replication)
        xb = jnp.moveaxis(x, xd, 0)
        C, B = xb.shape[0], xb.shape[1]
        y = _lax_fwd(xb.reshape((C * B,) + xb.shape[2:]), w, **params)
        return y.reshape((C, B) + y.shape[1:]), 0
    size = _batch_size(args, dims)
    xb = _lift(x, xd, size)
    wb = _lift(w, wd, size)
    return _cohort_fwd(xb, wb, **params), 0


def _dx_batch(args, dims, *, lhs_shape, **params):
    dy, w = args
    size = _batch_size(args, dims)
    dyb = _lift(dy, dims[0], size)
    wb = _lift(w, dims[1], size)
    x_aval = jax.ShapeDtypeStruct((size,) + tuple(lhs_shape), dyb.dtype)
    f = lambda xx: _cohort_fwd(xx, wb, **params)
    return jax.linear_transpose(f, x_aval)(dyb)[0], 0


def _dw_batch(args, dims, *, rhs_shape, lhs_shape, **params):
    x, dy = args
    size = _batch_size(args, dims)
    xb = _lift(x, dims[0], size)
    dyb = _lift(dy, dims[1], size)
    w_aval = jax.ShapeDtypeStruct((size,) + tuple(rhs_shape), dyb.dtype)
    f = lambda ww: _cohort_fwd(xb, ww, **params)
    return jax.linear_transpose(f, w_aval)(dyb)[0], 0


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def _make(name, impl, batch_rule, abstract):
    p = jex_core.Primitive(name)
    p.def_impl(impl)
    p.def_abstract_eval(abstract)
    mlir.register_lowering(p, mlir.lower_fun(impl, multiple_results=False))
    batching.primitive_batchers[p] = batch_rule
    return p


def _fwd_abstract(x, w, *, strides, padding, rhs_dilation, rhs_shape,
                  lhs_dilation=(1, 1), **_):
    spatial = tuple(
        _out_spatial(i, p, k, s, d, ld)
        for i, p, k, s, d, ld in zip(
            x.shape[1:3], padding, rhs_shape[:2], strides, rhs_dilation,
            lhs_dilation,
        )
    )
    return ShapedArray(
        (x.shape[0],) + spatial + (rhs_shape[-1],), x.dtype
    )


def _dx_abstract(dy, w, *, lhs_shape, **_):
    return ShapedArray(tuple(lhs_shape), dy.dtype)


def _dw_abstract(x, dy, *, rhs_shape, **_):
    return ShapedArray(tuple(rhs_shape), dy.dtype)


conv_fwd_p = _make("cohort_conv_fwd", _lax_fwd, _fwd_batch, _fwd_abstract)
conv_dx_p = _make("cohort_conv_dx", _lax_dx, _dx_batch, _dx_abstract)
conv_dw_p = _make("cohort_conv_dw", _lax_dw, _dw_batch, _dw_abstract)

# Bilinear AD wiring, mirroring lax.conv: jvp reuses the same primitive on
# tangents; transposes map within the closed {fwd, dx, dw} set, so every
# op the backward pass emits still carries the cohort batching rules.
ad.defbilinear(
    conv_fwd_p,
    lambda ct, x, w, **kw: conv_dx_p.bind(ct, w, **kw),
    lambda ct, x, w, **kw: conv_dw_p.bind(x, ct, **kw),
)
ad.defbilinear(
    conv_dx_p,
    lambda ct, dy, w, **kw: conv_fwd_p.bind(ct, w, **kw),
    lambda ct, dy, w, **kw: conv_dw_p.bind(ct, dy, **kw),
)
ad.defbilinear(
    conv_dw_p,
    lambda ct, x, dy, **kw: conv_dx_p.bind(dy, ct, **kw),
    lambda ct, x, dy, **kw: conv_fwd_p.bind(x, ct, **kw),
)


def cohort_conv(
    x: jax.Array,
    kernel: jax.Array,
    strides: Sequence[int] = (1, 1),
    padding: Any = "SAME",
    feature_group_count: int = 1,
    rhs_dilation: Sequence[int] = (1, 1),
    lhs_dilation: Sequence[int] = (1, 1),
) -> jax.Array:
    """2-D convolution (NHWC x HWIO -> NHWC) with cohort-aware batching.

    Semantically identical to ``lax.conv_general_dilated``; under ``vmap``
    over both operands it lowers to a single grouped convolution.
    ``lhs_dilation`` gives the fractionally-strided form used by
    transposed convolutions (:class:`ConvTranspose2D`).
    """
    strides = tuple(int(s) for s in strides)
    rhs_dilation = tuple(int(d) for d in rhs_dilation)
    lhs_dilation = tuple(int(d) for d in lhs_dilation)
    pad = _resolve_padding(
        padding, x.shape[1:3], kernel.shape[:2], strides, rhs_dilation,
        lhs_dilation,
    )
    if x.dtype != kernel.dtype:
        ct = jnp.promote_types(x.dtype, kernel.dtype)
        x, kernel = x.astype(ct), kernel.astype(ct)
    return conv_fwd_p.bind(
        x,
        kernel,
        strides=strides,
        padding=pad,
        fgc=int(feature_group_count),
        rhs_dilation=rhs_dilation,
        lhs_dilation=lhs_dilation,
        lhs_shape=tuple(x.shape),
        rhs_shape=tuple(kernel.shape),
    )


# ---------------------------------------------------------------------------
# Drop-in flax module
# ---------------------------------------------------------------------------

import flax.linen as nn  # noqa: E402  (after primitive setup)


class Conv2D(nn.Module):
    """Drop-in for the zoo's uses of ``nn.Conv`` (2-D, NHWC), backed by
    :func:`cohort_conv`. Parameter names ("kernel", "bias"), shapes, and
    initializers match ``nn.Conv``, so variable trees are interchangeable.
    """

    features: int
    kernel_size: Sequence[int]
    strides: Sequence[int] = (1, 1)
    padding: Any = "SAME"
    use_bias: bool = True
    feature_group_count: int = 1
    # same keyword as nn.Conv; an int applies to both spatial dims
    kernel_dilation: Any = (1, 1)
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        cin = x.shape[-1]
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (kh, kw, cin // self.feature_group_count, self.features),
        )
        if x.dtype != kernel.dtype:
            # mixed precision: follow the activation dtype (bf16 compute
            # casts params at the loss_fn boundary; this is belt-and-braces
            # for direct eval calls)
            kernel = kernel.astype(jnp.promote_types(x.dtype, kernel.dtype))
            x = x.astype(kernel.dtype)
        kd = self.kernel_dilation
        if kd is None:  # nn.Conv also treats None as no dilation
            kd = (1, 1)
        elif isinstance(kd, int):
            kd = (kd, kd)
        y = cohort_conv(
            x,
            kernel,
            strides=self.strides,
            padding=self.padding,
            feature_group_count=self.feature_group_count,
            rhs_dilation=kd,
        )
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,))
            y = y + bias.astype(y.dtype)
        return y


class ConvTranspose2D(nn.Module):
    """Drop-in for the zoo's uses of ``nn.ConvTranspose`` (2-D, NHWC),
    backed by :func:`cohort_conv` in fractionally-strided form
    (``lhs_dilation = strides``, explicit transpose padding, unit window
    strides — the same realization ``lax.conv_transpose`` uses, kernel
    unflipped). Parameter names, shapes, and initializers match
    ``nn.ConvTranspose``, so generators vmapped over per-client params
    get the grouped cohort lowering for free."""

    features: int
    kernel_size: Sequence[int]
    strides: Sequence[int] = (1, 1)
    padding: str = "SAME"
    use_bias: bool = True
    # cohort-grouped form (models.cohort / the gan cohort pyramid):
    # channel group c is client c, kernel cin is per-group
    feature_group_count: int = 1
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        cin = x.shape[-1]
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (kh, kw, cin // self.feature_group_count, self.features),
        )
        if x.dtype != kernel.dtype:
            kernel = kernel.astype(jnp.promote_types(x.dtype, kernel.dtype))
            x = x.astype(kernel.dtype)
        pads = tuple(
            _conv_transpose_pads(k, s, self.padding)
            for k, s in zip((kh, kw), self.strides)
        )
        y = cohort_conv(
            x,
            kernel,
            strides=(1, 1),
            padding=pads,
            feature_group_count=self.feature_group_count,
            lhs_dilation=self.strides,
        )
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,))
            y = y + bias.astype(y.dtype)
        return y
