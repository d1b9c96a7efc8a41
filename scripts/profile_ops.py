"""Amortized per-op microbenches: scan 20 inner iterations per timed call
so the per-call dispatch overhead washes out.

Answers: does XLA dense-expand the grouped conv at s2d widths (cpg=64,
C=10)? What do BN and the dense/residual glue cost?
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parent.parent))

from fedml_tpu.core.anatomy import fetch_corrected_time

INNER = 20


def timeit(fn, *args, n=15, warmup=2):
    # ONE timing path: the shared fetch-corrected loop from the
    # round-anatomy plane, amortized again over the INNER-step scan
    return fetch_corrected_time(fn, *args, n=n, warmup=warmup) / INNER


def conv_flops(B, H, W, k, ci, co):
    return 2 * B * H * W * k * k * ci * co


def bench_conv_grad(B, H, W, cpg, C, k=3, tag=""):
    """Amortized fwd+bwd of one grouped conv: scan INNER gradient steps."""
    ci = cpg * C
    x0 = jnp.ones((B, H, W, ci), jnp.bfloat16) * 0.01
    w0 = jnp.ones((k, k, cpg, ci), jnp.bfloat16) * 0.01

    def one(x, w):
        def loss(x, w):
            y = lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=C,
            )
            return jnp.sum(y.astype(jnp.float32) ** 2)

        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
        return x - 1e-6 * gx.astype(x.dtype), w - 1e-6 * gw.astype(w.dtype)

    @jax.jit
    def run(x, w):
        def body(c, _):
            return one(*c), None
        (x, w), _ = lax.scan(body, (x, w), None, length=INNER)
        return x, w

    t = timeit(run, x0, w0)
    fl = 3 * conv_flops(B, H, W, k, cpg, cpg) * C
    print(f"{tag:28s} t={t*1e3:7.3f} ms useful={fl/t/1e12:6.2f} TF/s "
          f"mfu={fl/t/197e12*100:5.1f}%")
    return t


def bench_fwd_only(B, H, W, cpg, C, k=3, tag=""):
    ci = cpg * C
    x0 = jnp.ones((B, H, W, ci), jnp.bfloat16) * 0.01
    w0 = jnp.ones((k, k, cpg, ci), jnp.bfloat16) * 0.001

    @jax.jit
    def run(x, w):
        def body(x, _):
            y = lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=C,
            )
            return y, None
        x, _ = lax.scan(body, x, None, length=INNER)
        return x

    t = timeit(run, x0, w0)
    fl = conv_flops(B, H, W, k, cpg, cpg) * C
    print(f"{tag:28s} t={t*1e3:7.3f} ms useful={fl/t/1e12:6.2f} TF/s "
          f"mfu={fl/t/197e12*100:5.1f}% bytes~{2*B*H*W*ci*2/1e6:.1f}MB "
          f"bw={(2*B*H*W*ci*2 + k*k*cpg*ci*2)/t/1e9:.0f}GB/s")
    return t


def bench_bn(B, H, W, ch, tag=""):
    import flax.linen as nn

    bn = nn.BatchNorm(use_running_average=False, momentum=0.9)
    x0 = jnp.ones((B, H, W, ch), jnp.bfloat16) * 0.01
    v = bn.init(jax.random.key(0), x0)

    @jax.jit
    def run(x):
        def body(x, _):
            y, _ = bn.apply(v, x, mutable=["batch_stats"])
            return y.astype(x.dtype), None
        x, _ = lax.scan(body, x, None, length=INNER)
        return x

    t = timeit(run, x0)
    by = 2 * B * H * W * ch * 2
    print(f"{tag:28s} t={t*1e3:7.3f} ms bw={by/t/1e9:.0f}GB/s")
    return t


def main():
    print("== does group width change lowering? (fwd, amortized) ==")
    bench_fwd_only(32, 16, 16, 128, 5, tag="grouped 128x5")
    bench_fwd_only(32, 16, 16, 320, 2, tag="grouped 320x2")
    bench_fwd_only(32, 16, 16, 64, 5, tag="grouped 64x5 (320 tot)")
    bench_fwd_only(32, 16, 16, 256, 5, tag="grouped 256x5 (1280 tot)")
    print("== fwd+bwd (amortized) ==")
    bench_conv_grad(32, 16, 16, 128, 5, tag="grouped 128x5")
    bench_conv_grad(32, 16, 16, 320, 2, tag="grouped 320x2")
    bench_conv_grad(32, 16, 16, 64, 10, tag="s2d st1 grouped 64x10")
    bench_conv_grad(32, 16, 16, 640, 1, tag="dense 640")
    print("== BN train-mode (amortized) ==")
    bench_bn(32, 16, 16, 640, tag="BN 16x16x640")
    bench_bn(32, 32, 32, 160, tag="BN 32x32x160")


if __name__ == "__main__":
    main()
