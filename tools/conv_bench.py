"""Conv-lowering A/B microbench at ResNet-50 b128 shapes (bf16).

Measures TF/s for each lowering strategy at each shape class:
device-resident inputs, reps chained inside one jit via lax.scan with
non-foldable scalar coupling (defeats CSE/hoisting), sync by host
materialization, and rates taken from the SLOPE between two rep counts, so
the per-call cost (~0.2 ms dispatch, ~3 ms host scalar on the v5e, PR 21)
cancels out.

Strategies:
  xla       - jax.lax.conv_general_dilated NCHW (the default lowering)
  xla_nhwc  - same, NHWC operands
  dot       - 1x1 conv as dot_general over channels (NCHW)
  dot_nhwc  - 1x1 conv as [NHW,C]@[C,O] (NHWC; the pure-matmul form)
  shift9    - KxK conv as sum of K*K channel dots on shifted slices
  pallas    - implicit-GEMM Pallas kernel (NHWC)

Usage: python tools/conv_bench.py [--quick] [--only SUBSTR]
"""

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

N_LO, N_HI = 64, 512
ROUNDS = 4


def _sync(x):
    return float(jnp.sum(x.astype(jnp.float32)))


def _time(fn, x):
    _sync(fn(x))  # warm compile + queue drain
    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        out = fn(x)
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _chain(conv, x0, w, n):
    def body(acc, _):
        # 1e-30*acc is not foldable (acc unknown at compile time) so the
        # conv stays in the loop; jnp.mean consumes every output element
        # so none of the conv can be dead-code-eliminated.
        x = (x0 * (1.0 + 1e-30 * acc)).astype(x0.dtype)
        y = conv(x, w)
        return acc + jnp.mean(y.astype(jnp.float32)), None

    acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None, length=n)
    return acc


def _rate(conv, x, w, flops_per_rep):
    f_lo = jax.jit(lambda xx: _chain(conv, xx, w, N_LO))
    f_hi = jax.jit(lambda xx: _chain(conv, xx, w, N_HI))
    dt_lo = _time(f_lo, x)
    dt_hi = _time(f_hi, x)
    per_rep = (dt_hi - dt_lo) / (N_HI - N_LO)
    return per_rep, flops_per_rep / max(per_rep, 1e-9)


def conv_xla(x, w, stride):
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(w.shape[2] // 2, w.shape[2] // 2)] * 2,
        dimension_numbers=dn)


def conv_xla_nhwc(x, w, stride):
    # x [N,H,W,C], w [kh,kw,I,O]
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(w.shape[0] // 2, w.shape[0] // 2)] * 2,
        dimension_numbers=dn)


def conv_dot1x1(x, w, stride):
    if stride > 1:
        x = x[:, :, ::stride, ::stride]
    out = jax.lax.dot_general(w[:, :, 0, 0], x, (((1,), (1,)), ((), ())))
    return jnp.transpose(out, (1, 0, 2, 3))


def conv_dot1x1_nhwc(x, w, stride):
    # x [N,H,W,C], w [1,1,I,O] -> pure matmul on the trailing dim
    if stride > 1:
        x = x[:, ::stride, ::stride, :]
    return x @ w[0, 0]


def conv_shift9(x, w, stride):
    k = w.shape[2]
    p = k // 2
    xp = jnp.pad(x, ((0, 0), (0, 0), (p, p), (p, p)))
    H, W = x.shape[2], x.shape[3]
    out = None
    for dy in range(k):
        for dx in range(k):
            xs = xp[:, :, dy:dy + H:stride, dx:dx + W:stride]
            t = jax.lax.dot_general(w[:, :, dy, dx], xs, (((1,), (1,)), ((), ())))
            out = t if out is None else out + t
    return jnp.transpose(out, (1, 0, 2, 3))


def conv_pallas(x, w, stride):
    from paddlepaddle_tpu.ops.kernels.conv_gemm import conv2d_gemm_nhwc

    return conv2d_gemm_nhwc(x, w, stride=stride)


SHAPES = [
    # (name, Cin, Cout, k, stride, H=W)
    ("s1_3x3", 64, 64, 3, 1, 56),
    ("s2_3x3", 128, 128, 3, 1, 28),
    ("s3_3x3", 256, 256, 3, 1, 14),
    ("s4_3x3", 512, 512, 3, 1, 7),
    ("s2_3x3_ds", 128, 128, 3, 2, 56),
    ("s1_1x1_exp", 64, 256, 1, 1, 56),
    ("s3_1x1_red", 1024, 256, 1, 1, 14),
    ("s4_1x1_exp", 512, 2048, 1, 1, 7),
    ("stem_7x7", 3, 64, 7, 2, 224),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    N = args.batch
    shapes = SHAPES[:4] if args.quick else SHAPES
    if args.only:
        shapes = [s for s in shapes if args.only in s[0]]
    rng = np.random.default_rng(0)
    print(f"{'shape':<14}{'strategy':<10}{'ms/rep':>8}{'TF/s':>8}")
    for name, cin, cout, k, s, hw in shapes:
        x_nchw = jnp.asarray(rng.standard_normal((N, cin, hw, hw)), jnp.bfloat16)
        x_nhwc = jnp.transpose(x_nchw, (0, 2, 3, 1))
        w_oihw = jnp.asarray(rng.standard_normal((cout, cin, k, k)) * 0.05, jnp.bfloat16)
        w_hwio = jnp.transpose(w_oihw, (2, 3, 1, 0))
        ho = (hw + s - 1) // s
        flops = 2 * N * ho * ho * cout * cin * k * k
        configs = [("xla", conv_xla, x_nchw, w_oihw),
                   ("xla_nhwc", conv_xla_nhwc, x_nhwc, w_hwio)]
        if k == 1:
            configs += [("dot", conv_dot1x1, x_nchw, w_oihw),
                        ("dot_nhwc", conv_dot1x1_nhwc, x_nhwc, w_hwio)]
        elif k == 3:
            configs.append(("shift9", conv_shift9, x_nchw, w_oihw))
            try:
                from paddlepaddle_tpu.ops.kernels.conv_gemm import conv2d_gemm_nhwc  # noqa
                configs.append(("pallas", conv_pallas, x_nhwc, w_hwio))
            except ImportError:
                pass
        for sname, fn, xx, ww in configs:
            conv = functools.partial(fn, stride=s)
            try:
                per_rep, rate = _rate(conv, xx, ww, flops)
            except Exception as e:
                print(f"{name:<14}{sname:<10}{'ERR':>8} {type(e).__name__}: {str(e)[:70]}")
                continue
            print(f"{name:<14}{sname:<10}{per_rep*1e3:>8.3f}{rate/1e12:>8.1f}", flush=True)


if __name__ == "__main__":
    main()
