"""ResNet-50 b128 bf16: NCHW vs NHWC END-TO-END train step A/B.

The segment budget (resnet_segments.py) shows the step is HBM-bound and
the high-resolution stages dominate; per-conv micro A/Bs drown in
run-to-run noise. This times the whole train step (slope over scan length, host
readback sync) with every Conv/BN/Pool layer flipped to channels-last,
which changes the layouts XLA sees end-to-end.

Usage: python tools/resnet_nhwc_ab.py [--batch 128]
"""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K_LO, K_HI = 2, 8
ROUNDS = 5


def _sync(x):
    leaves = jax.tree_util.tree_leaves(x)
    return float(jnp.sum(leaves[0].astype(jnp.float32)))


def _time(fn, *args):
    _sync(fn(*args))
    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        _sync(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _slope(make_fn, *args):
    f_lo, f_hi = jax.jit(make_fn(K_LO)), jax.jit(make_fn(K_HI))
    dt_lo = _time(f_lo, *args)
    dt_hi = _time(f_hi, *args)
    return (dt_hi - dt_lo) / (K_HI - K_LO)


def to_nhwc(model):
    """Flip every layout-carrying layer of the module tree to NHWC."""
    from paddlepaddle_tpu.nn import (AdaptiveAvgPool2D, AvgPool2D,
                                     BatchNorm2D, Conv2D, MaxPool2D)

    for m in model.sublayers(include_self=True):
        if isinstance(m, Conv2D):
            m._data_format = "NHWC"
        elif isinstance(m, BatchNorm2D):
            m._data_format = "NHWC"
        elif isinstance(m, (MaxPool2D, AvgPool2D)):
            args = list(m.args)
            args[-1] = "NHWC"
            m.args = tuple(args)
        elif isinstance(m, AdaptiveAvgPool2D):
            m.data_format = "NHWC"
    return model


def build(batch, nhwc):
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.models.resnet import resnet50
    from paddlepaddle_tpu.nn.functional import cross_entropy
    from paddlepaddle_tpu.optimizer import Momentum

    model = resnet50(num_classes=1000)
    if nhwc:
        to_nhwc(model)
    model.to(dtype="bfloat16")
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters())
    ts = TrainStep(model, opt,
                   lambda m, x, y: cross_entropy(m(x), y).mean())
    rng = np.random.default_rng(0)
    shape = (batch, 224, 224, 3) if nhwc else (batch, 3, 224, 224)
    imgs = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)).astype(np.int64))
    return ts, (imgs, labels)


def measure(batch, nhwc):
    ts, batch_data = build(batch, nhwc)
    lr = jnp.asarray(0.1, jnp.float32)
    key = jax.random.PRNGKey(0)

    def make(k_steps):
        def f(p, o, b):
            def body(carry, kk):
                p_, o_ = carry
                p2, o2, loss = ts._step_impl(p_, o_, b, kk, lr)
                return (p2, o2), loss

            (_, _), losses = jax.lax.scan(
                body, (p, o), jax.random.split(key, k_steps))
            return losses[-1]

        return f

    per = _slope(make, ts.params, ts.opt_state, batch_data)
    # sanity: same loss scale both layouts
    l = jax.jit(make(2))(ts.params, ts.opt_state, batch_data)
    return per, float(l)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args()
    for nhwc in (False, True):
        per, loss = measure(args.batch, nhwc)
        fmt = "NHWC" if nhwc else "NCHW"
        mfu = args.batch * 4.1e9 * 3 / per / 394e12
        print(f"{fmt}: {per*1e3:7.2f} ms/step  {args.batch/per:6.0f} img/s  "
              f"mfu~{mfu:.3f}  loss={loss:.3f}", flush=True)


if __name__ == "__main__":
    main()
