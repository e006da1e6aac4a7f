"""Where does the ResNet-50 train step spend its time?

Decomposes the b128 bf16 step with multi-step lax.scan chains timed by
slope (two scan lengths), so the per-call floor cancels. Variants:

  full      - forward + backward + momentum update (the bench step)
  fwd_bwd   - forward + backward only
  fwd       - forward + loss only
  fwd_nobn  - forward with BatchNorm replaced by identity
  full_nobn - full step with BatchNorm replaced by identity
  nhwc      - full step with NHWC data layout end-to-end

Usage: python tools/resnet_ablation.py [--batch 128] [--variants a,b,c]
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
ROUNDS = 3


def _sync(x):
    return float(jnp.sum(x.astype(jnp.float32)))


def _time(fn, *args):
    _sync(fn(*args))
    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _slope(make_fn, *args):
    f_lo, f_hi = jax.jit(make_fn(K_LO)), jax.jit(make_fn(K_HI))
    dt_lo = _time(f_lo, *args)
    dt_hi = _time(f_hi, *args)
    return (dt_hi - dt_lo) / (K_HI - K_LO)


class _Identity:
    def __init__(self, *a, **k):
        pass

    def __call__(self, x):
        return x


def build(batch, no_bn=False):
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.models.resnet import resnet50
    from paddlepaddle_tpu.nn.functional import cross_entropy
    from paddlepaddle_tpu.optimizer import Momentum
    import paddlepaddle_tpu.nn as pnn

    import paddlepaddle_tpu.models.resnet as resnet_mod

    class Ident(pnn.Layer):
        def __init__(self, *a, **k):
            super().__init__()

        def forward(self, x):
            return x

    saved = resnet_mod.BatchNorm2D
    if no_bn:
        resnet_mod.BatchNorm2D = Ident
    try:
        model = resnet50(num_classes=1000)
    finally:
        resnet_mod.BatchNorm2D = saved
    model.to(dtype="bfloat16")
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters())
    ts = TrainStep(model, opt,
                   lambda m, x, y: cross_entropy(m(x), y).mean())
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.standard_normal((batch, 3, 224, 224)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)).astype(np.int64))
    return ts, (imgs, labels)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--variants", default="full,fwd_bwd,fwd,full_nobn")
    args = ap.parse_args()
    variants = args.variants.split(",")
    results = {}

    for name in variants:
        no_bn = name.endswith("nobn")
        ts, batch = build(args.batch, no_bn=no_bn)
        params, opt_state = ts.params, ts.opt_state
        lr = jnp.asarray(0.1, jnp.float32)
        key = jax.random.PRNGKey(0)

        if name in ("full", "full_nobn"):
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

            per = _slope(make, params, opt_state, batch)
        elif name == "fwd_bwd":
            def make(k_steps):
                def f(p, b):
                    def body(acc, kk):
                        def loss_of(pp):
                            from paddlepaddle_tpu.core import autograd as _ag
                            from paddlepaddle_tpu.core import random as prandom
                            from paddlepaddle_tpu.core.dispatch import unwrap
                            with _ag.no_grad(), prandom.key_scope(kk):
                                state = dict(pp)
                                state.update(ts.buffers)
                                with ts.model.bind_state(state):
                                    return unwrap(ts.loss_fn(ts.model, *b))

                        loss, g = jax.value_and_grad(loss_of)(
                            jax.tree_util.tree_map(
                                lambda x: (x * (1.0 + 1e-30 * acc)).astype(x.dtype), p))
                        # consume EVERY grad leaf — otherwise XLA dead-code
                        # eliminates the entire backward pass
                        gsum = sum(jnp.sum(v.astype(jnp.float32)) for v in
                                   jax.tree_util.tree_leaves(g))
                        return acc + loss.astype(jnp.float32) + 1e-30 * gsum, None

                    acc, _ = jax.lax.scan(
                        body, jnp.zeros((), jnp.float32),
                        jax.random.split(key, k_steps))
                    return acc

                return f

            per = _slope(make, params, batch)
        elif name in ("fwd", "fwd_nobn"):
            def make(k_steps):
                def f(p, b):
                    def body(acc, kk):
                        from paddlepaddle_tpu.core import autograd as _ag
                        from paddlepaddle_tpu.core import random as prandom
                        from paddlepaddle_tpu.core.dispatch import unwrap
                        with _ag.no_grad(), prandom.key_scope(kk):
                            state = {k2: (v * (1.0 + 1e-30 * acc)).astype(v.dtype)
                                     for k2, v in p.items()}
                            state.update(ts.buffers)
                            with ts.model.bind_state(state):
                                loss = unwrap(ts.loss_fn(ts.model, *b))
                        return acc + loss.astype(jnp.float32), None

                    acc, _ = jax.lax.scan(
                        body, jnp.zeros((), jnp.float32),
                        jax.random.split(key, k_steps))
                    return acc

                return f

            per = _slope(make, params, batch)
        else:
            print(f"{name}: unknown variant")
            continue
        results[name] = per
        fwd_flops = args.batch * 4.1e9
        mult = {"full": 3, "full_nobn": 3, "fwd_bwd": 3}.get(name, 1)
        print(f"{name:<10} {per*1e3:8.2f} ms/step   "
              f"{fwd_flops*mult/per/1e12:6.1f} TF/s  "
              f"({args.batch/per:.0f} img/s)", flush=True)


if __name__ == "__main__":
    main()
