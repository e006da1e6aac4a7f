"""Full per-segment budget for the ResNet-50 b128 bf16 train step (r4
verdict item 1): where do the ~45 ms go?

Three lenses:
  cost      - XLA's own cost_analysis of the compiled step (flops + bytes
              accessed -> roofline bound on this chip)
  segments  - slope-timed fwd+bwd of each pipeline segment IN ISOLATION
              (stem+pool, layer1..layer4, head+CE) + optimizer-only
  nhwc      - every unique conv layer shape A/B'd NCHW vs NHWC (fwd+bwd)

Usage: python tools/resnet_segments.py [--batch 128] [--lens cost,segments,nhwc]
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
    # a host scalar ends the timed region; block_until_ready is an equally
    # real barrier on this chip (PR 21: 142.4 ms vs 142.7 ms for the same
    # 142 ms program), the scalar is kept so the sum is actually consumed
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


def build_step(batch):
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.models.resnet import resnet50
    from paddlepaddle_tpu.nn.functional import cross_entropy
    from paddlepaddle_tpu.optimizer import Momentum

    model = resnet50(num_classes=1000)
    model.to(dtype="bfloat16")
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters())
    ts = TrainStep(model, opt,
                   lambda m, x, y: cross_entropy(m(x), y).mean())
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.standard_normal((batch, 3, 224, 224)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)).astype(np.int64))
    return ts, model, (imgs, labels)


def lens_cost(batch):
    """XLA cost_analysis of the full compiled step: the compiler's own
    flops/bytes — divide by peak to get the roofline floor."""
    ts, model, (imgs, labels) = build_step(batch)
    lr = jnp.asarray(0.1, jnp.float32)
    key = jax.random.PRNGKey(0)

    def step(p, o, b):
        return ts._step_impl(p, o, b, key, lr)

    c = jax.jit(step).lower(ts.params, ts.opt_state, (imgs, labels)).compile()
    ca = c.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", float("nan"))
    bytes_ = ca.get("bytes accessed", float("nan"))
    print(f"cost_analysis: flops={flops:.3e}  bytes={bytes_:.3e}")
    # v5e-ish peaks; override via env for other chips
    peak_tf = float(os.environ.get("PEAK_BF16_TFLOPS", 394))
    peak_bw = float(os.environ.get("PEAK_HBM_GBS", 820))
    t_flops = flops / (peak_tf * 1e12)
    t_bytes = bytes_ / (peak_bw * 1e9)
    print(f"roofline: compute {t_flops*1e3:.1f} ms | memory "
          f"{t_bytes*1e3:.1f} ms | bound = {max(t_flops, t_bytes)*1e3:.1f} ms")
    mem = c.memory_analysis()
    if mem is not None:
        print(f"memory: argument {mem.argument_size_in_bytes/1e9:.2f} GB, "
              f"temp {mem.temp_size_in_bytes/1e9:.2f} GB, "
              f"output {mem.output_size_in_bytes/1e9:.2f} GB")


def _seg_fwd_bwd(fwd, params, x, k_steps_key=None):
    """Slope-timed fwd+bwd of one segment: grad wrt params AND input."""
    def make(k_steps):
        def f(p, xx):
            def body(acc, _):
                def loss_of(pp, xi):
                    return jnp.sum(fwd(pp, xi).astype(jnp.float32))

                l, (gp, gx) = jax.value_and_grad(loss_of, argnums=(0, 1))(
                    p, (xx * (1.0 + 1e-30 * acc)).astype(xx.dtype))
                gsum = sum(jnp.sum(v.astype(jnp.float32))
                           for v in jax.tree_util.tree_leaves((gp, gx)))
                return acc + l + 1e-30 * gsum, None

            acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                  None, length=k_steps)
            return acc

        return f

    return _slope(make, params, x)


def lens_segments(batch):
    from paddlepaddle_tpu.core import autograd as ag
    from paddlepaddle_tpu.core.dispatch import unwrap, wrap

    ts, model, (imgs, labels) = build_step(batch)
    state = dict(ts.params)
    state.update(ts.buffers)

    segs = []

    def seg_fn(sub, prefix):
        names = [n for n in state if n.startswith(prefix)]

        def fwd(p, x):
            full = dict(state)
            full.update(p)
            with ag.no_grad(), model.bind_state(full):
                return unwrap(sub(wrap(x)))

        p0 = {n: state[n] for n in names}
        return fwd, p0

    def stem(x):
        return model.maxpool(model.relu(model.bn1(model.conv1(x))))

    rng = np.random.default_rng(0)

    shapes = {
        "stem(conv7+bn+relu+maxpool)": (stem, "", (batch, 3, 224, 224)),
        "layer1": (model.layer1, "layer1.", (batch, 64, 56, 56)),
        "layer2": (model.layer2, "layer2.", (batch, 256, 56, 56)),
        "layer3": (model.layer3, "layer3.", (batch, 512, 28, 28)),
        "layer4": (model.layer4, "layer4.", (batch, 1024, 14, 14)),
    }
    total = 0.0
    for name, (sub, prefix, in_shape) in shapes.items():
        fwd, p0 = seg_fn(sub, prefix)
        x = jnp.asarray(rng.standard_normal(in_shape), jnp.bfloat16)
        per = _seg_fwd_bwd(fwd, p0, x)
        total += per
        print(f"{name:<28} {per*1e3:7.2f} ms", flush=True)

    # head: avgpool + fc + CE + label pipeline
    def head_fwd(p, x):
        from paddlepaddle_tpu.nn.functional import cross_entropy
        full = dict(state)
        full.update(p)
        with ag.no_grad(), model.bind_state(full):
            h = model.avgpool(wrap(x))
            h = model.fc(h.flatten(1))
            return unwrap(cross_entropy(h, wrap(labels)).mean())

    p_head = {n: state[n] for n in state if n.startswith("fc.")}
    xh = jnp.asarray(rng.standard_normal((batch, 2048, 7, 7)), jnp.bfloat16)
    per = _seg_fwd_bwd(head_fwd, p_head, xh)
    total += per
    print(f"{'head(avgpool+fc+CE)':<28} {per*1e3:7.2f} ms", flush=True)

    # optimizer-only: momentum update on the full param tree
    lr = jnp.asarray(0.1, jnp.float32)

    def make_opt(k_steps):
        def f(p, o):
            def body(carry, _):
                pp, oo = carry
                g = jax.tree_util.tree_map(
                    lambda v: (v.astype(jnp.float32) * 1e-3).astype(v.dtype),
                    pp)
                new_p, new_o = ts.optimizer.apply(g, oo, pp, lr=lr)
                return (new_p, new_o), None

            carry, _ = jax.lax.scan(body, (p, o), None, length=k_steps)
            return jax.tree_util.tree_leaves(carry[0])[0]

        return f

    try:
        per = _slope(make_opt, ts.params, ts.opt_state)
        print(f"{'optimizer(momentum)':<28} {per*1e3:7.2f} ms", flush=True)
        total += per
    except Exception as e:
        print(f"optimizer: skipped ({type(e).__name__}: {e})")
    print(f"{'SUM of isolated segments':<28} {total*1e3:7.2f} ms")


_R50_CONVS = [
    # (cin, cout, k, stride, spatial_in) — unique conv shapes of ResNet-50
    (3, 64, 7, 2, 224),
    (64, 64, 1, 1, 56), (64, 64, 3, 1, 56), (64, 256, 1, 1, 56),
    (256, 64, 1, 1, 56), (256, 128, 1, 2, 56), (256, 512, 1, 2, 56),
    (128, 128, 3, 2, 56), (128, 128, 3, 1, 28), (128, 512, 1, 1, 28),
    (512, 128, 1, 1, 28), (512, 256, 1, 2, 28), (512, 1024, 1, 2, 28),
    (256, 256, 3, 2, 28), (256, 256, 3, 1, 14), (256, 1024, 1, 1, 14),
    (1024, 256, 1, 1, 14), (1024, 512, 1, 2, 14), (1024, 2048, 1, 2, 14),
    (512, 512, 3, 2, 14), (512, 512, 3, 1, 7), (512, 2048, 1, 1, 7),
    (2048, 512, 1, 1, 7),
]


def lens_nhwc(batch):
    """Each unique conv fwd+bwd: NCHW vs NHWC wall time."""
    rng = np.random.default_rng(0)
    tot = {"NCHW": 0.0, "NHWC": 0.0}
    print(f"{'conv':<24} {'NCHW ms':>8} {'NHWC ms':>8}")
    for cin, cout, k, stride, s in _R50_CONVS:
        res = {}
        for fmt in ("NCHW", "NHWC"):
            if fmt == "NCHW":
                x = jnp.asarray(rng.standard_normal((batch, cin, s, s)),
                                jnp.bfloat16)
                dn = ("NCHW", "OIHW", "NCHW")
            else:
                x = jnp.asarray(rng.standard_normal((batch, s, s, cin)),
                                jnp.bfloat16)
                dn = ("NHWC", "HWIO", "NHWC")
            w_shape = (cout, cin, k, k) if fmt == "NCHW" \
                else (k, k, cin, cout)
            w = jnp.asarray(rng.standard_normal(w_shape) * 0.05, jnp.bfloat16)

            def make(k_steps, x=x, w=w, dn=dn, k_=k, stride=stride):
                pad = [(k_ // 2, k_ // 2)] * 2

                def f(xx, ww):
                    def body(acc, _):
                        def loss_of(wi, xi):
                            o = jax.lax.conv_general_dilated(
                                xi, wi, (stride, stride), pad,
                                dimension_numbers=dn)
                            return jnp.sum(o.astype(jnp.float32))

                        l, (gw, gx) = jax.value_and_grad(
                            loss_of, argnums=(0, 1))(
                                ww, (xx * (1.0 + 1e-30 * acc)).astype(xx.dtype))
                        return acc + l + 1e-30 * (
                            jnp.sum(gw.astype(jnp.float32))
                            + jnp.sum(gx.astype(jnp.float32))), None

                    acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                          None, length=k_steps)
                    return acc

                return f

            res[fmt] = _slope(make, x, w)
            tot[fmt] += res[fmt]
        print(f"{f'{cin}->{cout} k{k} s{stride} @{s}':<24} "
              f"{res['NCHW']*1e3:8.3f} {res['NHWC']*1e3:8.3f}", flush=True)
    print(f"{'TOTAL (unique shapes x1)':<24} "
          f"{tot['NCHW']*1e3:8.2f} {tot['NHWC']*1e3:8.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--lens", default="cost,segments")
    args = ap.parse_args()
    for lens in args.lens.split(","):
        print(f"== {lens} ==")
        {"cost": lens_cost, "segments": lens_segments,
         "nhwc": lens_nhwc}[lens](args.batch)


if __name__ == "__main__":
    main()
