"""Pipeline schedule micro-bench: bubble fraction + memory + wall-clock.

Compares the schedule zoo (parallel/schedules.py: gpipe / 1f1b / interleaved
VPP) three ways on a virtual 8-device CPU mesh:

  * analytic bubble fraction from the instruction table (exact),
  * peak stashed activations per device (the 1F1B memory win),
  * measured wall-clock of the compiled executor (spmd_pipeline_train).

Reference behavior being matched: pipeline_parallel.py:575 (1F1B) and :1179
(interleaved) trade bubble against activation memory; FThenB keeps all M
microbatch residuals live. Equal-total-compute comparison: V chunks mean
each slot runs depth/V layers, so interleaved runs more, cheaper slots.

Caveat on wall-clock: the virtual CPU devices share host cores, so an idle
slot on one "device" frees cycles for the busy ones — bubble barely shows in
CPU wall time, and per-slot fixed overhead (scan/switch/permute dispatch)
penalizes the 2x-slot interleaved schedule. The analytic bubble fraction is
the hardware-relevant number (on real chips a bubble slot is a stalled chip);
wall-clock here validates that the executors run and that costs are sane.

Run: python tools/pipeline_bubble_bench.py  (forces an 8-CPU platform).
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_platforms", "cpu")

from jax.sharding import Mesh

from paddlepaddle_tpu.parallel.pipeline_spmd import (
    spmd_pipeline_train, stack_stage_params, stack_virtual_stage_params)
from paddlepaddle_tpu.parallel.schedules import build_schedule


def main():
    S, M = 4, 16
    depth, h, mb_rows = 8, 256, 64  # depth layers total, split across virtual stages
    B = M * mb_rows
    rng = np.random.default_rng(0)

    def mklayer(seed):
        r = np.random.default_rng(seed)
        return {"w": jnp.asarray(r.standard_normal((h, h)) / np.sqrt(h), jnp.float32)}

    head = {"wo": jnp.asarray(rng.standard_normal((h, h)) / np.sqrt(h), jnp.float32)}

    def head_loss(hp, a, y):
        return jnp.mean((a @ hp["wo"] - y) ** 2)

    x = jnp.asarray(rng.standard_normal((B, h)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((B, h)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()).reshape(2, S), ("dp", "pp"))

    results = []
    for name, V in [("gpipe", 1), ("1f1b", 1), ("interleaved", 2),
                    ("zbh1", 1), ("zbvpp", 2)]:
        G = V * S
        per_virtual = depth // G  # layers per virtual stage: equal total depth
        layers = [mklayer(g) for g in range(G)]

        def block(p, a, _n=per_virtual):
            for _ in range(_n):
                a = jnp.tanh(a @ p["w"])
            return a

        stacked = (stack_stage_params(layers) if V == 1
                   else stack_virtual_stage_params(layers, S))
        sched = build_schedule(name, S, M, V=V)

        def step(sp, hp, x_, y_):
            return spmd_pipeline_train(sp, hp, x_, y_, block, head_loss, mesh,
                                       schedule=sched, pp_axis="pp",
                                       data_axis="dp")

        jitted = jax.jit(step)
        out = jitted(stacked, head, x, y)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        iters = 20
        for _ in range(iters):
            out = jitted(stacked, head, x, y)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / iters * 1e3
        results.append({
            "schedule": name, "V": V, "T_slots": sched.T,
            "bubble_fraction": round(sched.stats["bubble_fraction"], 4),
            "stash_per_device": sched.stash_cap,
            "wall_ms": round(ms, 2),
        })
        print(f"{name:12s} V={V}  slots={sched.T:3d}  "
              f"bubble={sched.stats['bubble_fraction']:.3f}  "
              f"stash={sched.stash_cap:2d}  wall={ms:8.2f} ms")

    print(json.dumps({"pipeline_bubble_bench": results}))


if __name__ == "__main__":
    main()
