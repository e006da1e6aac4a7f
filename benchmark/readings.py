"""Readings for the limits of ``correct`` (not run by the benchmark's own runs).

    python benchmark/readings.py --workload <name> --seeds 1,2,3 [--control-seeds 2] [--seconds 8]

For each seed, in this one process: the program's numbers against the plain
reference (the lower reading), and on the first ``--control-seeds`` seeds the
control (the reference put in the program's place in float8 arithmetic) and,
for a training cell, the planted fault "half of the batch left out". One JSON
line per seed on standard output.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402


def train_readings(data, family, seed: int, controls: bool) -> dict:
    import numpy as np

    from benchmark.kinds import packed

    cfg, traffic = data.config, data.traffic
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    batches = [rng.integers(0, cfg["vocab_size"], (traffic["rows"], traffic["tokens_per_row"])).astype(np.int32)
               for _ in range(packed.FIRST_STEPS)]
    prog = family.build_train(cfg, seed)
    got = packed.first_steps(prog, batches)
    prog.free()
    ref = family.train_reference(cfg, seed, batches)
    out = {"seed": seed, "program": dict(packed.compare(*got, ref)), "losses": got[0]}
    if controls:
        for name, kw in (("control_fp8", {"precision": "fp8"}), ("fault_half_batch", {"half_batch": True})):
            alt = family.train_reference(cfg, seed, batches, **kw)
            out[name] = dict(packed.compare(alt["loss"], alt["grad_norm"], alt["change_norm"], ref))
    return out


def serve_readings(data, family, seed: int, controls: bool, seconds: float) -> dict:
    from benchmark.kinds import open_loop_sessions as ols

    ctx = bench_run.Context(data, family, seed, seconds, 0, os.path.join(ROOT, "benchmark_out"))
    ctx.control = "fp8" if controls else ""
    res = ols.run(ctx)
    return {"seed": seed, "program": dict(res["checks"]), "end_to_end": res["end_to_end"],
            "checked_tokens": res["obs"]["checked_tokens"], "failed": res["failed"]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=8.0)
    a = ap.parse_args(argv)
    data = bench_run.load_cell(ROOT, a.workload)
    bench_run.require_chips(int(data.cell["chips"]))
    from paddlepaddle_tpu.core import compile_cache

    compile_cache.arm()
    family = importlib.import_module("benchmark.families." + data.config["family"])
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        controls = i < a.control_seeds
        if data.traffic["kind"] == "packed":
            out = train_readings(data, family, seed, controls)
        else:
            out = serve_readings(data, family, seed, controls, a.seconds)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
