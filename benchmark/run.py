"""Run ONE cell of ``BENCHMARK.json`` once, in this process, on the chip.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is a file found by its name in ``BENCHMARK.json``:
``benchmark/configs/<config>.json`` (its ``family`` names the builder, the
reference and the counts of operations and bytes in ``benchmark/families/``), ``benchmark/traffic/<traffic>.json``
(its ``kind`` names the runner in ``benchmark/kinds/``),
``benchmark/metrics/<metric>.py`` (``read(obs)`` returns the value, or None
where there is nothing to read) and ``benchmark/limits/<workload>.json`` (the
limit of each number ``correct`` compares). The last line of standard output
is the result; without a TPU, or with fewer chips than the cell asks for, the
exit code is not 0 and nothing is printed there.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import window  # noqa: E402


class Refused(SystemExit):
    """No result can be produced here (no chip, no such cell)."""

    def __init__(self, msg: str):
        print(f"[benchmark] {msg}", file=sys.stderr)
        super().__init__(3)


def load_cell(data_root: str, workload: str) -> SimpleNamespace:
    with open(os.path.join(data_root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    base = os.path.join(data_root, manifest["paths"][0])

    def read(*parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    limits_file = os.path.join(base, "limits", workload + ".json")
    return SimpleNamespace(
        manifest=manifest, cell=cell, base=base,
        config=read(data_root, config["file"]),
        traffic=read(base, "traffic", cell["traffic"] + ".json"),
        limits=read(limits_file) if os.path.exists(limits_file) else {})


def cell_metrics(manifest: dict, cell: dict, group: str, reported=None) -> list:
    """The entries of ``group`` that this cell reports."""
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or reported is None or m["moves"] in reported:
            out.append(m)
    return out


def load_reader(base: str, name: str):
    path = os.path.join(base, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("benchmark_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"jax found platform {devices[0].platform!r}, not a TPU: no result")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, jax found {len(devices)}")
    return devices


class Context:
    """What a kind's runner gets: the cell's data, the clock of set-up, the
    profiler (only with ``--trace 1``) and the program's compile counters."""

    def __init__(self, data, family, seed, seconds, trace, scratch):
        self.config, self.traffic, self.family = data.config, data.traffic, family
        self.seed, self.trace = int(seed), bool(trace)
        # a traced run measures the traced window alone: the profiler's start
        # and stop then fall outside what the per-layer metrics are taken over
        self.seconds = min(float(seconds), float(data.traffic["trace_seconds"])) if trace else float(seconds)
        self.trace_dir = os.path.join(scratch, "trace")
        self.setup_s = None
        self.memory_peak_bytes = None
        self.control = ""           # readings.py: also read this precision put in the program's place
        self._tracing = False
        self._window_span = None

    def log(self, what: str):
        """A phase mark on standard error, in seconds since the process started."""
        print(f"[benchmark] {time.perf_counter() - T_START:8.2f}s {what}", file=sys.stderr, flush=True)

    def span(self, name: str):
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def compile_stats(self) -> dict:
        from paddlepaddle_tpu.core import compile_cache

        return compile_cache.stats()

    def mark_window_open(self):
        """Set-up ends here. The counters a stall is named from are read at the
        window's two edges, never inside it."""
        self.setup_s = time.perf_counter() - T_START
        self.log("window opens")
        self.at_open = (self.compile_stats(), window.host_counters())

    def mark_window_close(self):
        self.at_close = (self.compile_stats(), window.host_counters())

    def trace_open(self, at: float = None):
        """Start the profiler; the traced window (``bench.window``) begins at
        ``at`` on the perf_counter clock, or now."""
        if not self.trace:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True
        if at is not None:
            time.sleep(max(0.0, at - time.perf_counter()))
        self._window_span = jax.profiler.TraceAnnotation("bench.window")
        self._window_span.__enter__()
        self.trace_t0 = time.perf_counter()

    def trace_close(self):
        if not self._tracing:
            return
        import jax

        self._window_span.__exit__(None, None, None)
        self.trace_t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self._tracing = False

    def read_memory_peak(self):
        import jax

        peaks = [d.memory_stats().get("peak_bytes_in_use", 0) for d in jax.local_devices()
                 if d.memory_stats()]
        self.memory_peak_bytes = max(peaks) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, trace: int, data_root: str = ROOT,
             check_chip: bool = True, family=None, out=sys.stdout, dump: str = "") -> dict:
    data = load_cell(data_root, workload)
    import jax

    if check_chip:
        require_chips(int(data.cell["chips"]))
        from paddlepaddle_tpu.core import compile_cache

        compile_cache.arm()       # <checkout>/.jax_cache, or JAX_COMPILATION_CACHE_DIR
    if family is None:
        family = importlib.import_module("benchmark.families." + data.config["family"])
    kind = importlib.import_module("benchmark.kinds." + data.traffic["kind"])
    scratch = os.path.join(ROOT, "benchmark_out")
    os.makedirs(scratch, exist_ok=True)
    ctx = Context(data, family, seed, seconds, trace, scratch)
    res = kind.run(ctx)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count(),
              "memory_peak_bytes": int(ctx.memory_peak_bytes or 0)}
    obs = res["obs"]
    # operations and bytes are the family's to count (its ``work``); the peaks are ``benchmark.work``'s alone
    obs.update(work=family.work, config=data.config, traffic=data.traffic, cell=data.cell,
               device_kind=dev.device_kind,
               chips=int(data.cell["chips"]), memory_peak_bytes=device["memory_peak_bytes"],
               end_to_end=res["end_to_end"], seconds=ctx.seconds)
    values = dict(res["end_to_end"], setup_s=ctx.setup_s)
    result = {"correct": None, "attempted": int(res["attempted"]), "failed": int(res["failed"]),
              "metrics": {}, "device": device}
    if trace:
        from benchmark import trace as tr

        reduced = tr.reduce(tr.read_planes(tr.find_trace(ctx.trace_dir)),
                            host_spans=obs["span_names"], unattributed=obs["unattributed"])
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        obs.update(trace=reduced, trace_t0=ctx.trace_t0, trace_t1=ctx.trace_t1)
        if dump:
            with open(dump, "w") as f:
                json.dump(reduced, f, indent=1)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        reported = {m["name"] for m in cell_metrics(data.manifest, data.cell, "end_to_end")
                    if m["name"] in values}
        for m in cell_metrics(data.manifest, data.cell, "per_layer", reported):
            value = load_reader(data.base, m["name"])(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(reduced)
    else:
        for m in cell_metrics(data.manifest, data.cell, "end_to_end"):
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    checks, correct = {}, res["failed"] == 0
    for name, value in res["checks"]:
        limit = data.limits.get(name)
        checks[name] = {"value": value if math.isfinite(value) else None, "limit": limit}
        if limit is not None and not (math.isfinite(value) and value <= limit):
            correct = False
    if not any(c["limit"] is not None for c in checks.values()):
        correct = False                   # nothing was compared: not proven
    result["correct"] = bool(correct)
    result["checks"] = checks
    for name, c in checks.items():       # a number without a limit is read and shown, not compared (PERF.md says why)
        print(f"[benchmark] compared {name}: {c['value']} (limit {c['limit']})" if c["limit"] is not None
              else f"[benchmark] read, not compared {name}: {c['value']}", file=sys.stderr)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default="", help="with --trace 1: also write the reduced trace here, to read by hand")
    a = ap.parse_args(argv)
    run_cell(a.workload, a.seed, a.seconds, a.trace, dump=a.dump)


if __name__ == "__main__":
    main()
