"""Program cost registry — exact XLA FLOPs/bytes per compiled program,
combined with measured wall time into per-program roofline numbers.

Reference surface: ``paddle.profiler``'s kernel statistics tables (per-
kernel FLOPs and occupancy in the GPU profiler summary). TPU-native
equivalent: XLA's own ``Compiled.cost_analysis()`` — the compiler counts
the FLOPs and HBM bytes of the exact program it emitted, so MFU stops
being an analytic approximation (``bench.py``'s ``6N`` convention, the
ResNet ``3x4.1 GFLOP/image`` guess) and becomes a measurement.

Capture rides the AOT path: :func:`capture_jit` lowers + compiles a
jitted callable at a concrete argument signature, records the cost, and
returns the ``Compiled`` object so the call site can EXECUTE through it —
one compile total, not jit-compile + AOT-compile. Call sites observe wall
time per execution with :func:`CostRegistry.observe`; the registry then
derives, per (program, shape-bucket):

* ``mfu``      — flops / (min_wall * peak_flops): achieved fraction of
  the chip's matmul peak at the program's best observed wall time;
* ``hbm_util`` — bytes / (min_wall * peak_bw): achieved fraction of HBM
  bandwidth;
* ``intensity`` (flops/byte) vs the device ridge point -> ``bound``
  ("compute" or "bandwidth") and ``pct_of_peak`` against the respective
  peak — the roofline classification.

Everything is guarded: a backend without ``cost_analysis`` (or an AOT
quirk) degrades to returning ``None`` and the call site keeps its
original jitted function. Never raises into a hot path.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, List, Optional, Tuple

from . import device as _device


class ProgramCost:
    """Cost + timing accumulator for one (program, bucket)."""

    __slots__ = ("name", "bucket", "flops", "bytes_accessed", "bytes_out",
                 "calls", "wall_total", "wall_min", "meta")

    def __init__(self, name: str, bucket: str):
        self.name = name
        self.bucket = bucket
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.bytes_out: Optional[float] = None
        self.calls = 0
        self.wall_total = 0.0
        self.wall_min = float("inf")
        self.meta: Dict[str, object] = {}

    def derived(self, specs: dict) -> dict:
        """One row of the /programs table: raw cost + roofline numbers."""
        row = {
            "program": self.name,
            "bucket": self.bucket,
            "flops": self.flops,
            "hbm_bytes": self.bytes_accessed,
            "out_bytes": self.bytes_out,
            "calls": self.calls,
            "wall_s_min": None if self.calls == 0 else self.wall_min,
            "wall_s_avg": (None if self.calls == 0
                           else self.wall_total / self.calls),
        }
        row.update(self.meta)
        f, b = self.flops, self.bytes_accessed
        if f is not None and b and b > 0:
            ai = f / b
            row["intensity_flops_per_byte"] = ai
            row["bound"] = ("compute" if ai >= specs["ridge_flops_per_byte"]
                            else "bandwidth")
        if self.calls and self.wall_min > 0:
            if f is not None:
                row["mfu"] = f / (self.wall_min * specs["peak_flops"])
            if b is not None:
                row["hbm_util"] = b / (self.wall_min
                                       * specs["peak_hbm_bytes_per_s"])
            bound = row.get("bound")
            if bound == "compute" and "mfu" in row:
                row["pct_of_peak"] = row["mfu"]
            elif bound == "bandwidth" and "hbm_util" in row:
                row["pct_of_peak"] = row["hbm_util"]
        return row


def parse_cost_analysis(ca) -> Tuple[Optional[float], Optional[float],
                                     Optional[float]]:
    """(flops, bytes_accessed, output_bytes) from whatever shape the
    backend's ``cost_analysis()`` returns (dict, or list of per-module
    dicts — summed). None fields where the backend doesn't report."""
    if ca is None:
        return None, None, None
    mods = ca if isinstance(ca, (list, tuple)) else [ca]
    flops = byts = out = None
    for d in mods:
        if not isinstance(d, dict):
            continue
        f = d.get("flops")
        b = d.get("bytes accessed")
        o = d.get("bytes accessedout{}")
        if f is not None:
            flops = (flops or 0.0) + float(f)
        if b is not None:
            byts = (byts or 0.0) + float(b)
        if o is not None:
            out = (out or 0.0) + float(o)
    return flops, byts, out


class CostRegistry:
    """Thread-safe store of :class:`ProgramCost` rows keyed by
    (program name, shape bucket)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[Tuple[str, str], ProgramCost] = {}

    def _get(self, name: str, bucket: str) -> ProgramCost:
        key = (str(name), str(bucket))
        with self._lock:
            pc = self._programs.get(key)
            if pc is None:
                pc = self._programs[key] = ProgramCost(*key)
            return pc

    def record(self, name: str, flops=None, bytes_accessed=None,
               bytes_out=None, bucket: str = "", **meta) -> ProgramCost:
        """Register (or update) a program's compiler-reported cost."""
        pc = self._get(name, bucket)
        if flops is not None:
            pc.flops = float(flops)
        if bytes_accessed is not None:
            pc.bytes_accessed = float(bytes_accessed)
        if bytes_out is not None:
            pc.bytes_out = float(bytes_out)
        if meta:
            pc.meta.update(meta)
        return pc

    def observe(self, name: str, wall_s: float, bucket: str = "") -> None:
        """Fold one measured execution wall time into the program's row
        (creates the row if cost capture hasn't happened / failed)."""
        pc = self._get(name, bucket)
        wall_s = float(wall_s)
        with self._lock:
            pc.calls += 1
            pc.wall_total += wall_s
            if wall_s < pc.wall_min:
                pc.wall_min = wall_s

    def programs(self) -> List[ProgramCost]:
        with self._lock:
            return list(self._programs.values())

    def table(self, specs: Optional[dict] = None) -> List[dict]:
        """Derived rows (roofline numbers included), MFU-descending."""
        programs = self.programs()
        if not programs:
            # nothing to derive: do not ask jax for a device — a /metrics
            # scrape in a process that only supervises chip-holding
            # children must not take the chip from them
            return []
        if specs is None:
            try:
                specs = _device.specs()
            except RuntimeError:   # no jax backend: raw costs, no roofline
                specs = {"peak_flops": 0.0, "peak_hbm_bytes_per_s": 0.0,
                         "ridge_flops_per_byte": float("inf")}
        rows = [pc.derived(specs) for pc in programs]
        rows.sort(key=lambda r: -(r.get("mfu") or 0.0))
        return rows

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()


_registry = CostRegistry()


def registry() -> CostRegistry:
    return _registry


def observe(name: str, wall_s: float, bucket: str = "") -> None:
    _registry.observe(name, wall_s, bucket=bucket)


def capture_jit(name: str, jit_fn, args: tuple = (), kwargs=None,
                bucket: str = "", **meta):
    """AOT lower + compile ``jit_fn`` at ``args``' signature, record its
    ``cost_analysis()`` under ``(name, bucket)``, and return the
    ``Compiled`` stage so the caller executes through it (one compile
    total; donation declared at ``jax.jit`` time is preserved).

    Returns None on ANY failure — the caller keeps its original jitted
    function and the only trace is a one-line stderr note plus a
    ``paddle_program_capture_failures_total`` counter. Cost capture must
    never be the thing that breaks a train step or a serving engine.
    """
    try:
        compiled = jit_fn.lower(*args, **(kwargs or {})).compile()
    except Exception as e:
        _capture_failed(name, e)
        return None
    try:
        flops, byts, out = parse_cost_analysis(compiled.cost_analysis())
        _registry.record(name, flops=flops, bytes_accessed=byts,
                         bytes_out=out, bucket=bucket,
                         cost_source="compiled", **meta)
    except Exception as e:
        # compiled fine but the cost query failed: still usable for
        # execution; record the row with no cost so /programs names it
        _registry.record(name, bucket=bucket, **meta)
        _capture_failed(name, e)
    return compiled


def cost_of_jit(name: str, jit_fn, args: tuple = (), kwargs=None,
                bucket: str = "", **meta) -> Optional[dict]:
    """Capture + record like :func:`capture_jit` but return the parsed
    cost dict instead of the Compiled (for callers that only want the
    numbers, e.g. a bench recording the analytic-vs-measured delta)."""
    compiled = capture_jit(name, jit_fn, args, kwargs, bucket=bucket, **meta)
    if compiled is None:
        return None
    pc = _registry._get(name, bucket)
    return {"flops": pc.flops, "bytes_accessed": pc.bytes_accessed,
            "bytes_out": pc.bytes_out, "compiled": compiled}


def cost_of_lowered(name: str, jit_fn, args: tuple = (), kwargs=None,
                    bucket: str = "", scale: float = 1.0,
                    record: bool = True, **meta) -> Optional[dict]:
    """Trace + lower ``jit_fn`` (NO backend compile — milliseconds, safe
    to do for a program the caller will never execute) and record the
    cost of the PRE-optimization HLO, scaled by ``scale``.

    Two uses where :func:`capture_jit` is wrong:

    * a program whose executed form wraps the interesting body in a
      ``lax.scan`` — XLA's cost analysis counts a loop body ONCE
      regardless of trip count, so the caller lowers a length-1 variant
      and passes ``scale=chunk`` (recorded in ``meta`` so the row says
      how its flops were derived);
    * a side measurement where an extra backend compile is unaffordable
      (the bench's single-step cost next to its chain timing).

    FLOP counts are identical pre/post optimization for the matmul-
    dominated programs this measures; BYTES from unoptimized HLO
    overcount real HBM traffic (fusion elides intermediates), so rows
    carry ``cost_source="lowered"`` and bandwidth numbers should be read
    as upper bounds. Returns the cost dict or None on failure.
    """
    try:
        lowered = jit_fn.lower(*args, **(kwargs or {}))
        flops, byts, out = parse_cost_analysis(lowered.cost_analysis())
    except Exception as e:
        _capture_failed(name, e)
        return None
    if scale != 1.0:
        flops = None if flops is None else flops * scale
        byts = None if byts is None else byts * scale
        out = None if out is None else out * scale
        meta.setdefault("cost_scale", scale)
    if record:
        _registry.record(name, flops=flops, bytes_accessed=byts,
                         bytes_out=out, bucket=bucket,
                         cost_source="lowered", **meta)
    return {"flops": flops, "bytes_accessed": byts, "bytes_out": out}


def _capture_failed(name: str, e: Exception) -> None:
    try:
        from .. import safe_inc

        safe_inc("paddle_program_capture_failures_total",
                 "program cost captures that failed (AOT compile or "
                 "cost_analysis)", program=name)
        sys.stderr.write(
            f"[obs.perf] cost capture for {name!r} failed: "
            f"{type(e).__name__}: {e}\n")
    except Exception:
        pass


# -- export ------------------------------------------------------------------

def table_jsonable() -> dict:
    """The /programs endpoint body: device specs + derived program rows
    (strict JSON — non-finite values nulled)."""
    import math

    try:
        specs = _device.specs()
    except Exception:
        specs = None

    def scrub(v):
        if isinstance(v, float) and not math.isfinite(v):
            return None
        return v

    rows = [{k: scrub(v) for k, v in r.items()}
            for r in _registry.table(specs)]
    return {"device": specs, "programs": rows}


def publish_gauges(metrics_registry) -> None:
    """Mirror the derived table into ``paddle_program_*`` gauges on the
    given metrics registry — called lazily from ``to_prometheus_text()``
    so every /metrics scrape sees fresh roofline numbers without any
    per-step publication cost."""
    rows = _registry.table()
    if not rows:
        return
    g = {
        "flops": metrics_registry.gauge(
            "paddle_program_flops",
            "XLA cost_analysis FLOPs per execution of the program"),
        "hbm_bytes": metrics_registry.gauge(
            "paddle_program_hbm_bytes",
            "XLA cost_analysis bytes accessed per execution"),
        "calls": metrics_registry.gauge(
            "paddle_program_calls",
            "observed executions folded into the program's timing"),
        "wall_s_min": metrics_registry.gauge(
            "paddle_program_wall_seconds_min",
            "best observed wall time of one execution"),
        "mfu": metrics_registry.gauge(
            "paddle_program_mfu",
            "measured FLOPs / (best wall * device peak FLOP/s)"),
        "hbm_util": metrics_registry.gauge(
            "paddle_program_hbm_util",
            "accessed bytes / (best wall * device peak HBM bandwidth)"),
    }
    bound = metrics_registry.gauge(
        "paddle_program_compute_bound",
        "roofline classification (1 = compute-bound, 0 = bandwidth-bound)")
    for row in rows:
        labels = {"program": row["program"], "bucket": row["bucket"]}
        for key, gauge in g.items():
            v = row.get(key)
            if v is not None:
                gauge.set(float(v), **labels)
        if row.get("bound") is not None:
            bound.set(1.0 if row["bound"] == "compute" else 0.0, **labels)


def render_table(rows: List[dict]) -> str:
    """Human-readable table over derived rows (summary() and obsctl)."""

    def fnum(v, unit=""):
        if v is None:
            return "-"
        for scale, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
            if abs(v) >= scale:
                return f"{v / scale:.2f}{suf}{unit}"
        return f"{v:.3g}{unit}"

    lines = [f"{'Program':<28}{'Bucket':>10}{'Calls':>7}{'FLOPs':>9}"
             f"{'Bytes':>9}{'Wall(ms)':>10}{'MFU':>7}{'BW%':>7}  Bound"]
    for r in rows:
        wall = r.get("wall_s_min")
        mfu = r.get("mfu")
        bw = r.get("hbm_util")
        lines.append(
            f"{r['program'][:28]:<28}{r['bucket'][:10]:>10}"
            f"{r.get('calls', 0):>7}{fnum(r.get('flops')):>9}"
            f"{fnum(r.get('hbm_bytes')):>9}"
            f"{'-' if wall is None else f'{wall * 1e3:.3f}':>10}"
            f"{'-' if mfu is None else f'{mfu:.3f}':>7}"
            f"{'-' if bw is None else f'{bw * 100:.1f}':>7}"
            f"  {r.get('bound', '-')}")
    return "\n".join(lines)
