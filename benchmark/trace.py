"""From a profiler trace (``*.xplane.pb``) to device busy and idle time, time
per XLA module and per operation, and the idle gaps named by what the host was
doing. Read with ``jax.profiler.ProfileData`` alone.

On this TPU client a device plane is ``/device:TPU:<n>`` with the lines
``XLA Modules`` (one event per program run, named ``jit_<fn>(<fingerprint>)``)
and ``XLA Ops`` (one event per HLO operation, named by its HLO text); host
threads are lines of ``/host:CPU``, and ``jax.profiler.TraceAnnotation`` spans
appear on the line of the Python thread that made them, which the client names
after the interpreter as it was started: ``python``, or ``python3`` under
``python3 benchmark/run.py``. Times are nanoseconds on one clock (device and
host differ by about a millisecond).
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Span = Tuple[str, float, float]          # name, start_ns, end_ns

_WRAPPERS = re.compile(r"^(while|conditional|call)(\.|$)")
# a Python thread's line carries the interpreter's name, whatever version it was started under
_PYTHON_LINE = re.compile(r"^python[\d.]*$")


def find_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_planes(path: str) -> Dict[str, Dict[str, List[Span]]]:
    """{plane: {line: [(name, start_ns, end_ns)]}} of the device planes and the
    host's Python threads' lines (merged under ``python``)."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Span]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith("/device:TPU:") or plane.name == "/host:CPU"):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            name = line.name
            if plane.name == "/host:CPU":
                if not _PYTHON_LINE.match(name):
                    continue
                name = "python"
            lines.setdefault(name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
                for ev in line.events)
    return out


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[2,4096]{...} fusion(...)`` -> ``fusion.12 bf16[2,4096]``."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?", hlo_text)
    if not m:
        return hlo_text[:60]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:80]


def union(spans: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(spans):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(ops: Sequence[Span]) -> List[Tuple[str, float, float]]:
    """(name, start, self_ns) of each event: its duration less its direct
    children's, so a ``while`` is not counted over its body."""
    out, stack = [], []           # stack of [name, start, end, child_ns]
    def close():
        name, a, b, child = stack.pop()
        out.append((name, a, (b - a) - child))
        if stack:
            stack[-1][3] += b - a
    for name, a, b in sorted(ops, key=lambda s: (s[1], -(s[2] - s[1]))):
        while stack and a >= stack[-1][2]:
            close()
        stack.append([name, a, b, 0.0])
    while stack:
        close()
    return out


def reduce(planes: Dict[str, Dict[str, List[Span]]], window_span: Optional[str] = "bench.window",
           host_spans: Sequence[str] = (), unattributed: str = "unattributed") -> dict:
    """Busy and idle seconds of the traced window (averaged over the device
    planes), seconds per module and per operation of device 0, the calls of each
    module (a call cut by the window's edge counts by its part inside), and the
    idle gaps of device 0 by host span. The window is the host
    span ``window_span``, and a trace without it is an error; only where None
    is asked for is it the first operation's start to the last one's end."""
    host = [s for line in planes.get("/host:CPU", {}).values() for s in line]
    devices = sorted(p for p in planes if p.startswith("/device:TPU:"))
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    all_ops = [s for d in devices for s in planes[d].get("XLA Ops", [])]
    if not all_ops:
        raise RuntimeError("no operation ran on the device in the traced window")
    if window_span is None:
        w0, w1 = min(s[1] for s in all_ops), max(s[2] for s in all_ops)
    else:
        win = next((s for s in host if s[0] == window_span), None)
        if win is None:
            raise RuntimeError(f"the trace holds no host span {window_span!r}: "
                               f"{len(host)} events on the host's Python lines")
        w0, w1 = win[1], win[2]
    clip = lambda spans: [(n, max(a, w0), min(b, w1)) for n, a, b in spans if b > w0 and a < w1]

    busy_per_device = []
    for d in devices:
        busy_per_device.append(sum(b - a for a, b in union(
            [(a, b) for _, a, b in clip(planes[d].get("XLA Ops", []))])))
    d0 = planes[devices[0]]
    ops0, mods0 = clip(d0.get("XLA Ops", [])), clip(d0.get("XLA Modules", []))
    mods_sorted = sorted(mods0, key=lambda s: s[1])

    def module_of(t: float) -> str:
        lo, hi = 0, len(mods_sorted)
        while lo < hi:
            mid = (lo + hi) // 2
            if mods_sorted[mid][1] <= t:
                lo = mid + 1
            else:
                hi = mid
        if lo and mods_sorted[lo - 1][2] >= t:
            return re.sub(r"\(\d+\)$", "", mods_sorted[lo - 1][0])
        return "no_module"

    per_op: Dict[str, float] = defaultdict(float)
    per_op_text: Dict[str, float] = defaultdict(float)
    per_op_text_n: Dict[str, int] = defaultdict(int)
    per_module: Dict[str, float] = defaultdict(float)
    for name, start, self_ns in self_times(ops0):
        short = op_name(name)
        per_module[module_of(start)] += self_ns
        if not _WRAPPERS.match(short):
            per_op[short] += self_ns
            per_op_text[name] += self_ns
            per_op_text_n[name] += 1
    # a call that an edge of the window cuts counts by the part of it inside, as its seconds do: seconds over calls
    # is then the time of a whole call wherever the edges fall
    calls: Dict[str, float] = defaultdict(float)
    for name, a, b in d0.get("XLA Modules", []):
        if b > w0 and a < w1:
            calls[re.sub(r"\(\d+\)$", "", name)] += (min(b, w1) - max(a, w0)) / (b - a) if b > a else 1.0

    wanted = set(host_spans)
    named = sorted((s for s in host if s[0] in wanted), key=lambda s: s[1])
    starts = [s[1] for s in named]
    ends_so_far = list(itertools.accumulate((s[2] for s in named), max))     # the latest end among the spans up to each one
    gaps: Dict[str, float] = defaultdict(float)
    busy0 = union([(a, b) for _, a, b in ops0])
    edges = [w0] + [x for ab in busy0 for x in ab] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        best, cover = unattributed, 0.0
        i = bisect.bisect_left(starts, b) - 1       # the spans that begin before the gap ends, latest first
        while i >= 0 and ends_so_far[i] > a:
            n, sa, sb = named[i]
            c = min(b, sb) - max(a, sa)
            if c > cover and c >= 0.5 * (b - a):   # a span names a gap it covers half of
                best, cover = n, c
            i -= 1
        gaps[best] += b - a

    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy_per_device) / len(busy_per_device) * ns,
        "devices": len(devices),
        "module_s": {k: v * ns for k, v in per_module.items()},
        "module_calls": dict(calls),
        "op_s": {k: v * ns for k, v in per_op.items()},
        "op_text_s": {k: v * ns for k, v in per_op_text.items()},
        "op_text_n": dict(per_op_text_n),
        "gap_s": {k: v * ns for k, v in gaps.items()},
    }


def matching(reduced: dict, pattern: str) -> Optional[Tuple[float, int]]:
    """(seconds, events) of the operations whose HLO text matches ``pattern``;
    None where nothing matches (a reader then returns nothing, never 0)."""
    rx = re.compile(pattern)
    names = [k for k in reduced["op_text_s"] if rx.search(k)]
    if not names:
        return None
    return sum(reduced["op_text_s"][k] for k in names), sum(reduced["op_text_n"][k] for k in names)


def breakdown(reduced: dict, top: int = 10) -> dict:
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(reduced["op_s"]), "idle_gaps": rank(reduced["gap_s"])}
