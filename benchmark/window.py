"""The measured window: its edges are events, never clock ticks.

Train: the window opens at a barrier (a loss scalar on the host) and closes at
the first barrier at or after ``seconds``; no step straddles an edge. Serve:
tokens reach the host once per decode chunk, so a poller (every 20 ms, never
faster: it shares the GIL with the engine thread) records each move of the
engine's delivered-token counter as a delivery event; the window opens at
the first delivery at or after the ramp's end and closes at the last delivery
at or before open + ``seconds``. A rate is all the work between the two events
over all the time between them; a tail is the tail of every request.

What would name a stall is recorded beside it and printed after the window:
the longest gaps between consecutive events, the poller's own late wake-ups (a
frozen host or a held GIL), garbage collections over 50 ms, and what the
kernel's counters say the machine did meanwhile.
"""

from __future__ import annotations

import gc
import math
import os
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

POLL_S = 0.02           # no thread of the benchmark wakes more often inside a window


def run_steps(step_to_barrier: Callable[[int], None], seconds: float,
              clock: Callable[[], float] = time.perf_counter) -> List[float]:
    """Call ``step_to_barrier(i)`` (it returns once step i's result is on the
    host) until a barrier falls at or after ``seconds``. Returns every
    barrier's time; the first is the opening edge."""
    stamps = [clock()]
    while stamps[-1] - stamps[0] < seconds:
        step_to_barrier(len(stamps) - 1)
        stamps.append(clock())
    return stamps


def rate(stamps: Sequence[float], work_per_step: float) -> float:
    return (len(stamps) - 1) * work_per_step / (stamps[-1] - stamps[0])


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile; ``math.inf`` entries (missed requests) sort last."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


class DeliveryLog:
    """Polls a counter every ``period_s`` and records ``(time, count)`` each
    time it moves. It also keeps its own latest wake-ups that came over
    ``period_s`` late by 50 ms or more: the host did not run this thread then."""

    def __init__(self, read_count: Callable[[], int], period_s: float = POLL_S,
                 clock: Callable[[], float] = time.perf_counter):
        self._read, self._period, self._clock = read_count, period_s, clock
        self.events: List[Tuple[float, int]] = []
        self.late_wakes: List[Tuple[float, float]] = []       # (time, seconds late)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-poller", daemon=True)

    def _run(self):
        last, woke = self._read(), self._clock()
        while not self._stop.wait(self._period):
            now = self._clock()
            if now - woke - self._period >= 0.05:
                self.late_wakes.append((now, now - woke - self._period))
            woke = now
            n = self._read()
            if n != last:
                self.events.append((now, n))
                last = n

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10)


class Heartbeat(DeliveryLog):
    """A poller with nothing to count: only its late wake-ups are read."""

    def __init__(self, period_s: float = 0.05, clock: Callable[[], float] = time.perf_counter):
        super().__init__(lambda: 0, period_s, clock)


class GcWatch:
    """Garbage collections that took ``at_least_s`` or longer, as (start, seconds, generation)."""

    def __init__(self, at_least_s: float = 0.05, clock: Callable[[], float] = time.perf_counter):
        self._at_least, self._clock, self._t0 = at_least_s, clock, None
        self.long: List[Tuple[float, float, int]] = []

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = self._clock()
        elif self._t0 is not None:
            took = self._clock() - self._t0
            if took >= self._at_least:
                self.long.append((self._t0, took, info.get("generation", -1)))
            self._t0 = None

    def start(self):
        gc.callbacks.append(self._on_gc)
        return self

    def stop(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    __enter__ = start

    def __exit__(self, *exc):
        self.stop()


def host_counters() -> Dict[str, float]:
    """The kernel's and the process's counters, read once at each edge of the
    window and never inside it: CPU seconds of the whole machine by state (a
    ``steal`` that grows is the hypervisor running someone else), this process's
    CPU seconds and involuntary context switches."""
    out: Dict[str, float] = {"process_cpu_s": time.process_time()}
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()[1:9]
        tick = float(os.sysconf("SC_CLK_TCK"))
        for name, v in zip(("user", "nice", "system", "idle", "iowait", "irq", "softirq", "steal"), fields):
            out["cpu_" + name + "_s"] = float(v) / tick
    except (OSError, ValueError):
        pass
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        out.update(involuntary_switches=float(ru.ru_nivcsw), major_faults=float(ru.ru_majflt))
    except (ImportError, OSError):
        pass
    return out


def gap_summary(times: Sequence[float], t_origin: Optional[float] = None, top: int = 3) -> dict:
    """Gaps between consecutive events: the median, the ``top`` longest with the
    offset (seconds after ``t_origin``, the window's opening event by default)
    at which each ended and its index, how many exceed 1.5 x the median and the
    seconds those hold in sum."""
    t_origin = times[0] if t_origin is None else t_origin
    gaps = [(b - a, b - t_origin, i + 1) for i, (a, b) in enumerate(zip(times, times[1:]))]
    if not gaps:
        return {"n": 0, "median_s": None, "longest": [], "over": 0, "over_s": 0.0}
    med = statistics.median(g for g, _, _ in gaps)
    over = [g for g, _, _ in gaps if g > 1.5 * med]
    return {"n": len(gaps), "median_s": med, "longest": sorted(gaps, reverse=True)[:top],
            "over": len(over), "over_s": sum(over)}


def longest_gap_ms(summary: dict) -> Optional[float]:
    """The longest gap of a ``gap_summary`` in milliseconds; nothing where the window held no two events."""
    return summary["longest"][0][0] * 1e3 if summary["longest"] else None


def stall_lines(what: str, summary: dict, occupancy: Optional[Sequence[float]] = None,
                late_wakes: Sequence[Tuple[float, float]] = (), gcs: Sequence[Tuple[float, float, int]] = (),
                t_open: float = 0.0, t_close: float = math.inf,
                at_open: Optional[Tuple[dict, dict]] = None,
                at_close: Optional[Tuple[dict, dict]] = None) -> List[str]:
    """The lines a run prints after its window, whatever it measured: enough to
    name a stall. ``occupancy[i]`` belongs to the gap that ends at event i;
    ``at_open`` and ``at_close`` are (compile cache stats, ``host_counters()``)
    as read at the window's two edges."""
    if not summary["n"]:
        return [f"stalls: no two {what} in the window"]
    def one(g, off, i):
        occ = f", slots {100.0 * occupancy[i]:.0f}%" if occupancy is not None else ""
        return f"{g * 1e3:.1f} ms ending {off:.2f} s in{occ}"
    lines = [f"stalls: {summary['n']} gaps between {what}, median {summary['median_s'] * 1e3:.1f} ms; longest "
             + "; ".join(one(*g) for g in summary["longest"]),
             f"stalls: {summary['over']} gaps over 1.5 x median hold {summary['over_s']:.3f} s in sum"]
    inside = lambda t: t_open <= t <= t_close
    wakes = [(t, late) for t, late in late_wakes if inside(t)]
    lines.append("stalls: the poller woke 50 ms or more late " + (
        f"{len(wakes)} times, {sum(l for _, l in wakes):.3f} s in sum, worst "
        + "; ".join(f"{l * 1e3:.0f} ms at {t - t_open:.2f} s" for t, l in sorted(wakes, key=lambda w: -w[1])[:3])
        if wakes else "never"))
    long_gcs = [(t, took, gen) for t, took, gen in gcs if inside(t)]
    lines.append("stalls: garbage collections over 50 ms " + (
        "; ".join(f"{took * 1e3:.0f} ms (generation {gen}) at {t - t_open:.2f} s" for t, took, gen in long_gcs)
        if long_gcs else "none"))
    if at_open is not None and at_close is not None:
        (compile_open, host_open), (compile_close, host_close) = at_open, at_close
        d = {k: compile_close[k] - compile_open[k] for k in ("hits", "misses", "backend_compile_s", "retrieval_s")}
        lines.append("stalls: compiled in the window " + (
            f"{d['misses']} programs ({d['backend_compile_s']:.2f} s), retrieved {d['hits']} ({d['retrieval_s']:.2f} s)"
            if d["hits"] or d["misses"] else "nothing, retrieved nothing"))
        d = {k: host_close[k] - host_open[k] for k in host_close if k in host_open}
        machine = {k[4:-2]: v for k, v in d.items() if k.startswith("cpu_")}
        busy = sum(machine.get(k, 0.0) for k in ("user", "nice", "system", "irq", "softirq"))
        lines.append("stalls: " + (
            f"machine CPU busy {busy:.2f} s, iowait {machine.get('iowait', 0.0):.2f} s, steal {machine.get('steal', 0.0):.2f} s"
            if any(machine.values()) else "the machine's CPU counters do not move here")
            + f"; process CPU {d.get('process_cpu_s', 0.0):.2f} s, {d.get('involuntary_switches', 0.0):.0f} "
            f"involuntary switches, {d.get('major_faults', 0.0):.0f} major faults")
    return lines


def delivery_window(events: Sequence[Tuple[float, int]], t_ready: float,
                    seconds: float) -> Tuple[int, int]:
    """Indices of the opening and closing delivery events."""
    opening = next((i for i, (t, _) in enumerate(events) if t >= t_ready), None)
    if opening is None:
        raise RuntimeError("no token was delivered after the ramp")
    limit = events[opening][0] + seconds
    closing = max(i for i, (t, _) in enumerate(events) if t <= limit)
    if closing <= opening:
        raise RuntimeError("the window holds a single delivery")
    return opening, closing


def delivery_rate(events: Sequence[Tuple[float, int]], opening: int, closing: int) -> float:
    (t0, c0), (t1, c1) = events[opening], events[closing]
    return (c1 - c0) / (t1 - t0)


def ttft_from_due(requests: Sequence[dict], t_open: float, t_close: float) -> List[float]:
    """First-token time minus DUE time of every request due in the window; a
    request that failed, was refused or never got a token is beyond any limit."""
    out = []
    for r in requests:
        if t_open <= r["due"] <= t_close:
            ok = r.get("error") is None and r.get("t_first") is not None
            out.append(r["t_first"] - r["due"] if ok else math.inf)
    return out


def delivery_occupancy(events: Sequence[Tuple[float, int]], slot_steps: int) -> List[float]:
    """Share of a chunk's slot-steps that delivered a token, for the delivery
    at each index (the first has no earlier count: nan). The counter also holds
    the first token of each request admitted before the chunk, so one full
    delivery can read a little over 1."""
    return [math.nan] + [(c1 - c0) / slot_steps for (_, c0), (_, c1) in zip(events, events[1:])]
