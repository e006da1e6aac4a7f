"""Median time from a decode slot to the first token on the host (the engine's own admit and first-token stamps) of
requests due in the window: the part of TTFT that follows ``sched.queue_wait_p50_ms``."""
from benchmark import window
from benchmark.metrics import _serve


def read(obs):
    waits = [(r["t_first"] - r["t_admit"]) * 1e3 for r in _serve.in_window(obs, "due")
             if r["t_admit"] is not None and r["t_first"] is not None]
    return window.percentile(waits, 50) if waits else None
