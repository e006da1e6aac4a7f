"""Median wait from submission to a decode slot (the engine's own admit stamp) of requests due in the window."""
from benchmark import window
from benchmark.metrics import _serve


def read(obs):
    waits = [(r["t_admit"] - r["submitted"]) * 1e3 for r in _serve.in_window(obs, "due") if r["t_admit"] is not None]
    return window.percentile(waits, 50) if waits else None
