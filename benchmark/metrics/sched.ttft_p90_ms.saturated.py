"""First token minus due time above the knee: the backlog's depth, never judged."""
from benchmark import window
from benchmark.metrics import _serve


def read(obs):
    t = [(r["t_first"] - r["due"]) * 1e3 for r in _serve.in_window(obs, "due") if r["t_first"] is not None]
    return window.percentile(t, 90) if t else None
