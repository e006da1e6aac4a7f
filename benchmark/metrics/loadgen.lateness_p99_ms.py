"""How late the generator submitted (submission minus due time), 99th percentile over the window's requests."""
from benchmark import window
from benchmark.metrics import _serve


def read(obs):
    late = [(r["submitted"] - r["due"]) * 1e3 for r in _serve.in_window(obs, "due") if r["submitted"] is not None]
    return window.percentile(late, 99) if late else None
