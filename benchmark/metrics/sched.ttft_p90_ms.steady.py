"""Nearest-rank 90th percentile over every request due in the window of first-token time minus due time, below the
knee: the tail beside ``ttft_mean_ms``. It is not judged end to end because over the ~190 requests of a run it reads
0.5% or 3.4% apart from run to run by whether the runs' timelines part (PERF.md section 6). A missed request leaves none."""
import math

from benchmark import window


def read(obs):
    ttfts = window.ttft_from_due(obs["requests"], obs["t_open"], obs["t_close"])
    p90 = window.percentile(ttfts, 90) if ttfts else math.inf
    return p90 * 1e3 if math.isfinite(p90) else None
