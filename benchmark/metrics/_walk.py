"""Shared by the readers of a decode walk's share of its roofline: the least time of the kernel's calls in the traced
window (each one layer's walk over every live slot, counted by the family's ``decode_walk_counts``: the keys it must
read and each slot's query, new row and output) over their device time. The live slots and the tokens they hold are
the window's means (``_serve.live_context``). None where no such call ran or the family counts no such walk."""
from benchmark import trace, work
from benchmark.metrics import _kernels, _serve


def roofline(obs, kernel, window):
    counts = work.counts(obs)
    hit = trace.matching(obs["trace"], _kernels.call((kernel,), "decode"))
    if hit is None or not hit[0] or not hasattr(counts, "decode_walk_counts"):
        return None
    tokens, slots = _serve.live_context(obs, obs["trace_t0"], obs["trace_t1"])
    ops, nbytes = counts.decode_walk_counts(obs["config"], window, tokens, slots)
    return 100.0 * hit[1] * work.roofline_least_s(ops, nbytes, work.peaks(obs["device_kind"])) / hit[0]
