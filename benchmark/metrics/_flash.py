"""Shared by the two flash rooflines: least time of the kernel's calls in the traced window over their device time."""
from benchmark import trace, work


def roofline(obs, pattern, ops_fn, calls_per_event):
    hit = trace.matching(obs["trace"], pattern)
    if hit is None:
        return None
    seconds, events = hit
    cfg, pk = obs["config"], work.peaks(obs["device_kind"])
    rows = obs["rows"] // obs["chips"] if obs["chips"] > 1 else obs["rows"]
    least = work.roofline_least_s(ops_fn(cfg, rows, obs["tokens_per_row"]),
                                  work.flash_bytes(cfg, rows, obs["tokens_per_row"]), pk)
    return 100.0 * events * calls_per_event * least / seconds
