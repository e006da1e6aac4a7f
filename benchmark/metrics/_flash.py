"""Shared by the two flash rooflines: least time of the kernel's calls in the traced window over their device time."""
from benchmark import trace, work


def roofline(obs, pattern, ops_name, calls_per_event):
    """``ops_name`` is the family's count of one kernel call's operations, by name (``flash_forward_ops``)."""
    hit = trace.matching(obs["trace"], pattern)
    if hit is None:
        return None
    seconds, events = hit
    cfg, pk, counts = obs["config"], work.peaks(obs["device_kind"]), work.counts(obs)
    rows = obs["rows"] // obs["chips"] if obs["chips"] > 1 else obs["rows"]
    least = work.roofline_least_s(getattr(counts, ops_name)(cfg, rows, obs["tokens_per_row"]),
                                  counts.flash_bytes(cfg, rows, obs["tokens_per_row"]), pk)
    return 100.0 * events * calls_per_event * least / seconds
