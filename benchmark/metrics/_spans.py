"""Shared by the readers of the serving loop's phase counters (``span_s.*``, ``span_n.*``, ``turnaround_*``,
``loop_busy_s`` in ``ServingEngine.stats``). The benchmark copies ``stats`` before the generator starts and after the
window's tail, so a reader is a ratio of two differences, never a sum over the window."""


def delta(obs, *keys):
    """Sum over ``keys`` of after - before; None where a copy lacks one (a program without the counters)."""
    before, after = obs.get("stats_before") or {}, obs.get("stats_after") or {}
    if any(k not in before or k not in after for k in keys):
        return None
    return sum(after[k] - before[k] for k in keys)


def ratio(obs, over, under, scale):
    num, den = delta(obs, *over), delta(obs, under)
    return scale * num / den if num is not None and den else None
