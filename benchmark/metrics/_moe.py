"""Shared by the readers of the expert shares' pick counters (``moe_*`` in ``ServingEngine.stats``, which the decode
program's packed payload brings with each chunk's one sync). Like every phase counter they are cumulative, and a reader
is a ratio of two differences between the copies of ``stats`` before and after the window."""
from benchmark.metrics import _spans

PICKS = ("moe_picks_zero", "moe_picks_held", "moe_picks_absent")


def expert_pairs(obs):
    """Pairs each held expert got between the two copies; None where a copy lacks the counter."""
    before, after = obs.get("stats_before") or {}, obs.get("stats_after") or {}
    if "moe_expert_pairs" not in before or "moe_expert_pairs" not in after:
        return None
    return [b - a for a, b in zip(before["moe_expert_pairs"], after["moe_expert_pairs"])]


def layer_steps(obs):
    return _spans.delta(obs, "moe_layer_steps")
