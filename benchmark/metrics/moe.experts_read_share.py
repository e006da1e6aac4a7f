"""Held experts whose weights a decode step's expert layer read, mean over the window's layer-steps with a live slot,
as a share of the experts held: those the step's rows touched where the few-row kernel reads the touched experts alone
(an idle lane's picks among them), all of them where a dense form reads every held expert. None on a program without
the counter (``moe_experts_read``), which is not the same as reading every expert."""
from benchmark.metrics import _moe, _spans


def read(obs):
    got, steps = _spans.delta(obs, "moe_experts_read"), _moe.layer_steps(obs)
    held = (obs.get("stats_after") or {}).get("moe_experts_held")
    return 100.0 * got / (steps * held) if got is not None and steps and held else None
