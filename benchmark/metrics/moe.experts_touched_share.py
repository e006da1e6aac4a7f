"""Held experts with at least one (token, expert) pair in a decode step's expert layer, mean over the window's
layer-steps with a live slot, as a share of the experts held: the expert weights a step has to read."""
from benchmark.metrics import _moe, _spans


def read(obs):
    touched, steps = _spans.delta(obs, "moe_experts_touched"), _moe.layer_steps(obs)
    held = (obs.get("stats_after") or {}).get("moe_experts_held")
    return 100.0 * touched / (steps * held) if steps and held else None
