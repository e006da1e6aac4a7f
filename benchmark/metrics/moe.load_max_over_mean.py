"""The busiest held expert's pairs over the mean over the held experts, across the window's decode steps: 1 is an even
load; the busiest expert's chip is what an expert-parallel step waits for."""
from benchmark.metrics import _moe


def read(obs):
    pairs = _moe.expert_pairs(obs)
    if not pairs or not sum(pairs):
        return None
    return max(pairs) * len(pairs) / sum(pairs)
