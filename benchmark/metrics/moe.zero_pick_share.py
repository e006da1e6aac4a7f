"""Of the top-k picks the decode steps of the window made (live slots, every expert layer), the share that chose a
zero-compute (identity) expert: matrix work a token does not cost. Nothing to read on a program without the counters."""
from benchmark.metrics import _moe, _spans


def read(obs):
    zero, picks = _spans.delta(obs, "moe_picks_zero"), _spans.delta(obs, *_moe.PICKS)
    return 100.0 * zero / picks if picks else None
