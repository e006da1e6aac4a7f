"""Of the top-k picks the decode steps of the window made (live slots, every expert layer), the share that landed on an
expert this chip holds: ``moe_picks_held`` over ``moe_picks_total`` between the two copies of ``ServingEngine.stats``
(3.125% where the routing is even over 384 experts and 12 are held; what the absent experts would add is left out).
Nothing to read on a program without the counters."""
from benchmark.metrics import _spans


def read(obs):
    return _spans.ratio(obs, ("moe_picks_held",), "moe_picks_total", 100.0)
