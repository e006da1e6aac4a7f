"""Share of the prompt tokens admitted between the two copies of ``ServingEngine.stats`` that a prefix hit took from
the cache, by the engine's own count of each admission (``admit_tokens_cached`` over cached + computed, whole prompts
and tails alike): a document evicted between two turns and computed again counts as computed. Nothing to read on a
program without the counters, or where nothing was admitted."""
from benchmark.metrics import _spans


def read(obs):
    cached = _spans.delta(obs, "admit_tokens_cached")
    computed = _spans.delta(obs, "admit_tokens_computed.whole", "admit_tokens_computed.prefix_hit")
    if cached is None or computed is None or not cached + computed:
        return None
    return 100.0 * cached / (cached + computed)
