"""Share of admitted prompt tokens that a prefix hit spared: the engine's hit count times the mean shareable prefix."""
from benchmark.metrics import _serve


def read(obs):
    page = obs["config"]["engine"]["kv_page_size"]
    admitted = [r for r in obs["requests"] if r["t_admit"] is not None]
    declared = [_serve.aligned_prefix(r, page) for r in admitted if r["prefix_len"]]
    hits = obs["kv_after"]["prefix"]["hits"] - obs["kv_before"]["prefix"]["hits"]
    if not declared or not admitted:
        return None
    return 100.0 * hits * (sum(declared) / len(declared)) / sum(r["prompt"] for r in admitted)
