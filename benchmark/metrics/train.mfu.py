"""Required forward + backward operations per token x tokens/s over chips x peak; recomputation not counted."""
from benchmark import work


def read(obs):
    ops = work.counts(obs).train_ops_per_step(obs["config"], obs["rows"], obs["tokens_per_row"])
    per_token = ops / obs["tokens_per_step"]
    peak = work.peaks(obs["device_kind"])["flops_per_s"] * obs["chips"]
    return 100.0 * per_token * obs["end_to_end"]["train_tokens_per_s"] / peak
