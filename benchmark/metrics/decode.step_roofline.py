"""Least time of a decode step (every weight and the live KV read once, or its operations) over its device time."""
from benchmark import work
from benchmark.metrics import _serve


def read(obs):
    seconds, calls = _serve.module_seconds(obs, _serve.DECODE_MODULES)
    if not calls:
        return None
    tokens, slots = _serve.live_context(obs, obs["trace_t0"], obs["trace_t1"])
    least = work.counts(obs).decode_step_least_s(obs["config"], tokens, slots, work.peaks(obs["device_kind"]))
    return 100.0 * least / (seconds / (calls * obs["decode_chunk"]))
