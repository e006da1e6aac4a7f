"""Device time of the decode program in the traced window over its steps (runs x decode_chunk)."""
from benchmark.metrics import _serve


def read(obs):
    seconds, calls = _serve.module_seconds(obs, _serve.DECODE_MODULES)
    return 1e3 * seconds / (calls * obs["decode_chunk"]) if calls else None
