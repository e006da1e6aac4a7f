"""Device time of the admission programs in the traced window over their runs."""
from benchmark.metrics import _serve


def read(obs):
    seconds, calls = _serve.module_seconds(obs, _serve.ADMIT_MODULES)
    return 1e3 * seconds / calls if calls else None
