"""``prefill.device_ms_per_request`` in a cell above its knee: device time of the admission programs in the traced
window over their runs (whole-document prefills and questions behind a cached document alike)."""
from benchmark.metrics import _serve


def read(obs):
    seconds, calls = _serve.module_seconds(obs, _serve.ADMIT_MODULES)
    return 1e3 * seconds / calls if calls else None
