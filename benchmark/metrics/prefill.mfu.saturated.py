"""``prefill.mfu`` in a cell above its knee: operations of the prompt tokens the admissions of the traced window had to
compute (a later turn's document counted as cached), over their device time and the peak."""
from benchmark import work
from benchmark.metrics import _serve


def read(obs):
    seconds, calls = _serve.module_seconds(obs, _serve.ADMIT_MODULES)
    t0, t1 = obs["trace_t0"], obs["trace_t1"]
    reqs = [r for r in obs["requests"] if r["t_admit"] is not None and t0 <= r["t_admit"] <= t1]
    if not calls or not reqs:
        return None
    return 100.0 * _serve.prompt_ops(obs, reqs) / seconds / work.peaks(obs["device_kind"])["flops_per_s"]
