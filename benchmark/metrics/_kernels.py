"""Shared by the readers of one kernel's part of a program: the device time of the Mosaic calls a program names after
the kernel and itself (``ops.kernels.call_name``: ``%<kernel>_<program>.N``, the program ``decode`` or ``admit``) over
the runs of that program's XLA modules, so that the part and the whole it belongs to read over the same runs. None
where no call carries the name (a program whose calls are unnamed, ``%_unknown_.N``) or no run of the modules lies in
the traced window."""
from benchmark import trace
from benchmark.metrics import _serve


def call(kernels, program):
    """The HLO text of a Mosaic call named by one of ``kernels`` inside ``program``."""
    return rf'^%(?:{"|".join(kernels)})_{program}(?:\.\d+)? = .*custom_call_target="tpu_custom_call"'


def ms_per_run(obs, pattern, modules, steps_per_run=1):
    hit = trace.matching(obs["trace"], pattern)
    _, runs = _serve.module_seconds(obs, modules)
    if hit is None or not runs:
        return None
    return 1e3 * hit[0] / (runs * steps_per_run)
