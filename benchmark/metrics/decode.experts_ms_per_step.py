"""The held experts' kernel in the decode step, by its calls in the decode program (``held_experts_decode``; the same
kernel's calls in a short admission, ``held_experts_admit``, are not counted), over the same steps as
``decode.device_ms_per_step``: the part of that step the held experts take where they run through the kernel."""
from benchmark.metrics import _kernels, _serve

PATTERN = _kernels.call(("held_experts",), "decode")


def read(obs):
    return _kernels.ms_per_run(obs, PATTERN, _serve.DECODE_MODULES, obs["decode_chunk"])
