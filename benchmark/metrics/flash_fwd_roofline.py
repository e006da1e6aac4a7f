"""The flash forward kernel's share of its roofline (compute-bound at 4k rows). The trace names a Mosaic call after the
jax transformation around it, not after the kernel: ``jvp`` is the forward, ``transpose(jvp)`` the two backward kernels."""
from benchmark.metrics import _flash

PATTERN = r'^%jvp__\.\d+ = .*custom_call_target="tpu_custom_call"'


def read(obs):
    return _flash.roofline(obs, PATTERN, "flash_forward_ops", 1.0)
