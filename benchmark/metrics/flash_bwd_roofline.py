"""The two flash backward kernels (dq; dk and dv) together against one layer's backward."""
from benchmark.metrics import _flash

PATTERN = r'^%transpose_jvp___\.\d+ = .*custom_call_target="tpu_custom_call"'


def read(obs):
    # a layer's backward is two kernel events: each stands for half of it
    return _flash.roofline(obs, PATTERN, "flash_backward_ops", 0.5)
