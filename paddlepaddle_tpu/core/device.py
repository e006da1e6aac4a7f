"""Device / place abstraction.

Reference surface: paddle.device.set_device/get_device, CPUPlace/CUDAPlace/
XPUPlace (paddle/phi/common/place.h). Here places name jax devices; "tpu" is
first-class ("gpu" is accepted as an alias for the accelerator for script
compatibility, mapping to the default jax backend device).
"""

from __future__ import annotations

import jax

_current_device = None


class Place:
    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.index == other.index
        )

    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_gpu_place(self):
        return self.kind == "gpu"


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(idx=0):
    return Place("tpu", idx)


def CUDAPlace(idx=0):  # script compat: maps to accelerator place
    return Place(jax.default_backend(), idx)


def _parse(device: str):
    if ":" in device:
        kind, idx = device.split(":")
        return kind, int(idx)
    return device, 0


def _resolve_jax_device(device: str):
    kind, idx = _parse(device)
    if kind in ("gpu", "cuda", "accelerator"):
        # script compat: the accelerator alias is whatever jax defaults to
        devs = jax.devices()
    else:
        devs = jax.devices(kind)
    return devs[idx]


def set_device(device: str):
    """Make ``device`` the default placement. A platform this machine does
    not have raises (jax's RuntimeError) and leaves the setting as it was:
    ``set_device("tpu")`` does not "succeed" on a machine without one."""
    global _current_device
    jax.config.update("jax_default_device", _resolve_jax_device(device))
    _current_device = device
    return get_device()


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def device_count(kind=None) -> int:
    return len(jax.devices(kind) if kind else jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def to_device(data, device: str):
    return jax.device_put(data, _resolve_jax_device(device))


def _place_of(data) -> Place:
    try:
        dev = list(data.devices())[0]
        return Place(dev.platform, dev.id)
    except Exception:
        return Place(jax.default_backend(), 0)
