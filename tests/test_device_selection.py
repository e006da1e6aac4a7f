"""Nothing hides the device: unknown accelerators raise, an absent platform
cannot be selected, importing the package takes no chip, and a launcher
refuses to start children that would fight over one."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peak_table_is_keyed_by_reported_kind_and_raises_on_a_miss():
    from paddlepaddle_tpu.observability.perf import device

    v5e = device.specs(_Dev("tpu", "TPU v5 lite"))
    assert v5e["peak_flops"] == 197e12
    assert v5e["peak_hbm_bytes_per_s"] == 819e9
    # "v5" as a substring used to match kinds nobody listed
    with pytest.raises(KeyError, match="TPU v5 mega"):
        device.peak_flops(_Dev("tpu", "TPU v5 mega"))
    with pytest.raises(KeyError, match="H100"):
        device.peak_hbm_bytes_per_s(_Dev("gpu", "NVIDIA H100"))
    assert device.peak_flops(_Dev("cpu", "cpu")) == 1e12


def test_set_device_refuses_a_platform_this_machine_lacks():
    import jax

    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.core import device as core_device

    before = paddle.get_device()
    with pytest.raises(RuntimeError):
        paddle.set_device("tpu")
    assert paddle.get_device() == before
    try:
        assert paddle.set_device("cpu:1") == "cpu:1"
    finally:
        core_device._current_device = None
        jax.config.update("jax_default_device", None)


def test_importing_the_package_initialises_no_backend():
    code = ("from jax._src import xla_bridge as xb\n"
            "import paddlepaddle_tpu\n"
            "import paddlepaddle_tpu.distributed.launch\n"
            "import paddlepaddle_tpu.inference.remote_replica\n"
            "assert not xb._backends, list(xb._backends)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                          capture_output=True, text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_chip_contention_is_refused_before_any_child_starts(monkeypatch):
    from jax._src import hardware_utils

    from paddlepaddle_tpu.distributed.env import refuse_chip_contention

    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (4, None))
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="--nproc_per_node 2 on a host "
                                         "with 4 TPU chip"):
        refuse_chip_contention(2, "--nproc_per_node")
    refuse_chip_contention(1, "--nproc_per_node")       # one child: fine
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    refuse_chip_contention(2, "--nproc_per_node")       # CPU children: fine
    # and a host without chips starts what it is asked to
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (0, None))
    refuse_chip_contention(2, "--replicas")
