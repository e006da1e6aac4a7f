"""Test configuration: force an 8-device virtual CPU platform BEFORE jax
import so sharding/mesh tests run without TPU hardware (the analogue of the
reference's fake_cpu_device plugin used in test/custom_runtime/). Pallas
kernels run under the interpreter here; Mosaic sees them in chip_smoke.py."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # the tests never take the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    import paddlepaddle_tpu as paddle

    paddle.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture(autouse=True)
def _per_test_timeout():
    """Per-test wall-clock watchdog (the reference pins per-test TIMEOUT
    labels in CMake, test/collective/CMakeLists.txt:1-4): a hung collective
    or runaway compile fails THAT test instead of stalling the whole run."""
    import signal

    seconds = int(os.environ.get("PADDLE_TPU_TEST_TIMEOUT", "300"))
    armed = seconds > 0 and hasattr(signal, "SIGALRM")

    def _on_timeout(signum, frame):
        raise TimeoutError(f"test exceeded {seconds}s watchdog "
                           f"(PADDLE_TPU_TEST_TIMEOUT to adjust)")

    old = signal.signal(signal.SIGALRM, _on_timeout) if armed else None
    if armed:
        signal.alarm(seconds)
    try:
        yield
    finally:
        if armed:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def can_serialize_executables() -> bool:
    """Whether this jaxlib's client can serialize the compiled programs of
    an engine that has served — what AOT serving bundles are made of. The
    installed CPU client answers UNIMPLEMENTED ("`LessThan` is not
    serializable") for an executable that has already RUN a
    sort-by-comparator, and every engine program holds a full-width
    ``lax.top_k`` (the sampler's filtering branch); the TPU client
    round-trips them, persistent-cache hits included (PR 21, chip run)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable

    x = jnp.zeros((3, 128))
    compiled = jax.jit(lambda x: jax.lax.top_k(x, 128)).lower(x).compile()
    # wait for the run: it is the run that binds the sort's comparator, and
    # dispatch is asynchronous, so a busy host would else serialize first
    jax.block_until_ready(compiled(x))
    try:
        serialize_executable.serialize(compiled)
    except jax.errors.JaxRuntimeError as e:
        if "UNIMPLEMENTED" in str(e):
            return False
        raise
    return True


@pytest.fixture
def needs_bundles(can_serialize_executables):
    if not can_serialize_executables:
        pytest.skip("this jaxlib's CPU client cannot serialize an executable "
                    "that has run a full-width top_k (UNIMPLEMENTED), so a "
                    "served engine has no bundle to save")


# tests/benchmark/test_window_and_traffic.py (PR 27) holds EVERY traffic file to
# prompt + output <= 4,096, the `max_len` every serving configuration had until
# PR 36. `longdoc-saturated.json` (ISSUE 36: 8k-16k documents under a `max_len`
# of 16,896) cannot meet that line, and a file under BENCHMARK.json's `paths` is
# only a `benchmark` PR's to edit (it should read the cap from the cell's
# configuration). Until then that ONE parametrised case is expected to fail, and
# STRICTLY: it has to fail, with an AssertionError (the cap is its last line and
# the only one this file can fail), or the run fails; the same invariants (the
# schedule fixed by the file, a longer horizon only appending, every request
# inside the configuration's own `max_len`) are held by
# tests/benchmark/test_kimi_family.py against 16,896.
_STALE_CAP = ("test_window_and_traffic.py::test_traffic_file_fixes_work_and_schedule"
              "[longdoc-saturated.json]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_STALE_CAP):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="a 4,096-token cap written for PR 27's configurations; "
                       "this cell's is 16,896 (see the comment above)"))
