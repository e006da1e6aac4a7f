"""The benchmark's tests leave the process as they found it: other test files
run after them in the same worker. Checked after every test of this directory:
the persistent compile cache is not left armed, jax's configuration, the
environment, ``gc.callbacks`` and ``sys.path`` are as before, and no thread of
the benchmark (poller, load generator, heartbeat) or of an engine it started
is still alive."""

import gc
import os
import sys
import threading

import pytest

_JAX_KEYS = ("jax_compilation_cache_dir", "jax_enable_x64", "jax_default_matmul_precision",
             "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")


def _state():
    import jax

    from paddlepaddle_tpu.core import compile_cache

    return {"jax": {k: getattr(jax.config, k) for k in _JAX_KEYS},
            "cache_armed": compile_cache.stats()["enabled"],
            "environ": dict(os.environ), "gc": list(gc.callbacks), "path": list(sys.path)}


@pytest.fixture(autouse=True)
def process_left_as_found():
    before = _state()
    before["environ"].pop("PYTEST_CURRENT_TEST", None)
    threads = set(threading.enumerate())
    yield
    import jax

    from paddlepaddle_tpu.core import compile_cache

    after = _state()
    after["environ"].pop("PYTEST_CURRENT_TEST", None)
    # undo first, so that one test's leak cannot reach the files that follow
    if after["cache_armed"] and not before["cache_armed"]:
        compile_cache.uninstall()
    for k, v in before["jax"].items():
        if after["jax"][k] != v:
            jax.config.update(k, v)
    for k in set(before["environ"]) | set(after["environ"]):
        if k not in before["environ"]:
            os.environ.pop(k, None)
        elif after["environ"].get(k) != before["environ"][k]:
            os.environ[k] = before["environ"][k]
    gc.callbacks[:] = before["gc"]
    sys.path[:] = before["path"]
    left = [t.name for t in threading.enumerate() if t not in threads and t.is_alive()
            and (t.name.startswith("bench-") or "serving" in t.name.lower() or "engine" in t.name.lower())]
    env_keys = sorted(k for k in set(before["environ"]) | set(after["environ"])
                      if before["environ"].get(k) != after["environ"].get(k)
                      and k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU", "PADDLE", "FLAGS_", "BENCH")))
    assert not env_keys, f"environment variables changed: {env_keys}"
    rest = {k: (before[k], after[k]) for k in before if k != "environ" and before[k] != after[k]}
    assert not rest, rest
    assert not left, left
