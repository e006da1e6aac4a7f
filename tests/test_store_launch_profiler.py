"""Native TCPStore, launch CLI, profiler, fft tests."""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddlepaddle_tpu as paddle


def test_tcp_store_native_roundtrip():
    from paddlepaddle_tpu.distributed.store import TCPStore

    s = TCPStore(is_master=True)
    c = TCPStore(port=s.port)
    c.set("k", b"v1")
    assert s.get("k") == b"v1"
    assert c.add("cnt", 2) == 2
    assert s.add("cnt", 3) == 5
    assert s.check("k") and not c.check("nope")

    res = {}
    t = threading.Thread(target=lambda: res.update(v=c.get("slow")))
    t.start()
    s.set("slow", b"done")
    t.join(10)
    assert res.get("v") == b"done"


def test_tcp_store_large_value():
    """Values beyond any fixed staging buffer round-trip exactly (the native
    path uses a fetch/copy two-call protocol sized to the actual value)."""
    from paddlepaddle_tpu.distributed.store import TCPStore

    s = TCPStore(is_master=True)
    big = bytes(range(256)) * (5 * 4096)  # 5 MiB
    s.set("big", big)
    assert s.get("big") == big
    s.set("empty", b"")
    assert s.get("empty") == b""

    # concurrent gets on ONE store must not cross-contaminate (the native
    # fetch/copy pair is serialized by a lock)
    s.set("a", b"A" * 100_000)
    s.set("b", b"B" * 50_000)
    results = {}

    def getter(key):
        for _ in range(20):
            results.setdefault(key, set()).add(s.get(key))

    ts = [threading.Thread(target=getter, args=(k,)) for k in ("a", "b") * 2]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert results["a"] == {b"A" * 100_000} and results["b"] == {b"B" * 50_000}


def test_tcp_store_rank_assignment():
    """The reference bootstrap pattern: ranks self-assign via atomic add."""
    from paddlepaddle_tpu.distributed.store import TCPStore

    master = TCPStore(is_master=True)
    ranks = []

    def worker():
        c = TCPStore(port=master.port)
        ranks.append(c.add("next_rank", 1) - 1)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert sorted(ranks) == [0, 1, 2, 3]


def test_launch_cli_runs_script(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(
        "import os, sys\n"
        "assert 'PADDLE_TRAINER_ID' in os.environ\n"
        "assert 'MASTER_PORT' in os.environ\n"
        "sys.stdout.write('worker %s of %s\\n' % (os.environ['PADDLE_TRAINER_ID'],\n"
        "                 os.environ['PADDLE_TRAINERS_NUM']))\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "paddlepaddle_tpu.distributed.launch",
         "--nproc_per_node", "2", str(script)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "worker 0 of 2" in out.stdout
    assert "worker 1 of 2" in out.stdout


def test_launch_restart_on_failure(tmp_path):
    marker = tmp_path / "marker"
    script = tmp_path / "flaky.py"
    script.write_text(
        f"import os, sys\n"
        f"m = {str(marker)!r}\n"
        f"if not os.path.exists(m):\n"
        f"    open(m, 'w').close()\n"
        f"    sys.exit(1)\n"
        f"print('recovered')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "paddlepaddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restarts", "1", str(script)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "recovered" in out.stdout


def test_record_event_and_summary():
    from paddlepaddle_tpu.profiler import Profiler, RecordEvent

    prof = Profiler(timer_only=True).start()
    with RecordEvent("my_region"):
        _ = paddle.to_tensor(np.ones((4, 4), np.float32)) * 2
    prof.step()
    prof.stop()
    out = prof.summary()
    assert "my_region" in out


def test_make_scheduler():
    from paddlepaddle_tpu.profiler import ProfilerState, make_scheduler

    sched = make_scheduler(closed=1, ready=1, record=2, skip_first=1)
    states = [sched(i) for i in range(6)]
    assert states[0] == ProfilerState.CLOSED        # skip_first
    assert states[1] == ProfilerState.CLOSED
    assert states[2] == ProfilerState.READY
    assert states[3] == ProfilerState.RECORD
    assert states[4] == ProfilerState.RECORD_AND_RETURN


def test_fft_roundtrip():
    x = np.random.default_rng(0).standard_normal(16).astype(np.float32)
    X = paddle.fft.fft(paddle.to_tensor(x))
    x2 = paddle.fft.ifft(X)
    np.testing.assert_allclose(np.asarray(x2.numpy()).real, x, atol=1e-5)
    np.testing.assert_allclose(np.asarray(X.numpy()),
                               np.fft.fft(x), rtol=1e-4, atol=1e-4)
    r = paddle.fft.rfft(paddle.to_tensor(x))
    np.testing.assert_allclose(np.asarray(r.numpy()), np.fft.rfft(x), rtol=1e-4, atol=1e-4)
