"""Fault-tolerant runtime: chaos injection, retry/backoff, preemption
handling, checkpoint integrity (resilience/)."""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.distributed import checkpoint as dist_ckpt
from paddlepaddle_tpu.observability import get_registry
from paddlepaddle_tpu.resilience import chaos
from paddlepaddle_tpu.resilience.chaos import ChaosError, chaos_point
from paddlepaddle_tpu.resilience.integrity import (
    CheckpointCorruptionError,
    CheckpointManager,
    find_latest_valid_checkpoint,
    validate_checkpoint,
)
from paddlepaddle_tpu.resilience.retry import (
    RetryPolicy,
    call_with_retry,
    compute_delay,
    retry,
)

REPO = str(Path(__file__).resolve().parent.parent)

# the whole module is part of the chaos suite (tools/run_chaos.sh); it stays
# in tier-1 too — these are fast, in-process unit tests
pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _chaos_off():
    """Every test starts and ends with chaos disarmed (module-global)."""
    chaos.disable()
    yield
    chaos.disable()


def _counter_value(name, **labels):
    m = get_registry().get(name)
    return m.value(**labels) if m is not None else 0


# -- chaos engine ------------------------------------------------------------

def test_chaos_spec_parsing():
    specs = chaos.parse_specs(
        "store.get:exc:0.25; ckpt.write_shard:latency:@3:0.2,"
        "step:kill:%4:7")
    assert [(s.point, s.mode, s.sched_kind, s.sched_value) for s in specs] == [
        ("store.get", "exc", "prob", 0.25),
        ("ckpt.write_shard", "latency", "at", 3.0),
        ("step", "kill", "every", 4.0),
    ]
    assert specs[1].arg == 0.2 and specs[2].arg == 7.0
    with pytest.raises(ValueError, match="needs name:mode:sched"):
        chaos.parse_specs("store.get:exc")
    with pytest.raises(ValueError, match="not in exc"):
        chaos.parse_specs("store.get:boom:0.5")


def test_chaos_exact_hit_schedule():
    chaos.configure("p:exc:@3")
    chaos_point("p")
    chaos_point("p")
    with pytest.raises(ChaosError, match="chaos injected at 'p'"):
        chaos_point("p")
    chaos_point("p")  # only the 3rd hit fires
    assert chaos.fire_counts() == {"p": 1}
    assert chaos.hit_counts()["p"] == 4


def test_chaos_first_n_and_every_n_schedules():
    chaos.configure("a:exc:x2; b:exc:%3")
    fired = []
    for point in ("a", "a", "a", "b", "b", "b", "b", "b", "b"):
        try:
            chaos_point(point)
            fired.append(0)
        except ChaosError:
            fired.append(1)
    #     a  a  a  b  b  b  b  b  b
    assert fired == [1, 1, 0, 0, 0, 1, 0, 0, 1]


def test_chaos_probability_is_seed_deterministic():
    def decisions(seed):
        chaos.configure("p:exc:0.5", seed=seed)
        out = []
        for _ in range(40):
            try:
                chaos_point("p")
                out.append(0)
            except ChaosError:
                out.append(1)
        return out

    a, b = decisions(1234), decisions(1234)
    assert a == b  # reproducible
    assert 0 < sum(a) < 40  # actually probabilistic
    assert decisions(99) != a  # and seed-sensitive


def test_chaos_latency_mode_sleeps():
    chaos.configure("p:latency:x1:0.15")
    t0 = time.perf_counter()
    chaos_point("p")
    assert time.perf_counter() - t0 >= 0.14


def test_chaos_disabled_is_noop():
    chaos.disable()
    for _ in range(3):
        chaos_point("anything")  # no engine, no error, no state


def test_chaos_injection_metrics():
    chaos.configure("p:exc:x1")
    before = _counter_value("paddle_chaos_injections_total",
                            point="p", mode="exc")
    with pytest.raises(ChaosError):
        chaos_point("p")
    assert _counter_value("paddle_chaos_injections_total",
                          point="p", mode="exc") == before + 1


# -- retry/backoff -----------------------------------------------------------

def test_retry_backoff_timing_and_success():
    delays = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 4:
            raise ConnectionError("transient")
        return "ok"

    policy = RetryPolicy(max_attempts=5, base_delay=0.1, multiplier=2.0,
                         max_delay=10.0, jitter=0.0)
    out = call_with_retry(flaky, policy=policy, sleep=delays.append)
    assert out == "ok" and len(calls) == 4
    # exponential: 0.1, 0.2, 0.4 (no jitter)
    np.testing.assert_allclose(delays, [0.1, 0.2, 0.4])


def test_retry_jitter_bounded_and_capped():
    import random

    policy = RetryPolicy(base_delay=0.1, multiplier=2.0, max_delay=0.25,
                         jitter=0.5)
    rng = random.Random(0)
    for attempt, base in [(1, 0.1), (2, 0.2), (3, 0.25), (9, 0.25)]:
        for _ in range(20):
            d = compute_delay(policy, attempt, rng)
            assert base <= d <= base * 1.5


def test_retry_exhaustion_raises_last_error():
    def always_fails():
        raise TimeoutError("still down")

    with pytest.raises(TimeoutError, match="still down"):
        call_with_retry(always_fails,
                        policy=RetryPolicy(max_attempts=3, base_delay=0.001))


def test_retry_non_retryable_propagates_immediately():
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        call_with_retry(bad, policy=RetryPolicy(max_attempts=5,
                                                base_delay=0.001))
    assert len(calls) == 1  # no retry on non-transient errors


def test_retry_deadline_stops_early():
    delays = []
    calls = []

    def flaky():
        calls.append(1)
        raise ConnectionError("down")

    policy = RetryPolicy(max_attempts=100, base_delay=10.0, jitter=0.0,
                         deadline=1.0)
    with pytest.raises(ConnectionError):
        call_with_retry(flaky, policy=policy, sleep=delays.append)
    assert len(calls) == 1 and delays == []  # first backoff would bust it


def test_retry_decorator_and_metrics():
    calls = []

    @retry(RetryPolicy(max_attempts=3, base_delay=0.001), name="unit.flaky")
    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise OSError("blip")
        return 42

    before = _counter_value("paddle_retry_attempts_total", op="unit.flaky")
    assert flaky() == 42
    assert _counter_value("paddle_retry_attempts_total",
                          op="unit.flaky") == before + 1


def test_chaos_error_is_retryable_by_default():
    chaos.configure("p:exc:x2")

    def op():
        chaos_point("p")
        return "recovered"

    assert call_with_retry(
        op, policy=RetryPolicy(max_attempts=3, base_delay=0.001)) == "recovered"


# -- store seams -------------------------------------------------------------

def test_store_get_retries_injected_faults():
    from paddlepaddle_tpu.distributed.store import TCPStore

    s = TCPStore(is_master=True)
    s.set("k", b"v")
    chaos.configure("store.get:exc:x2")  # first two attempts fail
    before = _counter_value("paddle_retry_attempts_total", op="store.get")
    assert s.get("k") == b"v"  # retry absorbs both injected faults
    assert chaos.fire_counts()["store.get"] == 2
    assert _counter_value("paddle_retry_attempts_total",
                          op="store.get") == before + 2


def test_store_get_exhausts_on_persistent_fault():
    from paddlepaddle_tpu.distributed.store import TCPStore

    s = TCPStore(is_master=True)
    s.set("k", b"v")
    chaos.configure("store.get:exc:1.0")  # every attempt fails
    with pytest.raises(ChaosError):
        s.get("k")


# -- checkpoint integrity (format v3) ---------------------------------------

def _state(n=4):
    m = paddle.nn.Linear(n, n)
    return m, m.state_dict()


def test_v3_metadata_records_crc(tmp_path):
    _, sd = _state()
    ck = str(tmp_path / "ckpt")
    dist_ckpt.save_state_dict(sd, ck)
    meta = dist_ckpt.get_checkpoint_metadata(ck)
    assert meta["format"].endswith("v3")
    for rec in meta["tensors"].values():
        for s in rec["shards"]:
            assert isinstance(s["crc32"], int)
    validate_checkpoint(ck)  # full CRC pass succeeds


def _flip_byte(fpath, offset=-3):
    with open(fpath, "r+b") as f:
        f.seek(offset, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))


def test_bitflip_detected_on_load(tmp_path):
    m, sd = _state()
    ck = str(tmp_path / "ckpt")
    dist_ckpt.save_state_dict(sd, ck)
    meta = dist_ckpt.get_checkpoint_metadata(ck)
    victim = meta["tensors"]["weight"]["shards"][0]["file"]
    _flip_byte(os.path.join(ck, victim))

    with pytest.raises(CheckpointCorruptionError, match="CRC mismatch"):
        validate_checkpoint(ck)
    m2 = paddle.nn.Linear(4, 4)
    with pytest.raises(CheckpointCorruptionError, match="CRC mismatch"):
        dist_ckpt.load_state_dict(m2.state_dict(), ck)


def test_crc_verify_flag_opt_out(tmp_path):
    m, sd = _state()
    ck = str(tmp_path / "ckpt")
    dist_ckpt.save_state_dict(sd, ck)
    meta = dist_ckpt.get_checkpoint_metadata(ck)
    _flip_byte(os.path.join(ck, meta["tensors"]["weight"]["shards"][0]["file"]))
    paddle.set_flags({"FLAGS_ckpt_verify_crc": False})
    try:
        m2 = paddle.nn.Linear(4, 4)
        dist_ckpt.load_state_dict(m2.state_dict(), ck)  # no CRC gate: loads
    finally:
        paddle.set_flags({"FLAGS_ckpt_verify_crc": True})


def test_uncommitted_dir_is_invalid(tmp_path):
    d = tmp_path / "torn"
    d.mkdir()
    (d / "weight.npy").write_bytes(b"partial")
    with pytest.raises(CheckpointCorruptionError, match="no metadata.json"):
        validate_checkpoint(str(d))


def test_atomic_commit_overwrite_never_torn(tmp_path):
    """Saving twice to one path goes through staging+rename; the final dir
    is always one complete checkpoint (old or new, never a mix)."""
    ck = str(tmp_path / "ckpt")
    m1, sd1 = _state()
    dist_ckpt.save_state_dict(sd1, ck)
    w1 = sd1["weight"].numpy().copy()
    m2 = paddle.nn.Linear(4, 4)
    dist_ckpt.save_state_dict(m2.state_dict(), ck)  # overwrite
    validate_checkpoint(ck)
    m3 = paddle.nn.Linear(4, 4)
    sd3 = m3.state_dict()
    dist_ckpt.load_state_dict(sd3, ck)
    assert not np.allclose(sd3["weight"].numpy(), w1)  # it's the NEW one
    # no staging or trash litter after successful commits
    leftovers = [n for n in os.listdir(tmp_path)
                 if "staging" in n or "__old__" in n]
    assert leftovers == []


def test_kill_during_save_leaves_no_torn_checkpoint(tmp_path):
    """Chaos kill inside the shard write: the process dies mid-save; the
    target path must be absent entirely (atomic commit) and no uncommitted
    directory may contain a metadata.json."""
    ck = str(tmp_path / "ckpt")
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import paddlepaddle_tpu as paddle\n"
        "from paddlepaddle_tpu.distributed import checkpoint as dist_ckpt\n"
        "m = paddle.nn.Linear(8, 8)\n"
        f"dist_ckpt.save_state_dict(m.state_dict(), {ck!r})\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_CHAOS_POINTS="ckpt.write_shard:kill:@1:77",
               PADDLE_CHAOS_SEED="1234")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 77, (proc.returncode, proc.stderr[-2000:])
    assert not os.path.exists(ck)  # committed-or-absent
    for root, _dirs, files in os.walk(tmp_path):
        assert "metadata.json" not in files, f"torn metadata in {root}"
    with pytest.raises(CheckpointCorruptionError):
        validate_checkpoint(ck)


# -- CheckpointManager: keep-K GC + newest-valid fallback --------------------

def test_manager_keeps_last_k_and_restores_newest(tmp_path):
    root = str(tmp_path / "run")
    mgr = CheckpointManager(root, keep_last_k=3)
    m, sd = _state()
    saved = {}
    for step in range(1, 6):
        sd["weight"].set_value(np.full((4, 4), float(step), np.float32))
        mgr.save(sd, step)
        saved[step] = sd["weight"].numpy().copy()
    from paddlepaddle_tpu.resilience.integrity import list_checkpoints

    assert [s for s, _ in list_checkpoints(root)] == [5, 4, 3]  # GC'd to K=3
    m2 = paddle.nn.Linear(4, 4)
    sd2 = m2.state_dict()
    assert mgr.restore(sd2) == 5
    np.testing.assert_allclose(sd2["weight"].numpy(), saved[5])


def test_manager_falls_back_past_corrupt_newest(tmp_path):
    root = str(tmp_path / "run")
    mgr = CheckpointManager(root, keep_last_k=3)
    m, sd = _state()
    saved = {}
    for step in range(1, 4):
        sd["weight"].set_value(np.full((4, 4), float(step), np.float32))
        mgr.save(sd, step)
        saved[step] = sd["weight"].numpy().copy()
    # corrupt the newest checkpoint's first shard
    meta = dist_ckpt.get_checkpoint_metadata(mgr.step_path(3))
    _flip_byte(os.path.join(mgr.step_path(3),
                            meta["tensors"]["weight"]["shards"][0]["file"]))
    before = _counter_value("paddle_ckpt_fallbacks_total")
    assert find_latest_valid_checkpoint(root)[0] == 2
    m2 = paddle.nn.Linear(4, 4)
    sd2 = m2.state_dict()
    assert mgr.restore(sd2) == 2  # skipped the corrupt step-3
    np.testing.assert_allclose(sd2["weight"].numpy(), saved[2])
    assert _counter_value("paddle_ckpt_fallbacks_total") > before


def test_manager_recovers_old_dir_from_interrupted_overwrite(tmp_path):
    """A kill between the commit's two renames leaves the previous good
    checkpoint at <step>.__old__.<pid>: restore must still find it, and the
    next successful commit's GC must clean it up."""
    root = str(tmp_path / "run")
    mgr = CheckpointManager(root, keep_last_k=3)
    m, sd = _state()
    sd["weight"].set_value(np.full((4, 4), 3.0, np.float32))
    mgr.save(sd, 3)
    # simulate the crash window: canonical renamed aside, new one never landed
    os.rename(mgr.step_path(3), mgr.step_path(3) + ".__old__.999")
    assert find_latest_valid_checkpoint(root)[0] == 3
    m2 = paddle.nn.Linear(4, 4)
    sd2 = m2.state_dict()
    assert mgr.restore(sd2) == 3  # recovered from the __old__ dir
    np.testing.assert_allclose(sd2["weight"].numpy(), 3.0)
    # a completed re-save supersedes the leftover; GC removes it
    mgr.save(sd, 3)
    assert not os.path.exists(mgr.step_path(3) + ".__old__.999")
    assert os.path.exists(mgr.step_path(3))


def test_preemption_reinstall_keeps_cooperative_mode():
    """Adding a callback with default args must not flip a polling-mode
    handler back into exit-on-signal mode."""
    from paddlepaddle_tpu.resilience import (
        install_preemption_handler,
        uninstall_preemption_handler,
    )

    try:
        h = install_preemption_handler(exit_on_signal=False, exit_code=7)
        h2 = install_preemption_handler(lambda: None)  # defaults: no override
        assert h2 is h
        assert h.exit_on_signal is False and h.exit_code == 7
        h3 = install_preemption_handler(exit_code=31)  # explicit: overrides
        assert h3.exit_code == 31 and h3.exit_on_signal is False
    finally:
        uninstall_preemption_handler()


def test_manager_restore_empty_root(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "none"), keep_last_k=2)
    m, sd = _state()
    assert mgr.restore(sd) is None
    assert mgr.latest_valid() is None


# -- wait_all_saves: every failure surfaced, state never poisoned ------------

def test_wait_all_saves_aggregates_all_failures(tmp_path):
    m, sd = _state()
    # Linear has 2 tensors -> 2 shard files per save; 3 retry attempts per
    # file; x6 fails the first file of BOTH async saves through its retries
    chaos.configure("ckpt.write_shard:exc:x6")
    dist_ckpt.save_state_dict(sd, str(tmp_path / "a"), async_save=True)
    dist_ckpt.save_state_dict(sd, str(tmp_path / "b"), async_save=True)
    with pytest.raises(dist_ckpt.CheckpointSaveError,
                       match="2 async checkpoint saves failed") as ei:
        dist_ckpt.wait_all_saves()
    assert len(ei.value.errors) == 2
    assert all(isinstance(e, ChaosError) for e in ei.value.errors)
    # pending list cleared: the NEXT save/wait is not poisoned
    dist_ckpt.wait_all_saves()
    chaos.disable()
    dist_ckpt.save_state_dict(sd, str(tmp_path / "c"), async_save=True)
    dist_ckpt.wait_all_saves()
    validate_checkpoint(str(tmp_path / "c"))


def test_single_async_failure_reraised_as_is(tmp_path):
    m, sd = _state()
    chaos.configure("ckpt.write_shard:exc:x3")  # one save, all 3 attempts
    dist_ckpt.save_state_dict(sd, str(tmp_path / "a"), async_save=True)
    with pytest.raises(ChaosError):
        dist_ckpt.wait_all_saves()


# -- preemption --------------------------------------------------------------

def test_preemption_cooperative_flag_and_callbacks():
    from paddlepaddle_tpu.resilience import (
        install_preemption_handler,
        preemption_requested,
        uninstall_preemption_handler,
    )

    ran = []
    try:
        h = install_preemption_handler(lambda: ran.append("saved"),
                                       exit_on_signal=False)
        assert not preemption_requested()
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while time.time() < deadline and not preemption_requested():
            time.sleep(0.01)
        assert preemption_requested()
        assert ran == ["saved"]
        assert h.requested()
    finally:
        uninstall_preemption_handler()


def test_preemption_sigterm_saves_and_exits_restartable(tmp_path):
    """SIGTERM → emergency save_state_dict + drain → exit 143: the full
    preemption flow in a real process."""
    ck = str(tmp_path / "emergency")
    code = (
        "import os, sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import paddlepaddle_tpu as paddle\n"
        "from paddlepaddle_tpu.distributed import checkpoint as dist_ckpt\n"
        "from paddlepaddle_tpu.resilience import install_preemption_handler\n"
        "m = paddle.nn.Linear(8, 8)\n"
        "install_preemption_handler(\n"
        f"    lambda: dist_ckpt.save_state_dict(m.state_dict(), {ck!r}))\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        proc.kill()
    assert rc == 143, (rc, proc.stderr.read()[-2000:])
    validate_checkpoint(ck)  # the emergency checkpoint is complete + intact
    m2 = paddle.nn.Linear(8, 8)
    dist_ckpt.load_state_dict(m2.state_dict(), ck)


# -- watchdog re-arm (satellite) ---------------------------------------------

def test_watchdog_rearms_after_timed_out_step_retires():
    from paddlepaddle_tpu.distributed.watchdog import Watchdog

    fired = []
    before = _counter_value("paddle_watchdog_step_timeouts_total",
                            step="slow")
    wd = Watchdog(timeout=0.2, poll_interval=0.05, abort=False,
                  on_timeout=lambda name, el: fired.append(name))
    with wd:
        with wd.step("slow"):
            time.sleep(0.5)
        with wd.step("fast"):
            time.sleep(0.01)
        with wd.step("slow"):
            time.sleep(0.5)  # the one-shot latch used to go dead here
    assert fired == ["slow", "slow"]
    assert _counter_value("paddle_watchdog_step_timeouts_total",
                          step="slow") == before + 2


def test_watchdog_fires_once_per_hung_step():
    from paddlepaddle_tpu.distributed.watchdog import Watchdog

    fired = []
    wd = Watchdog(timeout=0.1, poll_interval=0.02, abort=False,
                  on_timeout=lambda name, el: fired.append(name))
    with wd:
        with wd.step("hung"):
            time.sleep(0.6)  # several poll intervals past the deadline
    assert fired == ["hung"]  # no repeat-fire storm for ONE hung step


def test_step_chaos_seam():
    from paddlepaddle_tpu.distributed.watchdog import Watchdog

    chaos.configure("step:exc:@1")
    wd = Watchdog(timeout=30, abort=False)
    with wd:
        with pytest.raises(ChaosError):
            with wd.step("s"):
                pass


# -- dataloader worker death (satellite) -------------------------------------

def test_chaos_killed_worker_raises_dataloader_worker_error(monkeypatch):
    """A chaos-killed worker (fork start method: children inherit the armed
    engine) surfaces as DataLoaderWorkerError, not a hang."""
    from paddlepaddle_tpu.io import DataLoader, DataLoaderWorkerError
    from paddlepaddle_tpu.io.dataset import Dataset

    class Ds(Dataset):
        def __getitem__(self, i):
            return np.array([i], np.int64)

        def __len__(self):
            return 32

    monkeypatch.setenv("PADDLE_TPU_MP_START_METHOD", "fork")
    chaos.configure("dataloader.worker:kill:@3:99")
    dl = DataLoader(Ds(), batch_size=2, num_workers=2)
    with pytest.raises(DataLoaderWorkerError, match="died unexpectedly"):
        list(dl)


def test_worker_exception_is_dataloader_worker_error():
    from paddlepaddle_tpu.io import DataLoader, DataLoaderWorkerError
    from paddlepaddle_tpu.io.dataset import Dataset

    class Boom(Dataset):
        def __getitem__(self, i):
            raise RuntimeError("boom")

        def __len__(self):
            return 4

    dl = DataLoader(Boom(), batch_size=2, num_workers=0)
    with pytest.raises(RuntimeError):
        list(dl)
    # the mp path's public type: subclass of RuntimeError, importable
    assert issubclass(DataLoaderWorkerError, RuntimeError)
