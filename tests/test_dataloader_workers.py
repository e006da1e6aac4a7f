"""Subprocess DataLoader workers (reference: python/paddle/io/dataloader/
worker.py, reader.py:262): GIL-escaping throughput, worker_init_fn,
persistent workers, and IterableDataset self-sharding via get_worker_info.

Datasets are defined at module level so the default ``forkserver`` start
method (fork-safe under the multithreaded JAX parent) can pickle them; one
test covers the documented fallback-to-fork path for local classes.
"""

import os
import time
from pathlib import Path

import numpy as np
import pytest

from paddlepaddle_tpu.io import DataLoader, get_worker_info
from paddlepaddle_tpu.io.dataset import Dataset, IterableDataset


class _PyHeavy(Dataset):
    """Pure-python CPU-bound __getitem__ — threads serialize on the GIL,
    subprocess workers do not. An item says who made it and when: its
    index, its value, the pid, and its start and end on the machine's
    monotonic clock (one clock for every process of the host)."""

    def __init__(self, n=24, work=60_000):
        self.n = n
        self.work = work

    def __getitem__(self, i):
        t0 = time.monotonic()
        acc = 0
        for j in range(self.work):  # deliberately GIL-bound
            acc += (i * j) % 7
        return np.array([i, acc % 97, os.getpid(), t0, time.monotonic()],
                        np.float64)

    def __len__(self):
        return self.n


class _ArangeDs(Dataset):
    def __init__(self, n=10):
        self.n = n

    def __getitem__(self, i):
        info = get_worker_info()
        assert info is not None and 0 <= info.id < 2
        return np.array([i], np.int64)

    def __len__(self):
        return self.n


class _PidDs(Dataset):
    def __getitem__(self, i):
        return np.array([os.getpid(), i], np.int64)

    def __len__(self):
        return 8


class _PlainDs(Dataset):
    def __init__(self, n=16):
        self.n = n

    def __getitem__(self, i):
        return np.array([i], np.int64)

    def __len__(self):
        return self.n


class _BadDs(Dataset):
    def __getitem__(self, i):
        if i == 3:
            raise RuntimeError("boom")
        return np.array([i], np.int64)

    def __len__(self):
        return 8


class _Stream(IterableDataset):
    def __iter__(self):
        info = get_worker_info()
        lo, hi = 0, 16
        if info is not None:  # reference pattern: shard by worker id
            per = (hi - lo) // info.num_workers
            lo = info.id * per
            hi = lo + per
        for i in range(lo, hi):
            yield np.array([i], np.int64)


_init_calls = []


def _init_fn(worker_id):
    _init_calls.append(worker_id)  # runs in the child (parent list stays empty)


@pytest.mark.skipif(os.cpu_count() < 2, reason="needs 2 cores")
def test_subprocess_beats_threads_on_python_heavy():
    """Two worker PROCESSES run the GIL-bound transform at the same time,
    and hand over the batches threads make, in their order. What a busy
    neighbour cannot change is asserted, not a ratio of wall-clock times:
    an item of one pid is under way while an item of the other pid is."""
    ds = _PyHeavy()
    threads = DataLoader(ds, batch_size=4, num_workers=2,
                         use_multiprocess=False)
    procs = DataLoader(ds, batch_size=4, num_workers=2,
                       persistent_workers=True)
    # warmup epoch: child startup + interpreter/jax import can dwarf the
    # workload on a small box; the second epoch finds both workers up
    for _ in procs:
        pass
    out_t = [b.numpy() for b in threads]
    out_p = [b.numpy() for b in procs]
    procs._pool.shutdown()
    assert len(out_t) == len(out_p) == 6
    for a, b in zip(out_t, out_p):
        np.testing.assert_array_equal(a[:, :2], b[:, :2])  # same batches, same order
    assert {int(p) for a in out_t for p in a[:, 2]} == {os.getpid()}
    rows = np.concatenate(out_p)
    pids = sorted({int(p) for p in rows[:, 2]})
    assert len(pids) == 2 and os.getpid() not in pids, pids
    mine, theirs = (rows[rows[:, 2] == p][:, 3:] for p in pids)
    # intervals [t0, t1] of the two pids that intersect: max of the starts
    # before min of the ends
    both = (np.maximum(mine[:, None, 0], theirs[None, :, 0])
            < np.minimum(mine[:, None, 1], theirs[None, :, 1]))
    assert both.any(), (mine, theirs)


def test_worker_init_fn_and_order():
    loader = DataLoader(_ArangeDs(), batch_size=2, num_workers=2,
                        worker_init_fn=_init_fn)
    flat = np.concatenate([b.numpy().ravel() for b in loader])
    np.testing.assert_array_equal(flat, np.arange(10))
    assert _init_calls == []  # init ran in workers, not the parent
    assert get_worker_info() is None  # main process sees None


def test_persistent_workers_reuse_pool():
    loader = DataLoader(_PidDs(), batch_size=2, num_workers=2,
                        persistent_workers=True)
    pids1 = {int(b.numpy()[0, 0]) for b in loader}
    pool1 = loader._pool
    pids2 = {int(b.numpy()[0, 0]) for b in loader}
    assert loader._pool is pool1 and pool1.alive  # same processes both epochs
    assert pids1 == pids2
    assert os.getpid() not in pids1  # loading happened in children
    loader._pool.shutdown()


def test_abandoned_epoch_does_not_leak_stale_batches():
    """Early break with persistent workers: the next epoch must start from
    batch 0, discarding leftovers of the abandoned epoch (epoch-tag filter)."""
    dl = DataLoader(_PlainDs(), batch_size=2, num_workers=2,
                    persistent_workers=True)
    it = iter(dl)
    np.testing.assert_array_equal(next(it).numpy().ravel(), [0, 1])
    del it  # abandon mid-epoch
    flat = np.concatenate([b.numpy().ravel() for b in dl])
    np.testing.assert_array_equal(flat, np.arange(16))
    dl._pool.shutdown()


def test_dead_worker_pool_is_replaced_not_hung():
    """A worker exception kills its process; a persistent pool must be torn
    down (retry gets fresh workers) instead of hanging on a dead queue."""
    dl = DataLoader(_BadDs(), batch_size=2, num_workers=2,
                    persistent_workers=True)
    with pytest.raises(RuntimeError, match="boom"):
        list(dl)
    assert dl._pool is None  # broken pool not kept for reuse


def test_iterable_dataset_self_sharding():
    loader = DataLoader(_Stream(), batch_size=2, num_workers=2)
    got = sorted(int(x) for b in loader for x in b.numpy().ravel())
    assert got == list(range(16))  # every element exactly once


def test_unpicklable_dataset_falls_back_to_fork_with_warning():
    """A dataset class defined inside a function cannot pickle for the
    default forkserver start method; the loader must warn and fall back to
    fork rather than dying in Process.start()."""
    class Local(Dataset):
        def __getitem__(self, i):
            return np.array([i], np.int64)

        def __len__(self):
            return 6

    with pytest.warns(UserWarning, match="falling back to the 'fork'"):
        loader = DataLoader(Local(), batch_size=2, num_workers=2)
        flat = np.concatenate([b.numpy().ravel() for b in loader])
    np.testing.assert_array_equal(flat, np.arange(6))


def test_explicit_spawn_with_unpicklable_dataset_raises(monkeypatch):
    class Local(Dataset):
        def __getitem__(self, i):
            return np.array([i], np.int64)

        def __len__(self):
            return 4

    monkeypatch.setenv("PADDLE_TPU_MP_START_METHOD", "spawn")
    with pytest.raises(RuntimeError, match="picklable"):
        list(DataLoader(Local(), batch_size=2, num_workers=2))


def test_explicit_fork_still_works(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MP_START_METHOD", "fork")
    loader = DataLoader(_PlainDs(8), batch_size=2, num_workers=2)
    flat = np.concatenate([b.numpy().ravel() for b in loader])
    np.testing.assert_array_equal(flat, np.arange(8))


def test_killed_worker_raises_instead_of_hanging():
    """A worker that dies WITHOUT posting an error (SIGKILL, startup crash)
    must surface as an exception from the health poll, not a parent hang."""
    import signal

    dl = DataLoader(_PyHeavy(n=64, work=2_000_000), batch_size=2,
                    num_workers=2, persistent_workers=True)
    it = iter(dl)
    next(it)
    os.kill(dl._pool.procs[0].pid, signal.SIGKILL)
    with pytest.raises(RuntimeError, match="died unexpectedly"):
        for _ in it:
            pass
    dl._pool.shutdown()


def test_stdin_main_falls_back_to_fork():
    """A parent whose __main__ came from stdin (heredoc) cannot re-import
    it in forkserver workers; the loader must fall back to fork, warn, and
    still deliver batches (r5 verify finding)."""
    import subprocess
    import sys

    script = (
        "import sys, warnings, numpy as np\n"
        f"sys.path.insert(0, {repr(str(Path(__file__).parent))})\n"
        "from paddlepaddle_tpu.io import DataLoader\n"
        "import test_dataloader_workers as tw\n"
        "with warnings.catch_warnings(record=True) as w:\n"
        "    warnings.simplefilter('always')\n"
        "    dl = DataLoader(tw._PlainDs(6), batch_size=2, num_workers=2)\n"
        "    got = np.concatenate([b.numpy().ravel() for b in dl])\n"
        "assert got.tolist() == [0, 1, 2, 3, 4, 5], got\n"
        "assert any('falling back' in str(x.message) for x in w)\n"
        "print('OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).parent.parent))
    r = subprocess.run([sys.executable, "-"], input=script, text=True,
                       capture_output=True, env=env, timeout=240)
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout, r.stderr)
