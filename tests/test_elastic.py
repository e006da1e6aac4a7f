"""Elastic membership (scale up/down) over the native TCPStore.

Reference surface: python/paddle/distributed/fleet/elastic/manager.py:125,
237-316 — hosts register leases, the manager watches membership and rewrites
the world on scale events; plus the launcher relaunch loop.
"""

import os
import time

import numpy as np
import pytest

from paddlepaddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                        ElasticNode)
from paddlepaddle_tpu.distributed.store import TCPStore


def _store():
    return TCPStore(is_master=True)


def test_scale_up_commits_new_world():
    store = _store()
    mgr = ElasticManager(store, np_range=(1, 4), heartbeat_timeout=1.0)

    n0 = ElasticNode(store, "hostA", heartbeat_interval=0.1)
    n0.register()
    mgr.scan_once()
    assert mgr.version == 1 and mgr.members == ["hostA"]

    n1 = ElasticNode(store, "hostB", heartbeat_interval=0.1)
    n1.register()
    mgr.scan_once()
    assert mgr.version == 2 and mgr.members == ["hostA", "hostB"]

    # workers observe the committed world through the store
    version, members = ElasticManager.read_world(store)
    assert version == 2 and members == ["hostA", "hostB"]
    assert n0.world_changed(1) and not n0.world_changed(2)
    n0.stop()
    n1.stop()


def test_scale_down_on_dead_heartbeat():
    store = _store()
    mgr = ElasticManager(store, np_range=(1, 4), heartbeat_timeout=0.4)
    n0 = ElasticNode(store, "hostA", heartbeat_interval=0.1)
    n1 = ElasticNode(store, "hostB", heartbeat_interval=0.1)
    n0.register()
    n1.register()
    mgr.scan_once()
    assert sorted(mgr.members) == ["hostA", "hostB"]

    n1.stop()  # hostB stops heartbeating
    deadline = time.time() + 5
    while time.time() < deadline and "hostB" in mgr.members:
        time.sleep(0.1)
        mgr.scan_once()
    assert mgr.members == ["hostA"]  # shrunk world committed
    version, members = ElasticManager.read_world(store)
    assert members == ["hostA"] and version >= 2
    n0.stop()


def test_min_np_floor_blocks_undersized_world():
    store = _store()
    mgr = ElasticManager(store, np_range=(2, 4), heartbeat_timeout=0.3)
    n0 = ElasticNode(store, "hostA", heartbeat_interval=0.1)
    n1 = ElasticNode(store, "hostB", heartbeat_interval=0.1)
    n0.register()
    n1.register()
    mgr.scan_once()
    assert len(mgr.members) == 2

    n1.stop()
    time.sleep(0.8)
    mgr.scan_once()
    # one alive < min_np=2: the old world stays (job blocks rather than
    # committing an undersized membership)
    assert sorted(mgr.members) == ["hostA", "hostB"]
    n0.stop()


def test_wait_for_np_rendezvous():
    store = _store()
    mgr = ElasticManager(store, np_range=(2, 4), heartbeat_timeout=1.0)
    n0 = ElasticNode(store, "hostA", heartbeat_interval=0.1)
    n0.register()
    with pytest.raises(TimeoutError):
        mgr.wait_for_np(2, timeout=0.5)
    n1 = ElasticNode(store, "hostB", heartbeat_interval=0.1)
    n1.register()
    version, members = mgr.wait_for_np(2, timeout=5)
    assert version >= 1 and sorted(members) == ["hostA", "hostB"]
    n0.stop()
    n1.stop()


def test_max_np_caps_world():
    store = _store()
    mgr = ElasticManager(store, np_range=(1, 2), heartbeat_timeout=1.0)
    nodes = [ElasticNode(store, f"h{i}", heartbeat_interval=0.1)
             for i in range(3)]
    for n in nodes:
        n.register()
    mgr.scan_once()
    assert len(mgr.members) == 2  # capped at max_np
    # surplus nodes must NOT churn the version on every scan (review
    # finding: identical capped world was re-committed each poll)
    v = mgr.version
    for _ in range(5):
        mgr.scan_once()
    assert mgr.version == v
    for n in nodes:
        n.stop()


# -- r5: the composed kill-resume drill (verdict item 6) ---------------------

_DRILL_WORKER = r"""
import os, sys, time
sys.path.insert(0, os.environ["REPO_DIR"])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.distributed.host_collectives import get_host_group

rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
ckpt = os.environ["DRILL_CKPT"]
marker = os.environ["DRILL_MARKER"]
out_path = os.environ["DRILL_OUT"]
TOTAL = 10

g = get_host_group() if world > 1 else None

lin = paddle.nn.Linear(4, 1)
opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=lin.parameters())
start = 0
if os.path.exists(ckpt):
    blob = paddle.load(ckpt)
    lin.set_state_dict(blob["model"])
    opt.set_state_dict(blob["opt"])
    start = int(blob["step"])
    if g is not None:
        # deterministic op schedule: one all_reduce PER PARAMETER per
        # finished step, so the group sequence is derivable from the
        # checkpoint (the elastic re-admission contract — a fresh
        # incarnation must rejoin the stream at the exact op index, or its
        # collectives alias a live rank's older slots and read stale data)
        g.rejoin(start * len(lin.parameters()))

# fixed full batch: every rank computes the SAME grads, so the
# allreduce-mean trajectory is world-size independent (solo == duo)
rng = np.random.default_rng(0)
xb = rng.standard_normal((16, 4)).astype(np.float32)
w_true = np.asarray([[1.0], [2.0], [-1.0], [0.5]], np.float32)
yb = xb @ w_true

loss_val = None
for step in range(start, TOTAL):
    if rank == 1 and step == 6 and not os.path.exists(marker):
        open(marker, "w").close()
        os._exit(7)        # simulated hardware failure AFTER ckpt of step 6
    loss = ((lin(paddle.to_tensor(xb)) - paddle.to_tensor(yb)) ** 2).mean()
    loss.backward()
    if g is not None:
        for p in lin.parameters():
            p.grad = paddle.to_tensor(
                g.all_reduce(np.asarray(p.grad.numpy()), op="sum") / world)
    opt.step()
    opt.clear_grad()
    loss_val = float(loss.numpy())
    if rank == 0:
        tmp = ckpt + ".tmp"
        paddle.save({"model": lin.state_dict(), "opt": opt.state_dict(),
                     "step": step + 1}, tmp)
        os.replace(tmp, ckpt)

if rank == 0:
    with open(out_path, "w") as f:
        f.write(repr(loss_val))
print(f"DRILL_RANK{rank}_DONE loss={loss_val}")
"""


def test_kill_resume_drill_matches_uninterrupted(tmp_path):
    """The composed elastic story (reference fleet/elastic/manager.py:125):
    launcher starts 2 workers training with allreduced grads +
    per-step checkpoints; worker 1 is killed mid-train; the launcher
    re-admits it (restart), it resumes FROM THE CHECKPOINT and rejoins the
    collective mid-stream; the final loss equals an uninterrupted
    single-worker run of the same schedule."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(world, tag, with_kill):
        d = tmp_path / tag
        d.mkdir()
        script = d / "train.py"
        script.write_text(_DRILL_WORKER)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   REPO_DIR=repo,
                   DRILL_CKPT=str(d / "ckpt.pd"),
                   DRILL_MARKER=str(d / "marker"),
                   DRILL_OUT=str(d / "final_loss.txt"))
        cmd = [sys.executable, "-m", "paddlepaddle_tpu.distributed.launch",
               "--nproc_per_node", str(world), "--max_restarts", "1",
               str(script)]
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=300, env=env, cwd=repo)
        assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
        if with_kill:
            assert (d / "marker").exists(), "the kill never fired"
            assert "restart 1/1" in out.stderr
        return float((d / "final_loss.txt").read_text())

    interrupted = run(2, "duo_kill", with_kill=True)
    baseline = run(1, "solo", with_kill=False)
    np.testing.assert_allclose(interrupted, baseline, rtol=1e-6)
