"""End-to-end chaos drill: the elastic loop survives an injected worker
kill (launch --max_restarts + CheckpointManager resume) and a corrupted
checkpoint shard (newest-valid fallback).

The worker kill is a chaos-engine injection (``preempt:kill:@1``) armed only
in rank 1's first incarnation; the restarted incarnation sees
``PADDLE_RESTART_NUM=1`` and resumes from the newest valid checkpoint. The
final loss must equal an uninterrupted single-worker run of the same
schedule (fixed full batch → allreduce-mean trajectory is world-size
independent).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.chaos

_WORKER = r"""
import os, sys, time
sys.path.insert(0, os.environ["REPO_DIR"])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.distributed.host_collectives import get_host_group
from paddlepaddle_tpu.resilience import CheckpointManager, chaos
from paddlepaddle_tpu.resilience.chaos import chaos_point
from paddlepaddle_tpu.resilience.integrity import find_latest_valid_checkpoint

rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
incarnation = int(os.environ.get("PADDLE_RESTART_NUM", "0"))
root = os.environ["DRILL_ROOT"]
out_path = os.environ["DRILL_OUT"]
kill_step = int(os.environ.get("DRILL_KILL_STEP", "-1"))
TOTAL = 10

# chaos armed ONLY for rank 1's first incarnation: one deterministic kill
if rank == 1 and incarnation == 0 and kill_step >= 0:
    chaos.configure("preempt:kill:@1:77",
                    seed=int(os.environ.get("PADDLE_CHAOS_SEED", "0")))

g = get_host_group() if world > 1 else None
mgr = CheckpointManager(root, keep_last_k=3)

lin = paddle.nn.Linear(4, 1)
opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=lin.parameters())
start = mgr.restore(lin.state_dict()) or 0
if g is not None and start:
    # rejoin the collective stream at the exact op index derivable from the
    # checkpoint: one all_reduce per parameter per finished step
    g.rejoin(start * len(lin.parameters()))

rng = np.random.default_rng(0)
xb = rng.standard_normal((16, 4)).astype(np.float32)
w_true = np.asarray([[1.0], [2.0], [-1.0], [0.5]], np.float32)
yb = xb @ w_true

loss_val = None
for step in range(start, TOTAL):
    if rank == 1 and incarnation == 0 and step == kill_step:
        # cross the kill seam only once the checkpoint for THIS step is
        # committed, so the restarted incarnation resumes exactly here
        deadline = time.time() + 60
        while time.time() < deadline:
            latest = find_latest_valid_checkpoint(root)
            if latest is not None and latest[0] >= step:
                break
            time.sleep(0.05)
        chaos_point("preempt")  # armed above: os._exit(77)
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
        # every rank holds the full replicated state (allreduced grads):
        # rank 0 alone commits it through the atomic single-host path
        mgr.save(lin.state_dict(), step + 1,
                 process_index=0, process_count=1)

if rank == 0:
    with open(out_path, "w") as f:
        f.write(repr(loss_val))
print(f"CHAOS_RANK{rank}_DONE loss={loss_val} incarnation={incarnation}")
"""


def _run(tmp_path, tag, world, kill_step):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d = tmp_path / tag
    d.mkdir()
    script = d / "train.py"
    script.write_text(_WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPO_DIR=repo, PADDLE_CHAOS_SEED="1234",
               DRILL_ROOT=str(d / "ckpts"),
               DRILL_OUT=str(d / "final_loss.txt"),
               DRILL_KILL_STEP=str(kill_step))
    out = subprocess.run(
        [sys.executable, "-m", "paddlepaddle_tpu.distributed.launch",
         "--nproc_per_node", str(world), "--max_restarts", "2", str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    return out, d, float((d / "final_loss.txt").read_text())


@pytest.mark.slow
def test_injected_kill_resumes_from_checkpoint_matching_loss(tmp_path):
    out, d, interrupted = _run(tmp_path, "duo_kill", world=2, kill_step=6)
    assert "worker 1 exited 77" in out.stderr  # the chaos kill fired
    assert "restart 1/2" in out.stderr          # the launcher respawned it
    _out2, _d2, baseline = _run(tmp_path, "solo", world=1, kill_step=-1)
    np.testing.assert_allclose(interrupted, baseline, rtol=1e-6)

    # second half of the acceptance drill: corrupt the newest surviving
    # checkpoint shard; restore must fall back to the last VALID one
    from paddlepaddle_tpu.distributed import checkpoint as dist_ckpt
    from paddlepaddle_tpu.resilience import CheckpointManager
    from paddlepaddle_tpu.resilience.integrity import list_checkpoints

    import paddlepaddle_tpu as paddle

    root = str(d / "ckpts")
    steps = [s for s, _ in list_checkpoints(root)]
    assert steps == [10, 9, 8]  # keep_last_k=3 GC ran under the launcher
    mgr = CheckpointManager(root, keep_last_k=3)
    newest = mgr.step_path(10)
    meta = dist_ckpt.get_checkpoint_metadata(newest)
    victim = os.path.join(
        newest, meta["tensors"]["weight"]["shards"][0]["file"])
    with open(victim, "r+b") as f:
        f.seek(-3, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    lin = paddle.nn.Linear(4, 1)
    assert mgr.restore(lin.state_dict()) == 9  # skipped the corrupt newest


@pytest.mark.slow
def test_launcher_sigterm_drains_without_respawn(tmp_path):
    """A SIGTERMed launcher (preempted job) forwards the TERM, drains the
    workers, and exits 143 WITHOUT burning restarts respawning them."""
    import signal
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "sleeper.py"
    script.write_text(
        "import sys, time\n"
        "sys.stdout.write('WORKER_UP\\n'); sys.stdout.flush()\n"
        "time.sleep(120)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddlepaddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restarts", "3", str(script)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=repo)
    try:
        assert proc.stdout.readline().strip() == "WORKER_UP"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    err = proc.stderr.read()
    assert rc == 143, (rc, err[-2000:])
    assert "no restarts" in err
    assert "restart 1/3" not in err  # the old handler respawned here
