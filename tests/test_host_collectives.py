"""Cross-host eager collectives: 2 real processes over the TCPStore.

Reference: paddle/phi/core/distributed/collective/process_group.h:48 —
eager all_reduce/broadcast/all_gather/send/recv on a multi-process group.
Here two OS processes rendezvous through the (native C++ or python) store
and must produce identical, correct collective results.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["REPO_DIR"])
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddlepaddle_tpu as paddle
import paddlepaddle_tpu.distributed as dist

rank = int(os.environ["PADDLE_TRAINER_ID"])

# all_reduce (sum): in-place on the tensor
t = paddle.to_tensor(np.asarray([1.0 + rank, 2.0 * (rank + 1)], np.float32))
dist.all_reduce(t)
np.testing.assert_allclose(t.numpy(), [3.0, 6.0])

# all_reduce max
t = paddle.to_tensor(np.asarray([float(rank)], np.float32))
dist.all_reduce(t, op=dist.ReduceOp.MAX)
np.testing.assert_allclose(t.numpy(), [1.0])

# broadcast from rank 0
t = paddle.to_tensor(np.full((3,), float(rank), np.float32))
dist.broadcast(t, src=0)
np.testing.assert_allclose(t.numpy(), [0.0, 0.0, 0.0])

# all_gather
outs = []
dist.all_gather(outs, paddle.to_tensor(np.asarray([rank], np.int64)))
assert [int(o.numpy()[0]) for o in outs] == [0, 1]

# all_gather_object
objs = []
dist.all_gather_object(objs, {"rank": rank})
assert [o["rank"] for o in objs] == [0, 1]

# send / recv ping-pong
if rank == 0:
    dist.send(paddle.to_tensor(np.asarray([42.0], np.float32)), dst=1)
else:
    t = paddle.to_tensor(np.zeros((1,), np.float32))
    dist.recv(t, src=0)
    np.testing.assert_allclose(t.numpy(), [42.0])

# barrier then scatter from rank 1
dist.barrier()
parts = ([paddle.to_tensor(np.asarray([10.0], np.float32)),
          paddle.to_tensor(np.asarray([20.0], np.float32))]
         if rank == 1 else None)
t = paddle.to_tensor(np.zeros((1,), np.float32))
dist.scatter(t, parts, src=1)
np.testing.assert_allclose(t.numpy(), [10.0 if rank == 0 else 20.0])

# LAP REGRESSION (round-3 advisor, high): >window same-tag collectives must
# return the CURRENT step's payload, never a window-old one. This is the
# GradScaler pattern — one tiny MAX all_reduce per step, many steps.
from paddlepaddle_tpu.distributed.host_collectives import get_host_group, _SLOT_WINDOW
import time
g = get_host_group()
steps = _SLOT_WINDOW * 2 + 5
for step in range(steps):
    if rank == 1 and step == 0:
        time.sleep(0.3)               # skew: rank 0 runs ahead into the gate
    out = g.all_reduce(np.asarray([float(step * 2 + rank)], np.float32), op="max")
    np.testing.assert_allclose(out, [float(step * 2 + 1)], err_msg=f"step {step}")

# one-sided writer lap: broadcast source posts without reading; the window
# gate must keep it bounded and every reader must see its own step's value.
for step in range(steps):
    if rank == 1 and step == 0:
        time.sleep(0.3)
    val = np.asarray([float(step)], np.float32) if rank == 0 else np.zeros(1, np.float32)
    out = g.broadcast(val, src=0)
    np.testing.assert_allclose(out, [float(step)], err_msg=f"step {step}")

# barrier must be fresh per invocation (stale bar_done regression)
for _ in range(3):
    g.barrier()

# LocalSGD: k local steps then parameter averaging across the two ranks
from paddlepaddle_tpu.distributed.fleet import LocalSGD
lin = paddle.nn.Linear(2, 1)
lin.weight.set_value(np.full((2, 1), float(rank + 1), np.float32))
lin.bias.set_value(np.zeros((1,), np.float32))
lsgd = LocalSGD(paddle.optimizer.SGD(learning_rate=0.0,
                                     parameters=lin.parameters()), k_steps=2)
xloc = paddle.to_tensor(np.ones((1, 2), np.float32))
for s in range(2):   # lr=0: weights unchanged locally; avg fires at step 2
    loss = lin(xloc).mean()
    loss.backward()
    lsgd.step()
    lsgd.clear_grad()
np.testing.assert_allclose(lin.weight.numpy(), 1.5)  # avg of 1 and 2

# batch_isend_irecv (reference: communication/batch_isend_irecv.py): each
# rank sends to the other and receives, with recv ORDERED BEFORE send in
# the op list — the batch semantics must not deadlock on list order.
send_buf = paddle.to_tensor(np.asarray([float(100 + rank)], np.float32))
recv_buf = paddle.to_tensor(np.zeros((1,), np.float32))
ops = [dist.P2POp(dist.irecv, recv_buf, 1 - rank),
       dist.P2POp(dist.isend, send_buf, 1 - rank)]
for t in dist.batch_isend_irecv(ops):
    t.wait()
np.testing.assert_allclose(recv_buf.numpy(), [float(100 + (1 - rank))])

print(f"WORKER_{rank}_OK")
"""


def test_two_process_eager_collectives(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "REPO_DIR": repo,
            "JAX_PLATFORMS": "cpu",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
        })
        procs.append(subprocess.Popen([sys.executable, "-c", _WORKER],
                                      env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"rank {rank} hung")
        outs.append((p.returncode, out, err))
    for rank, (rc, out, err) in enumerate(outs):
        assert rc == 0 and f"WORKER_{rank}_OK" in out, (
            f"rank {rank} failed:\n{out[-1000:]}\n{err[-2000:]}")
