"""``python -m paddlepaddle_tpu.distributed.launch`` — multi-process launcher.

Reference surface: python/paddle/distributed/launch/main.py:23 (node/device
discovery, per-rank env injection, log management, watch loop with
restart-on-failure; controllers/collective.py + controllers/master.py).

TPU-native notes: one process drives all of a host's chips
(single-controller, distributed/env.py), so the default is
nproc_per_node=1 with multi-host rendezvous over the native TCPStore
(distributed/store.py). A chip belongs to one process at a time: the
launcher itself never touches a jax backend, and on a host with TPU chips
it refuses nproc_per_node > 1 — the extra children would fail or hang
waiting for chips the first one holds. Multi-process per node is for
CPU-mesh testing (``JAX_PLATFORMS=cpu``). The watch loop restarts failed workers up to --max_restarts times —
the launcher half of the reference's elastic story (checkpoint-resume
provides the state half).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

# a SIGTERMed launcher (preemption) exits 143 itself after draining workers
_SIGNAL_EXIT = {signal.SIGTERM: 143, signal.SIGINT: 130}


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddlepaddle_tpu.distributed.launch",
        description="launch distributed training")
    p.add_argument("--nnodes", type=str, default="1",
                   help="number of nodes, or range 'lo:hi' for elastic")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER", ""),
                   help="host:port of the rendezvous store (rank0 hosts it)")
    p.add_argument("--devices", "--gpus", type=str, default=None)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--max_restarts", type=int, default=0)
    p.add_argument("--run_mode", type=str, default="collective")
    p.add_argument("--obs_export", action="store_true",
                   default=os.environ.get("PADDLE_OBS_EXPORT", "").lower()
                   in ("1", "true", "yes", "on"),
                   help="start a telemetry exporter in every worker "
                        "(/metrics /healthz /vars /trace on obs_port+rank); "
                        "rank 0 additionally serves the fleet-merged view")
    p.add_argument("--obs_port", type=int, default=0,
                   help="base exporter port (0 = FLAGS_obs_port default); "
                        "worker rank r listens on obs_port + r")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _worker_env(args, local_rank: int, world_size: int, master_addr,
                master_port, node_index: int = None):
    env = dict(os.environ)
    # node_index: position in the elastic member list (falls back to the
    # static --node_rank) — after a scale event ranks must stay contiguous
    # within the committed world
    node = args.node_rank if node_index is None else node_index
    rank = node * args.nproc_per_node + local_rank
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world_size),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_NNODES": str(args.nnodes),
        "RANK": str(rank),
        "WORLD_SIZE": str(world_size),
        "LOCAL_RANK": str(local_rank),
        "MASTER_ADDR": master_addr,
        "MASTER_PORT": str(master_port),
        # the LAUNCHER hosts the rendezvous store (it must outlive worker
        # restarts — elastic re-admission depends on surviving store
        # state); workers always connect as clients, rank 0 included
        "PADDLE_LAUNCH_STORE": "1",
    })
    if args.obs_export:
        # fleet telemetry plane: every worker starts its exporter on
        # obs_port + rank and publishes snapshots into the launcher's
        # store; rank 0 serves the merged view (observability/aggregate.py)
        env["PADDLE_OBS_EXPORT"] = "1"
        env.setdefault("PADDLE_OBS_METRICS", "1")  # an empty /metrics helps no one
        if args.obs_port:
            env["PADDLE_OBS_PORT"] = str(args.obs_port)
    if args.devices:
        # reference env parity only: nothing on a TPU host reads it, and it
        # partitions no chips (see env.refuse_chip_contention)
        env["CUDA_VISIBLE_DEVICES"] = args.devices
    # make the framework importable in workers even when not pip-installed
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _count_restart(local_rank: int, rc: int) -> None:
    """Restart events feed the observability registry, so the launcher's
    /metrics (or a snapshot dump) shows fault handling happen."""
    try:
        from ...observability import safe_inc

        safe_inc("paddle_launch_restarts_total",
                 "workers respawned by the launch watch loop, by exit code",
                 exit_code=rc)
    except Exception:
        pass


def launch(argv=None) -> int:
    args = _parse_args(argv)
    from ..env import refuse_chip_contention

    refuse_chip_contention(args.nproc_per_node, "--nproc_per_node")
    # PADDLE_OBS_EXPORT in the shell autostarts an exporter in THIS process
    # at import time — on the launcher that squats rank 0's deterministic
    # port (obs_port + 0) and would force the real rank 0 onto an ephemeral
    # one. The launcher serves no telemetry; release it before spawning.
    try:
        from ...observability import stop_exporter

        stop_exporter()
    except Exception:
        pass
    spec = str(args.nnodes)
    lo = int(spec.split(":")[0])
    hi = int(spec.split(":")[1]) if ":" in spec else lo
    elastic = hi > lo
    nnodes = lo
    world_size = nnodes * args.nproc_per_node

    # rendezvous store: rank0 node hosts it (native TCPStore)
    if args.master:
        master_addr, master_port = args.master.split(":")
        master_port = int(master_port)
    else:
        master_addr, master_port = "127.0.0.1", 0
    store = None
    if args.node_rank == 0:
        from ..store import TCPStore

        store = TCPStore(master_addr if args.master else "127.0.0.1",
                         master_port, is_master=True, world_size=world_size)
        master_port = store.port

    # elastic membership (reference fleet/elastic/manager.py over etcd; here
    # over the same TCPStore): register this node, master watches liveness,
    # scale events relaunch workers with the new world
    enode = manager = None
    world_version = 0
    if elastic:
        from ..fleet.elastic import ElasticManager, ElasticNode
        from ..store import TCPStore

        # rendezvous: a non-master node routinely dials before the master's
        # store is up — TCPStore.__init__'s connect retry backs off under
        # this timeout instead of failing the whole node on the first dial
        client = store or TCPStore(master_addr, master_port, timeout=60.0)
        enode = ElasticNode(client, node_id=f"node{args.node_rank}")
        enode.register()
        if store is not None:  # master node runs the membership watcher
            manager = ElasticManager(client, (lo, hi)).start()
            manager.wait_for_np(lo)
        # all nodes wait for the first committed world
        members = []
        deadline = time.time() + 60
        while time.time() < deadline:
            world_version, members = ElasticManager.read_world(client)
            if world_version > 0:
                break
            time.sleep(0.2)
        if not members:
            raise RuntimeError(
                "elastic rendezvous: no world committed within 60s "
                "(is the master node up?)")
        world_size = len(members) * args.nproc_per_node

    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    procs = {}
    restarts = {i: 0 for i in range(args.nproc_per_node)}

    def spawn(local_rank):
        node_index = None
        if enode is not None and members:
            me = f"node{args.node_rank}"
            node_index = members.index(me) if me in members else args.node_rank
        env = _worker_env(args, local_rank, world_size, master_addr,
                          master_port, node_index=node_index)
        env["PADDLE_WORLD_VERSION"] = str(world_version)
        # incarnation counter: training scripts read this to distinguish a
        # fresh start from a post-failure resume (checkpoint restore path)
        env["PADDLE_RESTART_NUM"] = str(restarts[local_rank])
        cmd = [sys.executable, args.training_script] + args.training_script_args
        stdout = None
        if args.log_dir:
            stdout = open(os.path.join(
                args.log_dir, f"workerlog.{local_rank}"), "ab")
        procs[local_rank] = subprocess.Popen(cmd, env=env, stdout=stdout,
                                             stderr=subprocess.STDOUT if stdout else None)

    for i in range(args.nproc_per_node):
        spawn(i)

    stopping = {"requested": False, "code": 0}

    def shutdown(signum=None, frame=None):
        if signum is not None and not stopping["requested"]:
            # a signaled launcher is being preempted/cancelled: forward the
            # TERM to workers (their preemption handlers checkpoint), give
            # them the grace window, and DO NOT restart them — the old
            # handler fell back into the watch loop, which respawned the
            # just-terminated workers
            stopping["requested"] = True
            stopping["code"] = _SIGNAL_EXIT.get(signum, 1)
            print(f"[launch] signal {signum}: draining workers, no restarts",
                  file=sys.stderr)
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        t0 = time.time()
        while time.time() - t0 < 10 and any(p.poll() is None for p in procs.values()):
            time.sleep(0.2)
        for p in procs.values():
            if p.poll() is None:
                p.kill()

    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)

    # watch loop (reference: launch/controllers/watcher.py)
    exit_code = 0
    try:
        while procs:
            if stopping["requested"]:
                return stopping["code"]
            time.sleep(0.5)
            if stopping["requested"]:
                return stopping["code"]
            # elastic scale event: membership changed -> relaunch every local
            # worker against the new world (reference manager.py:237-316)
            if enode is not None and enode.world_changed(world_version):
                from ..fleet.elastic import ElasticManager

                world_version, members = ElasticManager.read_world(
                    enode.store)
                world_size = len(members) * args.nproc_per_node
                print(f"[launch] elastic scale event v{world_version}: "
                      f"{len(members)} nodes; relaunching workers",
                      file=sys.stderr)
                for p in procs.values():
                    if p.poll() is None:
                        p.terminate()
                for p in procs.values():
                    try:
                        p.wait(timeout=10)
                    except Exception:
                        p.kill()
                procs.clear()
                for i in range(args.nproc_per_node):
                    spawn(i)
                continue
            for lr, p in list(procs.items()):
                if stopping["requested"]:
                    # SIGTERM can land mid-reap: the handler already
                    # terminated everyone — don't respawn workers we just
                    # told to drain
                    return stopping["code"]
                rc = p.poll()
                if rc is None:
                    continue
                if rc == 0:
                    procs.pop(lr)
                elif stopping["requested"]:
                    procs.pop(lr)  # terminated by the drain; never respawn
                elif restarts[lr] < args.max_restarts:
                    restarts[lr] += 1
                    print(f"[launch] worker {lr} exited {rc}; restart "
                          f"{restarts[lr]}/{args.max_restarts}", file=sys.stderr)
                    _count_restart(lr, rc)
                    spawn(lr)
                else:
                    print(f"[launch] worker {lr} failed with {rc}; aborting job",
                          file=sys.stderr)
                    exit_code = rc
                    shutdown()
                    return exit_code
    finally:
        shutdown()
    return exit_code
