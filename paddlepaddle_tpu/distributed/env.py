"""Process environment (reference: python/paddle/distributed/parallel.py:978
init_parallel_env + TCPStore rendezvous).

TPU-native model: ONE python process per host drives all local chips; the
GSPMD runtime handles cross-chip collectives over ICI, and
``jax.distributed.initialize`` (TCP store rendezvous, the TCPStore analogue)
federates hosts over DCN. "rank" therefore means host index and "world size"
host count — per-chip ranks do not exist at the python level (SURVEY.md §2.6
TPU-native equivalent row)."""

from __future__ import annotations

import os

import jax

_initialized = False


def refuse_chip_contention(children: int, what: str) -> None:
    """One process per chip-holding host: exit rather than start
    ``children`` > 1 processes that would each try to take the host's TPU
    chips — all but the first would fail or hang at jax start-up. Chips are
    counted by the PCI scan jax's own start-up uses, so the caller (a
    launcher, a fleet bench) initialises no backend and leaves the chips to
    its children. ``JAX_PLATFORMS=cpu`` — children that never take a chip —
    passes."""
    if children <= 1 or os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return
    from jax._src import hardware_utils

    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    if chips:
        raise SystemExit(
            f"{what} {children} on a host with {chips} TPU chip(s): one "
            "process drives all of a host's chips and a chip belongs to one "
            "process at a time, so every child after the first would fail "
            f"or hang at jax start-up. Use {what} 1, or JAX_PLATFORMS=cpu "
            "for a CPU run.")


def init_parallel_env():
    """Multi-host rendezvous. Single-host (or driver-managed) setups no-op."""
    global _initialized
    if _initialized:
        return
    coord = os.environ.get("PADDLE_TPU_COORDINATOR") or os.environ.get("MASTER_ADDR")
    nprocs = os.environ.get("PADDLE_TRAINERS_NUM") or os.environ.get("WORLD_SIZE")
    pid = os.environ.get("PADDLE_TRAINER_ID") or os.environ.get("RANK")
    if coord and nprocs and int(nprocs) > 1:
        port = os.environ.get("MASTER_PORT", "8476")
        jax.distributed.initialize(
            coordinator_address=f"{coord}:{port}",
            num_processes=int(nprocs),
            process_id=int(pid or 0),
        )
    _initialized = True


def is_initialized():
    return _initialized


def get_rank(group=None) -> int:
    # launcher env first (reference parallel.py semantics): a spawned /
    # launched eager job has per-process ranks even though each process is
    # its own single-process jax runtime. Only OUR launcher's PADDLE_* names
    # are trusted — a stale torchrun RANK/WORLD_SIZE in the shell must not
    # lie about the world (host_collectives pins PADDLE_* from RANK when a
    # torch-style job actually rendezvouses).
    r = os.environ.get("PADDLE_TRAINER_ID")
    if r is not None:
        return int(r)
    return jax.process_index()


def get_world_size(group=None) -> int:
    w = os.environ.get("PADDLE_TRAINERS_NUM")
    if w is not None:
        return int(w)
    return jax.process_count()


def parallel_device_count() -> int:
    return jax.local_device_count()
