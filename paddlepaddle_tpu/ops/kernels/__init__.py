"""Pallas TPU kernels for the fused hot ops.

These are the TPU-native equivalents of the reference's fused CUDA kernels
(paddle/phi/kernels/fusion/gpu/: flash-attn via dynload, fused_rope,
fused_rms_norm, fused_bias_act …). The flash kernels have an XLA reference
path used on CPU (tests run on a virtual CPU mesh) and when
FLAGS_use_pallas_kernels=0.

gather_gemm.py (the MoE dispatch of ``MoELayer(dispatch_mode="fused")``),
paged_latent_attention.py and paged_gqa_attention.py (a decode step's
attention over a latent cache row and over a K/V pair, behind the serving
engine's paged view: docs/kernels.md), latent_prefill_attention.py (a long
prefill's latent attention) and held_experts.py (an expert share's held
experts for at most ``parallel.moe.HELD_KERNEL_MAX_TOKENS`` rows, reading
only the experts the rows picked) additionally run in Pallas INTERPRET mode
on CPU, so parity is test-pinned in the tier-1 environment.

Every kernel here is compiled by Mosaic and compared with its
``jax.numpy`` reference by ``chip_smoke.py``'s kernels leg on the chip;
the interpreter alone proves the program, not that the chip accepts it.

Every kernel but the flash family names its ``pallas_call`` by
:func:`call_name`, so a device trace shows the call as
``%<kernel>_<program>.N``. The flash kernels keep the names of the
transformations around them (``jvp``, ``transpose(jvp)``), which the train
benchmark's readers match.
"""

import contextlib
import contextvars
from typing import NamedTuple, Optional, Tuple


def interpret_mode() -> bool:
    """True when these kernels must run under the Pallas interpreter —
    any backend without a Mosaic compiler (the CPU tier-1 environment).
    ONE definition for every kernel in this package, so one kernel cannot
    run compiled and another interpreted on the same host."""
    import jax

    return jax.default_backend() != "tpu"


class KernelPartition(NamedTuple):
    """A sharded step's declaration, at trace time, of the mesh its program
    runs on and the mesh axes its batch dimension is split over."""

    mesh: object
    batch_axes: Tuple[str, ...]

    def split(self, batch: int, heads: int):
        """(batch axes, head axes) for a kernel that is independent per
        batch row and per head: the declared batch axes while they divide
        ``batch``, then every remaining mesh axis while it divides
        ``heads`` (a model-parallel axis shards heads already; any other
        split is as correct, the partitioner reshards to it)."""
        def take(axes, n):
            got = []
            for a in axes:
                size = self.mesh.shape[a]
                if n % size == 0:
                    got.append(a)
                    n //= size
            return tuple(got) or None

        b_axes = take(self.batch_axes, batch)
        rest = [a for a in self.mesh.axis_names if a not in (b_axes or ())]
        return b_axes, take(rest, heads)


_partition: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_partition", default=None)


@contextlib.contextmanager
def partition_over(mesh, batch_axes):
    """Entered by a sharded step around the code it traces: Mosaic kernels
    inside run under ``shard_map`` over ``mesh`` instead of being left to
    GSPMD, which cannot partition them."""
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    token = _partition.set(KernelPartition(mesh, tuple(batch_axes or ())))
    try:
        yield
    finally:
        _partition.reset(token)


def current_partition() -> Optional[KernelPartition]:
    return _partition.get()


_program: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_program", default=None)


@contextlib.contextmanager
def traced_by(program: str):
    """Entered by an engine's program while jax traces it (``decode``,
    ``admit``): a kernel called inside names its Mosaic call after the
    program too (:func:`call_name`), so a device trace tells a decode step's
    call from an admission's where one kernel runs in both. As a decorator it
    keeps the function's ``__name__``, which names the XLA module a jitted
    program becomes (``jit_run``)."""
    token = _program.set(program)
    try:
        yield
    finally:
        _program.reset(token)


def call_name(kernel: str) -> str:
    """The name a kernel gives its ``pallas_call``, and so its HLO
    instruction: the kernel's module name, ``_<program>`` appended inside
    :func:`traced_by`."""
    program = _program.get()
    return f"{kernel}_{program}" if program else kernel
