"""Fused gather-GEMM MoE dispatch — Pallas TPU kernel reading expert
inputs through the dispatch indices INSIDE the kernel (+ interpret-mode
execution on CPU).

The r5 decomposition (BASELINE.md "Round-5: MoE") ends at ~21 ms/step of
dispatch data movement the XLA formulations cannot remove: the capacity
path materializes the gathered ``[E*C, d]`` activations in HBM (written
by the dispatch gather, read back by the first expert GEMM) and the two
inner ``[E*C, 2h]``/``[E*C, h]`` FFN intermediates besides, and
``ragged_dot``/megablox ``gmm`` measured 2-4x slower at these shapes
(tools/moe_dispatch_bench.py). This kernel is the megablox-style move r5
names: grid (expert, token-block); the dispatch indices ride in as a
SCALAR-PREFETCH operand; each block DMAs its tokens' rows straight from
``x`` in HBM into VMEM by index and runs the whole expert FFN
(gate|up -> silu*mul -> down, f32 accumulation) before anything touches
HBM again — the gathered activations and both FFN intermediates never
exist in HBM. Per step the kernel writes only the ``[E*C, d]`` expert
output the combine gather reads, cutting the formulation's HBM traffic
by the three dropped round trips (the cost-registry rows in
tools/moe_dispatch_bench.py are the verifier).

Semantics are EXACTLY the capacity path's
(:func:`~paddlepaddle_tpu.parallel.moe._gathered_capacity_moe_ffn`):
static ``[E, C]`` slot buffers, tokens beyond capacity dropped, invalid
slots (sentinel index) contributing zero rows. The backward pass is the
reference gather formulation (recomputed; gather-only vjps) — fusing the
two backward GEMMs is a named follow-up seam in docs/kernels.md, so
training steps fuse the forward half today and inference/forward-only
paths get the full win.

Runs compiled on TPU backends (Mosaic accepts it and it matches the
reference at the MoE bench shape: chip_smoke.py, PR 21) and in Pallas
interpret mode elsewhere (CPU tier-1), which is how parity vs the einsum
dispatch is test-pinned without an accelerator
(tests/test_gather_gemm_kernel.py). The price of the tile-aligned gather is one
float32 copy of ``x`` per call; whether the kernel beats the XLA
formulation on the chip is ROADMAP 1.2's measurement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.flags import flag_value
from . import interpret_mode


def gather_gemm_supported(*, d_model: int, d_hidden: int) -> tuple:
    """(ok, reason) — the fallback matrix for the dispatch kernel; a
    False routes the layer to the reference ``sorted`` formulation."""
    if not flag_value("fused_gather_gemm"):
        return False, "FLAGS_fused_gather_gemm off"
    if not interpret_mode():
        # Mosaic wants lane-aligned GEMM operands; interpret mode (CPU
        # tests) accepts any width so tiny parity configs still run
        if d_model % 128 or d_hidden % 128:
            return False, (f"d_model {d_model} / d_hidden {d_hidden} "
                           "not 128-lane aligned")
    return True, "ok"


def _block_m(C: int) -> int:
    """Token-block size: whole capacity when small, 128-row tiles when
    large — always rounded up to a multiple of 8 so the (bm, d) VMEM
    blocks stay sublane-aligned for Mosaic at ANY capacity (small C or
    odd capacity_factor products; the wrapper pads the slack with
    sentinel slots and slices it back off)."""
    return 128 if C >= 128 else -(-C // 8) * 8


def _gather_ffn_kernel(se_ref, x_ref, wgu_ref, wd_ref, o_ref,
                       xb_ref, sems, *, block_m, n_tokens, d_hidden,
                       lane, groups, group_pad):
    """Grid (expert e, token-block c): gather block_m rows of x by the
    prefetched slot->token indices, run the expert FFN, write the block
    of expert output. f32 accumulation on both GEMMs.

    ``x_ref`` is the HBM array ``[T + 1, group_pad, lane]``: one token is
    ``groups`` sublane rows of ``lane`` values (padded to whole 8-row
    tiles), so a token is a tile-aligned DMA — Mosaic refuses a one-row
    slice of a tiled 2-D array. The scratch holds the block's tokens
    back to back, ``[block_m * group_pad, lane]``, and lane-group ``g``
    of every token is the sublane-strided read ``g, g + group_pad, ...``."""
    e, c = pl.program_id(0), pl.program_id(1)
    bm, h = block_m, d_hidden

    def row_copy(i):
        # sentinel (>= n_tokens) marks an unfilled slot: it reads the
        # zero row the wrapper appended at index n_tokens, so FFN(0) = 0
        # comes out of the same GEMMs with no mask — never an OOB gather
        idx = jnp.minimum(se_ref[e, c * bm + i], n_tokens)
        return pltpu.make_async_copy(
            x_ref.at[idx], xb_ref.at[pl.ds(i * group_pad, group_pad), :],
            sems.at[i])

    for i in range(bm):
        row_copy(i).start()
    for i in range(bm):
        row_copy(i).wait()

    # GEMM operands go to the MXU in the weights' storage dtype (bf16 on
    # the chip) with f32 accumulation: f32 copies of both weight banks
    # would not fit the scoped VMEM beside their double-buffered blocks
    gu = jnp.zeros((bm, 2 * h), jnp.float32)
    for g in range(groups):
        xg = xb_ref[pl.ds(g, bm, stride=group_pad), :]    # [bm, lane]
        gu += jnp.dot(xg.astype(wgu_ref.dtype),
                      wgu_ref[0, g * lane:(g + 1) * lane, :],
                      preferred_element_type=jnp.float32)
    hmid = jax.nn.silu(gu[:, :h]) * gu[:, h:]
    out = jnp.dot(hmid.astype(wd_ref.dtype), wd_ref[0],
                  preferred_element_type=jnp.float32)     # [bm, d]
    o_ref[0] = out.astype(o_ref.dtype)


def gather_gemm_ffn(x, slot_entry, wgu, wd, *, capacity, interpret=None):
    """Fused dispatch + expert FFN: returns ``out [E*capacity, d]`` in
    x's dtype, out[e*C + c] = FFN_e(x[slot_entry[e*C + c]]) (zero where
    slot_entry carries the >=T sentinel). ``wgu`` is the concatenated
    ``[E, d, 2h]`` gate|up bank, ``wd`` the ``[E, h, d]`` down bank."""
    T, d = x.shape
    E, _, h2 = wgu.shape
    h = h2 // 2
    C = int(capacity)
    if interpret is None:
        interpret = interpret_mode()
    bm = _block_m(C)
    C_pad = -(-C // bm) * bm
    se = jnp.asarray(slot_entry, jnp.int32).reshape(E, C)
    if C_pad != C:
        se = jnp.concatenate(
            [se, jnp.full((E, C_pad - C), T, jnp.int32)], axis=1)
    # tokens as f32 [T + 1, group_pad, lane] tiles (see the kernel): 32-bit
    # so a tile is 8 sublanes whatever x's dtype, one zero token appended
    # for sentinel slots, lane groups padded to whole tiles
    lane = 128 if d % 128 == 0 else d
    groups = d // lane
    group_pad = -(-groups // 8) * 8
    x3 = jnp.pad(x.astype(jnp.float32).reshape(T, groups, lane),
                 ((0, 1), (0, group_pad - groups), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E, C_pad // bm),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),             # x stays in HBM
            pl.BlockSpec((1, d, h2), lambda e, c, se: (e, 0, 0)),
            pl.BlockSpec((1, h, d), lambda e, c, se: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, d), lambda e, c, se: (e, c, 0)),
        scratch_shapes=[
            pltpu.VMEM((bm * group_pad, lane), jnp.float32),  # gathered
            pltpu.SemaphoreType.DMA((bm,)),
        ],
    )
    kernel = functools.partial(
        _gather_ffn_kernel, block_m=bm, n_tokens=T, d_hidden=h, lane=lane,
        groups=groups, group_pad=group_pad)
    # Mosaic has no 64-bit types and the package turns x64 on at import:
    # trace the call (index maps and body) with it off
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((E, C_pad, d), x.dtype),
            interpret=interpret,
        )(se, x3, wgu, wd)
    return out[:, :C, :].reshape(E * C, d)
