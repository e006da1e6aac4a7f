"""The held experts of an expert share for a few rows, reading only the experts
those rows picked — a Pallas TPU kernel over the stacked weights (+ the Pallas
interpreter off the chip).

An expert share (``parallel/moe.py::expert_share_ffn``) holds ``count``
routed SwiGLU experts stacked ``wg, wu [count, d, f]``, ``wd [count, f, d]``.
Its dense form (``_held_experts_dense``) runs every held expert on every row
and weighs the pairs nobody picked by zero: at a decode step of 32 or 128
rows it reads every held expert's weights every call, whether a row picked
the expert or not. Here the pipeline copies the blocks of the experts that at
least one row picked, and no other.

Shape contract:

* ``x [T, d]``; ``w_local [T, count]`` float32: row ``t``'s weight on held
  expert ``e``, zero where the row did not pick it;
* ``picked_ids [count]`` int32: the touched experts first, in order, then the
  last touched one repeated; ``n_picked [1]`` int32: how many are touched.
  :func:`picked_experts` makes both from the picks, never from the weights: a
  pick that weighs exactly 0 still counts;
* the result is ``[T, d]`` float32, ``sum over e of w_local[:, e] * E_e(x)``,
  as the dense form gives it.

The walk. ``picked_ids`` and ``n_picked`` are scalar-prefetch operands; the
grid is ``(count, f // tf)``, the expert outer and the width tile inner. A
step's blocks are ``wg``/``wu`` ``(1, d, tf)`` at ``(ids[e], 0, f)`` and
``wd`` ``(1, tf, d)`` at ``(ids[e], f, 0)``. A step past the touched experts
(``e >= n``) maps every block to the last real step's, ``(ids[n - 1], ·, last
tile)``: the pipeline starts a copy only where a block index changes, so the
weights of an expert nobody picked are never read (megablox's rule for an
empty group). ``x``, the weights ``w_local`` and the float32 result are
blocks that stay resident; the result accumulates across the grid.

Arithmetic is the dense form's, product for product and cast for cast:
``a = (silu(x @ wg) * (x @ wu) * w).astype(x.dtype)`` with float32
accumulation, then ``a @ wd`` in float32; only the order of the sum over
experts and width tiles differs. Rows are padded to a whole bf16 sublane tile.

Forward only: the expert shares are served, never trained, and a gradient
that reaches this call raises rather than differentiating the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import call_name, interpret_mode

LANES = 128
SUBLANES = 16            # rows of a bf16 tile
# the expert width a grid step reads: tf = WIDTH_TILE where it divides the
# width, else the whole width. Read on a TPU v5 lite (PERF.md section 6; ms
# a layer at tf 256 / 512 / 1,024, the dense form beside): 32 rows x
# 7,168 x 2,048 with 5.2 of 12 held experts touched 0.802 / 0.809 / 0.776
# (dense 1.595), 128 rows x 6,144 x 2,048 with 14.25 of 16 1.698 / 1.774 /
# 1.663 (dense 1.821). 1,024 holds 88 MB of fast memory for 2-4% over 256,
# which holds 22 MB.
WIDTH_TILE = 256


def picked_experts(picks, count: int):
    """``(ids [count] int32, n [1] int32)`` of the held experts that
    ``picks [T, k]`` touch (a held expert's local index, ``count`` or more for
    any other pick): the touched ones first, in order, the last of them
    repeated after them (expert 0 where none is touched), and their number."""
    touched = jnp.any(picks.reshape(-1, 1)
                      == jnp.arange(count, dtype=picks.dtype), axis=0)
    n = jnp.sum(touched, dtype=jnp.int32)
    order = jnp.argsort(1 - touched.astype(jnp.int32), stable=True)
    order = order.astype(jnp.int32)
    ids = jnp.where(jnp.arange(count) < n, order, order[jnp.maximum(n - 1, 0)])
    return ids, n[None]


def _kernel(ids_ref, n_ref, x_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref):
    """Grid (expert step e, width tile f): one touched expert's tile of
    ``tf`` hidden units added to every row's result."""
    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(e < n_ref[0])
    def _():
        f32 = jnp.float32
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=f32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=f32)
        w = w_ref[...]                                  # [T, lanes], f32
        mine = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) == ids_ref[e]
        w = jnp.sum(jnp.where(mine, w, 0.0), axis=1, keepdims=True)
        a = (jax.nn.silu(g) * u * w).astype(x.dtype)
        o_ref[...] += jnp.dot(a, wd_ref[0], preferred_element_type=f32)


def held_experts_decode(x, w_local, picked_ids, n_picked, wg, wu, wd, *,
                        tile=None, interpret=None):
    """``[T, d]`` float32: the held experts' weighted sum for ``x``, reading
    the weights of the ``n_picked`` experts listed first in ``picked_ids``
    alone (module docstring). ``tile`` overrides the width tile (the tests
    walk several tiles of a tiny width with it)."""
    width = wg.shape[-1]
    if tile is None:
        tile = WIDTH_TILE if width % WIDTH_TILE == 0 else width
    if interpret is None:
        interpret = interpret_mode()
    return _forward_only(x, w_local, picked_ids, n_picked, wg, wu, wd,
                         int(tile), bool(interpret), call_name("held_experts"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _forward_only(x, w_local, ids, n, wg, wu, wd, tf, interpret, name):
    return _program(tf, interpret, name)(x, w_local, ids, n, wg, wu, wd)


def _forward_only_fwd(x, w_local, ids, n, wg, wu, wd, tf, interpret, name):
    return _forward_only(x, w_local, ids, n, wg, wu, wd, tf, interpret,
                         name), None


def _forward_only_bwd(tf, interpret, name, res, g):
    raise NotImplementedError(
        "held_experts_decode is forward-only: the expert shares it serves are "
        "not trained, and the kernel has no backward pass")


_forward_only.defvjp(_forward_only_fwd, _forward_only_bwd)


@functools.lru_cache(maxsize=None)
def _program(tf: int, interpret: bool, name: str):
    """The jitted call for one static choice and call name: the SAME
    callable for every expert layer, so a decode program traces the kernel
    once, not once a layer (as ``paged_latent_attention._program``)."""
    return jax.jit(functools.partial(_call, tf=tf, interpret=interpret,
                                     name=name))


def _call(x, w_local, ids, n, wg, wu, wd, *, tf, interpret, name):
    T, d = x.shape
    count, _, width = wg.shape
    nf = width // tf
    rows = -(-T // SUBLANES) * SUBLANES
    x = jnp.pad(x, ((0, rows - T), (0, 0)))
    # zero weights on the padded rows and on the lanes past ``count``
    w_local = jnp.pad(w_local.astype(jnp.float32),
                      ((0, rows - T), (0, -count % LANES)))

    def whole(e, f, ids, n):
        return (0, 0)

    def gate_up(e, f, ids, n):
        return (ids[e], 0, jnp.where(e < n[0], f, nf - 1))

    def down(e, f, ids, n):
        return (ids[e], jnp.where(e < n[0], f, nf - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count, nf),
        in_specs=[pl.BlockSpec((rows, d), whole),
                  pl.BlockSpec((rows, w_local.shape[1]), whole),
                  pl.BlockSpec((1, d, tf), gate_up),
                  pl.BlockSpec((1, d, tf), gate_up),
                  pl.BlockSpec((1, tf, d), down)],
        out_specs=pl.BlockSpec((rows, d), whole))
    item = jnp.dtype(wg.dtype).itemsize
    # double-buffered weight blocks, x, the weights and the result, and the
    # step's float32 intermediates
    need = (2 * 3 * d * tf * item + 2 * rows * d * (x.dtype.itemsize + 4)
            + 2 * rows * w_local.shape[1] * 4 + rows * (3 * tf + d) * 4)
    # Mosaic has no 64-bit types and the package turns x64 on at import:
    # trace the call (index maps and body) with it off
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=int(need) + (16 << 20)),
            interpret=interpret,
            name=name,
        )(jnp.asarray(ids, jnp.int32), jnp.asarray(n, jnp.int32), x, w_local,
          wg, wu, wd)
    return out[:T]
