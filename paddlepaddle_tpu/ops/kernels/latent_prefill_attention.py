"""Expanded-latent (MLA) prefill attention of a long prompt — a Pallas TPU kernel
that runs a block of queries against the blocks of keys up to its own last
position with an online softmax (+ the Pallas interpreter off the chip).

The expanded form (models/latent_attention.py) has 192-wide queries and keys in
two parts, ``nope`` (per head, expanded from the latent) and ``rope`` (the ONE
rotated key all heads share), against 128-wide values: no kernel of one width
for q, k and v fits it (ops/kernels/flash_attention.py). Here the two parts of
a score are two matmuls into one float32 tile, so the shared key is never
copied per head, and the values keep their own width.

Shape contract (one admission, or a whole causal forward):

* ``q_nope [b, s, H, nope]``, ``q_rope [b, s, H, rope]``: the queries, the rope
  part rotated already; row ``i`` of batch row ``r`` sits at position
  ``start[r] + i``;
* ``k_nope [b, L, H, nope]``, ``k_rope [b, L, rope]``, ``v [b, L, H, vd]``: key
  row ``l`` sits at position ``l``; a query sees the keys at or before its own
  position;
* ``start [b]`` int32: a scalar-prefetch operand, so one program serves every
  offset (a question behind a cached document, a whole prompt from 0).

The grid is (batch x heads, query blocks). A head's keys and values stay in
the chip's fast memory for all its query blocks (the block index does not
change, so nothing is copied again; 12.8 MB a head at 16,640 keys), and a
query block loops over ``cdiv(its last position + 1, block)`` key blocks: what
lies wholly above the diagonal is never read or computed. Scores exist one
``[block_q, block_kv]`` float32 tile at a time, whatever the prompt's length.
Operands go to the MXU in the model's dtype, scores, softmax and accumulation
are float32, ``P`` is cast to the values' dtype as the plain form casts it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

NEG_INF = -1e30
# queries and keys a block: a 512 x 512 float32 tile of scores is 1 MiB, and the
# masked half of a diagonal block is 1/24 of the work of a 12k-token prompt
BLOCK = 512


def _kernel(start_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, *, scale,
            block_q, block_kv, num_kv, heads):
    f32 = jnp.float32
    first = start_ref[pl.program_id(0) // heads] + pl.program_id(1) * block_q
    qn, qr = qn_ref[0], qr_ref[0]                               # [bq, nope], [bq, rope]
    vd = v_ref.shape[-1]
    # key blocks that hold a position at or before this block's last query
    num_visit = jnp.clip((first + block_q + block_kv - 1) // block_kv, 0, num_kv)
    nt = (((1,), (1,)), ((), ()))                               # a @ b.T

    def body(j, carry):
        m, l, acc = carry
        rows = pl.ds(pl.multiple_of(j * block_kv, block_kv), block_kv)
        s = (jax.lax.dot_general(qn, kn_ref[0, rows, :], nt, preferred_element_type=f32)
             + jax.lax.dot_general(qr, kr_ref[0, rows, :], nt, preferred_element_type=f32)) * scale
        q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        v = v_ref[0, rows, :]
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v, preferred_element_type=f32)
        return m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True), acc

    init = (jnp.full((block_q, 1), NEG_INF, f32), jnp.zeros((block_q, 1), f32),
            jnp.zeros((block_q, vd), f32))
    _, l, acc = jax.lax.fori_loop(0, num_visit, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def latent_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start, *, scale,
                             block=None, interpret=None):
    """``out [b, s, H, vd]`` in ``q_nope``'s dtype (module docstring: the shapes).
    ``block`` overrides the block of queries and of keys (the tests run several
    blocks of a tiny prompt with it)."""
    if interpret is None:
        interpret = interpret_mode()
    return _program(float(scale), int(block or BLOCK), bool(interpret))(
        q_nope, q_rope, k_nope, k_rope, v, start)


@functools.lru_cache(maxsize=None)
def _program(scale: float, block: int, interpret: bool):
    """The jitted call for one static choice: the same callable for every layer,
    so an admission traces and lowers the kernel once."""
    return jax.jit(functools.partial(_attend, scale=scale, block=block,
                                     interpret=interpret))


def _attend(q_nope, q_rope, k_nope, k_rope, v, start, *, scale, block, interpret):
    b, s, H, nope = q_nope.shape
    L, rope, vd = k_nope.shape[1], q_rope.shape[-1], v.shape[-1]
    dtype = k_nope.dtype
    bq = min(block, -(-s // 8) * 8)
    bk = min(block, -(-L // 8) * 8)
    nq, nk = -(-s // bq), -(-L // bk)

    def rows(a, n):
        """``[b, n, (H,) d] -> [b (x H), rows padded to n, d]``: heads lead. A
        padded key lies past every real query and is never seen; a padded
        query's row is dropped."""
        if a.ndim == 4:
            a = a.transpose(0, 2, 1, 3).reshape(b * H, a.shape[1], a.shape[3])
        return jnp.pad(a.astype(dtype), ((0, 0), (0, n - a.shape[1]), (0, 0)))

    per_head = lambda g, i, start: (g, i, 0)
    whole = lambda g, i, start: (g, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * H, nq),
        in_specs=[pl.BlockSpec((1, bq, nope), per_head),
                  pl.BlockSpec((1, bq, rope), per_head),
                  pl.BlockSpec((1, nk * bk, nope), whole),
                  pl.BlockSpec((1, nk * bk, rope), lambda g, i, start: (g // H, 0, 0)),
                  pl.BlockSpec((1, nk * bk, vd), whole)],
        out_specs=pl.BlockSpec((1, bq, vd), per_head))
    kernel = functools.partial(_kernel, scale=scale, block_q=bq, block_kv=bk,
                               num_kv=nk, heads=H)
    # a head's keys and values are resident and double-buffered, the narrow rope
    # part padded to whole lanes: ask for what that takes beyond the default scope
    resident = 2 * nk * bk * (2 * 128 + max(vd, 128)) * jnp.dtype(dtype).itemsize
    # Mosaic has no 64-bit types and the package turns x64 on at import: trace
    # the call (index maps and body) with it off.
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b * H, nq * bq, vd), q_nope.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=min(resident + (24 << 20), 100 << 20)),
            interpret=interpret,
        )(jnp.asarray(start, jnp.int32).reshape(b), rows(q_nope, nq * bq),
          rows(q_rope, nq * bq), rows(k_nope, nk * bk), rows(k_rope, nk * bk),
          rows(v, nk * bk))
    return out[:, :s].reshape(b, H, s, vd).transpose(0, 2, 1, 3)
