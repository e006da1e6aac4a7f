"""Ring attention — context parallelism over a sequence mesh axis.

The reference has NO ring/Ulysses attention (SURVEY.md §5 long-context: its
long-sequence story is the 'sep' axis + flash kernel only); this module
EXCEEDS it with true ring attention (Liu et al. 2023 style): the sequence dim
of Q/K/V is sharded over a mesh axis, K/V blocks rotate around the ring via
``lax.ppermute`` over ICI while each shard accumulates online-softmax partial
attention for its local Q block. Peak memory per chip is O(s_local²) and the
K/V transfer overlaps with the block matmuls (XLA pipelines the permute).

Causal masking is block-aware: a shard skips the numerator work for fully
masked future blocks via a zero multiplier (uniform control flow keeps it
SPMD-compilable), matching flash-attention's block-skip semantics.

The whole loop is a differentiable ``lax.scan`` — ``jax.grad`` yields the
backward ring pass automatically (reverse permutes), so no hand-written
backward kernel is needed.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn_update(q, k, v, m, l, acc, q_off, k_off, causal, scale):
    """Online-softmax update of (m, l, acc) with one K/V block.

    q: [b, sq, h, d]; k/v: [b, sk, h, d]; m/l: [b, h, sq, 1]; acc [b,h,sq,d].
    q_off/k_off: global sequence offsets of the blocks (traced scalars).
    """
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale   # [b,h,sq,d]
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if causal:
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return m_new, l_new, acc_new


def _ring_body(q, k0, v0, sp_axis, n_shards, causal, scale):
    """Per-shard program (inside shard_map). q/k0/v0: local [b, s_loc, h, d]."""
    my = jax.lax.axis_index(sp_axis)
    b, s_loc, h, d = q.shape
    m0 = jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    a0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    q_off = my * s_loc

    def accumulate(t, m, l, acc, k, v):
        kv_rank = (my - t) % n_shards
        k_off = kv_rank * s_loc
        m2, l2, a2 = _block_attn_update(q, k, v, m, l, acc, q_off, k_off,
                                        causal, scale)
        if causal:
            # whole block in the future -> keep previous stats (zero-mult
            # select keeps control flow uniform across shards)
            skip = kv_rank > my
            m2 = jnp.where(skip, m, m2)
            l2 = jnp.where(skip, l, l2)
            a2 = jnp.where(skip, acc, a2)
        return m2, l2, a2

    def step(carry, t):
        m, l, acc, k, v = carry
        m2, l2, a2 = accumulate(t, m, l, acc, k, v)
        k = jax.lax.ppermute(k, sp_axis, perm)
        v = jax.lax.ppermute(v, sp_axis, perm)
        return (m2, l2, a2, k, v), None

    # rotate K/V only n-1 times; the last block needs no onward transfer
    (m, l, acc, k, v), _ = jax.lax.scan(
        step, (m0, l0, a0, k0, v0), jnp.arange(n_shards - 1))
    m, l, acc = accumulate(jnp.int32(n_shards - 1), m, l, acc, k, v)
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [b, s_loc, h, d]


def ring_attention(q, k, v, mesh: Mesh, sp_axis: str = "sp", causal: bool = True,
                   scale: float = None, data_axis: str = None):
    """Context-parallel attention over BSHD arrays whose seq dim is sharded
    on ``sp_axis``. Returns same-shape output with the same layout."""
    n = mesh.shape[sp_axis]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if q.shape[1] % n:
        raise ValueError(f"seq {q.shape[1]} not divisible by {sp_axis}={n}")
    if data_axis is not None and data_axis not in mesh.shape:
        data_axis = None
    if data_axis is not None and q.shape[0] % mesh.shape[data_axis]:
        data_axis = None  # batch not divisible -> keep it replicated
    spec = P(data_axis, sp_axis, None, None)
    body = partial(_ring_body, sp_axis=sp_axis, n_shards=n, causal=causal,
                   scale=scale)
    return jax.shard_map(
        lambda q_, k_, v_: body(q_, k_, v_),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ring_flash_attention(query, key, value, mesh=None, sp_axis="sp",
                         causal=True, data_axis=None):
    """Tensor-level eager/traced op wrapper around :func:`ring_attention`."""
    from ...core.dispatch import apply_op

    if mesh is None:
        from ...distributed.mesh import get_mesh

        pm = get_mesh()
        if pm is None:
            raise ValueError("ring_flash_attention needs a mesh (set_mesh/fleet.init)")
        mesh = pm.to_jax()

    def f(q, k, v):
        return ring_attention(q, k, v, mesh, sp_axis=sp_axis, causal=causal,
                              data_axis=data_axis)

    return apply_op(f, query, key, value, op_name="ring_flash_attention")
