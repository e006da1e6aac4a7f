"""Flash attention — Pallas TPU kernels (forward AND backward) + XLA reference.

Reference surface: python/paddle/nn/functional/flash_attention.py:364 (BSHD
[batch, seq, heads, head_dim], fp16/bf16, causal) backed by dynload flashattn
CUDA kernels (paddle/phi/backends/dynload/flashattn.cc). TPU-native
implementation: online-softmax kernels tiled for the MXU —

* forward: grid (batch*heads, q-blocks), inner fori_loop over kv blocks in
  VMEM, f32 accumulators, causal block skip; also emits the log-sum-exp rows
  used by backward.
* backward: the standard flash bwd pair — a dQ kernel (grid over q-blocks,
  loop kv) and a dK/dV kernel (grid over kv-blocks, loop q), both
  recomputing p = exp(s - lse) blockwise so memory stays O(seq·d), never
  O(seq²). delta = rowsum(dO∘O) is precomputed with one fused XLA op.

Off the TPU (CPU tests), with ``FLAGS_use_pallas_kernels=0``, and for
shapes the kernels do not tile (:func:`_use_pallas`), both directions run
one XLA einsum attention (recompute-style backward). The route is decided
once per call from static shapes, before any kernel is traced, so a train
step either holds all three kernels or none.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ...core.dispatch import apply_op
from ...core.flags import flag_value
from . import current_partition

NEG_INF = -1e30


def _blocks(sq, sk):
    """(block_q, block_kv) for the kernels' grids, or None when the
    sequence lengths leave no sublane-aligned block that divides them."""
    block_q = min(int(flag_value("flash_attn_block_q")), sq)
    block_kv = min(int(flag_value("flash_attn_block_kv")), sk)
    while sq % block_q:
        block_q //= 2
    while sk % block_kv:
        block_kv //= 2
    block_q = max(block_q, 8)
    block_kv = max(block_kv, 8)
    if sq % block_q or sk % block_kv or block_q % 8 or block_kv % 8:
        return None
    return block_q, block_kv


def _use_pallas(sq, sk, head_dim, causal=False) -> bool:
    """The ONE route decision, from static facts: the kernels run on the
    TPU backend when the flag allows, the head dim is lane-friendly, the
    sequences tile, and no causal row is left without a visible key (the
    XLA path defines that case). Everything past a True here is a kernel
    call with no second way out."""
    if not flag_value("use_pallas_kernels"):
        return False
    if jax.default_backend() != "tpu":
        return False
    if not (head_dim % 128 == 0 or head_dim == 64):
        return False
    if causal and sq > sk:
        return False
    return _blocks(sq, sk) is not None


def _vmem_limit(seq, itemsize):
    """Scoped-VMEM request for the kernels that keep whole-sequence
    operands resident (two ``[seq, d]`` blocks and, in the backward pass,
    two ``[seq, 1]`` f32 row vectors): Mosaic pads the minor dimension to
    128 lanes and double-buffers every block, and the default 16 MiB scope
    is what a packed 8k row overflows. None (the default scope) while the
    estimate leaves it half free."""
    need = 2 * seq * 128 * (2 * itemsize + 2 * 4)
    if need <= 8 << 20:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need + (8 << 20),
                                                     96 << 20))


# ---------------------------------------------------------------------------
# XLA reference path (fwd + recompute bwd)
# ---------------------------------------------------------------------------


def _xla_attention(q, k, v, causal, mask, scale):
    # [b, s, h, d] -> [b, h, s, d]
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    sq, sk = logits.shape[-2], logits.shape[-1]
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(causal_mask, logits, NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, NEG_INF)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vt.dtype), vt)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# Pallas kernels. Block refs carry a leading singleton grid dim; [0] strips it.
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_kv, seq_q, seq_k):
    # Causal masking is bottom-right aligned like the reference flashattn and
    # the XLA path: query i sees keys j <= i + (seq_k - seq_q). For
    # seq_q == seq_k this is the familiar lower triangle.
    qi = pl.program_id(1)
    off = seq_k - seq_q
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
    d = q.shape[-1]

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    num_kv = seq_k // block_kv
    if causal:
        num_visit = jnp.minimum(pl.cdiv((qi + 1) * block_q + off, block_kv), num_kv)
    else:
        num_visit = num_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos + off >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_visit, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)  # [bq, 1]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, block_q, block_kv, seq_q, seq_k):
    qi = pl.program_id(1)
    off = seq_k - seq_q
    q = q_ref[0].astype(jnp.float32)                  # [bq, d]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                  # [bq, 1]
    delta = delta_ref[0]
    d = q.shape[-1]

    num_kv = seq_k // block_kv
    if causal:
        num_visit = jnp.minimum(pl.cdiv((qi + 1) * block_q + off, block_kv), num_kv)
    else:
        num_visit = num_kv

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos + off >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bkv]
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_visit, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *,
                scale, causal, block_q, block_kv, seq_q, seq_k):
    ki = pl.program_id(1)
    off = seq_k - seq_q
    k = k_ref[0].astype(jnp.float32)                  # [bkv, d]
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]
    num_q = seq_q // block_q
    if causal:
        # q rows with q_pos + off >= this block's first k index participate
        start = jnp.maximum(ki * block_kv - off, 0) // block_q
    else:
        start = 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)  # [bq, bkv]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos + off >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_new = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    zeros = jnp.zeros((block_kv, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, num_q, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_forward(q, k, v, causal, scale):
    """q,k,v: [bh, s, d] on a shape :func:`_use_pallas` accepted.
    Returns (out, lse)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_kv = _blocks(sq, sk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, seq_q=sq, seq_k=sk)
    grid = (bh, sq // block_q)
    # Mosaic lowering has no int64/float64 path (jax 0.9 _convert_helper
    # recurses forever on unsupported casts); the package enables x64 globally
    # for paddle dtype parity, so trace the kernel with x64 off.
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid=grid,
            compiler_params=_vmem_limit(sk, q.dtype.itemsize),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            ],
        )(q, k, v)


def _pallas_backward(q, k, v, out, lse, do, causal, scale):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_kv = _blocks(sq, sk)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [bh, sq, 1]

    full_q = pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0))
    full_kv = pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0))
    row_q = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    row_kv = pl.BlockSpec((1, block_kv, d), lambda b, i: (b, i, 0))
    vec_q_block = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    vec_q_full = pl.BlockSpec((1, sq, 1), lambda b, i: (b, 0, 0))

    with jax.enable_x64(False):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_kv=block_kv, seq_q=sq, seq_k=sk),
            grid=(bh, sq // block_q),
            in_specs=[row_q, full_kv, full_kv, row_q, vec_q_block, vec_q_block],
            out_specs=row_q,
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            compiler_params=_vmem_limit(sk, q.dtype.itemsize),
        )(q, k, v, do, lse, delta)

        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_kv=block_kv, seq_q=sq, seq_k=sk),
            grid=(bh, sk // block_kv),
            in_specs=[full_q, row_kv, row_kv, full_q, vec_q_full, vec_q_full],
            out_specs=[row_kv, row_kv],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
            ],
            compiler_params=_vmem_limit(sq, q.dtype.itemsize),
        )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


def _bshd_to_flat(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _flat_to_bshd(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


def _kernel_fwd(q, k, v, causal, scale):
    """[b, s, h, d] operands -> (out [b, s, h, d], lse [b, h, s])."""
    b, s, h, d = q.shape
    out_flat, lse = _pallas_forward(_bshd_to_flat(q), _bshd_to_flat(k),
                                    _bshd_to_flat(v), causal, scale)
    # keep the RESIDUAL compact: the kernel's [bh, sq, 1] output is
    # lane-padded 128x by Mosaic tiling (64 MB/layer at bench shapes);
    # squeezing it lets XLA free the padded temp while only 2 MB/layer
    # survives to the backward pass
    return _flat_to_bshd(out_flat, b, h), lse.reshape(b, h, s)


def _kernel_bwd(q, k, v, out, lse, g, causal, scale):
    b, s, h, d = q.shape
    dq, dk, dv = _pallas_backward(
        _bshd_to_flat(q), _bshd_to_flat(k), _bshd_to_flat(v),
        _bshd_to_flat(out), lse.reshape(b * h, s, 1), _bshd_to_flat(g),
        causal, scale)
    return (_flat_to_bshd(dq, b, h), _flat_to_bshd(dk, b, h),
            _flat_to_bshd(dv, b, h))


def _over_mesh(kernel, operands, out_kinds):
    """Run ``kernel(*operands)`` — [b, s, h, d] arrays and [b, h, s] row
    statistics — on every device's own block of batch rows and heads when
    a sharded step declared its mesh (``kernels.partition_over``); plainly
    otherwise. GSPMD cannot partition a Mosaic call by itself ("Mosaic
    kernels cannot be automatically partitioned"): on a real multi-chip
    mesh a jitted step holding the kernels does not lower at all, which a
    virtual CPU mesh (XLA route) never shows. Attention is independent per
    batch row and per head, so any split of those two is correct; sequence
    and head_dim stay whole."""
    part = current_partition()
    if part is None or part.mesh.size == 1:
        return kernel(*operands)
    b, _, h, _ = operands[0].shape
    b_axes, h_axes = part.split(b, h)
    specs = {"bshd": P(b_axes, None, h_axes, None),
             "bhs": P(b_axes, h_axes, None)}
    return jax.shard_map(
        kernel, mesh=part.mesh,
        in_specs=tuple(specs["bshd" if x.ndim == 4 else "bhs"]
                       for x in operands),
        out_specs=tuple(specs[kind] for kind in out_kinds),
        check_vma=False)(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, causal, scale, use_pallas):
    return _flash_fwd(q, k, v, causal, scale, use_pallas)[0]


def _flash_fwd(q, k, v, causal, scale, use_pallas):
    if not use_pallas:
        # out is a residual only for the Pallas backward (delta =
        # rowsum(dO∘O)); the XLA recompute never reads it — don't keep it
        # alive there
        return (_xla_attention(q, k, v, causal, None, scale),
                (q, k, v, None, None))
    out, lse = _over_mesh(
        lambda q_, k_, v_: _kernel_fwd(q_, k_, v_, causal, scale),
        (q, k, v), ("bshd", "bhs"))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, use_pallas, res, g):
    q, k, v, out, lse = res
    if use_pallas:
        return _over_mesh(
            lambda *a: _kernel_bwd(*a, causal, scale),
            (q, k, v, out, lse, g), ("bshd",) * 3)
    # XLA recompute (O(N²) intermediate, XLA-fused)
    _, vjp = jax.vjp(lambda q_, k_, v_: _xla_attention(q_, k_, v_, causal, None, scale), q, k, v)
    return vjp(g)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bshd(query, key, value, causal=False, mask=None, dropout=0.0):
    """Public entry — Tensor in/out, BSHD layout like the reference API."""

    def f(q, k, v, m):
        scale = 1.0 / math.sqrt(q.shape[-1])
        if m is None and (dropout == 0.0):
            return _flash_core(q, k, v, causal, scale, _use_pallas(
                q.shape[1], k.shape[1], q.shape[-1], causal))
        out = _xla_attention(q, k, v, causal, m, scale)
        if dropout > 0.0:
            from ...core import random as prandom

            keep = jax.random.bernoulli(prandom.next_key(), 1.0 - dropout, out.shape)
            out = jnp.where(keep, out / (1.0 - dropout), 0.0).astype(out.dtype)
        return out

    return apply_op(f, query, key, value, mask, op_name="flash_attention")
