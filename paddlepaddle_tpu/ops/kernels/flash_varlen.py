"""Varlen (unpadded/packed) flash attention — segment-masked Pallas kernels.

Reference surface: flash_attn_unpadded
(python/paddle/nn/functional/flash_attention.py:762): q/k/v packed as
[total_tokens, heads, head_dim] with ``cu_seqlens_q/k`` prefix sums
delimiting the sequences of the batch, backed by the varlen CUDA flashattn.

TPU-native design: sequences stay packed; the kernels derive each token's
(segment id, local position) IN-KERNEL from the cu_seqlens prefix sums held
in SMEM — a vectorized O(batch) comparison sweep per tile, no gather — and
mask logits where segments differ. Causal masking is per-segment and
bottom-right aligned like the dense kernels (local q position offset by
len_k - len_q of its own segment). Fully-masked rows (padding tokens, or a
query segment with no keys) produce zero output and zero gradients: the
online-softmax probabilities are multiplied by the mask so a row whose
running max never leaves -inf cannot fabricate exp(0)=1 weights.

The XLA path builds the same mask densely ([total_q, total_k]) and is
used on CPU and for odd shapes; jax.grad differentiates it directly. The
Pallas path wires a custom vjp (dQ and dK/dV kernels, same recompute
structure as the dense ones in flash_attention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.dispatch import apply_op
from .flash_attention import NEG_INF, _blocks, _use_pallas, _vmem_limit


# ---------------------------------------------------------------------------
# segment bookkeeping (shared by both paths)
# ---------------------------------------------------------------------------


def _seg_info(cu, total):
    """Per-token (segment id, local position, validity) from prefix sums.

    Tokens at or past cu[-1] (padding in the packed buffer) get seg == -1.
    """
    idx = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], idx, side="right").astype(jnp.int32)
    valid = idx < cu[-1]
    seg = jnp.where(valid, seg, -1)
    pos = idx - cu[jnp.clip(seg, 0, cu.shape[0] - 2)]
    return seg, pos.astype(jnp.int32), valid


def _varlen_xla(q, k, v, cu_q, cu_k, causal, scale, dropout=0.0,
                dropout_key=None):
    """Dense-mask reference path. q,k,v: [t, h, d] packed. ``dropout`` is
    applied to the attention probabilities (inverted scaling), matching the
    reference kernel's semantics."""
    tq, tk = q.shape[0], k.shape[0]
    seg_q, pos_q, valid_q = _seg_info(cu_q, tq)
    seg_k, pos_k, valid_k = _seg_info(cu_k, tk)
    len_q = jnp.diff(cu_q)
    len_k = jnp.diff(cu_k)
    off_q = (len_k - len_q)[jnp.clip(seg_q, 0, len_q.shape[0] - 1)]

    qt = jnp.transpose(q, (1, 0, 2)).astype(jnp.float32)  # [h, tq, d]
    kt = jnp.transpose(k, (1, 0, 2)).astype(jnp.float32)
    vt = jnp.transpose(v, (1, 0, 2))
    logits = jnp.einsum("hqd,hkd->hqk", qt, kt) * scale
    mask = (seg_q[:, None] == seg_k[None, :]) & valid_q[:, None] & valid_k[None, :]
    if causal:
        mask &= (pos_q + off_q)[:, None] >= pos_k[None, :]
    logits = jnp.where(mask[None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows with no visible key (padding / empty segments) -> exactly zero
    row_ok = jnp.any(mask, axis=-1)
    probs = jnp.where(row_ok[None, :, None], probs, 0.0)
    if dropout and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    out = jnp.einsum("hqk,hkd->hqd", probs.astype(vt.dtype), vt)
    return jnp.transpose(out, (1, 0, 2))


# ---------------------------------------------------------------------------
# Pallas kernels. q laid out [h, t, d]; grid (heads, blocks); cu_* in SMEM.
# The mask for a [bq, bkv] tile is rebuilt from cu prefix sums with an O(B)
# vectorized sweep (B = batch size = len(cu) - 1, a static python range).
# ---------------------------------------------------------------------------


def _tile_mask(q_pos, k_pos, cuq_ref, cuk_ref, causal, n_seq):
    segq = jnp.zeros_like(q_pos)
    segk = jnp.zeros_like(k_pos)
    startq = jnp.zeros_like(q_pos)
    startk = jnp.zeros_like(k_pos)
    off = jnp.zeros_like(q_pos)
    for b in range(n_seq):
        cuq_lo, cuq_hi = cuq_ref[b], cuq_ref[b + 1]
        cuk_lo, cuk_hi = cuk_ref[b], cuk_ref[b + 1]
        segq += (q_pos >= cuq_hi).astype(jnp.int32)
        segk += (k_pos >= cuk_hi).astype(jnp.int32)
        startq += jnp.where(q_pos >= cuq_hi, cuq_hi - cuq_lo, 0)
        startk += jnp.where(k_pos >= cuk_hi, cuk_hi - cuk_lo, 0)
        if causal:
            in_b = (q_pos >= cuq_lo) & (q_pos < cuq_hi)
            off += jnp.where(in_b, (cuk_hi - cuk_lo) - (cuq_hi - cuq_lo), 0)
    valid = (q_pos < cuq_ref[n_seq]) & (k_pos < cuk_ref[n_seq])
    mask = (segq == segk) & valid
    if causal:
        mask &= (q_pos - startq + off) >= (k_pos - startk)
    return mask


def _vfwd_kernel(cuq_ref, cuk_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                 scale, causal, block_q, block_kv, seq_k, n_seq):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
    d = q.shape[-1]
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = _tile_mask(q_pos, k_pos, cuq_ref, cuk_ref, causal, n_seq)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # multiply by the mask: a fully-masked row keeps m == -inf and would
        # otherwise see exp(s - m) == 1 for every masked entry
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, seq_k // block_kv, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def _vdq_kernel(cuq_ref, cuk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dq_ref, *, scale, causal, block_q, block_kv,
                seq_k, n_seq):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    delta = delta_ref[0]
    d = q.shape[-1]

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = _tile_mask(q_pos, k_pos, cuq_ref, cuk_ref, causal, n_seq)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse) * mask.astype(jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, seq_k // block_kv, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _vdkv_kernel(cuq_ref, cuk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                 delta_ref, dk_ref, dv_ref, *, scale, causal, block_q,
                 block_kv, seq_q, n_seq):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = _tile_mask(q_pos, k_pos, cuq_ref, cuk_ref, causal, n_seq)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse) * mask.astype(jnp.float32)
        dv_new = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    zeros = jnp.zeros((block_kv, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, seq_q // block_q, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _smem_spec(n):
    return pl.BlockSpec((n,), lambda hh, i: (0,), memory_space=pltpu.SMEM)


def _varlen_pallas_fwd(q, k, v, cu_q, cu_k, causal, scale):
    """q,k,v: [h, t, d] on a shape ``_use_pallas`` accepted.
    Returns (out, lse)."""
    h, tq, d = q.shape
    tk = k.shape[1]
    block_q, block_kv = _blocks(tq, tk)
    n_seq = cu_q.shape[0] - 1
    kernel = functools.partial(
        _vfwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, seq_k=tk, n_seq=n_seq)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid=(h, tq // block_q),
            compiler_params=_vmem_limit(tk, q.dtype.itemsize),
            in_specs=[
                _smem_spec(n_seq + 1), _smem_spec(n_seq + 1),
                pl.BlockSpec((1, block_q, d), lambda hh, i: (hh, i, 0)),
                pl.BlockSpec((1, tk, d), lambda hh, i: (hh, 0, 0)),
                pl.BlockSpec((1, tk, d), lambda hh, i: (hh, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda hh, i: (hh, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda hh, i: (hh, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((h, tq, d), q.dtype),
                jax.ShapeDtypeStruct((h, tq, 1), jnp.float32),
            ],
        )(cu_q, cu_k, q, k, v)


def _varlen_pallas_bwd(q, k, v, cu_q, cu_k, out, lse, do, causal, scale):
    h, tq, d = q.shape
    tk = k.shape[1]
    block_q, block_kv = _blocks(tq, tk)
    n_seq = cu_q.shape[0] - 1
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    full_q = pl.BlockSpec((1, tq, d), lambda hh, i: (hh, 0, 0))
    full_kv = pl.BlockSpec((1, tk, d), lambda hh, i: (hh, 0, 0))
    row_q = pl.BlockSpec((1, block_q, d), lambda hh, i: (hh, i, 0))
    row_kv = pl.BlockSpec((1, block_kv, d), lambda hh, i: (hh, i, 0))
    vec_q_block = pl.BlockSpec((1, block_q, 1), lambda hh, i: (hh, i, 0))
    vec_q_full = pl.BlockSpec((1, tq, 1), lambda hh, i: (hh, 0, 0))
    smem = _smem_spec(n_seq + 1)

    with jax.enable_x64(False):
        dq = pl.pallas_call(
            functools.partial(_vdq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_kv=block_kv, seq_k=tk,
                              n_seq=n_seq),
            grid=(h, tq // block_q),
            in_specs=[smem, smem, row_q, full_kv, full_kv, row_q,
                      vec_q_block, vec_q_block],
            out_specs=row_q,
            out_shape=jax.ShapeDtypeStruct((h, tq, d), q.dtype),
            compiler_params=_vmem_limit(tk, q.dtype.itemsize),
        )(cu_q, cu_k, q, k, v, do, lse, delta)

        dk, dv = pl.pallas_call(
            functools.partial(_vdkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_kv=block_kv, seq_q=tq,
                              n_seq=n_seq),
            grid=(h, tk // block_kv),
            in_specs=[smem, smem, full_q, row_kv, row_kv, full_q,
                      vec_q_full, vec_q_full],
            out_specs=[row_kv, row_kv],
            out_shape=[
                jax.ShapeDtypeStruct((h, tk, d), k.dtype),
                jax.ShapeDtypeStruct((h, tk, d), v.dtype),
            ],
            compiler_params=_vmem_limit(tq, q.dtype.itemsize),
        )(cu_q, cu_k, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp core + public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _varlen_core(q, k, v, cu_q, cu_k, causal, scale):
    """Pallas path, [h, t, d] layout (only called when shapes allow it)."""
    out, _ = _varlen_pallas_fwd(q, k, v, cu_q, cu_k, causal, scale)
    return out


def _varlen_fwd(q, k, v, cu_q, cu_k, causal, scale):
    out, lse = _varlen_pallas_fwd(q, k, v, cu_q, cu_k, causal, scale)
    return out, (q, k, v, cu_q, cu_k, out, lse)


def _varlen_bwd(causal, scale, res, g):
    q, k, v, cu_q, cu_k, out, lse = res
    dq, dk, dv = _varlen_pallas_bwd(q, k, v, cu_q, cu_k, out, lse, g,
                                    causal, scale)
    return dq, dk, dv, None, None


_varlen_core.defvjp(_varlen_fwd, _varlen_bwd)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Packed varlen attention (reference flash_attention.py:762).

    Args:
        query/key/value: [total_tokens, num_heads, head_dim] packed sequences.
        cu_seqlens_q/k: [batch+1] int32 prefix sums delimiting sequences.
        max_seqlen_q/k: accepted for API parity (shapes are static here).
        scale: softmax scale; default 1/sqrt(head_dim).
        causal: per-segment bottom-right-aligned causal masking.
        dropout: attention-probability dropout rate (reference
            flash_attention.py:762 semantics). A non-zero rate routes
            through the dense-mask XLA path — probability dropout defeats
            the flash recomputation trick (the bwd would need the exact
            mask), so the trade is memory for exactness, applied only when
            ``training`` and the rate is non-zero.
    Returns:
        (out, None) — softmax is never materialized on TPU
        (return_softmax=True raises, as the flash path does upstream).
    """
    if return_softmax:
        raise ValueError(
            "return_softmax=True requires materializing the [tq, tk] matrix; "
            "the flash path does not support it")
    drop = float(dropout) if training else 0.0
    dropout_key = None
    if drop:
        from ...core import random as prandom

        if fixed_seed_offset is not None:
            dropout_key = jax.random.PRNGKey(int(fixed_seed_offset))
        else:
            dropout_key = prandom.next_key()

    def f(q, k, v, cu_q, cu_k):
        s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        cu_q32 = cu_q.astype(jnp.int32)
        cu_k32 = cu_k.astype(jnp.int32)
        if not drop and _use_pallas(q.shape[0], k.shape[0], q.shape[-1]):
            qt = jnp.transpose(q, (1, 0, 2))
            kt = jnp.transpose(k, (1, 0, 2))
            vt = jnp.transpose(v, (1, 0, 2))
            out = _varlen_core(qt, kt, vt, cu_q32, cu_k32, causal, s)
            return jnp.transpose(out, (1, 0, 2))
        return _varlen_xla(q, k, v, cu_q32, cu_k32, causal, s,
                           dropout=drop, dropout_key=dropout_key)

    out = apply_op(f, query, key, value, cu_seqlens_q, cu_seqlens_k,
                   op_name="flash_attn_unpadded")
    return out, None
