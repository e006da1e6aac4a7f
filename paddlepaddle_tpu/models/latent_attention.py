"""The latent-attention (MLA) block, its rope helpers and its two prefill forms:
ONE module for every decoder of this package that caches a latent row
(``models/longcat_flash.py``, ``models/kimi_k2.py``).

**The cache row.** A latent block caches, per token, ONE vector shared by all
heads: ``[c, rope(k_rope)]``, ``kv_lora_rank + qk_rope_head_dim`` values (576
as published by both families), where ``c = N(x Wkva[:, :rank])`` (times
``sqrt(hidden / rank)`` where the model's ``mla_scale_kv_lora`` says so). A
model declares it (``cache_spec``) as two pools a block, ``c`` in rows
``[512]`` and the rotated key in rows ``[64]``. The row is kept in two pieces
because of how the TPU lays a buffer out: a pool whose minor axis is 576 wide
(no multiple of the 128 lanes) is given a transposed layout with the PAGES
minor, and every program that touches it then turns the whole pool round at
its entry and its exit, with a second copy of the pool in memory meanwhile.
512 is four lane tiles; the 64-wide pool is a ninth of the bytes, and turning
that round is cheap.

**Two attention forms**, the same mathematics:

* expanded (``cache=None``, and against a dense cache as the admission
  prefill uses): keys and values are expanded per head from the cached rows,
  ``[k_nope, v] = c Wkvb``, 192-wide q/k against 128-wide v. The cheaper form
  where many queries share the expansion. Its scores never pass
  ``_SCORE_VALUES`` float32 values at once, whatever the prompt's length: a
  short prompt runs a group of heads at a time over all its keys
  (:func:`_expanded_attention`), a long one runs BLOCKS of queries against
  blocks of keys with an online softmax in a Pallas kernel, and skips the
  blocks that lie wholly above the diagonal (:func:`_long_attention`,
  ops/kernels/latent_prefill_attention.py).
* absorbed (against the serving engine's paged view, ``cache.attend_latent``):
  the up-projections move to the query and the output,
  ``q' = q_nope Wkvb_k^T``, scores ``q' . c + q_rope . k_rope``,
  ``o = (P c) Wkvb_v``: all heads attend the one 576-wide row and nothing is
  expanded. The cheaper form for a decode step.

**Positions.** Interleaved-pair RoPE on the rope dimensions of every query
head and of the one key shared by all heads; with a ``rope_scaling`` block of
type ``yarn`` the inverse frequencies are blended as published
(:func:`yarn_inv_freq`) and the softmax scale grows by
``yarn_mscale(factor, mscale_all_dim) ** 2`` (:func:`softmax_scale`).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..nn.norm import RMSNorm
from ..ops.kernels.latent_prefill_attention import latent_prefill_attention


class Dense(Layer):
    """``y = x W``, ``W [in, out]`` made in the model's own dtype (a float32
    copy of these widths would not fit beside the weights)."""

    def __init__(self, n_in, n_out, dtype, std):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter([n_in, n_out],
                                            default_initializer=Normal(0.0, std))

    def forward(self, x):
        return F.linear(x, self.weight)


class SwiGLU(Layer):
    """``down(silu(gate(x)) * up(x))``, three :class:`Dense`."""

    def __init__(self, n_in, n_hidden, dtype, std):
        super().__init__(dtype=dtype)
        self.gate_proj = Dense(n_in, n_hidden, dtype, std)
        self.up_proj = Dense(n_in, n_hidden, dtype, std)
        self.down_proj = Dense(n_hidden, n_in, dtype, std)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


def rms_norm(width, config):
    n = RMSNorm(width, epsilon=config.rms_norm_eps)
    if config.dtype != "float32":
        n.to(dtype=config.dtype)
    return n


# -- positions ------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """float32 ``[dim / 2]`` inverse frequencies of YaRN as the family's public
    code computes them: ``theta ** (-2i / dim)`` for the pairs that turn often
    (extrapolated), the same over ``factor`` for those that turn seldom
    (interpolated), blended by a linear ramp over the pairs between the
    correction dimensions of ``beta_fast`` and ``beta_slow`` rotations within
    ``original_max_position_embeddings`` (floor of the one, ceil of the other,
    both clamped to ``[0, dim - 1]``; equal ends are parted by 0.001)."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))), dim - 1)
    if low == high:
        high = high + 0.001
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = extra / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_tables(dim: int, max_len: int, theta: float, scaling: Optional[dict] = None):
    """float32 cos/sin ``[max_len, dim / 2]`` of the interleaved-pair RoPE;
    ``scaling`` is a published ``rope_scaling`` block of type ``yarn`` or None."""
    if scaling is None:
        inv, mscale = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)), 1.0
    else:
        if scaling.get("type", scaling.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling {scaling!r}: only type 'yarn' is computed")
        inv = yarn_inv_freq(dim, theta, scaling)
        mscale = (yarn_mscale(scaling["factor"], scaling.get("mscale", 1.0))
                  / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0.0)))
    ang = jnp.arange(max_len, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * mscale, jnp.sin(ang) * mscale


def softmax_scale(qk_head_dim: int, scaling: Optional[dict] = None) -> float:
    """``qk_head_dim ** -0.5``, times ``yarn_mscale(factor, mscale_all_dim) ** 2``
    under a ``yarn`` block that states ``mscale_all_dim``."""
    scale = 1.0 / math.sqrt(qk_head_dim)
    if scaling is not None and scaling.get("mscale_all_dim"):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def _rope_interleaved(x, cos, sin, positions):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by the angle of
    ``positions``; ``x [b, s, ..., dim]``, ``positions [b or 1, s]``."""
    c, s = cos[positions], sin[positions]                    # [b, s, dim/2]
    while c.ndim < x.ndim:
        c, s = c[:, :, None], s[:, :, None]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * c - b * s, a * s + b * c], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _positions(pos, b, s):
    """``[b or 1, s]`` int32 positions from a scalar or per-row start."""
    pos = jnp.asarray(0 if pos is None else pos, jnp.int32)
    steps = jnp.arange(s, dtype=jnp.int32)[None, :]
    return (pos[:, None] if pos.ndim else pos[None, None]) + steps


# -- the expanded form ------------------------------------------------------------

# The scores that exist at once stay under this many float32 values (256 MiB),
# whatever the prompt: a 1,792-token prefill at 64 heads is 822 MB of scores in
# one piece, a 16,640-token one 71 GB.
_SCORE_VALUES = 1 << 26
# Heads a group at least: below it the one-piece form would loop over single
# heads whose masked half is computed all the same, and the blocked kernel takes
# over (at 64 heads: beyond 4,096 queries x 4,096 keys).
_MIN_GROUP = 4


def _expanded_attention(q_nope, q_rope, c, k_rope, wkv_b, q_pos, nope, scale):
    """Causal attention of queries at ``q_pos [b or 1, s]`` over the latent
    rows ``c [b, L, rank]`` and their rotated keys ``k_rope [b, L, rope]``
    (row ``l`` sits at position ``l``), keys and values expanded per head, a
    group of heads at a time; where a group of ``_MIN_GROUP`` heads' scores
    would pass the bound, over blocks of keys (:func:`_long_attention`)."""
    b, s, H, _ = q_nope.shape
    L, rank = c.shape[1], c.shape[2]
    G = H
    while G > _MIN_GROUP and G * s * L > _SCORE_VALUES and G % 2 == 0:
        G //= 2
    if G * s * L > _SCORE_VALUES:
        return _long_attention(q_nope, q_rope, c, k_rope, wkv_b, q_pos, nope, scale)
    w = wkv_b.reshape(rank, H // G, G, -1).transpose(1, 0, 2, 3)     # [groups, rank, G, nope+v]
    qn = q_nope.reshape(b, s, H // G, G, -1).transpose(2, 0, 1, 3, 4)
    qr = q_rope.reshape(b, s, H // G, G, -1).transpose(2, 0, 1, 3, 4)
    valid = jnp.arange(L, dtype=jnp.int32)[None, None, :] <= q_pos[:, :, None]

    def group(args):
        wg, qng, qrg = args
        kv = jnp.einsum("blr,rgd->blgd", c, wg)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        att = (jnp.einsum("bsgd,blgd->bgsl", qng, k_nope,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bsgd,bld->bgsl", qrg, k_rope,
                            preferred_element_type=jnp.float32)) * scale
        p = jax.nn.softmax(jnp.where(valid[:, None], att, -1e30), axis=-1)
        return jnp.einsum("bgsl,blgd->bsgd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(qng.dtype)

    out = jax.lax.map(group, (w, qn, qr))                   # [groups, b, s, G, v]
    return out.transpose(1, 2, 0, 3, 4).reshape(b, s, -1)


def _long_attention(q_nope, q_rope, c, k_rope, wkv_b, q_pos, nope, scale):
    """:func:`_expanded_attention` for a prompt of any length: keys and values
    are expanded once for the whole prompt (``L x heads x (nope + v)`` in the
    model's dtype: 545 MB at 16,640 rows as published), and the Pallas kernel
    of ops/kernels/latent_prefill_attention.py runs each block of queries
    against the blocks of keys up to its own last position, the softmax carried
    online: the scores alive are one ``block x block`` tile, and what lies
    wholly above the diagonal is never computed. ``q_pos`` is a start a row and
    consecutive positions after it, as every caller makes it."""
    b, s, H, _ = q_nope.shape
    rank = c.shape[2]
    with jax.named_scope("latent_prefill_attention"):
        kv = jnp.einsum("blr,rhd->blhd", c, wkv_b.reshape(rank, H, -1))
        out = latent_prefill_attention(
            q_nope, q_rope, kv[..., :nope], k_rope, kv[..., nope:],
            jnp.broadcast_to(q_pos[:, 0], (b,)), scale=scale)
    return out.reshape(b, s, -1)


def _write_rows(cache, rows, pos):
    """The dense cache ``[b, L, row]`` with ``rows [b, s, row]`` written at
    ``pos`` (a scalar, or one start per row)."""
    new = rows.astype(cache.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        zero = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(cache, new, (zero, pos, zero))
    b, s = rows.shape[:2]
    cols = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    return cache.at[jnp.arange(b, dtype=jnp.int32)[:, None], cols].set(new)


class LatentAttention(Layer):
    """One latent-attention block (module docstring: the row, the two forms).
    ``config`` gives the widths (``hidden_size``, ``num_attention_heads``, the
    two ranks, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``),
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` and, where the model scales
    its positions, ``rope_scaling``."""

    def __init__(self, config):
        super().__init__(dtype=config.dtype)
        c, dt, std = config, config.dtype, config.initializer_range
        self.config = config
        H = c.num_attention_heads
        self.q_a_proj = Dense(c.hidden_size, c.q_lora_rank, dt, std)
        self.q_a_layernorm = rms_norm(c.q_lora_rank, c)
        self.q_b_proj = Dense(c.q_lora_rank,
                               H * (c.qk_nope_head_dim + c.qk_rope_head_dim), dt, std)
        self.kv_a_proj_with_mqa = Dense(c.hidden_size, c.latent_row, dt, std)
        self.kv_a_layernorm = rms_norm(c.kv_lora_rank, c)
        self.kv_b_proj = Dense(c.kv_lora_rank,
                                H * (c.qk_nope_head_dim + c.v_head_dim), dt, std)
        self.o_proj = Dense(H * c.v_head_dim, c.hidden_size, dt, std)
        self.q_scale = (math.sqrt(c.hidden_size / c.q_lora_rank)
                        if c.mla_scale_q_lora else None)
        self.kv_scale = (math.sqrt(c.hidden_size / c.kv_lora_rank)
                         if c.mla_scale_kv_lora else None)
        self.scale = softmax_scale(c.qk_nope_head_dim + c.qk_rope_head_dim,
                                   getattr(c, "rope_scaling", None))

    def forward(self, x, cos, sin, cache=None, pos=None, block=0):
        """``cache``: None (whole causal forward), this block's dense cache,
        the pair ``(c [b, L, rank], k_rope [b, L, rope])`` (rows written at
        ``pos``, expanded form; the updated pair comes back), or a paged
        store with ``attend_latent`` (absorbed form; the pair of new rows
        comes back for its owner to store)."""
        c = self.config
        b, s = x.shape[0], x.shape[1]
        H, rank = c.num_attention_heads, c.kv_lora_rank
        nope, rope, vd = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        scale = self.scale
        cq = self.q_a_layernorm(self.q_a_proj(x))
        if self.q_scale is not None:
            cq = cq * self.q_scale
        q = self.q_b_proj(cq).reshape([b, s, H, nope + rope])
        ckv = self.kv_a_proj_with_mqa(x)
        lat = self.kv_a_layernorm(ckv[..., :rank])
        if self.kv_scale is not None:
            lat = lat * self.kv_scale
        paged = hasattr(cache, "attend_latent")

        def attend(q, lat, k_rope, wkv_b, cos, sin, *dense):
            positions = _positions(pos, b, s)
            q_nope, q_rope = q[..., :nope], q[..., nope:]
            q_rope = _rope_interleaved(q_rope, cos, sin, positions)
            k_rope = _rope_interleaved(k_rope, cos, sin, positions)
            if paged:
                w = wkv_b.reshape(rank, H, nope + vd)
                q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, w[..., :nope])
                o_lat, new_c, new_r = cache.attend_latent(
                    block, q_abs, q_rope, lat, k_rope, pos, scale)
                out = jnp.einsum("bshr,rhd->bshd", o_lat, w[..., nope:])
                return (out.reshape(b, s, H * vd).astype(q.dtype), new_c,
                        new_r)
            if dense:
                lat = _write_rows(dense[0], lat, pos)
                k_rope = _write_rows(dense[1], k_rope, pos)
            out = _expanded_attention(q_nope, q_rope, lat, k_rope, wkv_b,
                                      positions, nope, scale)
            return out, lat, k_rope

        extra = () if cache is None or paged else tuple(cache)
        out, new_c, new_r = apply_op(attend, q, lat, ckv[..., rank:],
                                     self.kv_b_proj.weight, cos, sin, *extra,
                                     op_name="latent_attention")
        return self.o_proj(out), (new_c, new_r)
