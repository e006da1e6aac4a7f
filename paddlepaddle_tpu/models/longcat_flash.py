"""LongCat-Flash decoder LM: latent attention (MLA), zero-compute experts and
the shortcut-connected double layer.

Follows the public ``config.json`` of ``meituan-longcat/LongCat-Flash-Omni``
(its language model) and the family's public modelling code. One published
layer holds TWO latent-attention blocks ``A0, A1``, TWO dense SwiGLU MLPs
``D0, D1`` and ONE expert layer ``M``; with ``N`` an RMSNorm of its own weight::

    x1 = x  + A0(N(x));   h = N(x1);   s = M(h);   x2 = x1 + D0(h)
    x3 = x2 + A1(N(x2));  out = x3 + D1(N(x3)) + s

The expert layer reads the first half's normalised state and its result joins
the residual after the second half (the shortcut a deployment hides the
exchange behind). ``M`` is :class:`~paddlepaddle_tpu.parallel.moe.ExpertShareLayer`:
it is told which routed experts this chip holds, and adds their part and the
identity experts' part alone.

**The cache row.** A latent block caches, per token, ONE vector shared by all
heads: ``[c, rope(k_rope)]``, ``kv_lora_rank + qk_rope_head_dim`` values (576
as published), where ``c = N(x Wkva[:, :rank]) * sqrt(hidden / rank)``. The
model declares it (:meth:`LongcatFlashForCausalLM.cache_spec`) as two pools a
block, ``c`` in rows ``[512]`` and the rotated key in rows ``[64]``, four
pools a layer. The row is kept in two pieces because of how the TPU lays a
buffer out: a pool whose minor axis is 576 wide (no multiple of the 128
lanes) is given a transposed layout with the PAGES minor, and every program
that touches it then turns the whole pool round at its entry and its exit,
with a second copy of the pool in memory meanwhile. 512 is four lane tiles;
the 64-wide pool is a ninth of the bytes, and turning that round is cheap.
The bytes a token are the row's own: 2 x 576 x 2 a layer in bfloat16.

**Two attention forms**, the same mathematics:

* expanded (``cache=None``, and against a dense cache as the admission
  prefill uses): keys and values are expanded per head from the cached rows,
  ``[k_nope, v] = c Wkvb``, 192-wide q/k against 128-wide v. The cheaper form
  where many queries share the expansion.
* absorbed (against the serving engine's paged view, ``cache.attend_latent``):
  the up-projections move to the query and the output,
  ``q' = q_nope Wkvb_k^T``, scores ``q' . c + q_rope . k_rope``,
  ``o = (P c) Wkvb_v``: all heads attend the one 576-wide row and nothing is
  expanded. The cheaper form for a decode step.

Served, one chip's share of an expert-parallel deployment; not trained (the
expert share has no backward-tuned path and no exchange across chips).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.common import Embedding
from ..nn.container import LayerList
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..nn.norm import RMSNorm
from ..parallel.moe import ExpertShareLayer


@dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    # the chip's share: routed experts experts_first .. + n_routed_experts_held
    # live here (None: all of them)
    experts_first: int = 0
    n_routed_experts_held: Optional[int] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def num_hidden_layers(self) -> int:
        return self.num_layers

    @property
    def latent_row(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def held(self) -> tuple:
        count = (self.n_routed_experts if self.n_routed_experts_held is None
                 else self.n_routed_experts_held)
        return self.experts_first, count

    @staticmethod
    def tiny(vocab_size=128, hidden_size=32, layers=2, heads=4, routed=8,
             zero=4, topk=3, held=None, max_len=128,
             dtype="float32") -> "LongcatFlashConfig":
        first, count = (0, routed) if held is None else held
        return LongcatFlashConfig(
            vocab_size=vocab_size, hidden_size=hidden_size,
            ffn_hidden_size=hidden_size * 2, expert_ffn_hidden_size=hidden_size,
            num_layers=layers, num_attention_heads=heads, kv_lora_rank=16,
            q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
            v_head_dim=8, n_routed_experts=routed, zero_expert_num=zero,
            moe_topk=topk, experts_first=first, n_routed_experts_held=count,
            max_position_embeddings=max_len, dtype=dtype)


class _Dense(Layer):
    """``y = x W``, ``W [in, out]`` made in the model's own dtype (a float32
    copy of these widths would not fit beside the weights)."""

    def __init__(self, n_in, n_out, dtype, std):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter([n_in, n_out],
                                            default_initializer=Normal(0.0, std))

    def forward(self, x):
        return F.linear(x, self.weight)


def _norm(width, config):
    n = RMSNorm(width, epsilon=config.rms_norm_eps)
    if config.dtype != "float32":
        n.to(dtype=config.dtype)
    return n


def rope_tables(dim: int, max_len: int, theta: float):
    """float32 cos/sin ``[max_len, dim / 2]`` of the interleaved-pair RoPE."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(max_len, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


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


# scores of one head group stay under this many float32 values (256 MiB): a
# 1,792-token prefill at 64 heads is 822 MB of scores in one piece
_SCORE_VALUES = 1 << 26


def _expanded_attention(q_nope, q_rope, c, k_rope, wkv_b, q_pos, nope, scale):
    """Causal attention of queries at ``q_pos [b or 1, s]`` over the latent
    rows ``c [b, L, rank]`` and their rotated keys ``k_rope [b, L, rope]``
    (row ``l`` sits at position ``l``), keys and values expanded per head, a
    group of heads at a time."""
    b, s, H, _ = q_nope.shape
    L, rank = c.shape[1], c.shape[2]
    G = H
    while G > 1 and G * s * L > _SCORE_VALUES and G % 2 == 0:
        G //= 2
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


class LongcatFlashMLA(Layer):
    """One latent-attention block (module docstring: the row, the two forms)."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__(dtype=config.dtype)
        c, dt, std = config, config.dtype, config.initializer_range
        self.config = config
        H = c.num_attention_heads
        self.q_a_proj = _Dense(c.hidden_size, c.q_lora_rank, dt, std)
        self.q_a_layernorm = _norm(c.q_lora_rank, c)
        self.q_b_proj = _Dense(c.q_lora_rank,
                               H * (c.qk_nope_head_dim + c.qk_rope_head_dim), dt, std)
        self.kv_a_proj_with_mqa = _Dense(c.hidden_size, c.latent_row, dt, std)
        self.kv_a_layernorm = _norm(c.kv_lora_rank, c)
        self.kv_b_proj = _Dense(c.kv_lora_rank,
                                H * (c.qk_nope_head_dim + c.v_head_dim), dt, std)
        self.o_proj = _Dense(H * c.v_head_dim, c.hidden_size, dt, std)
        self.q_scale = (math.sqrt(c.hidden_size / c.q_lora_rank)
                        if c.mla_scale_q_lora else 1.0)
        self.kv_scale = (math.sqrt(c.hidden_size / c.kv_lora_rank)
                         if c.mla_scale_kv_lora else 1.0)

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
        scale = 1.0 / math.sqrt(nope + rope)
        cq = self.q_a_layernorm(self.q_a_proj(x)) * self.q_scale
        q = self.q_b_proj(cq).reshape([b, s, H, nope + rope])
        ckv = self.kv_a_proj_with_mqa(x)
        lat = self.kv_a_layernorm(ckv[..., :rank]) * self.kv_scale
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


class LongcatFlashMLP(Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__(dtype=config.dtype)
        h, i = config.hidden_size, config.ffn_hidden_size
        dt, std = config.dtype, config.initializer_range
        self.gate_proj = _Dense(h, i, dt, std)
        self.up_proj = _Dense(h, i, dt, std)
        self.down_proj = _Dense(i, h, dt, std)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LongcatFlashDecoderLayer(Layer):
    """The shortcut-connected double layer (module docstring)."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.input_layernorm = LayerList([_norm(c.hidden_size, c) for _ in range(2)])
        self.post_attention_layernorm = LayerList(
            [_norm(c.hidden_size, c) for _ in range(2)])
        self.self_attn = LayerList([LongcatFlashMLA(c) for _ in range(2)])
        self.mlps = LayerList([LongcatFlashMLP(c) for _ in range(2)])
        self.mlp = ExpertShareLayer(
            c.hidden_size, c.expert_ffn_hidden_size, c.n_routed_experts,
            c.zero_expert_num, c.moe_topk, held=c.held,
            scaling=c.routed_scaling_factor, dtype=c.dtype,
            init_std=c.initializer_range)

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None):
        """``cache``: None, four dense caches (``c`` and rotated key of each
        attention block, in the order of the cache spec), or ONE paged store
        holding the four pools; with a cache the second value returned is
        the four updated caches, or the four new rows for the store's
        owner."""
        if attn_mask is not None:
            raise NotImplementedError("latent attention is causal only")
        paged = hasattr(cache, "attend_latent")
        pick = lambda j: (cache if paged or cache is None
                          else cache[2 * j: 2 * j + 2])
        a0, new0 = self.self_attn[0](self.input_layernorm[0](x), cos, sin,
                                     cache=pick(0), pos=pos, block=0)
        x = x + a0
        h = self.post_attention_layernorm[0](x)
        shortcut, _ = self.mlp(h)
        x = x + self.mlps[0](h)
        a1, new1 = self.self_attn[1](self.input_layernorm[1](x), cos, sin,
                                     cache=pick(1), pos=pos, block=1)
        x = x + a1
        x = x + self.mlps[1](self.post_attention_layernorm[1](x)) + shortcut
        return x if cache is None else (x, (*new0, *new1))


class LongcatFlashModel(Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        if config.dtype != "float32":
            self.embed_tokens.to(dtype=config.dtype)
        self.layers = LayerList([LongcatFlashDecoderLayer(config)
                                 for _ in range(config.num_layers)])
        self.norm = _norm(config.hidden_size, config)
        cos, sin = rope_tables(config.qk_rope_head_dim,
                               config.max_position_embeddings, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None):
        x = self.embed_tokens(input_ids)
        cos, sin = self.rope_cos, self.rope_sin
        if caches is None:
            for layer in self.layers:
                x = layer(x, cos, sin, attn_mask)
            return self.norm(x)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, nc = layer(x, cos, sin, attn_mask, cache=cache, pos=pos)
            new_caches.append(nc)
        return self.norm(x), new_caches


class LongcatFlashForCausalLM(Layer):
    """The surface the serving engine takes from a decoder LM: ``.model(ids,
    caches=, pos=)``, ``.config``, ``.lm_head``, ``functional_state()`` and
    the declared cache rows (:meth:`cache_spec`)."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = LongcatFlashModel(config)
        self.lm_head = _Dense(config.hidden_size, config.vocab_size,
                              config.dtype, config.initializer_range)

    def cache_spec(self):
        """Per layer, the pools a cached token has a row in: for each of the
        two attention blocks the latent ``[kv_lora_rank]`` and the rotated
        key ``[qk_rope_head_dim]``, both shared by all heads (module
        docstring: why the row is kept in two pieces)."""
        from ..inference.kv_pool import PoolSpec

        block = (PoolSpec("latent", (self.config.kv_lora_rank,)),
                 PoolSpec("latent", (self.config.qk_rope_head_dim,)))
        return [block + block for _ in range(self.config.num_layers)]

    def forward(self, input_ids, labels=None, attn_mask=None):
        if labels is not None:
            raise NotImplementedError(
                "LongcatFlashForCausalLM is served, not trained: the expert "
                "share has no exchange across chips and no loss")
        return self.lm_head(self.model(input_ids, attn_mask))
