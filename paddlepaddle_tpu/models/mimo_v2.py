"""MiMo-V2-Flash decoder LM: window and full attention layers mixed, a learned
sink logit a head in the window layers, keys of 192 and values of 128, a dense
leading layer, then sigmoid-routed experts with no shared expert.

Follows the public ``config.json`` of ``XiaomiMiMo/MiMo-V2-Flash``. With
``N1``, ``N2`` RMSNorms of their own weight (epsilon ``layernorm_epsilon``),
one layer is::

    h = x + A(N1(x));   y = h + F(N2(h))

``A`` in layer ``i`` is a full layer where ``hybrid_layer_pattern[i]`` is 0
and a window layer where it is 1:

* ``q = x Wq`` (heads x ``head_dim``), ``k = x Wk`` (kv heads x
  ``head_dim``), ``v = x Wv`` (kv heads x ``v_head_dim``); a full layer has
  ``num_key_value_heads`` kv heads, a window layer ``swa_num_key_value_heads``
  (and the ``swa_`` widths, published equal to the full layer's);
* RoPE, half-split pairs, on the first ``int(head_dim x
  partial_rotary_factor)`` dims of every query and key head, ``rope_theta`` on
  full layers and ``swa_rope_theta`` on window layers;
* scores ``q_i . k_j / sqrt(head_dim)``, a query at ``i`` seeing ``j <= i``,
  and in a window layer only ``i - sliding_window < j <= i``;
* ``p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(b_h))`` where the layer has a
  sink (``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias``):
  ``b_h`` is head ``h``'s learned logit, one more term in the denominator with
  no value; without one the plain softmax;
* ``o_i = attention_value_scale x sum_j p_ij v_j``, then ``Wo``.

``F`` is a dense SwiGLU of ``intermediate_size`` where ``moe_layer_freq[i]``
is 0 and otherwise :class:`~paddlepaddle_tpu.parallel.moe.ExpertShareLayer`:
``routing="sigmoid"`` over all ``n_routed_experts`` (the
``num_experts_per_tok`` largest of ``sigmoid + e_score_correction_bias``, a
pick weighing its renormalised score times ``routed_scaling_factor``, 1 where
the config states none), SwiGLU experts of ``moe_intermediate_size``, no
shared expert. The layer is told which routed experts this chip holds and adds
their part alone.

:meth:`MimoV2ForCausalLM.cache_spec` declares two classes of pages: the full
layers' K and V rows, and the window layers', marked with the window. A row is
one a token, every kv head's side by side (``[kv heads x 192]``, ``[kv heads x
128]``): a 192-wide head is no whole number of lane tiles, and the page walk
reads such rows in place (ops/kernels/paged_gqa_attention.py).

Served, one chip's share of an expert-parallel deployment; not trained. The
multi-token-prediction modules the release describes are not built.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..nn.common import Embedding
from ..nn.container import LayerList
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..parallel.moe import ExpertShareLayer
from .latent_attention import Dense, SwiGLU, _positions, _write_rows, rms_norm, rope_tables


def _published_pattern() -> List[int]:
    return [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]


def _published_moe_freq() -> List[int]:
    return [0] + [1] * 47


@dataclass
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    hybrid_layer_pattern: List[int] = field(default_factory=_published_pattern)
    moe_layer_freq: List[int] = field(default_factory=_published_moe_freq)
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: Optional[float] = None
    # the chip's share: routed experts experts_first .. + n_routed_experts_held
    # live here (None: all of them)
    experts_first: int = 0
    n_routed_experts_held: Optional[int] = None
    max_position_embeddings: int = 262144
    layernorm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # a sink logit is drawn Normal(mean, std): with every other weight at
    # initializer_range its share of a row's mass stands where a comparison
    # sees it (benchmark/configs: ``assumed``)
    sink_init: Tuple[float, float] = (5.5, 1.0)
    dtype: str = "float32"

    @property
    def rms_norm_eps(self) -> float:
        return self.layernorm_epsilon

    @property
    def held(self) -> tuple:
        count = (self.n_routed_experts if self.n_routed_experts_held is None
                 else self.n_routed_experts_held)
        return self.experts_first, count

    def windowed(self, i: int) -> bool:
        return bool(self.hybrid_layer_pattern[i])

    def attention_shape(self, i: int) -> tuple:
        """(heads, kv heads, qk width, v width) of layer ``i``'s attention."""
        if self.windowed(i):
            return (self.swa_num_attention_heads, self.swa_num_key_value_heads,
                    self.swa_head_dim, self.swa_v_head_dim)
        return (self.num_attention_heads, self.num_key_value_heads,
                self.head_dim, self.v_head_dim)

    def rotary_dim(self, qk_width: int) -> int:
        return int(qk_width * self.partial_rotary_factor)

    @staticmethod
    def tiny(vocab_size=128, hidden_size=64, layers=5, routed=8, topk=2,
             held=None, window=8, max_len=128, dtype="float32",
             **kw) -> "MimoV2Config":
        """Five layers (dense full, window, window, full, window) at toy
        widths that keep what the published ones make the program do: keys
        wider than values (64 and 32), four kv heads on full layers and eight
        on window layers, a quarter of each key rotated, rows of one a token
        in whole lane tiles (256, 128, 512, 256 wide)."""
        first, count = (0, routed) if held is None else held
        cfg = dict(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=hidden_size * 2, moe_intermediate_size=hidden_size // 2,
            num_hidden_layers=layers, num_attention_heads=16, num_key_value_heads=4,
            head_dim=64, v_head_dim=32, swa_num_attention_heads=16,
            swa_num_key_value_heads=8, swa_head_dim=64, swa_v_head_dim=32,
            sliding_window=window, rope_theta=50000.0, swa_rope_theta=100.0,
            partial_rotary_factor=0.25,
            hybrid_layer_pattern=([0, 1, 1, 0, 1] * layers)[:layers],
            moe_layer_freq=[0] + [1] * (layers - 1), n_routed_experts=routed,
            num_experts_per_tok=topk, experts_first=first,
            n_routed_experts_held=count, max_position_embeddings=max_len,
            initializer_range=0.1, sink_init=(1.5, 1.0), dtype=dtype)
        cfg.update(kw)
        return MimoV2Config(**cfg)


def _rope_half_split(x, cos, sin, positions, rot: int, at: int):
    """Rotate the first ``rot`` dims of ``x [b, s, heads, d]`` in half-split
    pairs ``(x[i], x[i + rot/2])`` by the angle of ``positions [b or 1, s]``;
    ``cos``/``sin`` ``[max_len, n]`` hold the angles in columns ``at .. at +
    rot/2``. The rows of the positions are gathered first and the columns
    taken from them: a slice of the whole table would be a copy of it."""
    cols = slice(at, at + rot // 2)
    c, s = cos[positions][..., cols][:, :, None], sin[positions][..., cols][:, :, None]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :rot // 2], xf[..., rot // 2:rot]
    out = jnp.concatenate([a * c - b * s, b * c + a * s, xf[..., rot:]], -1)
    return out.astype(x.dtype)


def window_attention(q, k, v, q_pos, window: int, sink, scale):
    """``[b, s, H, vd]`` in ``q``'s dtype: queries ``q [b, s, H, hd]`` at
    positions ``q_pos [b or 1, s]`` over keys ``k [b, L, kvh, hd]`` and values
    ``v [b, L, kvh, vd]`` at positions 0..L-1; query head ``h`` reads kv head
    ``h // (H / kvh)``, sees the keys at or before its own position (and past
    ``q_pos - window`` where ``window`` > 0), and with a ``sink [H]`` (float32
    logits) one more term in its denominator. Scores, softmax and sums in
    float32."""
    b, s, H, hd = q.shape
    L, kvh = k.shape[1], k.shape[2]
    rep = H // kvh
    qg = q.reshape(b, s, kvh, rep, hd)
    logits = jnp.einsum("bskrd,blkd->bkrsl", qg, k,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(L, dtype=jnp.int32)[None, None, :]
    qp = q_pos[:, :, None]
    valid = k_pos <= qp
    if window:
        valid = valid & (k_pos > qp - window)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    if sink is not None:
        b_h = sink.astype(jnp.float32).reshape(1, kvh, rep, 1, 1)
        m = jnp.maximum(m, b_h)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(b_h - m)
    out = jnp.einsum("bkrsl,blkd->bskrd", (p / denom).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, H, v.shape[-1]).astype(q.dtype)


class MimoV2Attention(Layer):
    """One layer's attention ``A`` (module docstring), full or window."""

    def __init__(self, config: MimoV2Config, index: int):
        super().__init__(dtype=config.dtype)
        c, dt, std = config, config.dtype, config.initializer_range
        self.window = c.sliding_window if c.windowed(index) else 0
        # where the layer's angles start in the model's rope tables
        self.rope_at = c.rotary_dim(c.head_dim) // 2 if self.window else 0
        H, kvh, hd, vd = c.attention_shape(index)
        self.heads, self.kv_heads, self.qk_width, self.v_width = H, kvh, hd, vd
        self.rot = c.rotary_dim(hd)
        self.scale = 1.0 / math.sqrt(hd)
        self.value_scale = float(c.attention_value_scale)
        self.q_proj = Dense(c.hidden_size, H * hd, dt, std)
        self.k_proj = Dense(c.hidden_size, kvh * hd, dt, std)
        self.v_proj = Dense(c.hidden_size, kvh * vd, dt, std)
        self.o_proj = Dense(H * vd, c.hidden_size, dt, std)
        sink = (c.add_swa_attention_sink_bias if self.window
                else c.add_full_attention_sink_bias)
        self.attention_sink_bias = None
        if sink:
            mean, sd = c.sink_init
            self.attention_sink_bias = self.create_parameter(
                [H], dtype="float32", default_initializer=Normal(mean, sd))

    def forward(self, x, cos, sin, cache=None, pos=None):
        """``cache``: None (whole forward), the dense pair ``(k [b, L, kvh x
        hd], v [b, L, kvh x vd])`` (rows written at ``pos``; the updated pair
        comes back), or a paged store with ``attend`` (its ``window`` is this
        layer's; the new rows come back for its owner to store)."""
        b, s = x.shape[0], x.shape[1]
        H, kvh, hd, vd = self.heads, self.kv_heads, self.qk_width, self.v_width
        paged = hasattr(cache, "attend")
        sink = () if self.attention_sink_bias is None else (
            self.attention_sink_bias,)

        def attend(q, k, v, cos, sin, *rest):
            sink_b = rest[0] if sink else None
            dense = rest[len(sink):]
            positions = _positions(pos, b, s)
            q = _rope_half_split(q.reshape(b, s, H, hd), cos, sin, positions,
                                 self.rot, self.rope_at)
            k = _rope_half_split(k.reshape(b, s, kvh, hd), cos, sin,
                                 positions, self.rot, self.rope_at)
            v = v.reshape(b, s, kvh, vd)
            if paged:
                out, new_k, new_v = cache.attend(q, k, v, pos, H // kvh,
                                                 self.scale, sink=sink_b)
            else:
                new_k, new_v = k.reshape(b, s, -1), v.reshape(b, s, -1)
                if dense:
                    new_k = _write_rows(dense[0], new_k, pos)
                    new_v = _write_rows(dense[1], new_v, pos)
                L = new_k.shape[1]
                out = window_attention(
                    q, new_k.reshape(b, L, kvh, hd).astype(q.dtype),
                    new_v.reshape(b, L, kvh, vd).astype(q.dtype), positions,
                    self.window, sink_b, self.scale)
            out = (out.astype(jnp.float32) * self.value_scale).astype(q.dtype)
            return out.reshape(b, s, H * vd), new_k, new_v

        extra = () if cache is None or paged else tuple(cache)
        out, new_k, new_v = apply_op(
            attend, self.q_proj(x), self.k_proj(x), self.v_proj(x), cos, sin,
            *sink, *extra, op_name="mimo_attention")
        return self.o_proj(out), (new_k, new_v)


class MimoV2DecoderLayer(Layer):
    """``h = x + A(N1(x))``, then ``h + F(N2(h))`` (module docstring)."""

    def __init__(self, config: MimoV2Config, index: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.input_layernorm = rms_norm(c.hidden_size, c)
        self.post_attention_layernorm = rms_norm(c.hidden_size, c)
        self.self_attn = MimoV2Attention(c, index)
        self.sparse = bool(c.moe_layer_freq[index])
        if self.sparse:
            self.mlp = ExpertShareLayer(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts, 0,
                c.num_experts_per_tok, held=c.held,
                scaling=(1.0 if c.routed_scaling_factor is None
                         else c.routed_scaling_factor),
                dtype=c.dtype, init_std=c.initializer_range,
                routing="sigmoid")
        else:
            self.mlp = SwiGLU(c.hidden_size, c.intermediate_size, c.dtype,
                              c.initializer_range)

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None):
        if attn_mask is not None:
            raise NotImplementedError("MiMo-V2 attention is causal only")
        a, new = self.self_attn(self.input_layernorm(x), cos, sin, cache=cache,
                                pos=pos)
        x = x + a
        h = self.post_attention_layernorm(x)
        x = x + (self.mlp(h)[0] if self.sparse else self.mlp(h))
        return x if cache is None else (x, tuple(new))


class MimoV2Model(Layer):
    def __init__(self, config: MimoV2Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        if config.dtype != "float32":
            self.embed_tokens.to(dtype=config.dtype)
        self.layers = LayerList([MimoV2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = rms_norm(config.hidden_size, config)
        # the two layer kinds' angles side by side, [max_len, full rotary
        # dims / 2 + window rotary dims / 2]: a step gathers its positions'
        # rows once for both
        tables = [rope_tables(config.rotary_dim(w), config.max_position_embeddings,
                              theta)
                  for w, theta in ((config.head_dim, config.rope_theta),
                                   (config.swa_head_dim, config.swa_rope_theta))]
        self.register_buffer("rope_cos", Tensor(jnp.concatenate([c for c, _ in tables], -1)),
                             persistable=False)
        self.register_buffer("rope_sin", Tensor(jnp.concatenate([s for _, s in tables], -1)),
                             persistable=False)

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


class MimoV2ForCausalLM(Layer):
    """The surface the serving engine takes from a decoder LM: ``.model(ids,
    caches=, pos=)``, ``.config``, ``.lm_head``, ``functional_state()`` and
    the declared cache (:meth:`cache_spec`)."""

    def __init__(self, config: MimoV2Config):
        super().__init__(dtype=config.dtype)
        if config.rotary_dim(config.head_dim) % 2 or config.rotary_dim(config.swa_head_dim) % 2:
            raise ValueError("the rotated dims must pair up")
        self.config = config
        self.model = MimoV2Model(config)
        self.lm_head = Dense(config.hidden_size, config.vocab_size,
                             config.dtype, config.initializer_range)

    def cache_spec(self):
        """Per layer, its K and V pools, one row a token of every kv head:
        ``[kv heads x head_dim]`` and ``[kv heads x v_head_dim]``; a window
        layer's marked with ``sliding_window``, a class of pages of its own
        (``inference.kv_pool.page_classes``)."""
        from ..inference.kv_pool import PoolSpec

        c, out = self.config, []
        for i in range(c.num_hidden_layers):
            _, kvh, hd, vd = c.attention_shape(i)
            w = c.sliding_window if c.windowed(i) else 0
            out.append((PoolSpec("k", (kvh * hd,), w),
                        PoolSpec("v", (kvh * vd,), w)))
        return out

    def forward(self, input_ids, labels=None, attn_mask=None):
        if labels is not None:
            raise NotImplementedError(
                "MimoV2ForCausalLM is served, not trained: the expert share "
                "has no exchange across chips and no loss")
        return self.lm_head(self.model(input_ids, attn_mask))
