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

``A`` is the latent-attention block of :mod:`.latent_attention`, which this
model shares with ``models/kimi_k2.py``: the cache row and why it is kept in two
pools a block, the expanded and the absorbed form, the bound on the scores of a
long prefill, and the rope helpers are described there.
:meth:`LongcatFlashForCausalLM.cache_spec` declares two blocks a layer, four
pools, 2 x 576 x 2 bytes a token a layer in bfloat16.

Served, one chip's share of an expert-parallel deployment; not trained (the
expert share has no backward-tuned path and no exchange across chips).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..core.tensor import Tensor
from ..nn.common import Embedding
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..parallel.moe import ExpertShareLayer
from .latent_attention import Dense, LatentAttention, SwiGLU, rms_norm, rope_tables


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


class LongcatFlashDecoderLayer(Layer):
    """The shortcut-connected double layer (module docstring)."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.input_layernorm = LayerList([rms_norm(c.hidden_size, c) for _ in range(2)])
        self.post_attention_layernorm = LayerList(
            [rms_norm(c.hidden_size, c) for _ in range(2)])
        self.self_attn = LayerList([LatentAttention(c) for _ in range(2)])
        self.mlps = LayerList([SwiGLU(c.hidden_size, c.ffn_hidden_size, c.dtype,
                                     c.initializer_range) for _ in range(2)])
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
        self.norm = rms_norm(config.hidden_size, config)
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
        self.lm_head = Dense(config.hidden_size, config.vocab_size,
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
