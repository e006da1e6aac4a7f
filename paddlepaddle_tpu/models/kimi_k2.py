"""Kimi-K2 decoder LM (the DeepSeek-V3 layer): latent attention (MLA), a dense
leading layer, then sparse expert layers with sigmoid routing and a shared
expert, YaRN-scaled positions.

Follows the public ``config.json`` of ``moonshotai/Kimi-K2-Instruct`` and the
family's public modelling code. With ``N1``, ``N2`` RMSNorms of their own
weight, one layer is::

    x = x + A(N1(x));   x = x + F(N2(x))

``A`` is the latent-attention block of :mod:`.latent_attention` (shared with
``models/longcat_flash.py``; ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` are
off here), its positions scaled by the published ``rope_scaling`` block
(YaRN: blended inverse frequencies, and a softmax scale of
``(128 + 64) ** -0.5 x (0.1 ln(32) + 1) ** 2``). ``F`` is a dense SwiGLU of
width ``intermediate_size`` in the first ``first_k_dense_replace`` layers and,
in every later one, ``shared(h) + routed share(h)``:
:class:`~paddlepaddle_tpu.parallel.moe.ExpertShareLayer` with
``routing="sigmoid"`` (the ``num_experts_per_tok`` largest of
``sigmoid + e_score_correction_bias`` over all ``n_routed_experts``, a pick
weighing its renormalised score times ``routed_scaling_factor``; ``n_group`` =
``topk_group`` = 1, so the group limit is the identity), no identity experts,
and a shared expert of width ``n_shared_experts x moe_intermediate_size`` that
every token passes. The layer is told which routed experts this chip holds and
adds their part alone.

:meth:`KimiK2ForCausalLM.cache_spec` declares ONE latent block a layer: two
pools, rows ``[kv_lora_rank]`` and ``[qk_rope_head_dim]``, 576 x 2 bytes a
token a layer in bfloat16.

Served, one chip's share of an expert-parallel deployment; not trained.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..core.tensor import Tensor
from ..nn.common import Embedding
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..parallel.moe import ExpertShareLayer
from .latent_attention import Dense, LatentAttention, SwiGLU, rms_norm, rope_tables


def _published_yarn() -> dict:
    return {"type": "yarn", "factor": 32.0, "original_max_position_embeddings": 4096,
            "beta_fast": 1.0, "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0}


@dataclass
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    # the chip's share: routed experts experts_first .. + n_routed_experts_held
    # live here (None: all of them)
    experts_first: int = 0
    n_routed_experts_held: Optional[int] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
    rope_scaling: Optional[dict] = field(default_factory=_published_yarn)
    initializer_range: float = 0.02
    dtype: str = "float32"
    # the shared latent block asks its model for these two; Kimi-K2 scales neither
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False

    @property
    def latent_row(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def held(self) -> tuple:
        count = (self.n_routed_experts if self.n_routed_experts_held is None
                 else self.n_routed_experts_held)
        return self.experts_first, count

    @staticmethod
    def tiny(vocab_size=128, hidden_size=32, layers=3, heads=4, routed=8,
             topk=3, held=None, max_len=128, original_max_len=32,
             dtype="float32") -> "KimiK2Config":
        """Three layers (a dense one and two expert layers) at toy widths; the
        YaRN block keeps the published factor over a short original length, so
        that most of ``max_len`` lies beyond it."""
        first, count = (0, routed) if held is None else held
        return KimiK2Config(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=hidden_size * 2, moe_intermediate_size=hidden_size,
            num_hidden_layers=layers, num_attention_heads=heads, kv_lora_rank=16,
            q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
            v_head_dim=8, n_routed_experts=routed, num_experts_per_tok=topk,
            experts_first=first, n_routed_experts_held=count,
            max_position_embeddings=max_len,
            rope_scaling=dict(_published_yarn(), factor=4.0,
                              original_max_position_embeddings=original_max_len),
            dtype=dtype)


class KimiK2DecoderLayer(Layer):
    """``x + A(N1(x))``, then ``+ F(N2(.))`` (module docstring)."""

    def __init__(self, config: KimiK2Config, index: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.input_layernorm = rms_norm(c.hidden_size, c)
        self.post_attention_layernorm = rms_norm(c.hidden_size, c)
        self.self_attn = LatentAttention(c)
        self.sparse = index >= c.first_k_dense_replace
        if self.sparse:
            self.mlp = ExpertShareLayer(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts, 0,
                c.num_experts_per_tok, held=c.held,
                scaling=c.routed_scaling_factor, dtype=c.dtype,
                init_std=c.initializer_range, routing="sigmoid",
                shared_hidden=c.n_shared_experts * c.moe_intermediate_size)
        else:
            self.mlp = SwiGLU(c.hidden_size, c.intermediate_size, c.dtype,
                              c.initializer_range)

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None):
        """``cache``: None, the block's two dense caches (``c`` and rotated
        key, in the order of the cache spec), or a paged store holding the two
        pools; with a cache the second value returned is the two updated
        caches, or the two new rows for the store's owner."""
        if attn_mask is not None:
            raise NotImplementedError("latent attention is causal only")
        a, new = self.self_attn(self.input_layernorm(x), cos, sin, cache=cache,
                                pos=pos)
        x = x + a
        h = self.post_attention_layernorm(x)
        x = x + (self.mlp(h)[0] if self.sparse else self.mlp(h))
        return x if cache is None else (x, tuple(new))


class KimiK2Model(Layer):
    def __init__(self, config: KimiK2Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        if config.dtype != "float32":
            self.embed_tokens.to(dtype=config.dtype)
        self.layers = LayerList([KimiK2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = rms_norm(config.hidden_size, config)
        cos, sin = rope_tables(config.qk_rope_head_dim,
                               config.max_position_embeddings, config.rope_theta,
                               config.rope_scaling)
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


class KimiK2ForCausalLM(Layer):
    """The surface the serving engine takes from a decoder LM: ``.model(ids,
    caches=, pos=)``, ``.config``, ``.lm_head``, ``functional_state()`` and
    the declared cache rows (:meth:`cache_spec`)."""

    def __init__(self, config: KimiK2Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = KimiK2Model(config)
        self.lm_head = Dense(config.hidden_size, config.vocab_size,
                             config.dtype, config.initializer_range)

    def cache_spec(self):
        """Per layer, the pools a cached token has a row in: the one block's
        latent ``[kv_lora_rank]`` and rotated key ``[qk_rope_head_dim]``, both
        shared by all heads (``latent_attention``: why two pools)."""
        from ..inference.kv_pool import PoolSpec

        block = (PoolSpec("latent", (self.config.kv_lora_rank,)),
                 PoolSpec("latent", (self.config.qk_rope_head_dim,)))
        return [block for _ in range(self.config.num_hidden_layers)]

    def forward(self, input_ids, labels=None, attn_mask=None):
        if labels is not None:
            raise NotImplementedError(
                "KimiK2ForCausalLM is served, not trained: the expert share "
                "has no exchange across chips and no loss")
        return self.lm_head(self.model(input_ids, attn_mask))
