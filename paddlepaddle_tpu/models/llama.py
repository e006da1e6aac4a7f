"""Llama-3-style decoder-only LM — the flagship pretrain model.

Capability parity target: PaddleNLP's LlamaForCausalLM recipe semantics
(reference framework surface: python/paddle/nn/layer/transformer.py,
python/paddle/incubate/nn/functional/ fused_rms_norm / fused_rotary_position_
embedding / swiglu, python/paddle/nn/functional/flash_attention.py:364).

TPU-native design notes:
* all compute is bf16-friendly and static-shape; attention goes through the
  Pallas flash-attention kernel (ops/kernels/flash_attention.py) on TPU,
  XLA fallback elsewhere;
* GQA repeats kv heads at trace time — XLA fuses the broadcast into the
  attention einsum, no materialized copy on TPU;
* ``llama_sharding_rules`` carries the GSPMD placement table (the analogue of
  the reference's per-layer ColumnParallel/RowParallel markup in
  fleet/layers/mpu/mp_layers.py): 2D (tp × fsdp) sharding of every matmul
  weight, so pjit emits all-gather/reduce-scatter over ICI.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..core.tensor import Tensor


@jax.custom_vjp
def _ce_rows(lg, labels):
    """Per-position NLL = lse(logits) - logits[label], fp32 math over bf16
    logits. The custom vjp keeps the fp32 [B,S,V] intermediates OUT of the
    saved residuals: backward rebuilds softmax rows from the bf16 logits
    and the saved [B,S] lse (tools/ce_head_ab.py A/B)."""
    lgf = lg.astype(jnp.float32)
    lse = jax.nn.logsumexp(lgf, axis=-1)
    picked = jnp.take_along_axis(lgf, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def _ce_rows_fwd(lg, labels):
    lgf = lg.astype(jnp.float32)
    lse = jax.nn.logsumexp(lgf, axis=-1)
    picked = jnp.take_along_axis(lgf, labels[..., None], axis=-1)[..., 0]
    return lse - picked, (lg, labels, lse)


def _ce_rows_bwd(res, g):
    lg, labels, lse = res
    p = jnp.exp(lg.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(labels, lg.shape[-1], dtype=jnp.float32)
    return ((p - onehot) * g[..., None]).astype(lg.dtype), None


_ce_rows.defvjp(_ce_rows_fwd, _ce_rows_bwd)
from ..nn import functional as F
from ..nn.common import Embedding, Linear
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..nn.norm import RMSNorm


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    # context parallelism: shard the sequence dim over this mesh axis and run
    # ring attention over ICI (exceeds the reference, which has no ring attn)
    context_parallel_axis: Optional[str] = None
    data_parallel_axis: str = "dp"  # batch-dim axis inside the ring shard_map
    # activation recompute per decoder layer (reference fleet recompute.py:459
    # -> jax.checkpoint): trades one extra forward for O(layers) activation
    # memory, what lets billion-param configs train on one chip
    recompute: bool = False
    # remat policy (reference recompute's selective-checkpoint knob ->
    # jax.checkpoint policy): None = full remat; "dots" saves matmul
    # outputs so backward skips recomputing the MXU work (more memory,
    # less recompute time)
    remat_policy: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    # ready-made sizes -----------------------------------------------------
    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192, rope_theta=500000.0, dtype="bfloat16")

    @staticmethod
    def tiny(vocab_size=256, hidden_size=64, layers=2, heads=4, kv_heads=2,
             max_len=128) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=hidden_size * 3, num_hidden_layers=layers,
            num_attention_heads=heads, num_key_value_heads=kv_heads,
            max_position_embeddings=max_len)

    def num_params(self) -> int:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        kv = self.num_key_value_heads * self.head_dim
        per_layer = h * h + 2 * h * kv + h * h + 3 * h * i + 2 * h
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        return self.num_hidden_layers * per_layer + embed + h


def rope_tables(head_dim: int, max_len: int, theta: float):
    """fp32 cos/sin tables [max_len, head_dim] for NeoX-style rope — shared
    by the Layer model and the hybrid-parallel functional stage."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)                       # [T, dim/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)       # [T, dim]
    return jnp.cos(emb), jnp.sin(emb)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope_cos_sin(config: LlamaConfig):
    return rope_tables(config.head_dim, config.max_position_embeddings,
                       config.rope_theta)


def _apply_rope(q, k, cos, sin, offset=0):
    """NeoX-style rotate-half rope on BSHD tensors; cos/sin precomputed fp32.

    ``offset``: scalar start position, or a PER-ROW [b] vector (ragged
    continuous batching — each sequence sits at its own position)."""

    rot = rotate_half

    def f(qa, ka, c, s):
        seq = qa.shape[1]
        if jnp.ndim(offset) == 0:
            c = jax.lax.dynamic_slice_in_dim(c, offset, seq, axis=0)[None, :, None, :]
            s = jax.lax.dynamic_slice_in_dim(s, offset, seq, axis=0)[None, :, None, :]
        else:
            idx = jnp.asarray(offset, jnp.int32)[:, None] \
                + jnp.arange(seq, dtype=jnp.int32)[None, :]       # [b, seq]
            c = c[idx][:, :, None, :]
            s = s[idx][:, :, None, :]
        c, s = c.astype(qa.dtype), s.astype(qa.dtype)
        return (qa * c + rot(qa) * s, ka * c + rot(ka) * s)

    return apply_op(f, q, k, cos, sin, op_name="fused_rope")


def _cached_attention(q, k_new, v_new, k_cache, v_cache, pos, n_rep, scale):
    """Write new K/V at [pos:pos+s] and attend q over the valid cache prefix.

    q/k_new/v_new: [b, s, h(…kv), d]; caches [b, L, kvh, d]; pos is a traced
    scalar, or a PER-ROW [b] vector for ragged continuous batching (each
    sequence writes and attends at its own length — the TPU-native role of
    the reference's paged block_multi_head_attention, with slot-contiguous
    static caches instead of block tables).
    Returns (out [b, s, h, d], k_cache', v_cache')."""
    b, s = q.shape[0], q.shape[1]
    L = k_cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        zero = jnp.zeros((), jnp.int32)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (zero, pos, zero, zero))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (zero, pos, zero, zero))
        q_pos = pos + jax.lax.broadcasted_iota(jnp.int32, (s, L), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (s, L), 1)
        valid = (k_pos <= q_pos)[None]                  # [1, s, L] broadcast b
    else:
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]          # [b, 1]
        cols = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]
        k_cache = k_cache.at[rows, cols].set(k_new.astype(k_cache.dtype))
        v_cache = v_cache.at[rows, cols].set(v_new.astype(v_cache.dtype))
        q_pos = cols[:, :, None]                                # [b, s, 1]
        k_pos = jnp.arange(L, dtype=jnp.int32)[None, None, :]   # [1, 1, L]
        valid = k_pos <= q_pos                                  # [b, s, L]
    # GQA without materialization: q regrouped [b, s, kvh, rep, d] contracts
    # straight against the UNREPEATED bf16 cache with f32 MXU accumulation —
    # jnp.repeat + .astype(f32) would write 4x the cache bytes every decode
    # step (the whole pool, per layer), which dominated serving step time
    h = q.shape[2]
    kvh = k_cache.shape[2]
    qg = q.reshape(b, s, kvh, n_rep, q.shape[3])
    logits = jnp.einsum("bskrd,blkd->bkrsl", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None], logits, -1e30)    # causal+prefix
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrsl,blkd->bskrd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, q.shape[3]).astype(q.dtype), k_cache, v_cache


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = Linear(h, h, bias_attr=False)
        self.k_proj = Linear(h, kv, bias_attr=False)
        self.v_proj = Linear(h, kv, bias_attr=False)
        self.o_proj = Linear(h, h, bias_attr=False)

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None):
        """``cache`` (decoding from ``pos``) is this layer's K/V store, in
        one of two forms, and the second element returned follows it:

        * a ``(k, v)`` pair of dense ``[b, L, kvh, hd]`` caches: the new
          rows are written at ``pos``, every position up to them is
          attended (:func:`_cached_attention`), and the updated pair comes
          back;
        * a store that keeps its own layout, as the serving engine's paged
          pool does: any object with ``attend(q, k_new, v_new, pos, n_rep,
          scale) -> (out, k_rows, v_rows)`` over jax arrays, holding the
          same write-then-attend order and causal rule. What comes back is
          the rows for its owner to store, not a cache."""
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if cache is not None:
            if attn_mask is not None:
                raise NotImplementedError(
                    "KV-cache decoding supports causal masking only; strip "
                    "padding (or use dense attention) when passing caches")
            # KV-cache decode: rope at the true positions, write-through cache,
            # attend over the valid prefix (one compiled step serves all pos)
            q, k = _apply_rope(q, k, cos, sin, offset=pos)
            rep = self.num_heads // self.num_kv_heads
            scale = 1.0 / math.sqrt(self.head_dim)
            if hasattr(cache, "attend"):
                out, kc, vc = apply_op(
                    lambda qa, ka, va: cache.attend(qa, ka, va, pos, rep,
                                                    scale),
                    q, k, v, op_name="cached_attention")
            else:
                out, kc, vc = apply_op(
                    lambda qa, ka, va, kca, vca: _cached_attention(
                        qa, ka, va, kca, vca, pos, rep, scale),
                    q, k, v, cache[0], cache[1], op_name="cached_attention")
            return self.o_proj(out.reshape([b, s, -1])), (kc, vc)
        q, k = _apply_rope(q, k, cos, sin)
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = apply_op(lambda a: jnp.repeat(a, rep, axis=2), k)
            v = apply_op(lambda a: jnp.repeat(a, rep, axis=2), v)
        if self.config.context_parallel_axis is not None:
            from ..ops.kernels.ring_attention import ring_flash_attention

            if attn_mask is not None:
                raise NotImplementedError(
                    "ring attention supports causal masking only; pad-free "
                    "batches (or dense attention) are required under context "
                    "parallelism")
            out = ring_flash_attention(q, k, v, causal=True,
                                       sp_axis=self.config.context_parallel_axis,
                                       data_axis=self.config.data_parallel_axis)
        elif attn_mask is not None:
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=True)
        else:
            out, _ = F.flash_attention(q, k, v, causal=True)
        return self.o_proj(out.reshape([b, s, -1]))


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = Linear(h, i, bias_attr=False)
        self.up_proj = Linear(h, i, bias_attr=False)
        self.down_proj = Linear(i, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None):
        if cache is not None:
            attn_out, new_cache = self.self_attn(self.input_layernorm(x), cos, sin,
                                                 attn_mask, cache=cache, pos=pos)
            x = x + attn_out
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        x = x + self.self_attn(self.input_layernorm(x), cos, sin, attn_mask)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        if config.dtype != "float32":
            self.to(dtype=config.dtype)
        # rope tables registered AFTER the dtype cast: they must stay fp32
        # (the reference keeps rotary tables fp32; casting to the activation
        # dtype happens per-use inside _apply_rope)
        cos, sin = _rope_cos_sin(config)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None):
        x = self.embed_tokens(input_ids)
        cos, sin = self.rope_cos, self.rope_sin
        if caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                x, nc = layer(x, cos, sin, attn_mask, cache=cache, pos=pos)
                new_caches.append(nc)
            return self.norm(x), new_caches
        if self.config.recompute:
            from ..distributed.fleet.recompute import recompute

            policies = {
                None: None,
                "dots": jax.checkpoint_policies.checkpoint_dots,
                "dots_no_batch":
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }
            if self.config.remat_policy not in policies:
                raise ValueError(
                    f"remat_policy={self.config.remat_policy!r} — valid: "
                    f"{sorted(k for k in policies if k)} or None")
            policy = policies[self.config.remat_policy]
            for layer in self.layers:
                x = recompute(layer, x, cos, sin, attn_mask, policy=policy)
        else:
            for layer in self.layers:
                x = layer(x, cos, sin, attn_mask)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size, bias_attr=False)
            if config.dtype != "float32":
                self.lm_head.to(dtype=config.dtype)

    def cache_spec(self):
        """Per layer, the pools a cached token has a row in: a K and a V pool
        of rows ``[kv heads, head size]`` (what the serving engine sizes its
        pools, its admission scratch and its bytes a token from)."""
        from ..inference.kv_pool import PoolSpec

        row = (self.config.num_key_value_heads, self.config.head_dim)
        return [(PoolSpec("k", row), PoolSpec("v", row))
                for _ in range(self.config.num_hidden_layers)]

    def forward(self, input_ids, labels=None, attn_mask=None):
        hidden = self.model(input_ids, attn_mask)
        if self.lm_head is None:
            logits = apply_op(lambda h, w: h @ w.T, hidden, self.model.embed_tokens.weight)
        else:
            logits = self.lm_head(hidden)
        if labels is None:
            return logits
        return self.loss_from_logits(logits, labels)

    def generate_cached(self, input_ids, max_new_tokens=32, temperature=1.0,
                        top_k=0, eos_token_id=None, seed=0):
        """KV-cache decoding: prefill once over the prompt, then O(1)-per-token
        single-position steps — the serving path (vs generate()'s O(L²) loop).
        Two compiles total (prefill + decode step)."""
        import numpy as np

        from ..core import autograd as _ag
        from ..core.dispatch import unwrap

        cfg = self.config
        ids = np.asarray(input_ids if not isinstance(input_ids, Tensor)
                         else input_ids.numpy()).astype(np.int32)
        b, prompt_len = ids.shape
        if prompt_len >= cfg.max_position_embeddings:
            raise ValueError(f"prompt length {prompt_len} exceeds "
                             f"max_position_embeddings {cfg.max_position_embeddings}")
        total = min(prompt_len + max_new_tokens, cfg.max_position_embeddings)
        # bucket the cache length so calls with different max_new_tokens reuse
        # the same compiled decode step (cache shape is part of the signature)
        cache_len = min(-(-total // 128) * 128, cfg.max_position_embeddings)
        state = self.functional_state()
        kvh, hd = cfg.num_key_value_heads, cfg.head_dim
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        caches = [(jnp.zeros((b, cache_len, kvh, hd), dtype),
                   jnp.zeros((b, cache_len, kvh, hd), dtype))
                  for _ in range(cfg.num_hidden_layers)]

        def sample(row, key):
            if top_k and top_k > 0:
                kth = jax.lax.top_k(row, top_k)[0][:, -1:]
                row = jnp.where(row < kth, -jnp.inf, row)
            if temperature == 0.0:
                return jnp.argmax(row, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, row / max(temperature, 1e-6)).astype(jnp.int32)

        def step(params, toks, caches, pos, key):
            with _ag.no_grad(), self.bind_state(params):
                hidden, new_caches = self.model(toks, caches=caches, pos=pos)
                if self.lm_head is None:
                    logits = apply_op(lambda h, w: h @ w.T, hidden,
                                      self.model.embed_tokens.weight)
                else:
                    logits = self.lm_head(hidden)
            new_caches = [(unwrap(k), unwrap(v)) for k, v in new_caches]
            row = unwrap(logits)[:, -1].astype(jnp.float32)
            key, sub = jax.random.split(key)
            nxt = sample(row, sub)
            return nxt, new_caches, pos + jnp.int32(toks.shape[1]), key

        # bucket gen length so nearby max_new_tokens values reuse the same
        # compiled program; the result is trimmed to the requested length
        gen_len = min(-(-(total - prompt_len) // 64) * 64,
                      cache_len - prompt_len)

        def run_all(params, prompt, caches, key):
            # prefill + the whole token loop in ONE compiled program: a single
            # dispatch per generate() call (per-call overhead over remote
            # transports would otherwise dominate single-token steps)
            nxt, caches, pos, key = step(params, prompt, caches, jnp.int32(0), key)
            buf = jnp.zeros((b, gen_len), jnp.int32)
            buf = buf.at[:, 0].set(nxt)
            finished = (nxt == eos_token_id) if eos_token_id is not None \
                else jnp.zeros((b,), bool)

            def cond(carry):
                i, nxt, caches, pos, key, buf, finished = carry
                return (i < gen_len) & ~jnp.all(finished)

            def body(carry):
                i, nxt, caches, pos, key, buf, finished = carry
                nxt, caches, pos, key = step(params, nxt[:, None], caches, pos, key)
                buf = jax.lax.dynamic_update_slice(buf, nxt[:, None],
                                                   (jnp.int32(0), i))
                if eos_token_id is not None:
                    finished = finished | (nxt == eos_token_id)
                return i + 1, nxt, caches, pos, key, buf, finished

            carry = (jnp.int32(1), nxt, caches, pos, key, buf, finished)
            _, _, _, _, _, buf, _ = jax.lax.while_loop(cond, body, carry)
            return buf

        # cache the compiled program per signature — jax.jit identity is the
        # function object, so a fresh jit per call would recompile every time
        sig = (b, prompt_len, gen_len, cache_len, temperature, top_k,
               eos_token_id)
        if not hasattr(self, "_decode_fns"):
            object.__setattr__(self, "_decode_fns", {})
        fn = self._decode_fns.get(sig)
        if fn is None:
            if len(self._decode_fns) >= 8:  # bound pinned executables
                self._decode_fns.pop(next(iter(self._decode_fns)))
            fn = jax.jit(run_all)
            self._decode_fns[sig] = fn
        key = jax.random.PRNGKey(seed)
        gen = np.asarray(fn(state, jnp.asarray(ids), caches, key))
        gen = gen[:, : total - prompt_len]  # trim gen-length bucketing
        if eos_token_id is not None:
            hit = gen == eos_token_id
            first = np.where(hit.any(1), hit.argmax(1), gen.shape[1] - 1)
            posn = np.arange(gen.shape[1])[None, :]
            gen = np.where(posn > first[:, None], eos_token_id, gen)
            # match generate(): stop at the last row's first eos
            gen = gen[:, : int(first.max()) + 1]
        result = np.concatenate([ids, gen], axis=1)
        return Tensor._from_data(jnp.asarray(result))

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0, top_k=0,
                 eos_token_id=None, seed=0):
        """Autoregressive decoding (PaddleNLP-style generate).

        TPU-shaped: the token buffer is padded to a STATIC length so the
        whole decode loop reuses ONE compiled step (no per-length
        recompiles); causal masking makes the padded tail inert for the row
        that is read each step. O(L²) per sequence — a KV-cache decode
        kernel is the planned optimization for serving."""
        import numpy as np

        from ..core import autograd as _ag
        from ..core.dispatch import unwrap

        ids = np.asarray(input_ids if not isinstance(input_ids, Tensor)
                         else input_ids.numpy()).astype(np.int32)
        b, prompt_len = ids.shape
        if prompt_len >= self.config.max_position_embeddings:
            raise ValueError(
                f"prompt length {prompt_len} exceeds max_position_embeddings "
                f"{self.config.max_position_embeddings}; truncate the prompt")
        total = min(prompt_len + max_new_tokens, self.config.max_position_embeddings)
        buf = np.zeros((b, total), np.int32)
        buf[:, :prompt_len] = ids
        state = self.functional_state()

        def step(params, buf_arr, cur_len, key):
            with _ag.no_grad(), self.bind_state(params):
                logits = unwrap(self(buf_arr))              # [b, L, V]
            row = jax.lax.dynamic_slice_in_dim(logits, cur_len - 1, 1, axis=1)[:, 0]
            row = row.astype(jnp.float32)
            if top_k and top_k > 0:
                kth = jax.lax.top_k(row, top_k)[0][:, -1:]
                row = jnp.where(row < kth, -jnp.inf, row)
            if temperature and temperature != 1.0:
                row = row / temperature
            if temperature == 0.0:
                nxt = jnp.argmax(row, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(key, row).astype(jnp.int32)
            buf_arr = jax.lax.dynamic_update_slice_in_dim(
                buf_arr, nxt[:, None], cur_len, axis=1)
            return buf_arr, nxt

        step_jit = jax.jit(step, donate_argnums=(1,))
        key = jax.random.PRNGKey(seed)
        buf_arr = jnp.asarray(buf)
        finished = np.zeros((b,), bool)
        cur = prompt_len
        while cur < total:
            key, sub = jax.random.split(key)
            # cur as a traced scalar: ONE compile serves every step
            buf_arr, nxt = step_jit(state, buf_arr, jnp.asarray(cur, jnp.int32), sub)
            cur += 1
            if eos_token_id is not None:
                finished |= np.asarray(nxt) == eos_token_id
                if finished.all():
                    break
        out = np.asarray(buf_arr[:, :cur])
        if eos_token_id is not None:
            # pad everything after each row's first eos with eos (reference
            # generate pads finished rows instead of keeping sampled garbage)
            gen = out[:, prompt_len:]
            hit = gen == eos_token_id
            first = np.where(hit.any(1), hit.argmax(1), gen.shape[1])
            pos = np.arange(gen.shape[1])[None, :]
            gen = np.where(pos > first[:, None], eos_token_id, gen)
            out = np.concatenate([out[:, :prompt_len], gen], axis=1)
        return Tensor._from_data(jnp.asarray(out))

    @staticmethod
    def loss_from_logits(logits, labels):
        """Next-token CE in fp32 over bf16 logits; labels == -100 ignored.

        Shape-preserving formulation (roll + position mask instead of the
        usual [:-1]/[1:] slices): slicing one element off a sharded sequence
        dim makes it unevenly sharded, which both costs a reshard and crashes
        XLA's SPMD partitioner under context parallelism; roll lowers to a
        collective-permute and keeps every tensor evenly sharded.

        The per-row NLL is a custom-vjp lse formulation: forward saves only
        the [B,S] logsumexp (softmax rows are recomputed from the bf16
        logits in backward), so no fp32 [B,S,V] residual crosses the
        fwd/bwd boundary — measured 14.1 -> 9.9 ms on the 254M head
        segment (tools/ce_head_ab.py), exact loss parity, grad diff 5e-7."""

        def f(lg, lb):
            seq = lg.shape[1]
            lb_next = jnp.roll(lb, -1, axis=1)           # label for pos t is token t+1
            nll = _ce_rows(lg, jnp.maximum(lb_next, 0))
            pos = jax.lax.broadcasted_iota(jnp.int32, nll.shape, 1)
            valid = ((lb_next >= 0) & (pos < seq - 1)).astype(jnp.float32)
            return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)

        return apply_op(f, logits, labels, op_name="cross_entropy")


def llama_sharding_rules(tp_axis="tp", fsdp_axis="fsdp"):
    """GSPMD placement table: param-name regex → PartitionSpec axes.

    The 2D-sharding recipe from the scaling playbook: every matmul weight is
    sharded on both tp (the contracted-or-output hidden dim that TP splits)
    and fsdp (the other dim, ZeRO-3 style), norms replicated. With this table
    alone pjit reproduces the reference's ColumnParallel/RowParallel +
    sharding-stage-3 composition (fleet/layers/mpu/mp_layers.py:336,543 +
    group_sharded_stage3.py) as compiler-inserted ICI collectives.
    """
    return [
        # embed: vocab over fsdp, hidden over tp — hidden-over-tp matches the
        # activation-cotangent layout in backward, so the embedding VJP needs
        # no "involuntary full rematerialization" reshard (the (tp, fsdp)
        # orientation forced XLA to replicate the [b,s,h] cotangent when the
        # batch is sharded over dp x fsdp)
        (r".*embed_tokens\.weight$", (fsdp_axis, tp_axis)),
        (r".*(q|k|v)_proj\.weight$", (fsdp_axis, tp_axis)),   # column-parallel
        (r".*o_proj\.weight$", (tp_axis, fsdp_axis)),          # row-parallel
        (r".*(gate|up)_proj\.weight$", (fsdp_axis, tp_axis)),  # column-parallel
        (r".*down_proj\.weight$", (tp_axis, fsdp_axis)),       # row-parallel
        (r".*lm_head\.weight$", (fsdp_axis, tp_axis)),
        (r".*", ()),                                           # norms etc. replicated
    ]
