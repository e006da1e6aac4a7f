"""Plain reference of the LongCat-Flash language model, one chip's share of its
experts: a full forward in straightforward ``jax.numpy``, float32, matmuls at
``highest``, no cache, no kernels, no batching.

It follows the public ``config.json`` of ``meituan-longcat/LongCat-Flash-Omni``
and the family's published modelling code. One layer, ``N`` an RMSNorm with a
weight of its own::

    x1 = x  + A0(N(x));   h = N(x1);   s = M(h);   x2 = x1 + D0(h)
    x3 = x2 + A1(N(x2));  out = x3 + D1(N(x3)) + s

``A`` is latent attention in its EXPANDED form (keys and values per head from
the normalised, scaled latent; interleaved-pair RoPE on the 64 rope
dimensions of every query head and of the one key shared by all heads;
scores over ``sqrt(128 + 64)``); ``D`` a dense SwiGLU; ``M`` the expert layer:
softmax over every output of the router, the 12 largest of ``p + bias``
picked, a pick weighs ``6 p`` with no renormalisation, routed experts are
SwiGLUs and the zero-compute experts the identity. The chip's share: routed
experts ``experts_first .. + n_routed_experts_held`` are computed (a dense
loop over them), the identity picks too, and what the absent experts would
add is left out, here as in the program. Departures from the release, all
listed under ``assumed`` in the configuration file: no bias term in the
router's classifier, ``e_score_correction_bias`` zeros, an untied head, no
RoPE scaling. It imports nothing of the program under test and takes no
routing from it: weights come from ``--seed`` through :func:`init_leaf`, the
same specification the builder feeds the program.

``precision``: ``"f32"`` is the reference; ``"fp8"`` the control (every matmul
operand rounded to float8_e4m3 under a per-tensor absmax scale: the nearest
precision below the bfloat16 the configuration states); ``"bf16"`` rounds
operands to bfloat16. One layer's weights are made at a time (5 GB in
float32 at the published widths), so the reference fits beside nothing else.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
F32 = jnp.float32
HEAD_GROUP = 8          # heads whose [T, T] scores are held at once


# -- weights from the seed ------------------------------------------------------

def mla_specs(cfg: dict, p: str) -> List[Tuple[str, Tuple[int, ...], str]]:
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return [
        (p + "q_a_proj.weight", (h, qr), "normal"),
        (p + "q_a_layernorm.weight", (qr,), "ones"),
        (p + "q_b_proj.weight", (qr, H * (nope + rope)), "normal"),
        (p + "kv_a_proj_with_mqa.weight", (h, kr + rope), "normal"),
        (p + "kv_a_layernorm.weight", (kr,), "ones"),
        (p + "kv_b_proj.weight", (kr, H * (nope + vd)), "normal"),
        (p + "o_proj.weight", (H * vd, h), "normal"),
    ]


def layer_specs(cfg: dict, i: int) -> List[Tuple[str, Tuple[int, ...], str]]:
    h, f, ef = cfg["hidden_size"], cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    held = cfg["n_routed_experts_held"]
    wide = cfg["n_routed_experts"] + cfg["zero_expert_num"]
    p = f"model.layers.{i}."
    specs = []
    for j in range(2):
        specs += [(p + f"input_layernorm.{j}.weight", (h,), "ones"),
                  (p + f"post_attention_layernorm.{j}.weight", (h,), "ones")]
        specs += mla_specs(cfg, p + f"self_attn.{j}.")
        specs += [(p + f"mlps.{j}.gate_proj.weight", (h, f), "normal"),
                  (p + f"mlps.{j}.up_proj.weight", (h, f), "normal"),
                  (p + f"mlps.{j}.down_proj.weight", (f, h), "normal")]
    specs += [(p + "mlp.router", (h, wide), "normal"),
              (p + "mlp.e_score_correction_bias", (wide,), "zeros"),
              (p + "mlp.gate_proj", (held, h, ef), "normal"),
              (p + "mlp.up_proj", (held, h, ef), "normal"),
              (p + "mlp.down_proj", (held, ef, h), "normal")]
    return specs


def leaf_specs(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every weight as (name, shape, kind); matrices are [in, out], an expert
    layer's held experts are stacked on a leading axis."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    specs = [("model.embed_tokens.weight", (v, h), "normal")]
    for i in range(cfg["num_layers"]):
        specs += layer_specs(cfg, i)
    specs += [("model.norm.weight", (h,), "ones"), ("lm_head.weight", (h, v), "normal")]
    return specs


def seed_key(seed: int):
    """A key from any whole number up to 2**62 (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def init_leaf(key, name: str, shape, kind: str, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, F32)            # the router's correction bias stays float32
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, F32) * INIT_STD).astype(dtype)


def make_weights(specs, seed: int, dtype) -> Dict[str, jax.Array]:
    """All of ``specs`` in one jitted program, on the device, in ``dtype``."""
    specs = tuple((n, tuple(s), k) for n, s, k in specs)

    @jax.jit
    def build(key):
        return {n: init_leaf(key, n, s, k, dtype) for n, s, k in specs}

    return build(seed_key(seed))


def served_weights(specs, seed: int, stated_dtype) -> Dict[str, jax.Array]:
    """float32 copies of the weights as the configuration states them (made in
    float32, rounded once to ``stated_dtype``): what the reference computes on."""
    w = make_weights(specs, seed, stated_dtype)
    return {n: a.astype(F32) for n, a in w.items()}


# -- arithmetic -----------------------------------------------------------------

def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _round(x, precision: str):
    if precision == "fp8":
        return _fp8(x)
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision), precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """Interleaved-pair RoPE on ``[T, ..., d]``, positions 0..T-1: the pair
    ``(x[2i], x[2i+1])`` turns by ``t * theta ** (-2i / d)``."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = (jnp.arange(t, dtype=F32)[:, None] * inv[None, :]).reshape(t, *([1] * (x.ndim - 2)), d // 2)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def latent_attention(cfg: dict, w, p: str, x, precision: str):
    """One latent-attention block on one row ``x [T, hidden]``, expanded form."""
    t = x.shape[0]
    H, qr, kr = cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta, h = cfg["rms_norm_eps"], cfg["rope_theta"], cfg["hidden_size"]
    cq = _rms(_mm(x, w[p + "q_a_proj.weight"], precision), w[p + "q_a_layernorm.weight"], eps)
    if cfg["mla_scale_q_lora"]:
        cq = cq * float(np.sqrt(h / qr))
    q = _mm(cq, w[p + "q_b_proj.weight"], precision).reshape(t, H, nope + rope)
    ckv = _mm(x, w[p + "kv_a_proj_with_mqa.weight"], precision)
    c = _rms(ckv[:, :kr], w[p + "kv_a_layernorm.weight"], eps)
    if cfg["mla_scale_kv_lora"]:
        c = c * float(np.sqrt(h / kr))
    kv = _mm(c, w[p + "kv_b_proj.weight"], precision).reshape(t, H, nope + vd)
    q_rope, k_rope = _rope(q[..., nope:], theta), _rope(ckv[:, kr:], theta)
    mask = jnp.tril(jnp.ones((t, t), bool))
    g = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    split = lambda a: a.reshape(t, H // g, g, a.shape[-1]).transpose(1, 2, 0, 3)      # [groups, g, T, d]

    def group(args):
        qn, qro, kn, v = args
        s = (_mm(qn, kn.transpose(0, 2, 1), precision) + _mm(qro, k_rope.T, precision)) * float(1.0 / np.sqrt(nope + rope))
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return _mm(pr, v, precision)                                                # [g, T, vd]

    out = jax.lax.map(group, (split(q[..., :nope]), split(q_rope), split(kv[..., :nope]), split(kv[..., nope:])))
    out = out.transpose(2, 0, 1, 3).reshape(t, H * vd)
    return _mm(out, w[p + "o_proj.weight"], precision)


def swiglu(w, p: str, x, precision: str):
    a = jax.nn.silu(_mm(x, w[p + "gate_proj.weight"], precision)) * _mm(x, w[p + "up_proj.weight"], precision)
    return _mm(a, w[p + "down_proj.weight"], precision)


def route(cfg: dict, w, p: str, h, precision: str):
    """(weights [T, k], expert ids [T, k]): softmax over every router output in
    float32, the k largest of ``p + bias``, weight ``routed_scaling_factor * p``."""
    prob = jax.nn.softmax(_mm(h, w[p + "mlp.router"], precision), -1)
    _, ids = jax.lax.top_k(prob + w[p + "mlp.e_score_correction_bias"], cfg["moe_topk"])
    return cfg["routed_scaling_factor"] * jnp.take_along_axis(prob, ids, -1), ids


def expert_share(cfg: dict, w, p: str, h, precision: str):
    """The chip's part of the expert layer on ``h [T, hidden]``: a dense loop
    over the held routed experts, and the identity experts' part."""
    first, n_routed = cfg["experts_first"], cfg["n_routed_experts"]
    weights, ids = route(cfg, w, p, h, precision)

    def one(acc, args):
        j, wg, wu, wd = args
        wj = jnp.sum(jnp.where(ids == first + j, weights, 0.0), -1, keepdims=True)
        a = jax.nn.silu(_mm(h, wg, precision)) * _mm(h, wu, precision)
        return acc + wj * _mm(a, wd, precision), None

    held = w[p + "mlp.gate_proj"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(held), w[p + "mlp.gate_proj"],
                                                   w[p + "mlp.up_proj"], w[p + "mlp.down_proj"]))
    return out + h * jnp.sum(jnp.where(ids >= n_routed, weights, 0.0), -1, keepdims=True)


def block(cfg: dict, w: Dict[str, jax.Array], i: int, x, precision: str):
    """One shortcut-connected double layer on one row ``x [T, hidden]``."""
    p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    x = x + latent_attention(cfg, w, p + "self_attn.0.", _rms(x, w[p + "input_layernorm.0.weight"], eps), precision)
    h = _rms(x, w[p + "post_attention_layernorm.0.weight"], eps)
    shortcut = expert_share(cfg, w, p, h, precision)
    x = x + swiglu(w, p + "mlps.0.", h, precision)
    x = x + latent_attention(cfg, w, p + "self_attn.1.", _rms(x, w[p + "input_layernorm.1.weight"], eps), precision)
    return x + swiglu(w, p + "mlps.1.", _rms(x, w[p + "post_attention_layernorm.1.weight"], eps), precision) + shortcut


def head_logits(cfg: dict, w, x, precision: str):
    return _mm(_rms(x, w["model.norm.weight"], cfg["rms_norm_eps"]), w["lm_head.weight"], precision)


def forward_logits(cfg: dict, w, ids, precision: str = "f32"):
    """Whole forward of one row of token ids -> [T, vocab] logits."""
    x = w["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_layers"]):
        x = block(cfg, w, i, x, precision)
    return head_logits(cfg, w, x, precision)


# -- serving: the gap of each served token under the reference -------------------

def serve_reference(cfg: dict, seed: int, sequences: Sequence[np.ndarray], first_new: Sequence[int], stated_dtype,
                    control: str = "", pad_to: int = 256) -> dict:
    """One plain forward over each sequence (prompt + served tokens), a layer's
    weights at a time. For every served position: how far the served token's
    logit lies below the reference's best, over max|logit| there. With
    ``control`` the same is read for the token that precision puts first."""
    modes = ["f32"] + ([control] if control else [])

    def weights(specs):
        return served_weights(specs, seed, stated_dtype)

    @jax.jit
    def embed(table, ids):
        return table[ids]

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def run_block(w, x, i, precision):
        return block(cfg, w, i, x, precision)

    @functools.partial(jax.jit, static_argnums=(3,))
    def gaps(w, x, nxt, precision, ref_logits):
        logits = head_logits(cfg, w, x, precision)
        base = logits if ref_logits is None else ref_logits
        tok = nxt if ref_logits is None else jnp.argmax(logits, -1)
        picked = jnp.take_along_axis(base, tok[:, None], -1)[:, 0]
        return (base.max(-1) - picked) / jnp.abs(base).max(-1), logits

    h, v = cfg["hidden_size"], cfg["vocab_size"]
    padded = []
    for s in sequences:
        n = -(-len(s) // pad_to) * pad_to
        padded.append(np.pad(np.asarray(s, np.int32), (0, n - len(s))))
    table = weights([("model.embed_tokens.weight", (v, h), "normal")])
    acts = {mode: [embed(table["model.embed_tokens.weight"], jnp.asarray(p)) for p in padded] for mode in modes}
    del table
    for i in range(cfg["num_layers"]):
        w = weights(layer_specs(cfg, i))
        for mode in modes:
            acts[mode] = [run_block(w, x, i, mode) for x in acts[mode]]
        jax.block_until_ready(acts)
        del w
    w = weights([("model.norm.weight", (h,), "ones"), ("lm_head.weight", (h, v), "normal")])
    out = {"gap": [], "control_gap": []}
    for j, (s, p) in enumerate(zip(sequences, padded)):
        nxt = jnp.asarray(np.roll(p, -1))
        g, ref_logits = gaps(w, acts["f32"][j], nxt, "f32", None)
        sl = slice(first_new[j] - 1, len(s) - 1)      # position t-1 predicts token t
        out["gap"].append(np.asarray(g)[sl])
        if control:
            cg, _ = gaps(w, acts[control][j], nxt, control, ref_logits)
            out["control_gap"].append(np.asarray(cg)[sl])
    del acts, w
    return out
