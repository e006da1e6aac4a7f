"""Plain reference of Kimi-K2-Instruct, one chip's share of its experts: a full
forward in straightforward ``jax.numpy``, float32, matmuls at ``highest``, no
cache, no kernels, no batching, no routing taken from the program.

It follows the public ``config.json`` of ``moonshotai/Kimi-K2-Instruct`` and the
family's published modelling code (the DeepSeek-V3 layer). With ``N1``, ``N2``
RMSNorms of their own weight::

    x = x + A(N1(x));   x = x + F(N2(x))

``A`` is latent attention in its EXPANDED form: ``q = Wqb N(Wqa x)``, the latent
``c = N(Wkva x [:512])`` (neither rescaled), keys and values per head
``[k_nope, v] = Wkvb c``, RoPE on the 64 rope dimensions of every query head and
of the one key all heads share, scores times
``(128 + 64) ** -0.5 x (0.1 x mscale_all_dim x ln(factor) + 1) ** 2``. Positions
are YaRN's as published (:func:`yarn_inv_freq`: ``factor`` 32 over an original
4,096, ``beta_fast`` = ``beta_slow`` = 1, so the ramp between extrapolated and
interpolated pairs is one pair wide; cos/sin times
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` = 1). ``F`` is a dense
SwiGLU (18,432) in layer 0 (``first_k_dense_replace`` = 1) and in every later
layer ``shared(h) + routed(h)``: ``s = sigmoid(h Wr)`` in float32 over all 384
outputs, the 8 largest of ``s + e_score_correction_bias`` picked (``n_group`` =
``topk_group`` = 1: no group limit), a pick weighing
``2.827 x s_i / (sum of the 8 picked s + 1e-20)``; the shared expert is a SwiGLU
(2,048) every token passes. The chip's share: routed experts ``experts_first ..
+ n_routed_experts`` (the file's count of what is HELD; the router keeps the
``published`` 384 outputs) are computed (a dense loop over them), the shared
expert too, vocabulary rows 0 .. ``vocab_size`` - 1 of the published 163,840,
and what the absent experts would add is left out, here as in the program.

What is compared (:func:`serve_reference`): a top-8 pick is a step function of
the hidden state. Where the 8th and the 9th score all but tie, rounding to the
stated bfloat16 decides which expert is picked, and if one of the two is held
here the layer's result moves by ``2.827 / 8`` of an expert's output: neither
pick is wrong, and no tolerance on a logit covers both. The reference therefore
names, from its OWN float32 router scores alone, the positions where a held
pick is decided (margin of ``UNDECIDED`` or more in every expert layer), and a
served token is compared there and nowhere else.

Departures from the release, all listed under ``assumed`` in the configuration
file: RoPE turns the INTERLEAVED pairs ``(x[2i], x[2i+1])`` where the public
code permutes each head's rope dimensions and turns the half-split pairs (the
same scores: the permutation is applied to query and key alike, and random
weights have no preferred order); the router's classifier is random like every
other matrix and ``e_score_correction_bias`` is zeros; an untied head behind a
final RMSNorm; weights from ``--seed`` through :func:`init_leaf`, the same
specification the builder feeds the program.

``precision``: ``"f32"`` is the reference; ``"fp8"`` the control (every matmul
operand rounded to float8_e4m3 under a per-tensor absmax scale: the nearest
precision below the bfloat16 the configuration states); ``"bf16"`` rounds
operands to bfloat16. One layer's weights are made at a time (2.7 GB in float32
at the published widths) and every part that is a row's own runs over blocks of
rows, attention over blocks of queries and groups of heads, so that 16,896
positions fit one chip.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.longcat_flash_reference import F32, _mm, _rms, mla_specs, seed_key

INIT_STD = 0.02
HEAD_GROUP = 8          # heads whose [block, T] scores are held at once
ROW_BLOCK = 2048        # rows a block of whatever is a row's own
QUERY_BLOCK = 1024      # queries a block of the attention
# A held pick whose margin (:func:`held_pick_margin`, in units of a router logit) is under this is not decided at the
# stated precision, and its position is not compared. On the chip at the published widths (PR 36, 4,096 served tokens on
# 4 seeds) every served token that lay over 0.015 under the reference's best, 23 of them up to 0.087, had a margin of
# 0.051 or less (16 of them under 0.01), and the 3,129 tokens with a margin of 0.08 or more read 0.0137 at most. The
# bound stands four times over the widest margin at which a pick was seen to turn; half of the served positions lie over it.
UNDECIDED = 0.2


# -- weights from the seed ------------------------------------------------------

def router_width(cfg: dict) -> int:
    """The router keeps its published width; the file's ``n_routed_experts`` counts the experts HELD here."""
    return cfg["published"]["n_routed_experts"]


def layer_specs(cfg: dict, i: int) -> List[Tuple[str, Tuple[int, ...], str]]:
    h, ef, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    p = f"model.layers.{i}."
    specs = [(p + "input_layernorm.weight", (h,), "ones"), (p + "post_attention_layernorm.weight", (h,), "ones")]
    specs += mla_specs(cfg, p + "self_attn.")
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        return specs + [(p + "mlp.gate_proj.weight", (h, f), "normal"), (p + "mlp.up_proj.weight", (h, f), "normal"),
                        (p + "mlp.down_proj.weight", (f, h), "normal")]
    sf, wide = cfg["n_shared_experts"] * ef, router_width(cfg)
    return specs + [(p + "mlp.router", (h, wide), "normal"),
                    (p + "mlp.e_score_correction_bias", (wide,), "zeros"),
                    (p + "mlp.gate_proj", (held, h, ef), "normal"),
                    (p + "mlp.up_proj", (held, h, ef), "normal"),
                    (p + "mlp.down_proj", (held, ef, h), "normal"),
                    (p + "mlp.shared_gate_proj", (h, sf), "normal"),
                    (p + "mlp.shared_up_proj", (h, sf), "normal"),
                    (p + "mlp.shared_down_proj", (sf, h), "normal")]


def leaf_specs(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every weight as (name, shape, kind); matrices are [in, out], an expert
    layer's held experts are stacked on a leading axis."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    specs = [("model.embed_tokens.weight", (v, h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("model.norm.weight", (h,), "ones"), ("lm_head.weight", (h, v), "normal")]


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
    return {n: a.astype(F32) for n, a in make_weights(specs, seed, stated_dtype).items()}


# -- positions ------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, sc: dict) -> np.ndarray:
    """The published YaRN frequencies, float64 numpy: pair ``i`` turns by
    ``theta ** (-2i / dim)`` a position where the ramp reads 0 (extrapolated), by
    that over ``factor`` where it reads 1 (interpolated); the ramp rises linearly
    from ``floor`` of the correction dimension of ``beta_fast`` rotations to
    ``ceil`` of that of ``beta_slow``, both clamped to ``[0, dim - 1]``."""
    def corr(rot):
        return dim * math.log(sc["original_max_position_embeddings"] / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = max(math.floor(corr(sc["beta_fast"])), 0), min(math.ceil(corr(sc["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo) / (hi - lo), 0.0, 1.0)
    return base / sc["factor"] * ramp + base * (1.0 - ramp)


def attention_scale(cfg: dict) -> float:
    sc = cfg.get("rope_scaling")
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if sc and sc.get("mscale_all_dim"):
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return float(scale)


def _rope(cfg: dict, x):
    """Interleaved-pair RoPE on ``[T, ..., d]``, positions 0..T-1."""
    t, d = x.shape[0], x.shape[-1]
    sc = cfg.get("rope_scaling")
    if sc:
        inv = yarn_inv_freq(d, cfg["rope_theta"], sc)
        m = yarn_mscale(sc["factor"], sc.get("mscale", 1.0)) / yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0.0))
    else:
        inv, m = cfg["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d), 1.0
    ang = (jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]).reshape(t, *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


# -- arithmetic -----------------------------------------------------------------

def by_rows(fn, x):
    """``fn`` of ``[rows, ...] -> [rows, ...]`` over blocks of ``ROW_BLOCK`` rows, one after another."""
    t, block = x.shape[0], ROW_BLOCK
    if t <= block:
        return fn(x)
    n = -(-t // block)
    xs = jnp.pad(x, ((0, n * block - t),) + ((0, 0),) * (x.ndim - 1)).reshape(n, block, *x.shape[1:])
    out = jax.lax.map(fn, xs)
    return out.reshape(n * block, *out.shape[2:])[:t]


def latent_attention(cfg: dict, w, p: str, x, precision: str):
    """One latent-attention block on one row ``x [T, hidden]``, expanded form."""
    t = x.shape[0]
    H, kr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, scale = cfg["rms_norm_eps"], attention_scale(cfg)

    def project(rows):
        cq = _rms(_mm(rows, w[p + "q_a_proj.weight"], precision), w[p + "q_a_layernorm.weight"], eps)
        ckv = _mm(rows, w[p + "kv_a_proj_with_mqa.weight"], precision)
        c = _rms(ckv[:, :kr], w[p + "kv_a_layernorm.weight"], eps)
        return jnp.concatenate([_mm(cq, w[p + "q_b_proj.weight"], precision),
                                _mm(c, w[p + "kv_b_proj.weight"], precision), ckv[:, kr:]], -1)

    qkv = by_rows(project, x)
    nq, nkv = H * (nope + rope), H * (nope + vd)
    q, kv, k_rope = qkv[:, :nq].reshape(t, H, nope + rope), qkv[:, nq:nq + nkv].reshape(t, H, nope + vd), qkv[:, nq + nkv:]
    q_rope, k_rope = _rope(cfg, q[..., nope:]), _rope(cfg, k_rope)
    g = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    nb = -(-t // QUERY_BLOCK)
    B = -(-t // nb)
    heads = lambda a: a.reshape(t, H // g, g, a.shape[-1]).transpose(1, 2, 0, 3)      # [groups, g, T, d]
    qb = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, nb * B - t), (0, 0))).reshape(H // g, g, nb, B, a.shape[-1])
    at = jnp.arange(nb * B).reshape(nb, B)
    keys = jnp.arange(t)

    def group(args):
        qn, qro, kn, v = args                         # [g, nb, B, d] x 2, [g, T, d] x 2

        def one(blk):
            qn_b, qr_b, at_b = blk                    # [g, B, d], [g, B, d], [B]
            s = (_mm(qn_b, kn.transpose(0, 2, 1), precision) + _mm(qr_b, k_rope.T, precision)) * scale
            pr = jax.nn.softmax(jnp.where(keys[None, None, :] <= at_b[None, :, None], s, -jnp.inf), -1)
            return _mm(pr, v, precision)              # [g, B, vd]

        out = jax.lax.map(one, (qn.transpose(1, 0, 2, 3), qro.transpose(1, 0, 2, 3), at))     # [nb, g, B, vd]
        return out.transpose(1, 0, 2, 3).reshape(g, nb * B, vd)[:, :t]

    out = jax.lax.map(group, (qb(heads(q[..., :nope])), qb(heads(q_rope)), heads(kv[..., :nope]), heads(kv[..., nope:])))
    out = out.transpose(2, 0, 1, 3).reshape(t, H * vd)
    return by_rows(lambda rows: _mm(rows, w[p + "o_proj.weight"], precision), out)


def swiglu(wg, wu, wd, x, precision: str):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision), wd, precision)


def route(cfg: dict, w, p: str, h, precision: str):
    """(weights [T, k], expert ids [T, k]): sigmoid over every router output in
    float32, the k largest of ``s + bias``, weight ``routed_scaling_factor * s_i /
    (sum of the picked s + 1e-20)``."""
    s = jax.nn.sigmoid(_mm(h, w[p + "mlp.router"], precision))
    _, ids = jax.lax.top_k(s + w[p + "mlp.e_score_correction_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, ids, -1)
    return cfg["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-20), ids


def held_pick_margin(cfg: dict, w, p: str, h):
    """[T]: how far ONE router logit would have to move, to first order, before
    the part of the pick that lies on this chip changed: for a held expert among
    the k picked, its selection score less the (k+1)-th largest; for a held expert
    left out, the k-th largest less its own; over the sigmoid's slope at that
    expert's score; the least over the held experts. A pick that changes among
    absent experts changes nothing here but the renormalising sum, by the
    difference of two scores that all but tie."""
    first, held, k = cfg["experts_first"], cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(h, w[p + "mlp.router"], "f32"))
    v = s + w[p + "mlp.e_score_correction_bias"]
    if held == 0:
        return jnp.full(h.shape[:1], jnp.inf, F32)
    top = jax.lax.top_k(v, k + 1)[0]
    vh, sh = v[:, first:first + held], s[:, first:first + held]
    need = jnp.where(vh >= top[:, k - 1:k], vh - top[:, k:], top[:, k - 1:k] - vh)
    return (need / (sh * (1.0 - sh))).min(-1)


def expert_layer(cfg: dict, w, p: str, h, precision: str):
    """The chip's part of the expert layer on ``h [T, hidden]``: the shared expert
    and a dense loop over the held routed experts."""
    first = cfg["experts_first"]

    def rows(h):
        weights, ids = route(cfg, w, p, h, precision)

        def one(acc, args):
            j, wg, wu, wd = args
            wj = jnp.sum(jnp.where(ids == first + j, weights, 0.0), -1, keepdims=True)
            return acc + wj * swiglu(wg, wu, wd, h, precision), None

        shared = swiglu(w[p + "mlp.shared_gate_proj"], w[p + "mlp.shared_up_proj"], w[p + "mlp.shared_down_proj"], h,
                        precision)
        held = w[p + "mlp.gate_proj"].shape[0]
        out, _ = jax.lax.scan(one, shared, (jnp.arange(held), w[p + "mlp.gate_proj"], w[p + "mlp.up_proj"],
                                            w[p + "mlp.down_proj"]))
        return out

    return by_rows(rows, h)


def block(cfg: dict, w: Dict[str, jax.Array], i: int, x, precision: str, margin: bool = False):
    """One layer on one row ``x [T, hidden]``; with ``margin`` also each
    position's :func:`held_pick_margin` in this layer (infinite in a dense one)."""
    p, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    x = x + latent_attention(cfg, w, p + "self_attn.", _rms(x, w[p + "input_layernorm.weight"], eps), precision)
    h = _rms(x, w[p + "post_attention_layernorm.weight"], eps)
    if i < cfg["first_k_dense_replace"]:
        out = x + by_rows(lambda r: swiglu(w[p + "mlp.gate_proj.weight"], w[p + "mlp.up_proj.weight"],
                                           w[p + "mlp.down_proj.weight"], r, precision), h)
        return (out, jnp.full(x.shape[:1], jnp.inf, F32)) if margin else out
    out = x + expert_layer(cfg, w, p, h, precision)
    return (out, by_rows(lambda r: held_pick_margin(cfg, w, p, r), h)) if margin else out


def head_logits(cfg: dict, w, x, precision: str):
    return _mm(_rms(x, w["model.norm.weight"], cfg["rms_norm_eps"]), w["lm_head.weight"], precision)


def forward_logits(cfg: dict, w, ids, precision: str = "f32"):
    """Whole forward of one row of token ids -> [T, vocab] logits."""
    x = w["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = block(cfg, w, i, x, precision)
    return head_logits(cfg, w, x, precision)


# -- serving: the gap of each served token under the reference -------------------

def serve_reference(cfg: dict, seed: int, sequences: Sequence[np.ndarray], first_new: Sequence[int], stated_dtype,
                    control: str = "", pad_to: int = 256, undecided: float = UNDECIDED) -> dict:
    """One plain forward over each sequence (prompt + served tokens), a layer's
    weights at a time. For every served position: how far the served token's
    logit lies below the reference's best, over max|logit| there. With
    ``control`` the same is read for the token that precision puts first. Only
    the served positions pass the head (a 16k-token document's logits would be
    1.4 GB).

    A position whose :func:`held_pick_margin` is under ``undecided`` in some
    layer is one where the published computation itself is not decided at the
    stated precision: which of two all but tied experts is picked turns on the
    last bits of the hidden state, and one of them, held here, adds or withholds
    ``routed_scaling_factor / k`` of an expert's output. Either pick is the
    model's. Such a position reads 0 in ``gap`` and ``control_gap`` (it is not
    compared); ``gap_all`` and ``control_gap_all`` hold every position and
    ``pick_margin`` ``[served, layers]`` what decided, all from the float32
    reference alone: nothing the program computes chooses what is compared."""
    modes = ["f32"] + ([control] if control else [])

    def weights(specs):
        return served_weights(specs, seed, stated_dtype)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def run_block(w, x, i, precision):
        return block(cfg, w, i, x, precision, margin=precision == "f32")

    @functools.partial(jax.jit, static_argnums=(3,))
    def gaps(w, x, nxt, precision, ref_logits):
        logits = head_logits(cfg, w, x, precision)
        base = logits if ref_logits is None else ref_logits
        tok = nxt if ref_logits is None else jnp.argmax(logits, -1)
        picked = jnp.take_along_axis(base, tok[:, None], -1)[:, 0]
        return (base.max(-1) - picked) / jnp.abs(base).max(-1), logits

    h, v = cfg["hidden_size"], cfg["vocab_size"]
    padded = [np.pad(np.asarray(s, np.int32), (0, -len(s) % pad_to)) for s in sequences]
    table = weights([("model.embed_tokens.weight", (v, h), "normal")])["model.embed_tokens.weight"]
    acts = {mode: [table[jnp.asarray(p)] for p in padded] for mode in modes}
    del table
    margins = [[] for _ in sequences]
    for i in range(cfg["num_hidden_layers"]):
        w = weights(layer_specs(cfg, i))
        for mode in modes:
            acts[mode] = [run_block(w, x, i, mode) for x in acts[mode]]
        for j, (x, m) in enumerate(acts["f32"]):
            margins[j].append(m)
        acts["f32"] = [x for x, _ in acts["f32"]]
        jax.block_until_ready(acts)
        del w
    w = weights([("model.norm.weight", (h,), "ones"), ("lm_head.weight", (h, v), "normal")])
    out = {"gap": [], "gap_all": [], "pick_margin": [], "control_gap": [], "control_gap_all": []}
    for j, s in enumerate(sequences):
        # position t-1 predicts token t: the served positions alone, padded to whole blocks
        lo, n = first_new[j] - 1, len(s) - first_new[j]
        rows = slice(lo, lo + n + (-n % pad_to))
        take = lambda a: jnp.pad(a[rows], ((0, rows.stop - rows.start - a[rows].shape[0]),) + ((0, 0),) * (a.ndim - 1))
        nxt = take(jnp.asarray(np.roll(padded[j], -1)))
        g, ref_logits = gaps(w, take(acts["f32"][j]), nxt, "f32", None)
        margin = np.stack([np.asarray(m)[lo:lo + n] for m in margins[j]], -1)
        compared = margin.min(-1) >= undecided
        out["pick_margin"].append(margin)
        out["gap_all"].append(np.asarray(g)[:n])
        out["gap"].append(np.where(compared, out["gap_all"][-1], 0.0))
        if control:
            cg, _ = gaps(w, take(acts[control][j]), nxt, control, ref_logits)
            out["control_gap_all"].append(np.asarray(cg)[:n])
            out["control_gap"].append(np.where(compared, out["control_gap_all"][-1], 0.0))
    del acts, w
    return out
