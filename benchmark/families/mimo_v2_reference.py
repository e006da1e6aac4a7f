"""Plain reference of MiMo-V2-Flash, one chip's share of its experts: a full
forward in straightforward ``jax.numpy``, float32, matmuls at ``highest``, no
cache, no kernels, no batching, no routing taken from the program.

It follows the public ``config.json`` of ``XiaomiMiMo/MiMo-V2-Flash``. With
``N1``, ``N2`` RMSNorms of their own weight (epsilon ``layernorm_epsilon``)::

    h = x + A(N1(x));   y = h + F(N2(h))

``A`` of layer ``i`` is a window layer where ``hybrid_layer_pattern[i]`` is 1
(``swa_`` heads and widths, ``swa_rope_theta``, a query at ``t`` seeing the
``sliding_window`` keys ``t - 127 .. t``, and a learned sink logit a head
where ``add_swa_attention_sink_bias``) and a full layer where it is 0 (every
key up to ``t``, ``rope_theta``, a sink where ``add_full_attention_sink_bias``).
Both: ``q = x Wq``, ``k = x Wk``, ``v = x Wv``; RoPE in half-split pairs on the
first ``int(head_dim x partial_rotary_factor)`` dims of every query and key
head; scores ``q . k / sqrt(head_dim)``; a sink is one more term ``exp(b_h)``
in each denominator, with no value; the output ``attention_value_scale x P V``,
then ``Wo``. ``F`` is a dense SwiGLU where ``moe_layer_freq[i]`` is 0 and
otherwise the routed experts: ``s = sigmoid(h Wr)`` in float32 over all
``published.n_routed_experts`` outputs, the 8 largest of
``s + e_score_correction_bias`` picked, a pick weighing ``s_i / (sum of the 8
picked s + 1e-20)`` (``routed_scaling_factor`` null: 1); no shared expert. The
chip's share: routed experts ``experts_first .. + n_routed_experts`` (what is
HELD) are computed, a dense loop over them, vocabulary rows 0 .. ``vocab_size``
- 1 of the published 152,576; what the absent experts would add is left out,
here as in the program.

What is compared (:func:`serve_reference`) is Kimi-K2's rule
(``kimi_k2_reference``): a served token is compared where the reference's own
float32 router decides this chip's part of every expert layer's pick (margin
``UNDECIDED`` or more), since a pick two all but tied experts share turns on
the last bits of a bfloat16 hidden state.

Departures from the release, all listed under ``assumed`` in the configuration
file: the router's classifier is random and its correction bias zeros;
weights from ``--seed`` through :func:`init_leaf`, the sink logits drawn
``Normal(sink_logit_init)``; ``attention_chunk_size`` (a detail of the
published kernel) changes no result and is not modelled.

``precision`` of a run: ``"f32"`` is the reference; ``"fp8"`` the control
(every matmul operand rounded to float8_e4m3 under a per-tensor absmax scale,
the nearest precision below the stated bfloat16); ``"bf16"`` rounds operands to
bfloat16. The planted faults of :data:`FAULTS` are the f32 reference with one
part of the layer left out. One layer's weights are made at a time and every
part that is a row's own runs over blocks of rows, attention over blocks of
queries and one kv head's query heads at a time.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.kimi_k2_reference import UNDECIDED, by_rows, held_pick_margin, swiglu  # noqa: F401
from benchmark.families.longcat_flash_reference import F32, _mm, _rms, seed_key

INIT_STD = 0.02
QUERY_BLOCK = 1024      # queries a block of the attention
# a part of the layer left out, each read like the float8 control (PERF.md section 6)
FAULTS = ("no_window", "no_sink", "no_value_scale", "full_rotary")


# -- weights from the seed ------------------------------------------------------

def router_width(cfg: dict) -> int:
    """The router keeps its published width; the file's ``n_routed_experts`` counts the experts HELD here."""
    return cfg["published"]["n_routed_experts"]


def windowed(cfg: dict, i: int) -> bool:
    return bool(cfg["hybrid_layer_pattern"][i])


def attention_shape(cfg: dict, i: int) -> Tuple[int, int, int, int]:
    """(heads, kv heads, qk width, v width) of layer ``i``."""
    if windowed(cfg, i):
        return (cfg["swa_num_attention_heads"], cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                cfg["swa_v_head_dim"])
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["v_head_dim"]


def has_sink(cfg: dict, i: int) -> bool:
    return bool(cfg["add_swa_attention_sink_bias"] if windowed(cfg, i) else cfg["add_full_attention_sink_bias"])


def layer_specs(cfg: dict, i: int) -> List[Tuple[str, Tuple[int, ...], str]]:
    h = cfg["hidden_size"]
    H, kvh, hd, vd = attention_shape(cfg, i)
    p = f"model.layers.{i}."
    specs = [(p + "input_layernorm.weight", (h,), "ones"), (p + "post_attention_layernorm.weight", (h,), "ones")]
    if has_sink(cfg, i):
        specs.append((p + "self_attn.attention_sink_bias", (H,), "sink"))
    specs += [(p + "self_attn.q_proj.weight", (h, H * hd), "normal"),
              (p + "self_attn.k_proj.weight", (h, kvh * hd), "normal"),
              (p + "self_attn.v_proj.weight", (h, kvh * vd), "normal"),
              (p + "self_attn.o_proj.weight", (H * vd, h), "normal")]
    if not cfg["moe_layer_freq"][i]:
        f = cfg["intermediate_size"]
        return specs + [(p + "mlp.gate_proj.weight", (h, f), "normal"), (p + "mlp.up_proj.weight", (h, f), "normal"),
                        (p + "mlp.down_proj.weight", (f, h), "normal")]
    ef, held, wide = cfg["moe_intermediate_size"], cfg["n_routed_experts"], router_width(cfg)
    return specs + [(p + "mlp.router", (h, wide), "normal"),
                    (p + "mlp.e_score_correction_bias", (wide,), "zeros"),
                    (p + "mlp.gate_proj", (held, h, ef), "normal"),
                    (p + "mlp.up_proj", (held, h, ef), "normal"),
                    (p + "mlp.down_proj", (held, ef, h), "normal")]


def leaf_specs(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every weight as (name, shape, kind); matrices are [in, out], an expert
    layer's held experts are stacked on a leading axis."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    specs = [("model.embed_tokens.weight", (v, h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    return specs + [("model.norm.weight", (h,), "ones"), ("lm_head.weight", (h, v), "normal")]


def salt(name: str) -> int:
    """What a leaf's name folds into the seed's key."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def init_leaf(key, shape, kind: str, dtype, sink=(0.0, 1.0)):
    """One leaf from ``key``, the seed's key with the leaf's :func:`salt` folded in."""
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, F32)            # the router's correction bias stays float32
    if kind == "sink":                          # float32, as the program keeps it
        return sink[0] + sink[1] * jax.random.normal(key, shape, F32)
    return (jax.random.normal(key, shape, F32) * INIT_STD).astype(dtype)


def sink_init(cfg: dict) -> Tuple[float, float]:
    s = cfg["sink_logit_init"]
    return float(s["mean"]), float(s["std"])


def make_weights(cfg: dict, specs, seed: int, dtype) -> Dict[str, jax.Array]:
    """Each of ``specs`` on the device, in ``dtype``, by one jitted program a
    shape and kind that takes the name's salt as an operand: the program's
    build and the reference's share those few programs."""
    key, sink = seed_key(seed), sink_init(cfg)
    return {n: _leaf(tuple(s), k, dtype, sink)(key, jnp.uint32(salt(n))) for n, s, k in specs}


@functools.lru_cache(maxsize=None)
def _leaf(shape, kind, dtype, sink):
    return jax.jit(lambda key, salt: init_leaf(jax.random.fold_in(key, salt), shape, kind, dtype, sink))


def served_weights(cfg: dict, specs, seed: int, stated_dtype) -> Dict[str, jax.Array]:
    """float32 copies of the weights as the configuration states them."""
    return {n: a.astype(F32) for n, a in make_weights(cfg, specs, seed, stated_dtype).items()}


# -- arithmetic -----------------------------------------------------------------

def split_mode(mode: str) -> Tuple[str, str]:
    """(precision, fault) of a run's mode: a fault runs at float32."""
    return ("f32", mode) if mode in FAULTS else (mode, "")


def _rope(x, theta: float, rot: int):
    """Half-split RoPE on the first ``rot`` dims of ``[T, heads, d]``, positions 0..T-1: the pair
    ``(x[i], x[i + rot/2])`` turns by ``t * theta ** (-2i / rot)``."""
    t = x.shape[0]
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(t, dtype=F32)[:, None, None] * jnp.asarray(inv, F32)[None, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang), x[..., rot:]], -1)


def attention(cfg: dict, w, i: int, x, mode: str):
    """Layer ``i``'s attention on one row ``x [T, hidden]``."""
    precision, fault = split_mode(mode)
    t = x.shape[0]
    p = f"model.layers.{i}.self_attn."
    H, kvh, hd, vd = attention_shape(cfg, i)
    win = windowed(cfg, i)
    window = cfg["sliding_window"] if win and fault != "no_window" else 0
    rot = hd if fault == "full_rotary" else int(hd * cfg["partial_rotary_factor"])
    theta = cfg["swa_rope_theta"] if win else cfg["rope_theta"]
    vscale = 1.0 if fault == "no_value_scale" else cfg["attention_value_scale"]
    sink = w[p + "attention_sink_bias"] if has_sink(cfg, i) and fault != "no_sink" else None
    scale = hd ** -0.5
    rep = H // kvh

    qkv = by_rows(lambda r: jnp.concatenate([_mm(r, w[p + n + ".weight"], precision)
                                             for n in ("q_proj", "k_proj", "v_proj")], -1), x)
    q = _rope(qkv[:, :H * hd].reshape(t, H, hd), theta, rot)
    k = _rope(qkv[:, H * hd:(H + kvh) * hd].reshape(t, kvh, hd), theta, rot)
    v = qkv[:, (H + kvh) * hd:].reshape(t, kvh, vd)
    nb = -(-t // QUERY_BLOCK)
    B = -(-t // nb)
    qg = jnp.pad(q.reshape(t, kvh, rep, hd).transpose(1, 2, 0, 3),
                 ((0, 0), (0, 0), (0, nb * B - t), (0, 0))).reshape(kvh, rep, nb, B, hd)
    sinks = (jnp.full((H,), -jnp.inf, F32) if sink is None else sink).reshape(kvh, rep)
    at = jnp.arange(nb * B).reshape(nb, B)
    keys = jnp.arange(t)

    def group(args):
        qh, kh, vh, sh = args                     # [rep, nb, B, hd], [T, hd], [T, vd], [rep]

        def one(blk):
            qb, at_b = blk                        # [rep, B, hd], [B]
            s = _mm(qb, kh.T, precision) * scale  # [rep, B, T]
            valid = keys[None, :] <= at_b[:, None]
            if window:
                valid = valid & (keys[None, :] > at_b[:, None] - window)
            s = jnp.where(valid[None], s, -jnp.inf)
            m = jnp.maximum(s.max(-1, keepdims=True), sh[:, None, None])
            e = jnp.exp(s - m)
            den = e.sum(-1, keepdims=True) + jnp.exp(sh[:, None, None] - m)
            return _mm(e / den, vh, precision)    # [rep, B, vd]

        out = jax.lax.map(one, (qh.transpose(1, 0, 2, 3), at))     # [nb, rep, B, vd]
        return out.transpose(1, 0, 2, 3).reshape(rep, nb * B, vd)[:, :t]

    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2), sinks))   # [kvh, rep, T, vd]
    out = out.transpose(2, 0, 1, 3).reshape(t, H * vd) * vscale
    return by_rows(lambda rows: _mm(rows, w[p + "o_proj.weight"], precision), out)


def route(cfg: dict, w, p: str, h, precision: str):
    """(weights [T, k], expert ids [T, k]): sigmoid over every router output in
    float32, the k largest of ``s + bias``, weight ``scaling * s_i / (sum of the
    picked s + 1e-20)``, ``scaling`` 1 where the config states none."""
    s = jax.nn.sigmoid(_mm(h, w[p + "mlp.router"], precision))
    _, ids = jax.lax.top_k(s + w[p + "mlp.e_score_correction_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, ids, -1)
    scaling = 1.0 if cfg["routed_scaling_factor"] is None else cfg["routed_scaling_factor"]
    return scaling * picked / (picked.sum(-1, keepdims=True) + 1e-20), ids


def expert_layer(cfg: dict, w, p: str, h, precision: str):
    """The chip's part of the expert layer on ``h [T, hidden]``: a dense loop over the held routed experts."""
    first = cfg["experts_first"]

    def rows(h):
        weights, ids = route(cfg, w, p, h, precision)

        def one(acc, args):
            j, wg, wu, wd = args
            wj = jnp.sum(jnp.where(ids == first + j, weights, 0.0), -1, keepdims=True)
            return acc + wj * swiglu(wg, wu, wd, h, precision), None

        held = w[p + "mlp.gate_proj"].shape[0]
        out, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(held), w[p + "mlp.gate_proj"],
                                                       w[p + "mlp.up_proj"], w[p + "mlp.down_proj"]))
        return out

    return by_rows(rows, h)


def block(cfg: dict, w: Dict[str, jax.Array], i: int, x, mode: str, margin: bool = False):
    """One layer on one row ``x [T, hidden]``; with ``margin`` also each
    position's ``held_pick_margin`` in this layer (infinite in a dense one)."""
    precision, _ = split_mode(mode)
    p, eps = f"model.layers.{i}.", cfg["layernorm_epsilon"]
    x = x + attention(cfg, w, i, _rms(x, w[p + "input_layernorm.weight"], eps), mode)
    h = _rms(x, w[p + "post_attention_layernorm.weight"], eps)
    if not cfg["moe_layer_freq"][i]:
        out = x + by_rows(lambda r: swiglu(w[p + "mlp.gate_proj.weight"], w[p + "mlp.up_proj.weight"],
                                           w[p + "mlp.down_proj.weight"], r, precision), h)
        return (out, jnp.full(x.shape[:1], jnp.inf, F32)) if margin else out
    out = x + expert_layer(cfg, w, p, h, precision)
    return (out, by_rows(lambda r: held_pick_margin(cfg, w, p, r), h)) if margin else out


def head_logits(cfg: dict, w, x, mode: str):
    precision, _ = split_mode(mode)
    return _mm(_rms(x, w["model.norm.weight"], cfg["layernorm_epsilon"]), w["lm_head.weight"], precision)


def forward_logits(cfg: dict, w, ids, mode: str = "f32"):
    """Whole forward of one row of token ids -> [T, vocab] logits."""
    x = w["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = block(cfg, w, i, x, mode)
    return head_logits(cfg, w, x, mode)


# -- serving: the gap of each served token under the reference -------------------

def serve_reference(cfg: dict, seed: int, sequences: Sequence[np.ndarray], first_new: Sequence[int], stated_dtype,
                    control: str = "", pad_to: int = 256, undecided: float = UNDECIDED) -> dict:
    """One plain forward over each sequence (prompt + served tokens), a layer's
    weights at a time. For every served position: how far the served token's
    logit lies below the reference's best, over max|logit| there, read where
    this chip's part of every expert layer's pick is decided (``gap``; every
    position in ``gap_all``). ``control`` names one mode or several, comma
    separated (``"fp8"``, a fault of :data:`FAULTS`): for each, the same gap
    of the token that mode puts first, under the float32 reference (``controls``;
    ``control_gap`` is the first one's)."""
    modes = ["f32"] + [c for c in control.split(",") if c]
    # layers of one kind (window or full, experts or dense) run ONE compiled block, handed the weights under the
    # first such layer's names, and every sequence is padded to one length: a compile a kind and a mode
    kinds = {}
    for i in range(cfg["num_hidden_layers"]):
        kinds.setdefault((windowed(cfg, i), bool(cfg["moe_layer_freq"][i])), i)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def run_block(w, x, i, mode):
        return block(cfg, w, i, x, mode, margin=mode == "f32")

    def as_first_of_kind(w, i):
        first = kinds[(windowed(cfg, i), bool(cfg["moe_layer_freq"][i]))]
        old, new = f"model.layers.{i}.", f"model.layers.{first}."
        return {new + k[len(old):]: v for k, v in w.items()}, first

    @functools.partial(jax.jit, static_argnums=(3,))
    def gaps(w, x, nxt, mode, ref_logits):
        logits = head_logits(cfg, w, x, mode)
        base = logits if ref_logits is None else ref_logits
        tok = nxt if ref_logits is None else jnp.argmax(logits, -1)
        picked = jnp.take_along_axis(base, tok[:, None], -1)[:, 0]
        return (base.max(-1) - picked) / jnp.abs(base).max(-1), logits

    def weights(specs):
        return served_weights(cfg, specs, seed, stated_dtype)

    h, v = cfg["hidden_size"], cfg["vocab_size"]
    length = max(len(s) + (-len(s) % pad_to) for s in sequences)
    padded = [np.pad(np.asarray(s, np.int32), (0, length - len(s))) for s in sequences]
    table = weights([("model.embed_tokens.weight", (v, h), "normal")])["model.embed_tokens.weight"]
    acts = {mode: [table[jnp.asarray(p)] for p in padded] for mode in modes}
    del table
    margins = [[] for _ in sequences]
    for i in range(cfg["num_hidden_layers"]):
        w, first = as_first_of_kind(weights(layer_specs(cfg, i)), i)
        for mode in modes:
            acts[mode] = [run_block(w, x, first, mode) for x in acts[mode]]
        for j, (x, m) in enumerate(acts["f32"]):
            margins[j].append(m)
        acts["f32"] = [x for x, _ in acts["f32"]]
        jax.block_until_ready(acts)
        del w
    w = weights([("model.norm.weight", (h,), "ones"), ("lm_head.weight", (h, v), "normal")])
    out = {"gap": [], "gap_all": [], "pick_margin": [], "control_gap": [], "controls": {m: [] for m in modes[1:]}}
    served = max(len(s) - f for s, f in zip(sequences, first_new))
    served += -served % pad_to
    for j, s in enumerate(sequences):
        # position t-1 predicts token t: the served positions alone, padded to one number of rows
        lo, n = first_new[j] - 1, len(s) - first_new[j]
        rows = slice(lo, lo + served)
        take = lambda a: jnp.pad(a[rows], ((0, rows.stop - rows.start - a[rows].shape[0]),) + ((0, 0),) * (a.ndim - 1))
        nxt = take(jnp.asarray(np.roll(padded[j], -1)))
        g, ref_logits = gaps(w, take(acts["f32"][j]), nxt, "f32", None)
        margin = np.stack([np.asarray(m)[lo:lo + n] for m in margins[j]], -1)
        compared = margin.min(-1) >= undecided
        out["pick_margin"].append(margin)
        out["gap_all"].append(np.asarray(g)[:n])
        out["gap"].append(np.where(compared, out["gap_all"][-1], 0.0))
        for mode in modes[1:]:
            cg, _ = gaps(w, take(acts[mode][j]), nxt, mode, ref_logits)
            out["controls"][mode].append(np.where(compared, np.asarray(cg)[:n], 0.0))
    if len(modes) > 1:
        out["control_gap"] = out["controls"][modes[1]]
    del acts, w
    return out
