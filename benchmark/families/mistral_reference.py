"""Plain reference of the Mistral decoder: forward, next-token loss, gradients
and AdamW in straightforward ``jax.numpy``, float32, matmuls at ``highest``.

It follows ``mistralai/Mistral-7B-v0.3`` ``config.json`` and the published
block: RMSNorm -> grouped-query attention with rotate-half RoPE, causal, no
sliding window -> residual -> RMSNorm -> SwiGLU -> residual; untied output
head. It imports nothing of the program under test and takes nothing the
program made: weights come from ``--seed`` through :func:`init_leaf`, the
same specification the builder feeds the program.

``precision`` chooses the arithmetic: ``"f32"`` is the reference; ``"fp8"``
is the control (every matmul operand rounded to float8_e4m3 under a
per-tensor absmax scale: the nearest precision below the bfloat16 the
configurations state); ``"bf16"`` rounds operands to bfloat16.

Memory: the train reference walks the batch row by row inside one program and
checkpoints every block; the serve reference makes one layer's weights at a
time. Both fit beside nothing else on a 16 GB chip at the cells' sizes.
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


# -- weights from the seed ------------------------------------------------------

def layer_specs(cfg: dict, i: int) -> List[Tuple[str, Tuple[int, ...], str]]:
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    p = f"model.layers.{i}."
    return [
        (p + "input_layernorm.weight", (h,), "ones"),
        (p + "self_attn.q_proj.weight", (h, q), "normal"),
        (p + "self_attn.k_proj.weight", (h, kv), "normal"),
        (p + "self_attn.v_proj.weight", (h, kv), "normal"),
        (p + "self_attn.o_proj.weight", (q, h), "normal"),
        (p + "post_attention_layernorm.weight", (h,), "ones"),
        (p + "mlp.gate_proj.weight", (h, inter), "normal"),
        (p + "mlp.up_proj.weight", (h, inter), "normal"),
        (p + "mlp.down_proj.weight", (inter, h), "normal"),
    ]


def leaf_specs(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every weight as (name, shape, kind); matrices are [in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    specs = [("model.embed_tokens.weight", (v, h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += layer_specs(cfg, i)
    specs += [("model.norm.weight", (h,), "ones"),
              ("lm_head.weight", (h, v), "normal")]
    return specs


def seed_key(seed: int):
    """A key from any whole number up to 2**62 (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def init_leaf(key, name: str, shape, kind: str, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
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
    """Round an operand; the gradient passes straight through (a cast's own
    transpose would round the cotangent to the narrow type and lose it)."""
    if precision == "fp8":
        return x + jax.lax.stop_gradient(_fp8(x) - x)
    if precision == "bf16":
        return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(F32) - x)
    return x


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """Rotate-half RoPE on [T, heads, d], positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(q, k, v, precision: str):
    """Causal grouped-query attention of one row; one KV group at a time so
    the [rep, T, T] scores of a 4k row stay small."""
    t, nh, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(t, kvh, nh // kvh, d).transpose(1, 2, 0, 3)   # [kvh, rep, T, d]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)          # [kvh, T, d]
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def group(args):
        qi, ki, vi = args
        s = _mm(qi, ki.T, precision) * float(1.0 / np.sqrt(d))
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return _mm(p, vi, precision)                              # [rep, T, d]

    out = jax.lax.map(group, (qg, kg, vg))                        # [kvh, rep, T, d]
    return out.transpose(2, 0, 1, 3).reshape(t, nh * d)


def block(cfg: dict, w: Dict[str, jax.Array], i: int, x, precision: str):
    """One decoder layer on one row ``x`` [T, hidden]."""
    p = f"model.layers.{i}."
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, w[p + "input_layernorm.weight"], eps)
    q = _mm(h, w[p + "self_attn.q_proj.weight"], precision).reshape(-1, nh, d)
    k = _mm(h, w[p + "self_attn.k_proj.weight"], precision).reshape(-1, kvh, d)
    v = _mm(h, w[p + "self_attn.v_proj.weight"], precision).reshape(-1, kvh, d)
    a = _attention(_rope(q, theta), _rope(k, theta), v, precision)
    x = x + _mm(a, w[p + "self_attn.o_proj.weight"], precision)
    h = _rms(x, w[p + "post_attention_layernorm.weight"], eps)
    g = _mm(h, w[p + "mlp.gate_proj.weight"], precision)
    u = _mm(h, w[p + "mlp.up_proj.weight"], precision)
    return x + _mm(jax.nn.silu(g) * u, w[p + "mlp.down_proj.weight"], precision)


def head_logits(cfg: dict, w, x, precision: str):
    return _mm(_rms(x, w["model.norm.weight"], cfg["rms_norm_eps"]),
               w["lm_head.weight"], precision)


def forward_logits(cfg: dict, w, ids, precision: str = "f32"):
    """Whole forward of one row of token ids -> [T, vocab] logits."""
    x = w["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = block(cfg, w, i, x, precision)
    return head_logits(cfg, w, x, precision)


# -- training: loss, gradients, AdamW -------------------------------------------

def _row_nll_sum(cfg, w, ids, precision):
    """Sum over a row's T-1 predicted positions of the next-token NLL."""
    x = w["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda w_, x_, i_=i: block(cfg, w_, i_, x_, precision))(w, x)
    logits = jax.checkpoint(lambda w_, x_: head_logits(cfg, w_, x_, precision))(w, x)
    lse = jax.nn.logsumexp(logits[:-1], -1)
    picked = jnp.take_along_axis(logits[:-1], ids[1:, None], -1)[:, 0]
    return jnp.sum(lse - picked)


def loss_and_grads(cfg: dict, w, batch, precision: str = "f32", rows=None):
    """Mean next-token NLL over the batch's rows (``rows``: the subset that
    counts; a fault leaves half out) and its gradient, row by row."""
    rows = range(batch.shape[0]) if rows is None else rows
    n = len(rows) * (batch.shape[1] - 1)
    grad_fn = jax.value_and_grad(lambda w_, ids: _row_nll_sum(cfg, w_, ids, precision) / n)
    loss, grads = None, None
    for r in rows:
        l, g = grad_fn(w, batch[r])
        loss = l if loss is None else loss + l
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    return loss, grads


def adamw(w, g, m, v, step: int, hp: dict):
    """Decoupled-weight-decay Adam as published (Loshchilov & Hutter), bias
    corrected; ``step`` counts from 1 (a traced scalar: one program for every step)."""
    b1, b2, eps, lr, wd = hp["beta1"], hp["beta2"], hp["epsilon"], hp["lr"], hp["weight_decay"]

    def one(p, g_, m_, v_):
        p = p * (1.0 - lr * wd)
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * g_ * g_
        mh, vh = m_ / (1 - b1 ** step), v_ / (1 - b2 ** step)
        return p - lr * mh / (jnp.sqrt(vh) + eps), m_, v_

    out = {k: one(w[k], g[k], m[k], v[k]) for k in w}
    return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))) for k, a in tree.items()}


def train_step_fn(cfg: dict, hp: dict, precision: str, rows: tuple):
    """(weights, m, v, batch, step) -> the next three, the loss and every
    leaf's gradient norm; one program, state donated."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step_fn(w, m, v, batch, step):
        loss, g = loss_and_grads(cfg, w, batch, precision, rows)
        gn = _norms(g)
        w, m, v = adamw(w, g, m, v, step, hp)
        return w, m, v, loss, gn

    return step_fn


def train_reference(cfg: dict, seed: int, batches: Sequence[np.ndarray], hp: dict,
                    stated_dtype, precision: str = "f32",
                    half_batch: bool = False) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's weights. Returns
    each step's loss, every leaf's first-gradient norm and the norm of every
    leaf's change after the last step. ``half_batch`` plants the fault "half
    of the batch left out, the mean taken over the rest"."""
    specs = leaf_specs(cfg)
    n_rows = batches[0].shape[0]
    rows = list(range(n_rows // 2)) if half_batch else list(range(n_rows))

    step_fn = train_step_fn(cfg, hp, precision, tuple(rows))

    @jax.jit
    def change(w, key):
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            w[n] - init_leaf(key, n, s, k, stated_dtype).astype(F32))))
            for n, s, k in specs}

    w = served_weights(specs, seed, stated_dtype)
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    losses, grad_norm = [], None
    for i, b in enumerate(batches, 1):
        w, m, v, loss, gn = step_fn(w, m, v, jnp.asarray(b, jnp.int32), jnp.float32(i))
        losses.append(float(loss))
        if i == 1:
            grad_norm = {k: float(x) for k, x in gn.items()}
    change_norm = {k: float(x) for k, x in change(w, seed_key(seed)).items()}
    del w, m, v
    return {"loss": losses, "grad_norm": grad_norm, "change_norm": change_norm}


# -- serving: the gap of each served token under the reference -------------------

def serve_reference(cfg: dict, seed: int, sequences: Sequence[np.ndarray],
                    first_new: Sequence[int], stated_dtype,
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
    acts = {mode: [embed(table["model.embed_tokens.weight"], jnp.asarray(p))
                   for p in padded] for mode in modes}
    del table
    for i in range(cfg["num_hidden_layers"]):
        w = weights(layer_specs(cfg, i))
        for mode in modes:
            acts[mode] = [run_block(w, x, i, mode) for x in acts[mode]]
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
