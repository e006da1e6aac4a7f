"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a user
calls, at the full width of the 254M Llama ``bench.py`` trains (depth and
weights as there: 12 layers, random weights from seed 0):

* **train** — ``jit.train.TrainStep`` + ``AdamW(multi_precision=True)``,
  five plain ``step(ids, labels)`` calls on one repeated batch; losses finite
  and falling; the step's program holds the Pallas flash forward and both
  backward kernels.
* **serve** — ``inference.serving.ServingEngine`` with its defaults, then
  with ``kv_quant="int8"``: eight greedy requests (two share a prefix),
  every future resolves, no decode failure, a prefix hit, and every token
  passes the margin rule below against a plain full-sequence forward of
  the same weights.
* **kernels** — every Pallas kernel in ``ops/kernels/`` compiled by Mosaic at
  these models' shapes and compared with its ``jax.numpy`` reference.
* **four chips** — when ``jax.device_count() >= 4``: ``ShardedTrainStep``
  under ``train_plan("dp2mp2")`` against the one-chip losses, tensor-parallel
  decode under ``decode_plan("mp4")``, and per-device shard and memory
  evidence. Printed as skipped, with the count, otherwise.

It exits non-zero, printing no result, unless ``jax.devices()[0].platform``
is ``"tpu"`` and every leg passes; no leg's exception is caught. The last
two lines of stdout are JSON objects: first the report (versions, every
leg, compile seconds, the compile cache with hits and misses, the kernel
table, peak HBM), then — last — the verdict the driver reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it. Any rate the report holds is a smoke
reading (compilation and a cold device included), not a benchmark number.

**The margin rule** (tokens against the reference). bf16 arithmetic in a
different order flips an argmax wherever two logits nearly tie, and random
weights tie often, so token-exact equality is not the test. Each request's
own output (prompt + generated tokens) is fed ONCE through the plain forward
(XLA attention, no KV cache), which scores every generated position given
the engine's own preceding tokens — one flip cannot cascade. The engine's
token passes when the reference ranks it within ``margin * max|logit|`` of
its own top logit for that position: ``2**-5`` (eight bf16 ulps of the
largest logit) for bf16 KV, ``2**-4`` for int8 KV pages, whose absmax codes
add a rounding step of about the same size again. A wrong page, position
or head is off by the width of the logit distribution, tens of margins.

**Kernel tolerance.** Each kernel's output is compared in float32 with the
repo's ``jax.numpy`` reference run on float32 copies of the same inputs;
it passes when the max-abs error is at most ``2**-6 * max|reference|``
(four bf16 ulps of the largest value). The table in the JSON gives both.

``run_legs(size, interpret)`` is the whole check as a function: the tier-1
test calls it at ``TINY`` width on the CPU with ``interpret=True`` (kernels
under the Pallas interpreter, chip-only assertions reported, not required).
"""

from __future__ import annotations

import contextlib
import gc
import json
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

MARGIN_BF16 = 2.0 ** -5
MARGIN_INT8_KV = 2.0 ** -4
KERNEL_TOL = 2.0 ** -6


@dataclass(frozen=True)
class Size:
    """Every shape the legs use, so the same code runs at two widths."""

    model: Dict[str, object]          # LlamaConfig kwargs
    train_batch: int
    train_seq: int
    train_steps: int
    prompt_lens: Tuple[int, ...]      # the last two share ``prefix_len`` ids
    prefix_len: int
    new_tokens: int
    flash: Tuple[int, int, int, int]  # batch, seq, heads, head_dim
    varlen: Tuple[int, int, int]      # packed tokens, heads, head_dim
    moe: Dict[str, int]               # experts, d, h, tokens, topk, capacity
    latent: Dict[str, int]            # slots, page, heads, rank, rope, pages_per_slot
    gqa: Dict[str, int]               # slots, page, heads, kv_heads, head_dim, pages_per_slot
    prefill: Dict[str, int]           # heads, nope, rope, v, rank, queries, start


# bench.py's primary config (254M) and the serving/kernel shapes it implies
FULL = Size(
    model=dict(vocab_size=32000, hidden_size=1024, intermediate_size=4096,
               num_hidden_layers=12, num_attention_heads=16,
               num_key_value_heads=8, max_position_embeddings=2048,
               dtype="bfloat16"),
    train_batch=8, train_seq=1024, train_steps=5,
    prompt_lens=(16, 48, 100, 300, 400, 512, 160, 200), prefix_len=128,
    new_tokens=32,
    flash=(8, 1024, 16, 64),
    varlen=(8192, 16, 64),
    # bench.py's MoE config: 8192 tokens top-2 over 16 experts, capacity
    # factor 1.25 -> 8192 * 2 * 1.25 / 16 = 1280 slots per expert
    moe=dict(experts=16, d=1024, h=768, tokens=8192, topk=2, capacity=1280),
    # serve-agent-saturated's decode attention: 128 slots of 64 heads over
    # the 512 + 64 latent row, a 4,096-token table of 64-token pages
    latent=dict(slots=128, page=64, heads=64, rank=512, rope=64,
                pages_per_slot=64),
    # serve-docqa-steady's and serve-chat-saturated's decode attention: 32
    # slots, 32 query over 8 kv heads of 128, the same table
    gqa=dict(slots=32, page=64, heads=32, kv_heads=8, head_dim=128,
             pages_per_slot=64),
    # serve-longdoc-saturated's whole-prompt attention at a tenth of its
    # length: 64 heads, 128 + 64 wide scores, 128-wide values, 1,500
    # queries behind 1,000 cached rows (blocks of 512 with both ragged ends)
    prefill=dict(heads=64, nope=128, rope=64, v=128, rank=512, queries=1500,
                 start=1000),
)

# the tier-1 width: same code, seconds on the CPU
TINY = Size(
    model=dict(vocab_size=128, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=8,
               num_key_value_heads=4, max_position_embeddings=256,
               dtype="bfloat16"),
    train_batch=4, train_seq=32, train_steps=5,
    prompt_lens=(5, 40, 70, 80), prefix_len=64, new_tokens=6,
    flash=(1, 32, 2, 64),
    varlen=(32, 2, 64),
    moe=dict(experts=4, d=16, h=24, tokens=48, topk=2, capacity=8),
    latent=dict(slots=3, page=8, heads=4, rank=16, rope=8, pages_per_slot=4),
    gqa=dict(slots=4, page=8, heads=4, kv_heads=2, head_dim=128,
             pages_per_slot=4),
    prefill=dict(heads=4, nope=8, rope=8, v=8, rank=16, queries=21, start=9),
)


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _lm_loss(m, ids, labels):
    return m(ids, labels=labels)


def _build_model(size: Size):
    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(**size.model))


def _train_batch(size: Size):
    import numpy as np

    return np.random.default_rng(0).integers(
        0, size.model["vocab_size"],
        (size.train_batch, size.train_seq)).astype(np.int32)


def _lower_like(jitted, *args):
    """Lower ``jitted`` for arguments shaped and placed like ``args`` (which
    may since have been donated: only shape, dtype and sharding are read).
    One-device arrays stay unplaced, as the plain call lowers them: a
    spelled-out single-device sharding makes a different module, and the
    compile below would miss the cache the call just filled."""
    import jax

    def aval(x):
        spread = len(x.sharding.device_set) > 1
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=x.sharding if spread else None)

    return jitted.lower(*jax.tree_util.tree_map(aval, args))


def _mosaic_calls(lowered) -> int:
    """Mosaic custom calls that survived into the executable."""
    return lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"')


# -- train ---------------------------------------------------------------------

def train_leg(size: Size, interpret: bool, model) -> Dict[str, object]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.optimizer import AdamW

    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                multi_precision=True)
    step = TrainStep(model, opt, _lm_loss)
    ids = _train_batch(size)
    losses, walls = [], []
    for _ in range(size.train_steps):
        t0 = time.perf_counter()
        # the host scalar ends the step: it cannot exist before the device
        # has finished
        losses.append(float(step(ids, ids).numpy()))
        walls.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss in {losses}")
    if not all(b < a for a, b in zip(losses, losses[1:])):
        raise AssertionError(
            f"train: loss not falling on a repeated batch: {losses}")

    # what program ran: the kernels by name in what jax handed to XLA, and
    # the custom calls that survived into the executable
    lowered = _lower_like(
        step._step, step.params, step.opt_state,
        (jnp.asarray(ids), jnp.asarray(ids)), jax.random.PRNGKey(0),
        jnp.float32(0))
    text = lowered.as_text()
    kernels = {k: text.count(k)
               for k in ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")}
    in_executable = _mosaic_calls(lowered)
    layers = int(size.model["num_hidden_layers"])
    if not interpret:
        if any(n != layers for n in kernels.values()) \
                or in_executable != 3 * layers:
            raise AssertionError(
                "train: the step is not running the Pallas flash forward "
                f"and both backward kernels once per layer ({layers}): "
                f"lowered {kernels}, tpu_custom_call in the executable "
                f"{in_executable} — flash_attention took its XLA route")
    steady = sorted(walls[1:])[len(walls[1:]) // 2]
    return {
        "status": "ok", "losses": [round(x, 4) for x in losses],
        "first_call_s": round(walls[0], 2), "step_s_smoke": round(steady, 4),
        "tokens_per_s_smoke": round(
            size.train_batch * size.train_seq / steady, 1),
        "flash_kernels_lowered": kernels,
        "tpu_custom_calls_in_executable": in_executable,
    }


# -- serve ---------------------------------------------------------------------

def _prompts(size: Size) -> Tuple[List, List[Optional[int]]]:
    """Random prompts of the configured lengths; the last two start with the
    same ``prefix_len`` ids and declare them as their shared prefix."""
    import numpy as np

    rng = np.random.default_rng(1)
    vocab = size.model["vocab_size"]
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
               for n in size.prompt_lens]
    prompts[-1][:size.prefix_len] = prompts[-2][:size.prefix_len]
    prefix = [None] * (len(prompts) - 2) + [size.prefix_len] * 2
    return prompts, prefix


class _Reference:
    """The plain full-sequence forward of the model's own weights, jitted
    once at a fixed [rows, length] so every engine is judged by one
    program. Attention takes the XLA route: the reference shares no kernel
    with anything it judges."""

    def __init__(self, model, size: Size):
        import jax
        import jax.numpy as jnp

        from paddlepaddle_tpu.core import autograd as _ag
        from paddlepaddle_tpu.core.dispatch import unwrap

        self.rows = len(size.prompt_lens)
        self.length = max(size.prompt_lens) + size.new_tokens
        self.params = model.functional_state()

        def score(params, ids):
            with _ag.no_grad(), model.bind_state(params):
                logits = unwrap(model(ids)).astype(jnp.float32)
            pred, nxt = logits[:, :-1], ids[:, 1:]
            chosen = jnp.take_along_axis(pred, nxt[..., None], -1)[..., 0]
            return (pred.max(-1) - chosen, jnp.abs(pred).max(-1),
                    pred.argmax(-1) == nxt)

        self._score = jax.jit(score)

    def check(self, what: str, prompts, outs, margin: float) -> Dict[str, object]:
        import numpy as np

        from paddlepaddle_tpu.core.flags import flag_value, set_flags

        ids = np.zeros((self.rows, self.length), np.int32)
        for i, o in enumerate(outs):
            ids[i, :len(o)] = o
        was = flag_value("use_pallas_kernels")
        set_flags({"use_pallas_kernels": False})
        try:
            gap, scale, exact = (np.asarray(a)
                                 for a in self._score(self.params, ids))
        finally:
            set_flags({"use_pallas_kernels": was})
        worst, n_tok, n_exact = 0.0, 0, 0
        for i, (p, o) in enumerate(zip(prompts, outs)):
            if not np.array_equal(o[:len(p)], p):
                raise AssertionError(f"{what}: request {i} lost its prompt")
            # position t-1 predicts token t
            sl = slice(len(p) - 1, len(o) - 1)
            rel = gap[i, sl] / np.maximum(scale[i, sl], 1e-30)
            worst = max(worst, float(rel.max()))
            n_tok += rel.size
            n_exact += int(exact[i, sl].sum())
            if rel.max() > margin:
                t = int(rel.argmax())
                raise AssertionError(
                    f"{what}: request {i} token {t} sits "
                    f"{rel.max():.4f} * max|logit| below the reference's "
                    f"top logit (margin {margin:.4f}): not a near-tie")
        return {"tokens": n_tok, "argmax_exact": n_exact,
                "worst_gap_over_max_logit": round(worst, 5),
                "margin": margin}


def _serve_once(what: str, model, size: Size, ref: _Reference,
                margin: float, **engine_kw):
    """Serve the eight requests through one engine and judge what came
    back; returns (report, outputs)."""
    import numpy as np

    from paddlepaddle_tpu.inference.serving import ServingEngine

    prompts, prefix = _prompts(size)
    eng = ServingEngine(model, **engine_kw)
    t0 = time.perf_counter()
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=size.new_tokens,
                              temperature=0.0, prefix_len=n)
                   for p, n in zip(prompts, prefix)]
        outs = [np.asarray(f.result(timeout=900)) for f in futures]
        health = eng.health()
    finally:
        eng.stop()
    wall = time.perf_counter() - t0
    want = [len(p) + size.new_tokens for p in prompts]
    if [len(o) for o in outs] != want:
        raise AssertionError(f"{what}: output lengths {[len(o) for o in outs]}"
                             f" != {want}")
    if health["stats"]["decode_failures"] or health["stats"]["batches_failed"]:
        raise AssertionError(f"{what}: decode failures: {health['stats']}")
    hits = health["kv"]["prefix"]["hits"]
    if hits < 1:
        raise AssertionError(f"{what}: no prefix-cache hit: {health['kv']}")
    out = {"status": "ok", "prefix_hits": hits,
           "kv_quant": health["kv"]["kv_quant"],
           "wall_s_with_compile": round(wall, 2)}
    out.update(ref.check(what, prompts, outs, margin))
    return out, outs


def serve_leg(size: Size, model):
    """The two engines in turn; returns (report, the reference)."""
    ref = _Reference(model, size)
    legs = {}
    legs["default"], _ = _serve_once(
        "serve", model, size, ref, MARGIN_BF16)
    legs["int8_kv"], _ = _serve_once(
        "serve int8 kv", model, size, ref, MARGIN_INT8_KV,
        kv_quant="int8")
    return {"status": "ok", **legs}, ref


# -- kernels -------------------------------------------------------------------

def _interpreted(interpret: bool):
    """The flash kernels take no ``interpret`` argument (on the chip there
    is nothing to choose); off the chip the test forces the interpreter
    around them."""
    if not interpret:
        return contextlib.nullcontext()
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.force_tpu_interpret_mode()


def _compare(name: str, got, want, interpret: bool, fn=None, args=()) -> Dict[str, object]:
    """One row of the kernel table: max-abs error over every output, the
    reference's own scale, and the verdict under KERNEL_TOL."""
    import jax
    import numpy as np

    got = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(got)]
    want = [np.asarray(w, np.float32) for w in jax.tree_util.tree_leaves(want)]
    err = max(float(np.abs(g - w).max()) for g, w in zip(got, want))
    scale = max(float(np.abs(w).max()) for w in want)
    if not all(np.isfinite(g).all() for g in got):
        raise AssertionError(f"kernel {name}: non-finite output")
    if err > KERNEL_TOL * scale:
        raise AssertionError(
            f"kernel {name}: max-abs error {err:.5g} exceeds "
            f"{KERNEL_TOL:.4f} * max|reference| = {KERNEL_TOL * scale:.5g}")
    return {"max_abs_err": float(f"{err:.4g}"),
            "ref_max": float(f"{scale:.4g}"),
            "mode": "interpret" if interpret else "mosaic"}


def _mosaic(name: str, fn, interpret: bool):
    """Run ``fn`` jitted; on the chip, refuse a program without a Mosaic
    call."""
    import jax

    def run(*args):
        lowered = jax.jit(fn).lower(*args)
        if not interpret and "tpu_custom_call" not in lowered.as_text():
            raise AssertionError(
                f"kernel {name}: no tpu_custom_call in its program")
        return lowered.compile()(*args)

    return run


def _f32(*xs):
    import jax.numpy as jnp

    return tuple(x.astype(jnp.float32) for x in xs)


def _kernel_flash(size: Size, interpret: bool) -> Dict[str, object]:
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.ops.kernels import flash_attention as fa

    b, s, h, d = size.flash
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v, g = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
                  for kk in ks)
    scale = d ** -0.5

    def route(use_pallas):
        def f(q, k, v, g):
            out, vjp = jax.vjp(
                lambda *a: fa._flash_core(*a, True, scale, use_pallas),
                q, k, v)
            return (out,) + vjp(g.astype(out.dtype))
        return f

    with _interpreted(interpret):
        got = _mosaic("flash", route(True), interpret)(q, k, v, g)
    want = jax.jit(route(False))(*_f32(q, k, v, g))
    return _compare("flash fwd+bwd", got, want, interpret)


def _kernel_varlen(size: Size, interpret: bool) -> Dict[str, object]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlepaddle_tpu.ops.kernels import flash_varlen as fv

    total, h, d = size.varlen
    # eight ragged documents packed into one row, the tail left as padding
    cuts = np.sort(np.random.default_rng(3).choice(
        np.arange(1, total * 15 // 16), 8, replace=False))
    cu = jnp.asarray(np.concatenate([[0], cuts]), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, g = (jax.random.normal(kk, (total, h, d), jnp.bfloat16)
                  for kk in ks)
    scale = d ** -0.5

    def kernel(q, k, v, g):
        t = lambda x: jnp.transpose(x, (1, 0, 2))        # noqa: E731
        out, vjp = jax.vjp(
            lambda *a: fv._varlen_core(*a, cu, cu, True, scale),
            t(q), t(k), t(v))
        return tuple(t(x) for x in (out,) + vjp(t(g)))

    def reference(q, k, v, g):
        # a few heads at a time: the dense [total, total] mask path keeps
        # float32 logits per head
        outs = []
        for lo in range(0, h, 4):
            sl = slice(lo, lo + 4)
            out, vjp = jax.vjp(
                lambda *a: fv._varlen_xla(*a, cu, cu, True, scale),
                q[:, sl], k[:, sl], v[:, sl])
            outs.append((out,) + vjp(g[:, sl]))
        return tuple(jnp.concatenate(x, axis=1) for x in zip(*outs))

    with _interpreted(interpret):
        got = _mosaic("flash_varlen", kernel, interpret)(q, k, v, g)
    want = jax.jit(reference)(*_f32(q, k, v, g))
    return _compare("flash_varlen fwd+bwd", got, want, interpret)


def _scattered_table(rng, extents, P: int, ps: int):
    """A page table ``[S, P]``: the pages that hold each slot's ``extents``
    tokens scattered over a pool of ``S * P + 1`` pages, the rest the null
    page 0."""
    import numpy as np

    S = len(extents)
    table = np.zeros((S, P), np.int32)
    perm = rng.permutation(np.arange(1, S * P + 1))
    for s in range(S):
        n = -(-int(extents[s]) // ps)
        table[s, :n] = perm[s * P:s * P + n]
    return table


def _kernel_paged_latent(size: Size, interpret: bool) -> Dict[str, object]:
    """The absorbed-latent decode kernel against the gathered view it takes
    the place of (``decode_engine._attend_view_latent`` over the whole
    table, in float32), on ragged lengths with both ends of the walk."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlepaddle_tpu.inference import decode_engine as de
    from paddlepaddle_tpu.ops.kernels.paged_latent_attention import \
        paged_latent_attention

    p = size.latent
    S, ps, H, rank, rope, P = (p["slots"], p["page"], p["heads"], p["rank"],
                               p["rope"], p["pages_per_slot"])
    n_pages = S * P + 1                      # page 0 is the null page
    rng = np.random.default_rng(6)
    lens = rng.integers(1, P * ps - 1, (S,)).astype(np.int32)
    lens[0], lens[-1] = 0, P * ps - 1        # one token; the table filled
    table = _scattered_table(rng, lens + 1, P, ps)
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    q_abs = jax.random.normal(ks[0], (S, 1, H, rank), bf)
    q_rope = jax.random.normal(ks[1], (S, 1, H, rope), bf)
    c_new = jax.random.normal(ks[2], (S, 1, rank), bf)
    r_new = jax.random.normal(ks[3], (S, 1, rope), bf)
    c_pool = jax.random.normal(ks[4], (n_pages, ps, rank), bf)
    r_pool = jax.random.normal(ks[5], (n_pages, ps, rope), bf)
    scale = (rank + rope) ** -0.5
    table, lens = jnp.asarray(table), jnp.asarray(lens)

    got = _mosaic("paged_latent_attention", lambda qa, qr, cn, rn, cp, rp:
                  paged_latent_attention(qa[:, 0], qr[:, 0], cn[:, 0],
                                         rn[:, 0], cp, rp, table, lens,
                                         scale=scale, interpret=interpret),
                  interpret)(q_abs, q_rope, c_new, r_new, c_pool, r_pool)
    want = jax.jit(lambda *a: de._attend_view_latent(
        P, ps, scale, *a, table, lens)[:, 0])(
            *_f32(q_abs, q_rope, c_new, r_new, c_pool, r_pool))
    row = _compare("paged_latent_attention", got, want, interpret)
    row["live_tokens"] = int(lens.sum()) + S
    row["table_tokens"] = S * P * ps
    return row


def _kernel_latent_prefill(size: Size, interpret: bool) -> Dict[str, object]:
    """The long prefill's attention kernel against the one-piece expanded
    form it takes over from beyond the bound on scores
    (``latent_attention._expanded_attention``, in float32): queries behind
    a cached prefix, lengths that are no multiple of the block."""
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.models import latent_attention as la

    p = size.prefill
    H, nope, rope, vd, rank, s, start = (p["heads"], p["nope"], p["rope"],
                                         p["v"], p["rank"], p["queries"],
                                         p["start"])
    L = start + s
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    q_nope = jax.random.normal(ks[0], (1, s, H, nope), bf)
    q_rope = jax.random.normal(ks[1], (1, s, H, rope), bf)
    c = jax.random.normal(ks[2], (1, L, rank), bf)
    k_rope = jax.random.normal(ks[3], (1, L, rope), bf)
    w = jax.random.normal(ks[4], (rank, H * (nope + vd)), bf) * rank ** -0.5
    pos = start + jnp.arange(s, dtype=jnp.int32)[None]
    scale = (nope + rope) ** -0.5
    got = _mosaic("latent_prefill_attention", lambda *a: la._long_attention(
        *a, pos, nope, scale), interpret)(q_nope, q_rope, c, k_rope, w)
    want = jax.jit(lambda *a: la._expanded_attention(*a, pos, nope, scale))(
        *_f32(q_nope, q_rope, c, k_rope, w))
    row = _compare("latent_prefill_attention", got, want, interpret)
    row["queries"], row["keys"] = s, L
    return row


def _kernel_paged_gqa(size: Size, interpret: bool) -> Dict[str, object]:
    """The GQA decode kernel against the gathered view it takes the place of
    (``decode_engine._attend_view`` over the whole table, in float32), on
    ragged lengths with both ends of the walk and idle slots between."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlepaddle_tpu.inference import decode_engine as de
    from paddlepaddle_tpu.ops.kernels.paged_gqa_attention import \
        paged_gqa_attention

    p = size.gqa
    S, ps, H, kvh, hd, P = (p["slots"], p["page"], p["heads"], p["kv_heads"],
                            p["head_dim"], p["pages_per_slot"])
    n_pages = S * P + 1                      # page 0 is the null page
    rng = np.random.default_rng(7)
    lens = rng.integers(1, P * ps - 1, (S,)).astype(np.int32)
    lens[0], lens[-1] = 0, P * ps - 1        # one token; the table filled
    lens[1:-1:3] = 0                         # idle slots: nothing to walk
    table = _scattered_table(rng, lens + 1, P, ps)
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (S, 1, H, hd), bf)
    k_new = jax.random.normal(ks[1], (S, 1, kvh, hd), bf)
    v_new = jax.random.normal(ks[2], (S, 1, kvh, hd), bf)
    k_pool = jax.random.normal(ks[3], (n_pages, ps, kvh, hd), bf)
    v_pool = jax.random.normal(ks[4], (n_pages, ps, kvh, hd), bf)
    scale = hd ** -0.5
    table, lens = jnp.asarray(table), jnp.asarray(lens)

    got = _mosaic("paged_gqa_attention", lambda q, kn, vn, kp, vp:
                  paged_gqa_attention(q[:, 0], kn[:, 0], vn[:, 0], kp, vp,
                                      table, lens, scale=scale,
                                      interpret=interpret),
                  interpret)(q, k_new, v_new, k_pool, v_pool)
    want = jax.jit(lambda *a: de._attend_view(
        P, ps, H // kvh, scale, *a, table, lens)[:, 0])(
            *_f32(q, k_new, v_new, k_pool, v_pool))
    row = _compare("paged_gqa_attention", got, want, interpret)
    row["live_tokens"] = int(lens.sum()) + S
    row["table_tokens"] = S * P * ps
    return row


def _kernel_gather_gemm(size: Size, interpret: bool) -> Dict[str, object]:
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.ops.kernels.gather_gemm import gather_gemm_ffn
    from paddlepaddle_tpu.parallel import moe

    m = size.moe
    E, d, h, T, topk, C = (m["experts"], m["d"], m["h"], m["tokens"],
                           m["topk"], m["capacity"])
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.bfloat16)
    logits = jax.random.normal(ks[1], (T, E), jnp.float32)
    wg = jax.random.normal(ks[2], (E, d, h), jnp.bfloat16) * d ** -0.5
    wu = jax.random.normal(ks[3], (E, d, h), jnp.bfloat16) * d ** -0.5
    wd = jax.random.normal(ks[4], (E, h, d), jnp.bfloat16) * h ** -0.5
    # the capacity path's own routing: ragged loads, dropped tokens past
    # capacity, unfilled (sentinel) slots
    _, _, slots_of_entry, slot_valid, slot_entry = jax.jit(
        lambda lg: moe._capacity_slot_maps(lg, topk, E, C, T))(logits)
    slot_token = jnp.where(slot_valid, slot_entry % T, T).astype(jnp.int32)

    got = _mosaic("gather_gemm", lambda x, st, wg, wu, wd: gather_gemm_ffn(
        x, st, jnp.concatenate([wg, wu], axis=-1), wd, capacity=C,
        interpret=interpret), interpret)(x, slot_token, wg, wu, wd)
    want = jax.jit(lambda x, wg, wu, wd: moe._reference_expert_ffn(
        x, slot_entry, slot_valid, slots_of_entry, wg, wu, wd, topk))(
            *_f32(x, wg, wu, wd))
    row = _compare("gather_gemm_ffn", got, want, interpret)
    row["valid_slots"] = int(slot_valid.sum())
    row["slots"] = int(E * C)
    return row


def kernels_leg(size: Size, interpret: bool) -> Dict[str, object]:
    table = {"flash": _kernel_flash(size, interpret),
             "flash_varlen": _kernel_varlen(size, interpret)}
    table["paged_latent_attention"] = _kernel_paged_latent(size, interpret)
    table["paged_gqa_attention"] = _kernel_paged_gqa(size, interpret)
    table["latent_prefill_attention"] = _kernel_latent_prefill(size, interpret)
    table["gather_gemm"] = _kernel_gather_gemm(size, interpret)
    return {"status": "ok", "tolerance": f"{KERNEL_TOL} * max|reference|",
            "table": table}


# -- four chips ----------------------------------------------------------------

def _device_evidence(what: str, arrays, tp: int, interpret: bool) -> Dict[str, object]:
    """Every device must hold its share: one model-axis-sharded weight is
    ``1/tp`` of its size on each device, the state bytes resident per
    device agree, and — on the chip, where allocators report — every
    device's ``bytes_in_use`` covers its share, the devices that ran
    nothing but this leg agreeing within 2x (the first device also carries
    what the one-chip legs left behind)."""
    import jax

    leaves = [a for a in jax.tree_util.tree_leaves(arrays)
              if isinstance(a, jax.Array)]
    devices = sorted({d for a in leaves for d in a.sharding.device_set},
                     key=lambda d: d.id)
    if len(devices) < 4:
        raise AssertionError(f"{what}: state lives on {len(devices)} device(s)")
    sharded = next(a for a in leaves
                   if not a.sharding.is_fully_replicated and a.ndim == 2)
    shard_sizes = [int(s.data.size) for s in sharded.addressable_shards]
    if len(shard_sizes) != len(devices) \
            or any(n * tp != sharded.size for n in shard_sizes):
        raise AssertionError(
            f"{what}: a model-axis weight {sharded.shape} has shards of "
            f"{shard_sizes} elements on {len(devices)} devices, "
            f"not 1/{tp} each")
    held = {d.id: 0 for d in devices}
    for a in leaves:
        for s in a.addressable_shards:
            held[s.device.id] += s.data.size * a.dtype.itemsize
    if max(held.values()) > 1.1 * min(held.values()):
        raise AssertionError(f"{what}: uneven bytes per device: {held}")
    out = {"devices": [d.id for d in devices],
           "sharded_weight": list(sharded.shape),
           "shard_elements": shard_sizes, "state_bytes_per_device": held}
    if not interpret:
        in_use = {d.id: int(d.memory_stats()["bytes_in_use"])
                  for d in devices}
        out["bytes_in_use"] = in_use
        others = [n for i, n in in_use.items() if i != jax.devices()[0].id]
        if any(in_use[i] < held[i] for i in held) \
                or max(others) > 2 * min(others):
            raise AssertionError(
                f"{what}: allocator bytes_in_use {in_use} do not show every "
                f"device holding its share {held}")
    return out


def four_chip_leg(size: Size, interpret: bool, model, one_chip_losses,
                  ref: _Reference) -> Dict[str, object]:
    import jax
    import numpy as np

    from paddlepaddle_tpu.distributed.shard_plan import decode_plan, train_plan
    from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
    from paddlepaddle_tpu.inference.serving import GenerationRequest
    from paddlepaddle_tpu.optimizer import AdamW
    from paddlepaddle_tpu.parallel import ShardedTrainStep

    n = jax.device_count()
    if n < 4:
        _say(f"four chips: skipped, jax.device_count() == {n}")
        return {"status": "skipped", "device_count": n}

    # train: same seed, same batch, same optimizer as the one-chip leg
    sharded_model = _build_model(size)
    opt = AdamW(learning_rate=1e-4, parameters=sharded_model.parameters(),
                multi_precision=True)
    step = ShardedTrainStep(sharded_model, opt, _lm_loss,
                            plan=train_plan("dp2mp2"))
    ids = _train_batch(size)
    losses = [float(step(ids, ids).numpy()) for _ in range(3)]
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, one_chip_losses)]
    # bf16 partial sums meet in another order on a mesh; the loss itself is
    # a float32 mean over batch*seq tokens
    if not all(np.isfinite(losses)) or max(rel) > 1e-2:
        raise AssertionError(
            f"four chips: dp2mp2 losses {losses} vs one chip "
            f"{one_chip_losses[:3]} (relative {rel}, bound 1e-2)")
    # the mesh must not cost the step its kernels: GSPMD cannot partition a
    # Mosaic call by itself, so the step declares its mesh and the flash
    # kernels run under shard_map over batch and heads
    batch = jax.device_put(ids, step._batch_sharding(ids))
    with step.mesh:
        in_executable = _mosaic_calls(_lower_like(
            step._step, step.params, step.buffers, step.opt_state,
            (batch, batch), jax.random.PRNGKey(0), jax.numpy.float32(0)))
    layers = int(size.model["num_hidden_layers"])
    if not interpret and in_executable != 3 * layers:
        raise AssertionError(
            f"four chips: the dp2mp2 step holds {in_executable} Mosaic calls"
            f", not the flash forward and two backward kernels per layer "
            f"({3 * layers})")
    train_ev = _device_evidence("dp2mp2 train", (step.params, step.opt_state),
                                tp=2, interpret=interpret)
    train_ev["tpu_custom_calls_in_executable"] = in_executable
    del step, opt, sharded_model
    gc.collect()

    # serve: the first four requests, tensor-parallel over all four chips
    prompts, _ = _prompts(size)
    prompts = prompts[:4]
    eng = BatchDecodeEngine(model, max_slots=4, plan=decode_plan("mp4"))
    reqs = [GenerationRequest(p, size.new_tokens, 0.0, 0, None)
            for p in prompts]
    eng.serve(reqs, timeout=900)
    outs = [np.asarray(r.result.result(5)) for r in reqs]
    tokens = ref.check("four chips mp4 decode", prompts, outs, MARGIN_BF16)
    serve_ev = _device_evidence("mp4 decode", (eng.params, eng.caches),
                                tp=4, interpret=interpret)
    return {"status": "ok", "device_count": n,
            "train_dp2mp2": {"losses": [round(x, 4) for x in losses],
                             "rel_vs_one_chip": [float(f"{r:.3g}")
                                                 for r in rel],
                             **train_ev},
            "decode_mp4": {**tokens, **serve_ev}}


# -- the whole check -----------------------------------------------------------

def run_legs(size: Size, interpret: bool) -> Dict[str, object]:
    """Run every leg at ``size`` and return the report; any failure raises.
    ``interpret`` is for the CPU: kernels run under the Pallas interpreter
    and the assertions only a chip can satisfy are reported, not required."""
    import jax
    import jaxlib

    from paddlepaddle_tpu.core import compile_cache

    dev = jax.devices()[0]
    legs: Dict[str, object] = {}
    compile_s: Dict[str, float] = {}

    def timed(name, fn, *args):
        before = compile_cache.stats()
        t0 = time.perf_counter()
        out = fn(*args)
        after = compile_cache.stats()
        compile_s[name] = round(
            after["backend_compile_s"] - before["backend_compile_s"], 2)
        _say(f"{name}: ok in {time.perf_counter() - t0:.1f}s "
             f"(backend compile {compile_s[name]}s, cache hits "
             f"{after['hits'] - before['hits']}, misses "
             f"{after['misses'] - before['misses']})")
        return out

    model = _build_model(size)
    legs["train"] = timed("train", train_leg, size, interpret, model)
    legs["serve"], ref = timed("serve", serve_leg, size, model)
    legs["kernels"] = timed("kernels", kernels_leg, size, interpret)
    gc.collect()
    legs["four_chips"] = timed("four_chips", four_chip_leg, size, interpret,
                               model, legs["train"]["losses"], ref)

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    cache = compile_cache.stats()
    mem = dev.memory_stats() or {}
    return {
        "ok": True,
        "device": _device(),
        "versions": {"python": sys.version.split()[0],
                     "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "legs": legs,
        "backend_compile_s": compile_s,
        "compile_cache": {"dir": cache["dir"], "hits": cache["hits"],
                          "misses": cache["misses"],
                          "retrieval_s": cache["retrieval_s"],
                          "backend_compile_s": cache["backend_compile_s"]},
        "peak_hbm_bytes": mem.get("peak_bytes_in_use"),
        "note": "rates are smoke readings (cold device, compilation "
                "nearby), not benchmark numbers",
    }


def _device() -> Dict[str, object]:
    """The device as jax reports it, in the verdict's three keys."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main() -> int:
    t0 = time.perf_counter()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            "chip_smoke: this check runs on a TPU and jax found none — "
            f"jax.devices()[0].platform is {dev.platform!r} "
            f"({dev.device_kind}). No result.\n")
        return 1
    from paddlepaddle_tpu.core import compile_cache

    _say(f"{dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}, "
         f"compile cache at {compile_cache.arm()}")
    report = run_legs(FULL, interpret=False)
    report["wall_s"] = round(time.perf_counter() - t0, 1)
    print(json.dumps(report), flush=True)
    # the verdict, last and alone on its line: these keys and no others
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
