"""Flagship benchmark: Llama pretrain train-step throughput on one chip.

Prints ONE JSON line: tokens/sec/chip + MFU-derived vs_baseline, where
baseline = the BASELINE.json north star (Llama pretrain at 40% MFU).
The primary metric stays the round-1 254M-proxy config for cross-round
comparability; `detail.configs` adds the north-star coverage the judge
asked for: the largest Llama that fits the chip (remat + donation), the
MoE model, and ResNet-50 step time.

It measures a TPU or it fails: every config stamps the device it ran on,
a machine without one exits non-zero with a message naming what jax found,
and a config that raises takes the run down with it — no ``{"error": ...}``
row sits beside exit code 0.

One process per chip: ``python bench.py`` is a parent that never touches a
jax backend and runs each config (``--config NAME``) in a child of its own,
one after the other, so every config starts with an empty HBM and no child
ever waits on a chip its parent holds. ``--mesh SPEC`` is one process over
all the chips of the host.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np


def _require_tpu():
    """The device every number below is stamped with — or no run."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            "bench.py measures a TPU and jax found none: "
            f"jax.devices()[0].platform is {dev.platform!r} "
            f"({dev.device_kind}). A CPU timing is not a benchmark number; "
            "nothing was measured.")
    from paddlepaddle_tpu.core import compile_cache

    compile_cache.arm()
    return dev


def _device_stamp(dev) -> dict:
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _peak_flops(device) -> float:
    # one shared peak table (observability/perf/device.py) feeds the bench
    # AND the cost registry's rooflines, so "MFU" means the same thing in
    # the bench record, /metrics and /programs
    from paddlepaddle_tpu.observability.perf.device import peak_flops

    return peak_flops(device)


def _step_cost(tag, step, batch, key0, lr):
    """Cost-registry capture of ONE train step: trace + lower (no backend
    compile) the TrainStep's own single-step program and read XLA's flop
    count. The scan-chained timing programs can't be cost-differenced —
    XLA's analysis counts a loop body ONCE regardless of trip count — so
    the per-step cost comes from the unscanned program, whose matmul
    flops are identical to one chain iteration by construction.

    The same body-once rule hits the grad-accum microbatch scan INSIDE
    the step, so accum configs scale the count by grad_accum (recorded as
    ``cost_scale``); the optimizer update rides the scale too, an
    overcount of (a-1) * ~10 flops/param — ~0.02% against the 6N-scale
    step, noise next to the 5%-band uses of these numbers."""
    from paddlepaddle_tpu.observability.perf import costs as _costs

    accum = float(getattr(step, "grad_accum", 1) or 1)
    return _costs.cost_of_lowered(
        f"bench.{tag}", step._step,
        (step.params, step.opt_state, batch, key0, lr), bucket="per_step",
        scale=accum)


def _is_oom(e: Exception) -> bool:
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s or "OOM" in s


def _sync(loss):
    """End a timed region and return the loss as a host float (the rows
    print it). On this chip (TPU v5 lite, jax 0.9.0, libtpu 0.0.34)
    ``block_until_ready`` is a real barrier — measured in PR 21: a 142 ms
    matmul chain returned from dispatch in 0.4 ms, from
    ``block_until_ready`` in 142.4 ms and from a host scalar in 142.7 ms —
    so either would do; the scalar costs ~3 ms more per sync and is what
    the row needs anyway."""
    return float(loss.numpy() if hasattr(loss, "numpy") else loss)


def _time_steps(step, ids, iters, batch=None, tag="train_step"):
    """Time `iters` train steps with the per-call cost taken out.

    Steps are chained INSIDE one jit with lax.scan over the TrainStep's pure
    step function (a device training loop — standard jax practice), and the
    per-step time is taken from the SLOPE between a short and a long chain,
    which cancels whatever one call and one sync cost. On this chip that is
    little (PR 21: ~0.2 ms to dispatch a call, ~3 ms to fetch a host
    scalar, against steps of 45-250 ms; on the 254M config
    ``chip_smoke.py``'s plain call-per-step loop read 112.6 ms where this
    harness read 107.3 ms), so the harness is kept for continuity with the
    earlier records, not because the plain loop would mislead. Inputs stay
    device-resident.

    Params/opt-state are donated through every call and rebound, so peak
    memory matches the plain step-by-step loop.

    A ShardedTrainStep (detected by its `_param_sh` table) rides the SAME
    slope harness: its batch lands via `_batch_sharding`, its buffers
    thread through `_step_impl`, and the chain is jitted with the step's
    own param/opt shardings donated through the carry — the timed program
    is the GSPMD-partitioned step the plan produces. Cost capture is
    skipped there (the sharded `_step` signature differs, and mesh rows
    quote tokens/s + scaling columns, not registry MFU).
    """
    import jax.numpy as jnp

    sharded = hasattr(step, "_param_sh")
    if batch is None:
        ids = jnp.asarray(ids)
        batch = (ids, ids)
    if sharded:
        batch = tuple(jax.device_put(jnp.asarray(b),
                                     step._batch_sharding(jnp.asarray(b)))
                      for b in batch)
    else:
        batch = tuple(jnp.asarray(b) for b in batch)
    lr = jnp.asarray(step.optimizer.get_lr(), jnp.float32)
    key0 = jax.random.PRNGKey(0)

    def make(k_steps):
        def f(p, o):
            def body(carry, kk):
                p_, o_ = carry
                if sharded:
                    p2, o2, loss = step._step_impl(p_, step.buffers, o_,
                                                   batch, kk, lr)
                else:
                    p2, o2, loss = step._step_impl(p_, o_, batch, kk, lr)
                return (p2, o2), loss

            (pf, of), losses = jax.lax.scan(
                body, (p, o), jax.random.split(key0, k_steps))
            return pf, of, losses[-1]

        kw = {}
        if sharded:
            from jax.sharding import NamedSharding, PartitionSpec as P

            kw = dict(in_shardings=(step._param_sh, step._opt_sh),
                      out_shardings=(step._param_sh, step._opt_sh,
                                     NamedSharding(step.mesh, P())))
        return jax.jit(f, donate_argnums=(0, 1), **kw)

    k_lo, k_hi = 2, max(iters, 4)
    f_lo, f_hi = make(k_lo), make(k_hi)
    p, o = step.params, step.opt_state

    # cost-registry capture (always on for the bench — a lowering, not an
    # extra backend compile): XLA-counted flops/bytes of ONE train step
    cost = None
    if not sharded:
        c = _step_cost(tag, step, batch, key0, lr)
        if c is not None and c.get("flops"):
            cost = {"flops_per_step": c["flops"],
                    "bytes_per_step": c.get("bytes_accessed")}

    def run(f):
        nonlocal p, o
        t0 = time.perf_counter()
        p, o, loss = f(p, o)
        _sync(loss)
        return time.perf_counter() - t0, loss

    run(f_lo)  # compile + warm
    run(f_hi)
    best_lo, best_hi = float("inf"), float("inf")
    for _ in range(3):
        d_lo, loss = run(f_lo)
        d_hi, loss = run(f_hi)
        best_lo = min(best_lo, d_lo)
        best_hi = min(best_hi, d_hi)
    step.params, step.opt_state = p, o  # keep the TrainStep consistent
    per_step = (best_hi - best_lo) / (k_hi - k_lo)
    if per_step <= 0:
        # noise beat the slope — use the long chain's per-step average
        # (includes one call floor: a conservative UPPER bound on step
        # time, never an inflated rate)
        per_step = best_hi / k_hi
    if cost is not None and cost["flops_per_step"]:
        # fold the measured wall into the row _step_cost recorded
        from paddlepaddle_tpu.observability.perf import costs as _costs

        _costs.observe(f"bench.{tag}", per_step, bucket="per_step")
    return per_step * iters, loss, cost


def _bench_llama(cfg, batch, seq, iters, peak, grad_accum=1):
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.models import LlamaForCausalLM
    from paddlepaddle_tpu.optimizer import AdamW

    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                multi_precision=True)
    step = TrainStep(model, opt, lambda m, ids, labels: m(ids, labels=labels),
                     grad_accum_steps=grad_accum)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    dt, loss, cost = _time_steps(step, ids, iters, tag="llama")
    tokens_per_sec = batch * seq * iters / dt
    n = cfg.num_params()
    # MFU by convention counts MODEL flops only (6N + attention); remat's
    # extra forward is hardware work but not model work, reported separately
    model_flops = 6 * n + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    out = {
        "params": n,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(tokens_per_sec * model_flops / peak, 4),
        "final_loss": round(_sync(loss), 4),
        "batch": batch, "seq": seq,
    }
    if cost is not None and cost.get("flops_per_step"):
        # cost-registry MFU: XLA-counted flops (not the 6N convention)
        # against the same measured step time — analytic `mfu` stays one
        # release for cross-round comparability. Both share dt, so the
        # ratio below IS the pure flop-accounting delta: the convention
        # charges 6*V*h/token for the input-embedding gather XLA never
        # executes (-11.7% on this config), XLA counts softmax/elementwise/
        # optimizer flops the convention omits (+1.9%)
        out["mfu_measured"] = round(
            cost["flops_per_step"] * iters / (dt * peak), 4)
        out["measured_vs_analytic_flops"] = round(
            cost["flops_per_step"] / (model_flops * batch * seq), 4)
    if cfg.recompute:
        # full remat re-runs the forward (2N/token); a dots-saving policy
        # keeps matmul outputs, so only cheap elementwise work re-runs
        extra = 0 if cfg.remat_policy is not None else 2 * n
        hw_flops = model_flops + extra
        out["hw_util"] = round(tokens_per_sec * hw_flops / peak, 4)
    return out


_LLAMA_MAX_CANDIDATES = [
    ("0.9b", dict(hidden_size=2048, intermediate_size=5632,
                  num_hidden_layers=16, num_attention_heads=16,
                  num_key_value_heads=8)),
    # selective remat (save matmul outputs) + 2-way grad accumulation: the
    # microbatch halves the saved-dots memory so the policy fits, and the
    # backward skips recomputing the MXU work (r5: +8% over full remat
    # same-session)
    ("0.7b_dots", dict(hidden_size=1536, intermediate_size=6144,
                       num_hidden_layers=16, num_attention_heads=12,
                       num_key_value_heads=6, remat_policy="dots")),
    ("0.7b", dict(hidden_size=1536, intermediate_size=6144,
                  num_hidden_layers=16, num_attention_heads=12,
                  num_key_value_heads=6)),
    ("0.5b", dict(hidden_size=1536, intermediate_size=4608,
                  num_hidden_layers=14, num_attention_heads=12,
                  num_key_value_heads=6)),
]


def _bench_llama_max_candidate(peak, name):
    """One candidate per process: a failed (OOM) attempt must not poison the
    next one's memory (north star: hold MFU as size grows). Running out of
    device memory is this probe's question, so it is the one exception
    caught here — reported as ``fits: false`` for the parent to try the next
    size; anything else raises."""
    from paddlepaddle_tpu.models import LlamaConfig

    kw = dict(_LLAMA_MAX_CANDIDATES)[name]
    accum = 2 if kw.get("remat_policy") == "dots" else 1
    cfg = LlamaConfig(vocab_size=32000, max_position_embeddings=2048,
                      dtype="bfloat16", recompute=True, **kw)
    try:
        out = _bench_llama(cfg, batch=8, seq=1024, iters=5, peak=peak,
                           grad_accum=accum)
    except Exception as e:
        if _is_oom(e):
            return {"config": name, "fits": False}
        raise
    out.update(config=name, fits=True)
    return out


def _bench_moe(peak):
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.models.moe import MoEConfig, MoEForCausalLM
    from paddlepaddle_tpu.optimizer import AdamW

    # intermediate 768 (not the 704 a naive Qwen2-MoE half-scale gives):
    # MXU lanes are 128-wide and a non-multiple FFN width measured ~9x
    # slower matmuls (tools/moe_dispatch_bench.py) — a TPU-first sizing rule
    cfg = MoEConfig(vocab_size=32000, hidden_size=1024, intermediate_size=768,
                    num_hidden_layers=8, num_attention_heads=16,
                    num_key_value_heads=8, num_experts=16,
                    num_experts_per_tok=2, max_position_embeddings=2048,
                    dtype="bfloat16")  # default dispatch: "sorted" capacity path
    model = MoEForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                multi_precision=True)
    step = TrainStep(model, opt, lambda m, ids, labels: m(ids, labels=labels))
    batch, seq, iters = 8, 1024, 8
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                            (batch, seq)).astype(np.int32)
    dt, loss, cost = _time_steps(step, ids, iters, tag="moe")
    tokens_per_sec = batch * seq * iters / dt
    total = sum(int(np.prod(p.shape)) for p in step.params.values())
    h, L = cfg.hidden_size, cfg.num_hidden_layers
    expert_ffn = 3 * h * cfg.intermediate_size
    inactive = L * (cfg.num_experts - cfg.num_experts_per_tok) * expert_ffn
    active = total - inactive
    flops_per_token = 6 * active + 12 * L * h * seq
    out = {
        "params_total": total, "params_active": active,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu_active": round(tokens_per_sec * flops_per_token / peak, 4),
        "final_loss": round(_sync(loss), 4),
        "experts": cfg.num_experts, "topk": cfg.num_experts_per_tok,
    }
    if cost is not None and cost.get("flops_per_step"):
        # XLA counts the flops the HARDWARE runs — including the sorted
        # capacity path's padded expert compute — so measured > active
        # by construction; the gap is the dispatch-efficiency number
        out["mfu_measured"] = round(
            cost["flops_per_step"] * iters / (dt * peak), 4)
    return out


def _bench_resnet50(peak):
    """bf16 b128 (tools/resnet_ablation.py is the breakdown harness):
    device-resident inputs, scan-chained steps, slope timing. Round-4 wins:
    one-pass fused BatchNorm stats (BN was ~30 ms of the 56 ms step; the
    convs themselves run at 150-200 TF/s here — the old '14-23 TF/s conv
    emitter ceiling' was a round-3 mismeasurement) and reusing the forward
    stats for the running-average update instead of recomputing them."""
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.models.resnet import resnet50
    from paddlepaddle_tpu.nn.functional import cross_entropy
    from paddlepaddle_tpu.optimizer import Momentum

    model = resnet50(num_classes=1000)
    model.to(dtype="bfloat16")
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters())
    step = TrainStep(model, opt,
                     lambda m, x, y: cross_entropy(m(x), y).mean())
    batch, iters = 128, 10  # longer chains: better slope SNR vs contention
    rng = np.random.default_rng(0)
    imgs = rng.standard_normal((batch, 3, 224, 224)).astype(np.float32)
    labels = rng.integers(0, 1000, (batch,)).astype(np.int64)

    import jax.numpy as jnp

    dt, loss, cost = _time_steps(
        step, None, iters,
        batch=(jnp.asarray(imgs, jnp.bfloat16), jnp.asarray(labels)),
        tag="resnet50")
    imgs_per_sec = batch * iters / dt
    step_ms = dt / iters * 1e3
    # ~4.1 GFLOP fwd per 224x224 image, x3 for training, alongside the
    # cost-registry measurement: `mfu_measured` uses XLA's own flop count
    # for the compiled step (ROADMAP 3.8 folds the spellings into one)
    out = {
        "images_per_sec": round(imgs_per_sec, 1),
        "step_ms": round(step_ms, 2),
        "mfu_approx": round(imgs_per_sec * 3 * 4.1e9 / peak, 4),
        "final_loss": round(_sync(loss), 4),
        "batch": batch,
    }
    if cost is not None and cost.get("flops_per_step"):
        out["mfu_measured"] = round(
            cost["flops_per_step"] * iters / (dt * peak), 4)
    return out


# -- multi-chip mesh mode (--mesh dpXmpY) ------------------------------------

def _bench_mesh_train(make_model, rules, spec, batch, seq, iters,
                      vocab_size, tag, extra=None):
    """One model config on a mesh through the sharding plan, with the
    SAME-config SAME-seed 1-chip TrainStep as the baseline — both timed
    by the one `_time_steps` slope harness, so the record's columns are
    directly comparable:

    * ``scaling_efficiency`` = mesh / (1chip × n_devices);
    * ``throughput_retention`` = mesh / 1chip (n_devices × efficiency);
    * ``final_loss`` vs ``loss_1chip`` is a real same-init parity column,
      not an init-noise delta.
    """
    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.distributed.shard_plan import train_plan
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.optimizer import AdamW
    from paddlepaddle_tpu.parallel import ShardedTrainStep

    plan = train_plan(spec, rules=rules, data_axes=("dp",))
    loss_fn = lambda m, ids, labels: m(ids, labels=labels)  # noqa: E731
    ids = np.random.default_rng(0).integers(
        0, vocab_size, (batch, seq)).astype(np.int32)

    def build(step_cls, **kw):
        paddle.seed(0)
        model = make_model()
        return step_cls(model, AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=True), loss_fn, **kw)

    dt1, loss1, _ = _time_steps(build(TrainStep), ids, iters,
                                tag=f"{tag}_1chip")
    tps1 = batch * seq * iters / dt1
    dt, loss, _ = _time_steps(build(ShardedTrainStep, plan=plan), ids,
                              iters, tag=f"{tag}@{spec}")
    tps = batch * seq * iters / dt
    row = {
        "mesh": spec, "devices": plan.n_devices,
        "tokens_per_sec": round(tps, 1),
        "tokens_per_sec_1chip": round(tps1, 1),
        "scaling_efficiency": round(tps / max(tps1 * plan.n_devices, 1e-9), 4),
        "throughput_retention": round(tps / max(tps1, 1e-9), 4),
        "final_loss": round(_sync(loss), 4),
        "loss_1chip": round(_sync(loss1), 4),
        "batch": batch, "seq": seq,
    }
    if extra:
        row.update(extra(plan, tps))
    return row


def _bench_llama_mesh(cfg, batch, seq, iters, peak, spec):
    """The llama config on a dpXmpY mesh (DP×TP rule table)."""
    from paddlepaddle_tpu.models import LlamaForCausalLM

    n = cfg.num_params()
    model_flops = 6 * n + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    return _bench_mesh_train(
        lambda: LlamaForCausalLM(cfg), None, spec, batch, seq, iters,
        cfg.vocab_size, "llama",
        extra=lambda plan, tps: {
            "params": n,
            "mfu_per_chip": round(
                tps * model_flops / (peak * plan.n_devices), 4)})


def _bench_moe_mesh(cfg, batch, seq, iters, peak, spec):
    """The MoE config on a dpXepY mesh: expert banks sharded on "ep"
    (expert parallelism), einsum dispatch (the ep-clean SPMD lowering)."""
    from paddlepaddle_tpu.distributed.shard_plan import moe_train_rules
    from paddlepaddle_tpu.models.moe import MoEForCausalLM

    return _bench_mesh_train(
        lambda: MoEForCausalLM(cfg), moe_train_rules(), spec, batch, seq,
        iters, cfg.vocab_size, "moe",
        extra=lambda plan, tps: {"experts": cfg.num_experts,
                                 "topk": cfg.num_experts_per_tok})


def _bench_decode_tp(cfg, tp, n_reqs=6, new_tokens=16):
    """Tensor-parallel decode through the continuous engine: aggregate
    tokens/s at tp=1 vs tp=N over the same greedy workload, plus the
    token-exactness bit the acceptance criteria pin."""
    from paddlepaddle_tpu.distributed.shard_plan import decode_plan
    from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
    from paddlepaddle_tpu.inference.serving import GenerationRequest
    from paddlepaddle_tpu.models import LlamaForCausalLM

    import paddlepaddle_tpu as paddle

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(8, 33)),)).astype(np.int32)
               for _ in range(n_reqs)]

    def run(plan):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        eng = BatchDecodeEngine(model, max_slots=4, chunk=8, plan=plan)
        reqs = [GenerationRequest(p, new_tokens, 0.0, 0, None)
                for p in prompts]
        eng.serve(reqs[:1], timeout=600)       # warm: compile out of window
        reqs = [GenerationRequest(p, new_tokens, 0.0, 0, None)
                for p in prompts]
        t0 = time.perf_counter()
        eng.serve(reqs, timeout=600)
        dt = time.perf_counter() - t0
        outs = [np.asarray(r.result.result(5)) for r in reqs]
        toks = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return toks / max(dt, 1e-9), outs

    tps1, outs1 = run(None)
    tpsN, outsN = run(decode_plan(f"mp{tp}"))
    return {
        "mesh": f"mp{tp}", "devices": tp,
        "tok_s": round(tpsN, 1), "tok_s_1chip": round(tps1, 1),
        "speedup": round(tpsN / max(tps1, 1e-9), 3),
        "token_exact": bool(all(np.array_equal(a, b)
                                for a, b in zip(outs1, outsN))),
    }


def run_multichip(n_devices: int, peak: float, mesh: str = None):
    """Per-config multi-chip record: tokens/s + scaling-efficiency columns
    per mesh config against the same-config one-chip step, all in this one
    process over the host's chips. A config that raises ends the run."""
    from paddlepaddle_tpu.models import LlamaConfig
    from paddlepaddle_tpu.models.moe import MoEConfig

    dp = max(n_devices // 2, 1)
    llama_mesh = mesh or (f"dp{dp}mp2" if n_devices % 2 == 0
                          else f"dp{n_devices}")
    ep = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    moe_mesh = f"dp{max(n_devices // ep, 1)}ep{ep}"
    if mesh is not None:
        # an explicit spec parameterizes the LLAMA row; the MoE row needs
        # an ep axis and the decode row an mp-only mesh, so they keep
        # their auto-derived shapes — say so instead of silently ignoring
        sys.stderr.write(
            f"[bench] --mesh {mesh} applies to the llama config; moe runs "
            f"{moe_mesh} (expert parallel), decode_tp runs mp2\n")

    lcfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=4096,
        num_hidden_layers=12, num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=2048,
        dtype="bfloat16")
    mcfg = MoEConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=768,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=8, num_experts=16, num_experts_per_tok=2,
        max_position_embeddings=2048, dtype="bfloat16",
        dispatch_mode="einsum")
    batch, seq, iters = 8, 1024, 5
    # largest-fit candidate on the mesh (remat like the 1-chip llama_max row)
    xcfg = LlamaConfig(vocab_size=32000, max_position_embeddings=2048,
                       dtype="bfloat16", recompute=True,
                       **dict(_LLAMA_MAX_CANDIDATES)["0.7b"])

    configs = {
        "llama": _bench_llama_mesh(lcfg, batch, seq, iters, peak, llama_mesh),
        "moe": _bench_moe_mesh(mcfg, batch, seq, iters, peak, moe_mesh),
        "llama_max": _bench_llama_mesh(xcfg, batch, seq, iters, peak,
                                       llama_mesh),
    }
    if n_devices % 2 == 0:
        configs["decode_tp"] = _bench_decode_tp(lcfg, tp=2)
    else:
        # tp=1 vs tp=1 would run the same workload twice and emit a
        # degenerate row (speedup ~1, trivially-true token_exact) — record
        # the skip instead
        configs["decode_tp"] = {
            "skipped": f"tensor-parallel decode needs an even device "
                       f"count, have {n_devices}"}
    return {"n_devices": n_devices, "configs": configs}


# -- one config per process ----------------------------------------------------

def _primary(peak):
    from paddlepaddle_tpu.models import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=4096,
        num_hidden_layers=12, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=2048, dtype="bfloat16")
    return _bench_llama(cfg, batch=8, seq=1024, iters=10, peak=peak)


_CONFIGS = {"llama": _primary, "moe": _bench_moe, "resnet50": _bench_resnet50}
for _n, _ in _LLAMA_MAX_CANDIDATES:
    _CONFIGS[f"llama_max:{_n}"] = (
        lambda peak, _name=_n: _bench_llama_max_candidate(peak, _name))


def _run_config_here(name: str) -> None:
    """Child mode (``--config NAME``): this process holds the chip, runs one
    config and prints its row, stamped with the device, as one JSON line."""
    dev = _require_tpu()
    row = _CONFIGS[name](_peak_flops(dev))
    row["device"] = _device_stamp(dev)
    print(json.dumps(row), flush=True)


def _run_config_in_child(name: str) -> dict:
    """Parent side: one fresh process (and fresh HBM) per config. The child's
    stderr passes through; a child that fails ends the whole run with its
    exit code — its row is not replaced by an error field."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--config", name],
        stdout=subprocess.PIPE, text=True, timeout=1800)
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        sys.exit(f"bench.py: config {name!r} failed with exit code "
                 f"{proc.returncode}; no record written")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    if len(sys.argv) > 2 and sys.argv[1] == "--config":
        _run_config_here(sys.argv[2])
        return

    if len(sys.argv) > 1 and sys.argv[1] == "--mesh":
        # multi-chip mode: llama / MoE DP(+TP/EP) train configs through the
        # sharding plan + the tp decode engine, with scaling-efficiency
        # columns vs the same-config 1-chip step. `--mesh auto` picks
        # dp(N/2)mp2 over all visible devices.
        spec = sys.argv[2] if len(sys.argv) > 2 else "auto"
        dev = _require_tpu()
        report = run_multichip(len(jax.devices()), _peak_flops(dev),
                               mesh=None if spec == "auto" else spec)
        report["device"] = _device_stamp(dev)
        print(json.dumps({"multichip": report}))
        return

    # parent: no jax backend is touched here, so each child finds the chip
    # free. Suite order matters for reproducibility: transformer configs
    # first, the conv suite last (mid-suite it inherits whatever state the
    # Llama OOM probes left and lands outside the bands the cards quote).
    primary = _run_config_in_child("llama")
    configs = {"moe": _run_config_in_child("moe")}
    for cand, _ in _LLAMA_MAX_CANDIDATES:      # largest-fit: first that fits
        row = _run_config_in_child(f"llama_max:{cand}")
        if row["fits"]:
            configs["llama_max"] = row
            break
    else:
        sys.exit("bench.py: no llama_max candidate fits the chip; "
                 "no record written")
    configs["resnet50"] = _run_config_in_child("resnet50")

    mfu = primary["mfu"]
    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": primary["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "device": primary["device"],
        "detail": {
            "mfu": mfu,
            "mfu_measured": primary.get("mfu_measured"),
            "params": primary["params"],
            "device": primary["device"]["device_kind"],
            "batch": primary["batch"], "seq": primary["seq"],
            "final_loss": primary["final_loss"],
            "configs": configs,
        },
    }))


if __name__ == "__main__":
    main()
