#!/usr/bin/env python
"""Weight-only int8 serving A/B: decode tokens/s + exact top-1 agreement.

Same-session harness (both engines built over ONE model in one process —
no cross-process compile-cache or clock drift).

* THROUGHPUT — decode chunks are slope-timed: fill every slot with a
  long-budget greedy request, warm, then time a short chain vs a long chain
  of `_decode_chunk` calls and take the slope. Each chunk already ends in
  exactly ONE host readback (the packed token sync, ~3 ms on the v5e,
  PR 21); the slope subtracts that per-call floor out.
* ACCURACY — the same fixed prompt set is decoded greedily (temp 0) by
  both engines; reported as per-token top-1 agreement and exact full-
  sequence match rate.

Run:  python tools/quant_ab.py [--config bench|tiny] [--slots 8]
          [--new-tokens 64] [--prompts 16] [--group-size -1]

`--config bench` is the serving-bench 254M bf16 Llama (the card config);
`tiny` is the CPU-sized smoke config.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from paddlepaddle_tpu.inference.serving import slo_summary


def _build_model(config: str):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if config == "bench":
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=4096, num_hidden_layers=12,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=2048, dtype="bfloat16")
    else:
        cfg = LlamaConfig.tiny(vocab_size=512, hidden_size=128, layers=2,
                               heads=4, kv_heads=2, max_len=512)
    return LlamaForCausalLM(cfg)


def _engine(model, quant, slots, chunk, group_size):
    from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine

    return BatchDecodeEngine(model, max_slots=slots, chunk=chunk,
                             quant=quant, quant_group_size=group_size)


def _requests(model, prompts, new_tokens):
    from paddlepaddle_tpu.inference.serving import GenerationRequest

    return [GenerationRequest(p, new_tokens, 0.0, 0, None) for p in prompts]


def _greedy_outputs(eng, prompts, new_tokens):
    """(decoded outputs, per-request SLO summary) for one engine pass."""
    reqs = _requests(eng.model, prompts, new_tokens)
    eng.serve(reqs, timeout=1800)
    outs = [np.asarray(r.result.result(5)) for r in reqs]
    return outs, slo_summary([r.result for r in reqs])


def _decode_tok_s(eng, prompts, repeats=3, n_lo=2, n_hi=8):
    """Slope-timed steady-state decode throughput over full slots."""
    L = eng.L
    budget = min(L - max(len(p) for p in prompts) - 1, 100000)
    # every chunk the function will run: warm + repeats x (short + long)
    need = (2 + repeats * (n_lo + n_hi)) * eng.chunk
    if budget < need:
        raise SystemExit(
            f"engine max_len {L} too short for the timing chains "
            f"({need} tokens needed, budget {budget}): raise max_len or "
            "lower --chunk")
    reqs = _requests(eng.model, prompts[: eng.S], budget)
    for r in reqs:
        if not eng._admit(r):
            raise RuntimeError("slot admission failed with free slots")
    eng.flush()
    # tokens/s must count the slots actually EMITTING (fewer prompts than
    # slots leaves idle lanes that still burn compute but produce nothing)
    active = len(reqs)

    def chain(n):
        t0 = time.perf_counter()
        for _ in range(n):
            eng._decode_chunk()   # ends in the one packed host sync
        return time.perf_counter() - t0

    chain(2)                      # warm (compile already done at admit? no:
    #                               first _decode_chunk compiles the scan)
    best_lo = best_hi = float("inf")
    for _ in range(repeats):
        best_lo = min(best_lo, chain(n_lo))
        best_hi = min(best_hi, chain(n_hi))
    per_chunk = (best_hi - best_lo) / (n_hi - n_lo)
    if per_chunk <= 0:            # noise beat the slope: conservative bound
        per_chunk = best_hi / n_hi
    toks_per_chunk = active * eng.chunk
    # release the slots so a later phase starts clean
    for i in range(eng.S):
        eng.release_slot(i)
    return toks_per_chunk / per_chunk, per_chunk * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=("bench", "tiny"),
                    default=None, help="default: bench on an accelerator, "
                    "tiny on cpu")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--prompts", type=int, default=16)
    ap.add_argument("--group-size", type=int, default=-1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    on_accel = dev.platform not in ("cpu",)
    config = args.config or ("bench" if on_accel else "tiny")
    if config == "tiny":
        args.slots = min(args.slots, 4)
        args.chunk = min(args.chunk, 8)

    model = _build_model(config)
    cfg = model.config
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(16, 64)),)).astype(np.int32)
               for _ in range(args.prompts)]

    results = {}
    outputs = {}
    for mode, quant in (("bf16", None), ("int8", "weight_only_int8")):
        eng = _engine(model, quant, args.slots, args.chunk, args.group_size)
        tok_s, chunk_ms = _decode_tok_s(eng, prompts)
        outs, slo = _greedy_outputs(eng, prompts, args.new_tokens)
        outputs[mode] = outs
        # SLO columns ride along so the quant A/B (and the continuous-
        # batching work it feeds) stays latency-honest, not just
        # throughput-honest: an int8 win that inflates TTFT is not a win
        results[mode] = dict({"decode_tok_s": round(tok_s, 1),
                              "chunk_ms": round(chunk_ms, 2)}, **slo)
        if quant is not None:
            m = eng.quant_meta
            results[mode]["weights_quantized"] = len(m["quantized"])
            results[mode]["weight_mb_saved"] = round(
                m["bytes_saved"] / 1e6, 1)
        print(f"{mode:>5}: {tok_s:9.1f} decode tok/s "
              f"({chunk_ms:.2f} ms / {args.slots}x{args.chunk}-token chunk)  "
              f"ttft p50={slo['ttft_p50_ms']}ms p99={slo['ttft_p99_ms']}ms "
              f"tpot={slo['tpot_ms']}ms",
              flush=True)

    agree = total = exact = 0
    for a, b in zip(outputs["bf16"], outputs["int8"]):
        n = min(len(a), len(b))
        agree += int((a[:n] == b[:n]).sum())
        total += max(len(a), len(b))
        exact += int(len(a) == len(b) and bool((a == b).all()))
    speedup = results["int8"]["decode_tok_s"] / max(
        results["bf16"]["decode_tok_s"], 1e-9)
    summary = {
        "config": config,
        "device": str(dev.device_kind),
        "slots": args.slots, "chunk": args.chunk,
        "group_size": args.group_size,
        "prompts": args.prompts, "new_tokens": args.new_tokens,
        "bf16": results["bf16"], "int8": results["int8"],
        "speedup": round(speedup, 3),
        "top1_agreement": round(agree / max(total, 1), 4),
        "exact_match": f"{exact}/{len(prompts)}",
    }
    print(f"int8 speedup {speedup:.2f}x | top-1 agreement "
          f"{summary['top1_agreement']:.2%} | exact {summary['exact_match']}")
    print(json.dumps({"quant_ab": summary}))


if __name__ == "__main__":
    main()
