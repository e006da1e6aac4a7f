"""Serving throughput: continuous batching, paged-KV A/B, prefix cache.

The BASELINE.md serving card. Three workload profiles:

* ``uniform``  — the original card: N concurrent ragged requests,
  aggregate new tokens/sec vs a single-sequence generate_cached baseline.
* ``mixed``    — mixed short/long prompts under a FIXED KV byte budget:
  the paged pool admits by real prompt+budget pages, the contiguous pool
  by worst-case ``max_len`` slots. ``--ab`` runs both layouts at the same
  HBM budget and prints concurrency + tokens/s side by side — the paged
  engine must sustain strictly more concurrent sequences.
* ``prefix``   — every request shares one system prompt (``--prefix-len``)
  plus a short unique tail, submitted with ``prefix_len=`` so the paged
  engine's prompt cache turns N prefills into 1 prefill + N tails.
  Reported against a control run with the cache disabled (TTFT delta).

``--spec-k N --draft <preset>`` adds a SPECULATIVE row beside the plain
one: the same workload through an engine where a draft model proposes N
greedy tokens per slot and one batched target forward verifies them
(docs/serving.md "Speculative decoding"). The row carries tokens/s,
TTFT/TPOT, the measured acceptance rate, accepted-run-length p50/p99 and
tokens-per-target-step; ``tools/perf_gate.py`` gates
``serving.spec_tok_s`` higher-is-better (acceptance rate rides along as
an informational column). Draft presets: ``self`` (the target itself —
acceptance 1.0, the amortization upper bound and the CPU plumbing
smoke), ``half``/``quarter`` (a fresh model at that fraction of the
target's width — RANDOM weights, so acceptance ~0 on this harness; on
real checkpoints this is where the distilled draft goes). ``--draft-
quant`` serves the draft weight-only int8.

``--replicas N`` routes the same profiles through the
:class:`~paddlepaddle_tpu.inference.router.ServingRouter` over N replica
engines instead of one: the report adds per-replica tokens/s, the fleet
aggregate, the failover count, and **availability**
(completed/submitted — the number the chaos drill defends and
``tools/perf_gate.py`` gates higher-is-better). The prefix profile is the
interesting one here: prefix-affine routing must keep the hit rate
fleet-wide, not divide it by N.

``--traffic step:<mult>@<t>|poisson:<rate>`` switches to an OPEN-LOOP
arrival schedule (submissions land on the wall clock regardless of
completions — the closed loop above hides queueing collapse) and reports
per-window tok/s, TTFT p99 and dropped count; ``--autoscale MIN:MAX``
arms a :class:`~paddlepaddle_tpu.inference.fleet.FleetController` over
the ``--replicas`` initial fleet so the 4x-step claim (BASELINE.md
"Elastic fleet") is measurable: ``tools/perf_gate.py`` gates
``fleet.step_ttft_p99_ms`` lower-is-better, ``fleet.dropped_requests``
as a hard zero floor, and ``fleet.scaleup_to_healthy_s`` lower-is-better.

Reports KV-pool occupancy, prefix hit rate and peak concurrency next to
the TTFT/TPOT SLO columns; ``tools/perf_gate.py`` gates the JSON artifact.

Run on the TPU: python tools/serving_bench.py [--profile mixed --ab]
CPU-container smoke: add ``--hidden 128 --layers 2 --max-len 1024``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from paddlepaddle_tpu.inference.serving import ServingEngine, slo_summary


# -- artifact emission (--out) -----------------------------------------------

def _git_sha() -> str:
    try:
        import subprocess
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _emit(body, args, bench="serving_bench"):
    """Print the final JSON line; mirror it to ``--out`` with a meta block.

    The artifact is the ``BENCH_serving_r<NN>.json`` shape
    ``tools/perf_gate.py`` loads directly: the bench body under its usual
    key, plus a ``meta`` block (git sha, unix stamp, argv) recording WHAT
    produced a saved baseline — without it a months-old baseline file is
    unattributable to a commit.
    """
    doc = {bench: body}
    print(json.dumps(doc))
    out = getattr(args, "out", None)
    if not out:
        return
    art = {"meta": {"bench": bench, "git_sha": _git_sha(),
                    "unix_time": int(time.time()),
                    "argv": sys.argv[1:]}}
    art.update(doc)
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    print(f"[{bench}] artifact -> {out}", file=sys.stderr)


# -- open-loop arrival profiles (--traffic) ----------------------------------
#
# The closed-loop runs above submit everything at t=0 and wait: they measure
# steady-state packing, but they HIDE queueing collapse — a fleet that takes
# 30s to absorb a burst still posts a fine aggregate tok/s. The open-loop
# profiles submit on a wall-clock ARRIVAL schedule regardless of completions
# (the "fleet absorbs a 4x traffic step" claim is only measurable this way):
#
#   step:<mult>@<t>   deterministic arrivals at --rate req/s, multiplied by
#                     <mult> from <t> seconds in (the autoscaler drill)
#   poisson:<rate>    memoryless arrivals at <rate> req/s (burstier than the
#                     deterministic schedule at the same mean)

def parse_traffic(spec):
    """'step:<mult>@<t>' | 'poisson:<rate>' -> profile dict."""
    kind, _, rest = spec.partition(":")
    try:
        if kind == "step":
            mult, sep, at = rest.partition("@")
            if not sep:
                raise ValueError("step needs <mult>@<t>")
            return {"kind": "step", "mult": float(mult), "at_s": float(at)}
        if kind == "poisson":
            return {"kind": "poisson", "rate": float(rest)}
    except ValueError as e:
        raise ValueError(
            f"unrecognized --traffic spec {spec!r}: {e} "
            "(expected step:<mult>@<t> or poisson:<rate>)") from None
    raise ValueError(
        f"unrecognized --traffic profile {kind!r} "
        "(expected step:<mult>@<t> or poisson:<rate>)")


def arrival_offsets(traffic, base_rate, n, rng):
    """``n`` submit-time offsets (seconds from start) for the profile."""
    out, t = [], 0.0
    if traffic["kind"] == "poisson":
        for _ in range(n):
            t += float(rng.exponential(1.0 / traffic["rate"]))
            out.append(t)
        return out
    for _ in range(n):
        rate = base_rate * (traffic["mult"] if t >= traffic["at_s"] else 1.0)
        t += 1.0 / rate
        out.append(t)
    return out


def _pct(vals, q):
    vals = sorted(vals)
    if not vals:
        return None
    return vals[min(len(vals) - 1, int(q * (len(vals) - 1) + 0.5))]


def _ms(v):
    return None if v is None else round(v * 1e3, 2)


def traffic_summary(records, traffic, window_s=1.0):
    """Headline + per-window rows from open-loop request records
    (``t_submit``/``outcome``/``ttft_s``/``tokens``/``t_done`` per
    request). ``dropped_requests`` counts every submitted request that
    did NOT resolve completed (typed sheds AND failures — the zero-drop
    claim admits neither); ``step_ttft_p99_ms`` is the TTFT p99 over
    requests arriving AT OR AFTER the step (the post-step SLO the
    autoscaler must hold)."""
    ok = [r for r in records if r.get("outcome") == "ok"]
    ttfts = [r["ttft_s"] for r in ok if r.get("ttft_s") is not None]
    at = traffic["at_s"] if traffic["kind"] == "step" else 0.0
    post = [r["ttft_s"] for r in ok
            if r.get("ttft_s") is not None and r["t_submit"] >= at]
    windows = {}

    def wrow(w):
        return windows.setdefault(w, {
            "t_s": round(w * window_s, 3), "submitted": 0, "completed": 0,
            "dropped": 0, "tokens": 0, "_ttfts": []})

    for r in records:
        row = wrow(int(r["t_submit"] // window_s))
        row["submitted"] += 1
        if r.get("outcome") == "ok":
            if r.get("ttft_s") is not None:
                row["_ttfts"].append(r["ttft_s"])
        else:
            row["dropped"] += 1
    for r in ok:
        # throughput is attributed to the window the tokens LANDED in
        row = wrow(int(r.get("t_done", r["t_submit"]) // window_s))
        row["completed"] += 1
        row["tokens"] += int(r.get("tokens") or 0)
    rows = []
    for w in sorted(windows):
        row = windows[w]
        row["tok_s"] = round(row.pop("tokens") / window_s, 1)
        row["ttft_p99_ms"] = _ms(_pct(row.pop("_ttfts"), 0.99))
        rows.append(row)
    return {
        "submitted": len(records),
        "completed": len(ok),
        "dropped_requests": len(records) - len(ok),
        "ttft_p50_ms": _ms(_pct(ttfts, 0.50)),
        "ttft_p99_ms": _ms(_pct(ttfts, 0.99)),
        "step_ttft_p99_ms": _ms(_pct(post, 0.99)),
        "window_s": window_s,
        "windows": rows,
    }


def run_open_loop(submit, prompts, offsets, args):
    """Drive ``submit`` on the arrival schedule; one record per request."""
    records, pending = [], []
    t0 = time.perf_counter()
    for (p, pl), off in zip(prompts, offsets):
        lag = off - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        rec = {"t_submit": round(time.perf_counter() - t0, 4)}
        records.append(rec)
        try:
            fut = submit(p, max_new_tokens=args.new_tokens, prefix_len=pl)
        except Exception as e:  # noqa: BLE001 — a refusal IS the datum
            rec.update(outcome="refused", error=type(e).__name__)
            continue
        pending.append((p, fut, rec))
    for p, fut, rec in pending:
        try:
            out = fut.result(1800)
        except Exception as e:  # noqa: BLE001
            rec.update(outcome="failed", error=type(e).__name__)
        else:
            slo = fut.slo()
            rec.update(outcome="ok", tokens=len(out) - len(p),
                       ttft_s=slo["ttft_s"],
                       t_done=round(rec["t_submit"]
                                    + (slo["latency_s"] or 0.0), 4))
    return records, round(time.perf_counter() - t0, 2)


def build_model(args):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=32000, hidden_size=args.hidden,
                      intermediate_size=args.hidden * 4,
                      num_hidden_layers=args.layers,
                      num_attention_heads=max(args.hidden // 64, 4),
                      num_key_value_heads=max(args.hidden // 128, 2),
                      max_position_embeddings=args.max_len,
                      dtype="bfloat16")
    return LlamaForCausalLM(cfg)


def gen_prompts(args, cfg, rng):
    """[(prompt_ids, prefix_len|None)] for the chosen profile."""
    V = cfg.vocab_size
    lo, hi = 32, 256
    if args.profile == "mixed":
        # half short, half long — the fragmentation workload the paged
        # pool exists for (long requests must not reserve max_len for
        # every short one)
        out = []
        long_hi = min(args.max_len - args.new_tokens - 1, 768)
        for i in range(args.reqs):
            n = (int(rng.integers(32, 64)) if i % 2 == 0
                 else int(rng.integers(long_hi // 2, long_hi)))
            out.append((rng.integers(0, V, (n,)).astype(np.int32), None))
        return out
    if args.profile == "prefix":
        # --prefix-count > 1 is the TIERED-cache drill shape: N distinct
        # system prompts visited round-robin, so a device pool smaller
        # than the prefix working set must spill/restore through the
        # host tier (--kv-host-mb) to keep the hit rate up
        systems = [rng.integers(0, V, (args.prefix_len,)).astype(np.int32)
                   for _ in range(max(args.prefix_count, 1))]
        out = []
        for i in range(args.reqs):
            tail = rng.integers(0, V, (int(rng.integers(16, 48)),))
            out.append((np.concatenate([systems[i % len(systems)],
                                        tail.astype(np.int32)]),
                        args.prefix_len))
        return out
    return [(rng.integers(0, V, (int(rng.integers(lo, hi)),)).astype(np.int32),
             None) for _ in range(args.reqs)]


def warm_engine(eng, model, prompts, args, prefix_cache=True):
    """Warm EVERY prefill bucket the prompts will hit + the decode program
    (and, for the prefix profile, the prefix-HIT admit program), so compile
    time doesn't pollute the timed window."""
    rng = np.random.default_rng(7)
    for blen in sorted({-(-len(p) // 128) * 128 for p, _ in prompts}):
        eng.generate(rng.integers(0, model.config.vocab_size,
                                  (min(blen, eng._max_len
                                       - args.new_tokens) - 1,)
                                  ).astype(np.int32),
                     max_new_tokens=4)
    pl = next((pl for _, pl in prompts if pl), None)
    if pl and prefix_cache and eng._engine.kv_layout == "paged":
        # warm the prefix-HIT admit program with a throwaway system
        # prompt (miss registers it, hit compiles the tail-only
        # program), then evict it and zero the counters
        V = model.config.vocab_size
        sysp = rng.integers(0, V, (pl,)).astype(np.int32)
        for _ in range(2):
            eng.generate(np.concatenate(
                [sysp, rng.integers(0, V, (24,)).astype(np.int32)]),
                max_new_tokens=4, prefix_len=pl)
        pfx, pool = eng._engine.prefix, eng._engine.pool
        pfx.evict_until(pool, pool.usable)
        pfx.hits = pfx.misses = pfx.evictions = 0


def build_draft(args, model):
    """Resolve the --draft preset into the engine's ``draft=`` argument:
    the target itself for ``self``, else a scaled-down CONFIG — the
    engine's ``resolve_draft`` builds the model and widens its rope
    tables to ``max_len + k``, the seam a real distilled-draft config
    would take."""
    from paddlepaddle_tpu.models import LlamaConfig

    if args.draft == "self":
        return model
    frac = {"half": 2, "quarter": 4}[args.draft]
    cfg = model.config
    hidden = max(cfg.hidden_size // frac, 64)
    return LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=hidden,
        intermediate_size=hidden * 4,
        num_hidden_layers=max(cfg.num_hidden_layers // frac, 2),
        num_attention_heads=max(hidden // 64, 4),
        num_key_value_heads=max(hidden // 128, 2),
        max_position_embeddings=cfg.max_position_embeddings,
        dtype=cfg.dtype)


def run_serving(model, prompts, args, kv_layout, slots, num_pages=None,
                prefix_cache=True, warm=True, tp=1, spec=False,
                kv_quant=None, kv_host_bytes=None):
    """One engine pass over the workload; returns the metrics row.
    ``tp > 1`` serves through a tensor-parallel engine (sharding plan over
    an ``mp``-axis mesh: weights column/row-parallel, KV pool sharded on
    kv heads — docs/distributed.md). ``spec=True`` arms speculative
    decoding from the --spec-k/--draft args and adds the acceptance
    columns."""
    spec_kw = {}
    if spec:
        spec_kw = dict(draft=build_draft(args, model), spec_k=args.spec_k,
                       draft_quant=("weight_only_int8" if args.draft_quant
                                    else None))
    with ServingEngine(model, max_batch_size=slots,
                       decode_chunk=args.chunk, kv_layout=kv_layout,
                       kv_page_size=args.page_size, kv_num_pages=num_pages,
                       prefix_cache=prefix_cache,
                       mesh=(f"mp{tp}" if tp > 1 else None),
                       kv_quant=kv_quant, kv_host_bytes=kv_host_bytes,
                       **spec_kw) as eng:
        if warm:
            warm_engine(eng, model, prompts, args, prefix_cache)
        if eng._engine.kv_layout == "paged":
            # occupancy peak must measure the WORKLOAD, not warm traffic
            eng._engine.pool.peak_used = eng._engine.pool.used
        eng._engine.stats["peak_busy"] = 0
        gp0 = _goodput_kinds()   # after warm: the row's waste is the
        t0 = time.perf_counter()  # workload's, not the warmup's
        futs = [eng.submit(p, max_new_tokens=args.new_tokens, prefix_len=pl)
                for p, pl in prompts]
        outs = [f.result(1800) for f in futs]
        dt = time.perf_counter() - t0
        kv = eng._engine.kv_stats()
        peak_busy = eng._engine.stats["peak_busy"]
        spec_info = eng._engine.spec_info() if spec else None
    new_tokens = sum(len(o) - len(p) for o, (p, _) in zip(outs, prompts))
    row = {"kv_layout": kv_layout, "slots": slots,
           "aggregate_tok_s": round(new_tokens / max(dt, 1e-9), 1),
           "wall_s": round(dt, 2), "new_tokens": new_tokens,
           "concurrency_peak": peak_busy}
    row.update(_goodput_cols(gp0, dt))
    if tp > 1:
        row["tp"] = tp
    row.update(slo_summary(futs))
    if kv["layout"] == "paged":
        row["kv_pages_total"] = kv["pages_total"]
        row["kv_occupancy_peak"] = round(
            kv["pages_peak"] / max(kv["pages_total"], 1), 4)
        pfx = kv["prefix"]
        looked = pfx["hits"] + pfx["misses"]
        row["prefix_hit_rate"] = (round(pfx["hits"] / looked, 4)
                                  if looked else None)
        row["prefix_evictions"] = pfx["evictions"]
        row["kv_quant"] = kv["kv_quant"]
        row["kv_page_bytes"] = kv["page_bytes"]
        host = kv.get("host") or {}
        if host.get("enabled"):
            # the tiered-prefix columns perf_gate tracks: restore latency
            # percentiles plus the spill/restore/discard census
            row["prefix_restore_ms_p50"] = host.get("restore_ms_p50")
            row["prefix_restore_ms_p99"] = host.get("restore_ms_p99")
            row["prefix_spills"] = host.get("spills")
            row["prefix_restores"] = host.get("restores")
            row["prefix_host_discards"] = host.get("discards")
    if spec_info is not None:
        row["spec_k"] = spec_info["k"]
        row["draft"] = args.draft
        row["draft_params_m"] = spec_info["draft"]["params_m"]
        row["draft_quant"] = spec_info["draft"]["quant"]
        row["acceptance_rate"] = spec_info["acceptance_rate"]
        row["tokens_per_target_step"] = spec_info["tokens_per_target_step"]
        row["accept_run_p50"] = spec_info["accept_run_p50"]
        row["accept_run_p99"] = spec_info["accept_run_p99"]
        row["rollbacks"] = spec_info["rollbacks"]
    return row


def run_fleet(model, prompts, args):
    """Route the workload through a ServingRouter over N replica engines:
    fleet + per-replica tokens/s, failover count, availability."""
    from paddlepaddle_tpu.inference.router import ServingRouter

    def factory():
        return ServingEngine(model, max_batch_size=args.slots,
                             decode_chunk=args.chunk,
                             kv_layout=args.kv_layout,
                             kv_page_size=args.page_size,
                             kv_num_pages=args.num_pages)

    router = ServingRouter([factory for _ in range(args.replicas)],
                           probe_interval_s=0.2)
    router.start()
    try:
        engines = [rep.client.engine for rep in router._replicas]
        for eng in engines:
            warm_engine(eng, model, prompts, args)
            if eng._engine.kv_layout == "paged":
                eng._engine.pool.peak_used = eng._engine.pool.used
            eng._engine.stats["peak_busy"] = 0
        before = [(eng.stats["decode_tokens"], eng.stats["requests"])
                  for eng in engines]
        gp0 = _goodput_kinds()   # replicas are in-process: one ledger
        t0 = time.perf_counter()
        # a synchronous refusal (overload shed, fleet unavailable) counts
        # against availability exactly like an in-flight failure — the
        # bench must produce its artifact UNDER the failure conditions
        # availability exists to measure, not die on them
        futs, submitted = [], 0
        for p, pl in prompts:
            submitted += 1
            try:
                futs.append((p, router.submit(
                    p, max_new_tokens=args.new_tokens, prefix_len=pl)))
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(
                    f"  submit refused: {type(e).__name__}: {e}\n")
        new_tokens = completed = 0
        for p, f in futs:
            try:
                out = f.result(1800)
            except Exception as e:  # noqa: BLE001 — availability is the metric
                sys.stderr.write(
                    f"  request failed: {type(e).__name__}: {e}\n")
            else:
                completed += 1
                new_tokens += len(out) - len(p)
        dt = time.perf_counter() - t0
        h = router.health()["router"]
        per_replica = []
        hits = misses = 0
        for rep, eng, (tok0, req0) in zip(router._replicas, engines, before):
            pr = {"replica": rep.name,
                  "tok_s": round((eng.stats["decode_tokens"] - tok0)
                                 / max(dt, 1e-9), 1),
                  "requests": eng.stats["requests"] - req0}
            kv = eng._engine.kv_stats()
            if kv["layout"] == "paged":
                pr["prefix_hits"] = kv["prefix"]["hits"]
                hits += kv["prefix"]["hits"]
                misses += kv["prefix"]["misses"]
            per_replica.append(pr)
        row = {"replicas": args.replicas, "kv_layout": args.kv_layout,
               "slots_per_replica": args.slots,
               "aggregate_tok_s": round(new_tokens / max(dt, 1e-9), 1),
               "wall_s": round(dt, 2), "new_tokens": new_tokens,
               "availability": round(completed / max(submitted, 1), 4),
               "failovers": h["failovers"], "retries": h["retries"],
               "per_replica": per_replica}
        row.update(_goodput_cols(gp0, dt))
        if hits + misses:
            # FLEET-wide hit rate: prefix-affine routing must keep it,
            # not divide it by the replica count
            row["prefix_hit_rate"] = round(hits / (hits + misses), 4)
        row.update(slo_summary([f for _, f in futs]))
        return row
    finally:
        router.stop()


def _scrape_counter(name):
    """Sum a counter's label variants from this process's registry via the
    exposition text — no private registry API needed."""
    try:
        from paddlepaddle_tpu.observability import to_prometheus_text

        total = 0.0
        for ln in to_prometheus_text().splitlines():
            if ln.startswith(name) and not ln.startswith("#"):
                try:
                    total += float(ln.rsplit(None, 1)[-1])
                except ValueError:
                    pass
        return total
    except Exception:
        return None


def _goodput_kinds():
    """Cumulative per-kind token counts from this process's goodput
    ledger (None if the observability package is unavailable)."""
    try:
        from paddlepaddle_tpu.observability import goodput

        return dict(goodput.snapshot()["kinds"])
    except Exception:
        return None


def _goodput_cols(before, dt, after=None):
    """``goodput_tok_s`` (useful tokens/s) + ``waste_pct`` for one run,
    from the per-kind delta across the timed window. Empty when the
    ledger was unreadable on either side — a row must never carry a
    goodput number computed against a missing baseline."""
    if after is None:
        after = _goodput_kinds()
    if before is None or after is None:
        return {}
    d = {k: int(after.get(k, 0)) - int(before.get(k, 0)) for k in after}
    useful = d.get("useful", 0)
    wasted = sum(v for k, v in d.items() if k != "useful")
    attributed = useful + wasted
    return {
        "goodput_tok_s": round(useful / max(dt, 1e-9), 1),
        "waste_pct": (round(100.0 * wasted / attributed, 2)
                      if attributed > 0 else 0.0),
    }


def _fmt_goodput(row, pad=""):
    if "goodput_tok_s" in row:
        print(f"{pad} goodput: {row['goodput_tok_s']:.1f} useful tok/s  "
              f"waste={row['waste_pct']}%", flush=True)


_HEDGE_FROM_ARGS = object()      # sentinel: None must mean OFF (the A/B
#   baseline leg), not "derive from --hedge"


def run_remote_fleet(args, hedge_after=_HEDGE_FROM_ARGS):
    """--remote-fleet: the fleet as REAL OS processes (one supervised
    replica_main per replica over the C-API socket protocol), optionally
    behind deterministic net-chaos proxies (--netchaos / --netchaos-first)
    and with hedged requests armed (--hedge). Reports availability,
    failover/retry/hedge/stall counts, per-point injection tallies — the
    hostile-network drill as a reproducible bench row."""
    from paddlepaddle_tpu.distributed.env import refuse_chip_contention
    from paddlepaddle_tpu.inference.remote_replica import (
        ProcessReplicaFactory,
    )
    from paddlepaddle_tpu.inference.router import ServingRouter
    from paddlepaddle_tpu.resilience.netchaos import NetChaosProxy

    # this process builds no model and touches no jax backend, so one
    # replica process finds the chip free; several would fight over it
    refuse_chip_contention(args.replicas, "--replicas")
    if hedge_after is _HEDGE_FROM_ARGS:
        hedge_after = (None if args.hedge in (None, "off")
                       else "auto" if args.hedge == "auto"
                       else float(args.hedge))
    factory = ProcessReplicaFactory(
        preset=args.preset,
        client_kw={"heartbeat_timeout_s": args.heartbeat_timeout})
    clients = [factory(name=f"r{i}") for i in range(args.replicas)]
    vocab = 128 if args.preset == "tiny" else 512
    rng = np.random.default_rng(0)
    # fixed prompt length: varying lengths would make the tail a
    # compile-bucket lottery (fresh processes pay one prefill compile
    # per shape), drowning the wire effects this row measures
    prompts = [rng.integers(1, vocab, size=8).astype(np.int32)
               for _ in range(args.reqs)]
    for c in clients:
        # warm each replica's compile caches BEFORE the chaos proxies
        # arm (a warmup frame must not burn a scheduled @N hit) and
        # outside the router, so the counters stay workload-only
        try:
            c.start()             # spawn the process now, not at probe
            c.submit(prompts[0],
                     max_new_tokens=args.new_tokens).result(120)
        except Exception as e:  # noqa: BLE001 — warmup best-effort
            sys.stderr.write(
                f"  warmup {c.name}: {type(e).__name__}: {e}\n")
    proxies = []
    for i, c in enumerate(clients):
        spec = args.netchaos or (args.netchaos_first if i == 0 else None)
        if spec:
            px = NetChaosProxy(c.address, specs=spec,
                               seed=args.netchaos_seed,
                               name=f"netchaos:{c.name}").start()
            c._nc_proxy = px      # the client's PADDLE_NETCHAOS seam,
            proxies.append(px)    # armed programmatically per replica
    def _fleet_goodput():
        # decode happens in the replica PROCESSES: their ledgers are the
        # source of truth, summed over the health RPC (a dead or chaos-
        # wedged replica just contributes nothing)
        total, seen = {}, 0
        for c in clients:
            try:
                kinds = (c.health().get("goodput") or {}).get("kinds") or {}
            except Exception:
                continue
            seen += 1
            for k, v in kinds.items():
                total[k] = total.get(k, 0) + int(v)
        return total if seen else None

    router = ServingRouter(clients, probe_interval_s=0.2,
                           hedge_after_s=hedge_after,
                           hedge_budget_pct=args.hedge_budget)
    stalls0 = _scrape_counter("paddle_replica_stalls_total") or 0.0
    gp0 = _fleet_goodput()
    router.start()
    try:
        t0 = time.perf_counter()
        futs, submitted = [], 0
        for p in prompts:
            submitted += 1
            try:
                futs.append((p, router.submit(
                    p, max_new_tokens=args.new_tokens)))
            except Exception as e:  # noqa: BLE001 — availability metric
                sys.stderr.write(
                    f"  submit refused: {type(e).__name__}: {e}\n")
            if args.pace:
                # open-loop pacing: keep in-flight low so TTFT measures
                # the wire/decode tail, not self-inflicted queue wait —
                # the regime hedging exists for
                time.sleep(args.pace)
        completed = new_tokens = 0
        for p, f in futs:
            try:
                out = f.result(600)
            except Exception as e:  # noqa: BLE001 — availability metric
                sys.stderr.write(
                    f"  request failed: {type(e).__name__}: {e}\n")
            else:
                completed += 1
                new_tokens += len(out) - len(p)
        dt = time.perf_counter() - t0
        h = router.health()["router"]
        stalls = (_scrape_counter("paddle_replica_stalls_total")
                  or 0.0) - stalls0
        row = {"remote_fleet": True, "replicas": args.replicas,
               "preset": args.preset,
               "netchaos": args.netchaos or args.netchaos_first,
               "netchaos_seed": args.netchaos_seed,
               "hedge_after_s": (str(hedge_after)
                                 if hedge_after is not None else "off"),
               "aggregate_tok_s": round(new_tokens / max(dt, 1e-9), 1),
               "wall_s": round(dt, 2),
               "availability": round(completed / max(submitted, 1), 4),
               "failovers": h["failovers"], "retries": h["retries"],
               "hedges": h["hedges"], "hedge_wins": h["hedge_wins"],
               "stalls": int(stalls)}
        row.update(_goodput_cols(gp0, dt, after=_fleet_goodput()))
        if proxies:
            fires = {}
            for px in proxies:
                for point, n in px.fire_counts().items():
                    fires[point] = fires.get(point, 0) + n
            row["netchaos_fires"] = fires
        row.update(slo_summary([f for _, f in futs]))
        return row
    finally:
        router.stop()
        for px in proxies:
            px.stop()
        for c in clients:
            c.stop()


def fmt_remote(row):
    print(f"remote fleet x{row['replicas']} ({row['preset']})  "
          f"availability={row['availability']:.3f}  "
          f"failovers={row['failovers']}  stalls={row['stalls']}  "
          f"hedges={row['hedges']} (wins={row['hedge_wins']})"
          + (f"  netchaos={row['netchaos']} fires={row['netchaos_fires']}"
             if row.get("netchaos") else ""))
    print(f"  SLO: ttft p50={row['ttft_p50_ms']}ms "
          f"p99={row['ttft_p99_ms']}ms  wall={row['wall_s']}s", flush=True)
    _fmt_goodput(row, " ")


def run_traffic(model, prompts, args):
    """Open-loop profile against one engine, a fixed router fleet
    (--replicas N), or an AUTOSCALED fleet (--autoscale MIN:MAX arms a
    FleetController whose replicas arm from the shared model; the row
    then carries scaleup_to_healthy_s + the final census)."""
    traffic = parse_traffic(args.traffic)
    rng = np.random.default_rng(42)
    offsets = arrival_offsets(traffic, args.rate, len(prompts), rng)

    def engine_factory(version=None):
        return ServingEngine(model, max_batch_size=args.slots,
                             decode_chunk=args.chunk,
                             kv_layout=args.kv_layout,
                             kv_page_size=args.page_size,
                             kv_num_pages=args.num_pages)

    fc = router = eng = None
    if args.autoscale:
        from paddlepaddle_tpu.inference.fleet import (
            FleetController,
            FleetPolicy,
        )

        lo, _, hi = args.autoscale.partition(":")
        lo, hi = int(lo), int(hi)
        policy = FleetPolicy(
            min_replicas=lo, max_replicas=hi,
            scale_up_est_wait_s=args.scale_est_wait,
            up_streak=2, down_streak=20,
            cooldown_up_s=2.0, cooldown_down_s=60.0,
            interval_s=0.25, health_timeout_s=300.0,
            drain_timeout_s=30.0)
        fc = FleetController(engine_factory,
                             initial_replicas=max(args.replicas, lo),
                             policy=policy, probe_interval_s=0.2)
        fc.start(autoscaler=False)   # warm first, scale later
        engines = [rep.client.engine for rep in fc.router._replicas]
        submit = fc.submit
    elif args.replicas > 1:
        from paddlepaddle_tpu.inference.router import ServingRouter

        router = ServingRouter([engine_factory
                                for _ in range(args.replicas)],
                               probe_interval_s=0.2)
        router.start()
        engines = [rep.client.engine for rep in router._replicas]
        submit = router.submit
    else:
        eng = engine_factory()
        engines = [eng]
        submit = eng.submit
    try:
        for e in engines:
            warm_engine(e, model, prompts, args)
        if fc is not None:
            fc.start()               # autoscaler loop joins, warmed
        records, wall = run_open_loop(submit, prompts, offsets, args)
        row = {"traffic": args.traffic, "rate": args.rate,
               "replicas": (len(fc.router._replicas) if fc is not None
                            else args.replicas),
               "wall_s": wall}
        row.update(traffic_summary(records, traffic, args.window))
        if fc is not None:
            h = fc.health()["fleet"]
            row["autoscale"] = args.autoscale
            row["replicas_initial"] = max(args.replicas, lo)
            row["replicas_final"] = h["replicas"]
            row["scale_ups"] = h["stats"]["scale_ups"]
            row["scale_downs"] = h["stats"]["scale_downs"]
            row["scaleup_to_healthy_s"] = h["stats"]["scaleup_to_healthy_s"]
        return row
    finally:
        if fc is not None:
            fc.stop()
        elif router is not None:
            router.stop()
        else:
            eng.stop()


def fmt_traffic(row):
    print(f"open-loop {row['traffic']:<14} rate={row['rate']}/s  "
          f"completed={row['completed']}/{row['submitted']}  "
          f"dropped={row['dropped_requests']}  "
          f"ttft p99={row['ttft_p99_ms']}ms  "
          f"post-step p99={row['step_ttft_p99_ms']}ms"
          + (f"  scaleup_to_healthy={row['scaleup_to_healthy_s']}s "
             f"(replicas {row['replicas_initial']}->"
             f"{row['replicas_final']})"
             if "scaleup_to_healthy_s" in row else ""))
    print(f"  {'t(s)':>6}{'subm':>6}{'done':>6}{'drop':>6}{'tok/s':>9}"
          f"{'ttft p99(ms)':>14}")
    for w in row["windows"]:
        print(f"  {w['t_s']:>6.1f}{w['submitted']:>6}{w['completed']:>6}"
              f"{w['dropped']:>6}{w['tok_s']:>9.1f}"
              f"{'-' if w['ttft_p99_ms'] is None else w['ttft_p99_ms']:>14}")
    sys.stdout.flush()


def fmt_fleet(row):
    print(f"fleet x{row['replicas']:<14} {row['aggregate_tok_s']:8.1f} "
          f"tok/s  availability={row['availability']:.3f}  "
          f"failovers={row['failovers']}"
          + (f"  prefix_hit_rate={row['prefix_hit_rate']}"
             if row.get("prefix_hit_rate") is not None else ""))
    for pr in row["per_replica"]:
        print(f"  {pr['replica']:<20} {pr['tok_s']:8.1f} tok/s  "
              f"requests={pr['requests']}"
              + (f"  prefix_hits={pr['prefix_hits']}"
                 if "prefix_hits" in pr else ""))
    print(f"{'':<22} SLO: ttft p50={row['ttft_p50_ms']}ms "
          f"p99={row['ttft_p99_ms']}ms  tpot={row['tpot_ms']}ms/token  "
          f"queue_wait p99={row['queue_wait_p99_ms']}ms", flush=True)
    _fmt_goodput(row, f"{'':<22}")


def fmt(row, label):
    print(f"{label:<22} {row['aggregate_tok_s']:8.1f} tok/s  "
          f"concurrency_peak={row['concurrency_peak']}"
          + (f"  occupancy_peak={row['kv_occupancy_peak']:.0%}"
             if "kv_occupancy_peak" in row else "")
          + (f"  prefix_hit_rate={row['prefix_hit_rate']}"
             if row.get("prefix_hit_rate") is not None else ""))
    print(f"{'':<22} SLO: ttft p50={row['ttft_p50_ms']}ms "
          f"p99={row['ttft_p99_ms']}ms  tpot={row['tpot_ms']}ms/token  "
          f"queue_wait p99={row['queue_wait_p99_ms']}ms", flush=True)
    _fmt_goodput(row, f"{'':<22}")
    if "spec_k" in row:
        print(f"{'':<22} spec: k={row['spec_k']} draft={row['draft']} "
              f"({row['draft_params_m']}M, {row['draft_quant']})  "
              f"acceptance={row['acceptance_rate']}  "
              f"tok/target-step={row['tokens_per_target_step']}  "
              f"run p50/p99={row['accept_run_p50']}/"
              f"{row['accept_run_p99']}  rollbacks={row['rollbacks']}",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", choices=("uniform", "mixed", "prefix"),
                    default="uniform")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--reqs", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--kv-layout", choices=("paged", "contiguous"),
                    default="paged")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged-pool capacity (default: slots x max_len "
                    "worth — the contiguous pool's bytes)")
    ap.add_argument("--ab", action="store_true",
                    help="run paged AND contiguous at the same KV byte "
                    "budget (--budget-slots contiguous slots define it)")
    ap.add_argument("--budget-slots", type=int, default=None,
                    help="contiguous slots whose bytes fix the A/B budget "
                    "(default slots//2)")
    ap.add_argument("--prefix-count", type=int, default=1,
                    help="distinct system prompts for --profile prefix "
                         "(> 1 turns it into the tiered-cache drill: a "
                         "prefix working set bigger than the device pool "
                         "round-robins through the host tier)")
    ap.add_argument("--prefix-len", type=int, default=256,
                    help="shared system-prompt length (prefix profile)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="route the workload through a ServingRouter over "
                    "N replica engines (per-replica + fleet tokens/s, "
                    "failovers, availability)")
    ap.add_argument("--traffic", default=None,
                    help="OPEN-LOOP arrival profile instead of the "
                    "closed-loop submit-all: step:<mult>@<t> (base --rate "
                    "req/s multiplied by <mult> from <t> seconds in) or "
                    "poisson:<rate>; reports per-window tok/s + TTFT p99 "
                    "+ dropped count (the queueing-collapse signal the "
                    "closed loop hides)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="base arrival rate req/s for --traffic "
                    "(default 4)")
    ap.add_argument("--window", type=float, default=1.0,
                    help="--traffic reporting window seconds (default 1)")
    ap.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                    help="arm a FleetController over the --replicas "
                    "initial fleet (requires --traffic): SLO/est-wait "
                    "autoscaling between MIN and MAX replicas; the row "
                    "adds scaleup_to_healthy_s + the final census")
    ap.add_argument("--scale-est-wait", type=float, default=0.5,
                    help="autoscaler scale-up est-wait bound seconds "
                    "(default 0.5)")
    ap.add_argument("--tp", type=int, default=1,
                    help="also run the workload through a TENSOR-PARALLEL "
                    "engine (mesh mp<N>, weights + kv heads sharded) and "
                    "report its tok/s + TTFT beside the 1-chip row; needs "
                    "N visible devices (CPU: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="arm speculative decoding with N draft proposals "
                    "per target step and report an A/B row beside the "
                    "plain engine (tok/s, TTFT/TPOT, acceptance rate, "
                    "accepted-run-length p50/p99)")
    ap.add_argument("--draft", choices=("self", "half", "quarter"),
                    default="quarter",
                    help="draft preset: 'self' = the target model itself "
                    "(acceptance 1.0 — the amortization upper bound), "
                    "'half'/'quarter' = fresh models at that fraction of "
                    "the target width (random weights: the overhead "
                    "lower bound on this harness)")
    ap.add_argument("--draft-quant", action="store_true",
                    help="serve the draft weight-only int8")
    ap.add_argument("--kv-quant", choices=("off", "int8"), default="off",
                    help="quantize paged KV pages to int8 codes with "
                         "per-page-per-head scales (halves page bytes; "
                         "with --ab, adds an int8 arm at the SAME byte "
                         "budget as the bf16 paged arm)")
    ap.add_argument("--kv-host-mb", type=int, default=0,
                    help="host-RAM prefix tier budget in MB: refcount-0 "
                         "prefix entries spill page slabs to host RAM on "
                         "eviction and restore into fresh device pages "
                         "on re-hit (0 = tier off)")
    ap.add_argument("--remote-fleet", action="store_true",
                    help="run the --replicas fleet as REAL OS processes "
                    "(supervised replica_main per replica over the C-API "
                    "socket protocol) — the surface --netchaos and "
                    "--hedge apply to")
    ap.add_argument("--preset", choices=("tiny", "small"), default="tiny",
                    help="replica_main model preset for --remote-fleet")
    ap.add_argument("--netchaos", default=None, metavar="SPEC",
                    help="deterministic net-fault proxy in front of EVERY "
                    "replica (PADDLE_NETCHAOS grammar, e.g. "
                    "'down:blackhole:@3' or 'down:delay:0.3:250'); "
                    "requires --remote-fleet")
    ap.add_argument("--netchaos-first", default=None, metavar="SPEC",
                    help="like --netchaos but only replica r0 — the "
                    "single-slow-replica tail profile hedging exists for")
    ap.add_argument("--netchaos-seed", type=int, default=0)
    ap.add_argument("--hedge", default="off",
                    help="router hedge_after_s: 'off', 'auto' (observed "
                    "TTFT p99 via tsdb), or seconds (e.g. 0.5)")
    ap.add_argument("--hedge-budget", type=float, default=25.0,
                    help="hedge budget as %% of submits (default 25)")
    ap.add_argument("--hedge-ab", action="store_true",
                    help="run the --remote-fleet workload twice — hedging "
                    "off then --hedge — and report the TTFT p99 delta")
    ap.add_argument("--heartbeat-timeout", type=float, default=2.0,
                    help="client stall-watchdog seconds (default 2)")
    ap.add_argument("--pace", type=float, default=0.0,
                    help="--remote-fleet: sleep this many seconds between "
                    "submits (open-loop pacing; 0 = submit all at once)")
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the final JSON to PATH as a "
                    "perf_gate-ready artifact (BENCH_serving_r<NN>.json "
                    "shape: the body plus a meta block with git sha + "
                    "unix stamp)")
    args = ap.parse_args()

    if (args.netchaos or args.netchaos_first or args.hedge_ab) \
            and not args.remote_fleet:
        ap.error("--netchaos/--netchaos-first/--hedge-ab exercise the "
                 "socket wire path; add --remote-fleet")
    if args.remote_fleet:
        # no local model: the replica processes build their own preset
        body = {"remote_fleet": True, "replicas": args.replicas,
                "requests": args.reqs,
                "new_tokens_per_req": args.new_tokens}
        if args.hedge_ab:
            base = run_remote_fleet(args, hedge_after=None)
            fmt_remote(base)
            hedge_after = ("auto" if args.hedge == "auto"
                           else float(args.hedge)
                           if args.hedge not in (None, "off") else 0.5)
            hedged = run_remote_fleet(args, hedge_after=hedge_after)
            fmt_remote(hedged)
            body["hedge_off"] = base
            body["hedge_on"] = hedged
            if base.get("ttft_p99_ms") and hedged.get("ttft_p99_ms"):
                body["hedge_ttft_p99_improvement_pct"] = round(
                    100.0 * (base["ttft_p99_ms"] - hedged["ttft_p99_ms"])
                    / base["ttft_p99_ms"], 1)
                print(f"hedge A/B: ttft p99 {base['ttft_p99_ms']}ms -> "
                      f"{hedged['ttft_p99_ms']}ms "
                      f"({body['hedge_ttft_p99_improvement_pct']:+.1f}%)",
                      flush=True)
        else:
            row = run_remote_fleet(args)
            fmt_remote(row)
            body.update(row)
        _emit(body, args)
        return

    model = build_model(args)
    cfg = model.config
    rng = np.random.default_rng(0)
    prompts = gen_prompts(args, cfg, rng)

    # single-sequence baseline (one request, same budget)
    p0 = prompts[0][0]
    model.generate_cached(p0[None], max_new_tokens=args.new_tokens,
                          temperature=0.0)
    t0 = time.perf_counter()  # second call: compiled
    model.generate_cached(p0[None], max_new_tokens=args.new_tokens,
                          temperature=0.0)
    single_dt = time.perf_counter() - t0
    single_tps = args.new_tokens / single_dt
    print(f"single-sequence: {single_tps:8.1f} tok/s "
          f"({args.new_tokens} tokens in {single_dt:.2f}s)", flush=True)

    body = {"profile": args.profile, "requests": args.reqs,
            "new_tokens_per_req": args.new_tokens,
            "single_tok_s": round(single_tps, 1)}

    if args.tp > 1 and (args.replicas > 1 or args.ab):
        ap.error("--tp compares one engine against its tensor-parallel "
                 "form; run it with --replicas 1 and without --ab")

    if args.autoscale:
        if not args.traffic:
            ap.error("--autoscale needs an open-loop --traffic profile "
                     "(a closed loop cannot exercise the scale signal)")
        lo, sep, hi = args.autoscale.partition(":")
        if not sep or not lo.isdigit() or not hi.isdigit():
            ap.error(f"--autoscale expects MIN:MAX (e.g. 2:4), "
                     f"got {args.autoscale!r}")
    if args.traffic:
        if args.ab or args.tp > 1 or args.spec_k > 0:
            ap.error("--traffic is the open-loop profile; run it without "
                     "--ab/--tp/--spec-k")
        row = run_traffic(model, prompts, args)
        fmt_traffic(row)
        body["traffic"] = row
        _emit(body, args)
        return

    if args.replicas > 1:
        if args.ab:
            ap.error("--ab compares one engine's KV layouts; "
                     "run it with --replicas 1")
        row = run_fleet(model, prompts, args)
        fmt_fleet(row)
        body.update(row)
        if args.profile == "mixed":
            body["mixed_tok_s"] = body["aggregate_tok_s"]
        _emit(body, args)
        return

    if args.ab:
        # fixed KV byte budget: slots_c contiguous slots' worth of pool
        slots_c = args.budget_slots or max(args.slots // 2, 1)
        pages_budget = slots_c * (-(-cfg.max_position_embeddings
                                    // args.page_size)) + 1
        print(f"A/B at a fixed KV budget = {slots_c} contiguous slots "
              f"({pages_budget - 1} pages of {args.page_size}):")
        con = run_serving(model, prompts, args, "contiguous", slots_c)
        fmt(con, f"contiguous x{slots_c}")
        pag = run_serving(model, prompts, args, "paged", args.slots,
                          num_pages=pages_budget)
        fmt(pag, f"paged x{args.slots}")
        body.update(pag)         # headline row = the paged engine
        body["contiguous"] = con
        body["kv_budget_slots"] = slots_c
        if args.kv_quant == "int8":
            # int8 arm at the SAME device byte budget: the bf16 arm's
            # pool bytes re-divided by the int8 page size (codes + f32
            # per-page-per-head scales) — more pages, identical HBM spend
            cfg_kv = model.config
            int8_page_bytes = (
                args.page_size * 2 * cfg_kv.num_key_value_heads
                * cfg_kv.head_dim * cfg_kv.num_hidden_layers
                + 2 * cfg_kv.num_key_value_heads * 4
                * cfg_kv.num_hidden_layers)
            usable = (pages_budget - 1) * pag["kv_page_bytes"]
            pages_int8 = int(usable // int8_page_bytes) + 1
            qrow = run_serving(model, prompts, args, "paged", args.slots,
                               num_pages=pages_int8, kv_quant="int8")
            fmt(qrow, f"paged int8 x{args.slots}")
            ratio = (qrow["concurrency_peak"]
                     / max(pag["concurrency_peak"], 1))
            print(f"(int8 KV: {pages_int8 - 1} pages vs "
                  f"{pages_budget - 1} at equal bytes, "
                  f"{ratio:.2f}x concurrency peak)")
            body["kv_quant_ab"] = {
                "baseline": {k: pag.get(k) for k in
                             ("aggregate_tok_s", "concurrency_peak",
                              "kv_pages_total", "kv_page_bytes")},
                "int8": qrow,
                "concurrency_ratio": round(ratio, 3),
            }
    else:
        row = run_serving(model, prompts, args, args.kv_layout, args.slots,
                          num_pages=args.num_pages,
                          kv_quant=(None if args.kv_quant == "off"
                                    else args.kv_quant),
                          kv_host_bytes=(args.kv_host_mb << 20
                                         if args.kv_host_mb else None))
        fmt(row, f"{args.kv_layout} x{args.slots}"
            + (f" kv={args.kv_quant}" if args.kv_quant != "off" else "")
            + (f" host={args.kv_host_mb}MB" if args.kv_host_mb else ""))
        body.update(row)
        print(f"({row['aggregate_tok_s'] / max(single_tps, 1e-9):.1f}x "
              "single-sequence)")

    if args.spec_k > 0:
        if args.ab or args.replicas > 1 or args.tp > 1:
            ap.error("--spec-k A/Bs one engine against its speculative "
                     "form; run it without --ab/--replicas/--tp")
        spec_row = run_serving(model, prompts, args, args.kv_layout,
                               args.slots, num_pages=args.num_pages,
                               spec=True)
        fmt(spec_row, f"spec k={args.spec_k} x{args.slots}")
        base = body["aggregate_tok_s"]
        print(f"({spec_row['aggregate_tok_s'] / max(base, 1e-9):.2f}x the "
              "non-speculative row)")
        body["spec"] = spec_row
        body["spec_tok_s"] = spec_row["aggregate_tok_s"]
        body["spec_acceptance_rate"] = spec_row["acceptance_rate"]

    if args.tp > 1:
        # tensor-parallel column: same workload through a plan-sharded
        # engine (single-chip row above is the baseline). On a real mesh
        # this is the models-bigger-than-one-chip row; on a forced-host
        # CPU mesh the speedup reads ~1x (shared silicon) and the value
        # is the parity + HBM-per-chip column
        tpr = run_serving(model, prompts, args, args.kv_layout, args.slots,
                          num_pages=args.num_pages, tp=args.tp)
        fmt(tpr, f"tp{args.tp} x{args.slots}")
        body["tp"] = tpr
        body["tp_tok_s"] = tpr["aggregate_tok_s"]

    if args.profile == "prefix":
        # control: same workload, prompt cache off — the TTFT delta IS the
        # prefill work the cache removes
        ctl = run_serving(model, prompts, args, args.kv_layout, args.slots,
                          num_pages=args.num_pages, prefix_cache=False)
        fmt(ctl, "prefix-cache OFF")
        body["no_prefix_cache"] = ctl
    if args.profile == "mixed":
        body["mixed_tok_s"] = body["aggregate_tok_s"]

    _emit(body, args)


if __name__ == "__main__":
    main()
