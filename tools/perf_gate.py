#!/usr/bin/env python
"""Perf regression gate — compare bench/serving artifacts against a baseline.

Given a baseline record (a driver artifact wrapping ``bench.py``'s line
under ``parsed``, or the raw ``bench.py`` JSON line) and a current one, compare every shared metric with direction-aware
tolerances and exit nonzero on regression:

* **higher-is-better** (tokens/s, images/s, MFU): regression when
  ``(base - cur) / base > tol`` (default ``--tol 0.05``);
* **lower-is-better** (TTFT p50/p99, TPOT, step_ms): regression when
  ``(cur - base) / base > tol-latency`` (default 0.25 — latency tails are
  noisier than throughput means).

Serving SLO artifacts (the JSON lines ``tools/serving_bench.py`` /
``tools/quant_ab.py`` print, or the ``--out`` artifacts with a ``meta``
block) are compared with ``--serving CUR BASE``.
Metrics present in the baseline but missing from the current artifact are
reported as warnings (``--strict`` promotes them to failures): a bench that
silently stopped reporting a number must not pass as "no regression".

``--json`` prints ONE machine-readable verdict object on stdout (the
human report moves to stderr) with per-field
baseline/candidate/delta/direction/verdict rows — the shape CI and the
``inference/fleet.py`` deploy gate (``perf_verdict_gate``) consume
without parsing human text::

    {"ok": bool, "strict": bool, "tol": .., "tol_latency": ..,
     "regressions": [names], "missing": [names],
     "fields": [{"metric", "baseline", "candidate", "delta",
                 "direction", "verdict"}, ...]}

Usage:
    python tools/perf_gate.py --baseline base.json --current out.json
    python tools/perf_gate.py --baseline base.json --current out.json \
        --serving serving_now.json serving_base.json
    python tools/perf_gate.py --baseline base.json --dry-run
        # parse + report only, always exit 0 (the run_tier1 smoke)
    python tools/perf_gate.py --baseline base.json --current out.json \
        --json > verdict.json

Exit codes: 0 ok / 1 regression (or missing metric under --strict) /
2 unusable inputs.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Optional, Tuple

HIGHER = "higher"   # throughput/utilization: dropping is a regression
LOWER = "lower"     # latency: rising is a regression


def _first_json(text: str) -> Optional[dict]:
    """Last parseable JSON object line (benches print progress first)."""
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def load_record(path: str) -> dict:
    """Load a driver record (uses its ``parsed`` field), a raw
    bench stdout capture, a bench ``--out`` artifact (``meta`` block +
    body — the body keys pass through untouched), or a plain JSON
    object."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = _first_json(text)
    if doc is None:
        raise ValueError(f"{path}: no JSON object found")
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object")
    return doc


def bench_metrics(doc: dict) -> Dict[str, Tuple[float, str]]:
    """{metric_name: (value, direction)} extracted from a bench record."""
    out: Dict[str, Tuple[float, str]] = {}

    def put(name, value, direction=HIGHER):
        if isinstance(value, (int, float)):
            out[name] = (float(value), direction)

    put("llama.tokens_per_sec", doc.get("value"))
    detail = doc.get("detail") or {}
    put("llama.mfu", detail.get("mfu"))
    put("llama.mfu_measured", detail.get("mfu_measured"))
    configs = detail.get("configs") or {}
    moe = configs.get("moe") or {}
    put("moe.tokens_per_sec", moe.get("tokens_per_sec"))
    put("moe.mfu_active", moe.get("mfu_active"))
    rn = configs.get("resnet50") or {}
    put("resnet50.images_per_sec", rn.get("images_per_sec"))
    put("resnet50.mfu_measured", rn.get("mfu_measured"))
    put("resnet50.step_ms", rn.get("step_ms"), LOWER)
    lm = configs.get("llama_max") or {}
    put("llama_max.tokens_per_sec", lm.get("tokens_per_sec"))
    put("llama_max.mfu", lm.get("mfu"))
    # multichip record (bench.py --mesh / the MULTICHIP dryrun line):
    # gate the per-config mesh THROUGHPUT columns only, higher-is-better.
    # scaling_efficiency / throughput_retention / speedup are the same
    # signal divided by the (gated) 1-chip rate — gating them too would
    # double-fail every real regression and flap on the ratio noise the
    # BASELINE.md multichip section documents
    mc = doc.get("multichip") or {}
    for cname, row in sorted((mc.get("configs") or {}).items()):
        if not isinstance(row, dict) or "error" in row:
            continue
        put(f"multichip.{cname}.tokens_per_sec", row.get("tokens_per_sec"))
        put(f"multichip.{cname}.tok_s", row.get("tok_s"))
    # MoE dispatch shoot-out (tools/moe_dispatch_bench.py {"moe_dispatch":
    # …} line): dispatch_ms is the best capacity-semantics formulation's
    # ms/call (the fused gather-GEMM row where it wins) — lower-is-better
    # under the latency budget; the fused row rides along so a kernel
    # regression can't hide behind the XLA path winning the min
    md = doc.get("moe_dispatch")
    if isinstance(md, dict):
        put("moe.dispatch_ms", md.get("dispatch_ms"), LOWER)
        put("moe.dispatch_fused_ms", md.get("fused_ms"), LOWER)
    return out


def serving_metrics(doc: dict) -> Dict[str, Tuple[float, str]]:
    """SLO metrics from a serving_bench / quant_ab JSON line."""
    out: Dict[str, Tuple[float, str]] = {}
    body = doc.get("serving_bench") or doc.get("quant_ab") or doc

    def put(name, value, direction):
        if isinstance(value, (int, float)):
            out[name] = (float(value), direction)

    put("serving.aggregate_tok_s", body.get("aggregate_tok_s"), HIGHER)
    # paged-KV / prefix-cache columns (serving_bench --profile mixed/prefix):
    # throughput-and-packing numbers fall under --tol, occupancy (a
    # memory-per-workload number, lower = better packing) under the
    # latency budget since it's the noisier tail-ish statistic
    put("serving.mixed_tok_s", body.get("mixed_tok_s"), HIGHER)
    put("serving.prefix_hit_rate", body.get("prefix_hit_rate"), HIGHER)
    put("serving.concurrency_peak", body.get("concurrency_peak"), HIGHER)
    put("serving.kv_occupancy_peak", body.get("kv_occupancy_peak"), LOWER)
    # fleet-router column (serving_bench --replicas N): completed/submitted
    # under the workload — the availability the failover path defends
    put("serving.availability", body.get("availability"), HIGHER)
    # goodput columns: USEFUL tokens/s (delivered, post-trim) and the
    # wasted share of attributed tokens. waste_pct LOWER with the
    # zero-LOWER-baseline rule means a clean baseline pins a zero floor —
    # any new hedging/retry/overshoot waste is an infinite regression
    # until the baseline is re-cut with it
    put("serving.goodput_tok_s", body.get("goodput_tok_s"), HIGHER)
    put("serving.waste_pct", body.get("waste_pct"), LOWER)
    # tiered-prefix columns (serving_bench --kv-host-mb N): the host-tier
    # restore must stay far cheaper than the prefill it replaces — both
    # percentiles gated LOWER so a serializer/scatter regression in the
    # spill/restore path cannot hide behind the hit-rate staying high
    put("serving.prefix_restore_ms_p50",
        body.get("prefix_restore_ms_p50"), LOWER)
    put("serving.prefix_restore_ms_p99",
        body.get("prefix_restore_ms_p99"), LOWER)
    # int8-KV arm (serving_bench --ab --kv-quant int8): at the SAME pool
    # bytes the quantized engine must keep its throughput AND its packing
    # win (the ~2x-pages concurrency peak) — either sliding means the
    # quant path lost its reason to exist
    kvq = body.get("kv_quant_ab")
    if isinstance(kvq, dict) and isinstance(kvq.get("int8"), dict):
        put("serving.kvq_mixed_tok_s",
            kvq["int8"].get("aggregate_tok_s"), HIGHER)
        put("serving.kvq_concurrency_peak",
            kvq["int8"].get("concurrency_peak"), HIGHER)
    # speculative column (serving_bench --spec-k N): gate the throughput;
    # the acceptance rate is a DRAFT-QUALITY number, not an engine-perf
    # number (a better-trained draft raises it, an engine change cannot),
    # so it is reported informationally by main(), never gated
    spec = body.get("spec")
    if isinstance(spec, dict):
        put("serving.spec_tok_s", spec.get("aggregate_tok_s"), HIGHER)
        put("serving.spec_ttft_p50_ms", spec.get("ttft_p50_ms"), LOWER)
        put("serving.spec_tpot_ms", spec.get("tpot_ms"), LOWER)
        # spec goodput: rejected drafts are the waste speculation PAYS
        # for its latency win — the pair keeps the trade visible
        put("serving.spec_goodput_tok_s", spec.get("goodput_tok_s"),
            HIGHER)
        put("serving.spec_waste_pct", spec.get("waste_pct"), LOWER)
    # elastic-fleet column (serving_bench --traffic [--autoscale]): the
    # post-step TTFT p99 is the SLO the autoscaler must hold through a
    # traffic step; dropped_requests is a HARD ZERO floor (the zero-LOWER-
    # baseline rule below makes ANY growth an infinite regression — the
    # fleet's zero-drop invariant is not a 25%-budget number); the
    # scale-up wall is the bundle-armed bring-up time — it creeping up
    # means replicas stopped arming from the AOT bundle/cache
    fl = body.get("traffic")
    if isinstance(fl, dict):
        put("fleet.step_ttft_p99_ms", fl.get("step_ttft_p99_ms"), LOWER)
        put("fleet.dropped_requests", fl.get("dropped_requests"), LOWER)
        put("fleet.scaleup_to_healthy_s",
            fl.get("scaleup_to_healthy_s"), LOWER)
    # tensor-parallel column (serving_bench --tp N): throughput up, TTFT/
    # TPOT down — a plan change that tanks the tp engine must not pass
    tp = body.get("tp")
    if isinstance(tp, dict):
        put("serving.tp_tok_s", tp.get("aggregate_tok_s"), HIGHER)
        put("serving.tp_ttft_p50_ms", tp.get("ttft_p50_ms"), LOWER)
        put("serving.tp_tpot_ms", tp.get("tpot_ms"), LOWER)
    for slo_src in (body,) + tuple(
            body.get(k) for k in ("bf16", "int8") if isinstance(
                body.get(k), dict)):
        prefix = "serving" if slo_src is body else (
            "quant.bf16" if slo_src is body.get("bf16") else "quant.int8")
        put(f"{prefix}.ttft_p50_ms", slo_src.get("ttft_p50_ms"), LOWER)
        put(f"{prefix}.ttft_p99_ms", slo_src.get("ttft_p99_ms"), LOWER)
        put(f"{prefix}.tpot_ms", slo_src.get("tpot_ms"), LOWER)
        put(f"{prefix}.decode_tok_s", slo_src.get("decode_tok_s"), HIGHER)
    # cold-start artifact (tools/coldstart_bench.py {"coldstart": …} line):
    # the headline pair is the production restart strategy's numbers —
    # both lower-is-better, both under the latency budget (restart walls
    # are box-noisy; compiles creeping up means programs leaked back into
    # the restart path). Per-mode restart walls ride along so a bundle
    # regression can't hide behind a faster cold path
    cs = doc.get("coldstart") if isinstance(doc.get("coldstart"), dict) \
        else (body.get("coldstart")
              if isinstance(body.get("coldstart"), dict) else None)
    if cs is None and "restart_to_first_token_s" in body:
        cs = body
    if cs is not None:
        put("coldstart.restart_to_first_token_s",
            cs.get("restart_to_first_token_s"), LOWER)
        put("coldstart.compiles", cs.get("compiles"), LOWER)
        for mode in ("cold", "cache_warm", "bundle", "bundle_cache"):
            row = cs.get(mode)
            if isinstance(row, dict):
                put(f"coldstart.{mode}.restart_to_first_token_s",
                    row.get("restart_to_first_token_s"), LOWER)
    return out


def compare(base: Dict[str, Tuple[float, str]],
            cur: Dict[str, Tuple[float, str]],
            tol: float, tol_latency: float) -> Tuple[list, list, list]:
    """(failures, report_lines, rows) over metrics in the baseline.

    ``rows`` are the machine-readable per-field records behind ``--json``:
    ``{"metric", "baseline", "candidate", "delta", "direction",
    "verdict"}`` with verdict one of ok/improved/regression/missing.
    ``delta`` is the signed worse-ness fraction (>0 = worse, direction
    already folded in); an infinite delta (growth over a zero LOWER
    baseline) is published as null — the verdict carries the failure.
    """
    failures, lines, rows = [], [], []
    for name in sorted(base):
        bval, direction = base[name]
        centry = cur.get(name)
        if centry is None:
            lines.append(f"  {name:<28} base={bval:<12g} MISSING in current")
            failures.append(("missing", name))
            rows.append({"metric": name, "baseline": bval,
                         "candidate": None, "delta": None,
                         "direction": direction, "verdict": "missing"})
            continue
        cval = centry[0]
        budget = tol if direction == HIGHER else tol_latency
        if bval == 0:
            # a zero LOWER baseline is a hard floor (0 compiles on the
            # bundle path): ANY growth is an infinite relative regression,
            # not a divide-by-zero pass. A zero HIGHER baseline stays
            # ungateable (nothing to lose)
            delta = (float("inf") if direction == LOWER and cval > 0
                     else 0.0)
        elif direction == HIGHER:
            delta = (bval - cval) / abs(bval)    # >0 = got worse
        else:
            delta = (cval - bval) / abs(bval)
        verdict = "ok"
        word = "ok"
        if delta > budget:
            verdict = f"REGRESSION ({delta:+.1%} worse > {budget:.0%} budget)"
            word = "regression"
            failures.append(("regression", name))
        elif delta < -0.02:
            verdict = f"improved ({-delta:+.1%})"
            word = "improved"
        rows.append({"metric": name, "baseline": bval, "candidate": cval,
                     "delta": (round(delta, 6)
                               if delta != float("inf") else None),
                     "direction": direction, "verdict": word})
        lines.append(f"  {name:<28} base={bval:<12g} cur={cval:<12g} "
                     f"{verdict}")
    return failures, lines, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--baseline", required=True,
                    help="baseline record (driver record or bench output)")
    ap.add_argument("--current",
                    help="current record to gate (default: baseline vs "
                    "itself — a wiring smoke)")
    ap.add_argument("--serving", nargs=2, metavar=("CUR", "BASE"),
                    help="also gate a pair of serving_bench/quant_ab "
                    "artifacts (current, baseline)")
    ap.add_argument("--tol", type=float, default=0.05,
                    help="throughput/MFU regression budget (default 5%%)")
    ap.add_argument("--tol-latency", type=float, default=0.25,
                    help="TTFT/TPOT/step-time regression budget "
                    "(default 25%%)")
    ap.add_argument("--strict", action="store_true",
                    help="metrics missing from the current artifact fail "
                    "the gate instead of warning")
    ap.add_argument("--dry-run", action="store_true",
                    help="report only; always exit 0 (CI smoke)")
    ap.add_argument("--json", action="store_true",
                    help="print ONE machine-readable verdict object on "
                    "stdout (per-field baseline/candidate/delta/"
                    "direction/verdict) and move the human report to "
                    "stderr — the shape fleet.perf_verdict_gate and CI "
                    "consume")
    args = ap.parse_args(argv)

    def say(msg):
        # --json owns stdout (one JSON object, nothing else); the human
        # report stays readable on stderr
        (sys.stderr.write(msg + "\n") if args.json else print(msg))

    try:
        base = bench_metrics(load_record(args.baseline))
        cur = bench_metrics(load_record(args.current or args.baseline))
    except (OSError, ValueError) as e:
        sys.stderr.write(f"[perf_gate] {e}\n")
        return 2
    if not base:
        sys.stderr.write(f"[perf_gate] {args.baseline}: no gateable "
                         "metrics found\n")
        return 2

    failures, lines, rows = compare(base, cur, args.tol, args.tol_latency)
    say(f"[perf_gate] bench: {args.current or args.baseline} vs "
        f"{args.baseline} (tol {args.tol:.0%} throughput, "
        f"{args.tol_latency:.0%} latency)")
    say("\n".join(lines))

    if args.serving:
        try:
            rec_cur = load_record(args.serving[0])
            rec_base = load_record(args.serving[1])
        except (OSError, ValueError) as e:
            sys.stderr.write(f"[perf_gate] serving: {e}\n")
            return 2
        sfail, slines, srows = compare(serving_metrics(rec_base),
                                       serving_metrics(rec_cur),
                                       args.tol, args.tol_latency)
        failures += sfail
        rows += srows
        say(f"[perf_gate] serving: {args.serving[0]} vs {args.serving[1]}")
        say("\n".join(slines))
        for label, rec in (("cur", rec_cur), ("base", rec_base)):
            sb = rec.get("serving_bench") or rec
            rate = sb.get("spec_acceptance_rate")
            if rate is not None:
                say(f"[perf_gate] info: spec_acceptance_rate[{label}]="
                    f"{rate} (informational — draft quality, not gated)")

    regressions = [n for kind, n in failures if kind == "regression"]
    missing = [n for kind, n in failures if kind == "missing"]
    if missing and not args.strict:
        say(f"[perf_gate] warning: {len(missing)} baseline metric(s) "
            f"missing from current ({', '.join(missing)}) — "
            "--strict to fail on this")
    bad = bool(regressions) or (args.strict and bool(missing))
    if args.json:
        # the one stdout line under --json: fleet.perf_verdict_gate and
        # CI read this verbatim. "ok" already folds --strict in; a
        # non-strict run still lists the missing fields so a stricter
        # consumer can veto on them
        print(json.dumps({
            "ok": not bad, "strict": bool(args.strict),
            "tol": args.tol, "tol_latency": args.tol_latency,
            "regressions": regressions, "missing": missing,
            "fields": rows,
        }))
    if args.dry_run:
        say(f"[perf_gate] dry-run: would "
            f"{'FAIL' if bad else 'pass'} ({len(regressions)} "
            f"regression(s), {len(missing)} missing)")
        return 0
    if bad:
        say(f"[perf_gate] FAIL: {len(regressions)} regression(s)"
            + (f", {len(missing)} missing metric(s)" if args.strict
               and missing else ""))
        return 1
    say("[perf_gate] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
