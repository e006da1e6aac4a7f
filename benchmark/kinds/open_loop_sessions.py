"""Traffic kind ``open_loop_sessions``: requests sent to the serving engine on
a schedule, whatever it does with them. The schedule and the lengths come from
the traffic file (``loadgen.schedule``); the token ids and the weights from
``--seed``. All requests are greedy, so every served token can be checked.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import loadgen, window


def _send(engine, r):
    return engine.submit(r["ids"], max_new_tokens=r["output"], temperature=0.0,
                         prefix_len=r["prefix_len"])


def warm_up(engine, requests, seed, cfg, traffic):
    """One short session per class of shapes in the schedule, sent one request
    at a time so that a later turn finds its document cached, as in the window."""
    q = cfg["engine_facts"]
    groups = loadgen.warm_classes(requests, q["prompt_bucket"], q["page_tokens"])
    sent = 0
    for gi, group in enumerate(groups):
        for r in sorted(group, key=lambda r: r["turn"]):
            w = dict(r, session=10 ** 6 + gi, output=int(traffic["warm_output_tokens"]))
            loadgen.fill_tokens([w], seed, cfg["vocab_size"])
            _send(engine, w).result(timeout=900)
            sent += 1
    return sent


def record(r: dict) -> dict:
    """What the engine stamped on one request, on the benchmark's clock."""
    fut = r.get("future")
    out = {k: r.get(k) for k in ("session", "turn", "due", "submitted", "prompt", "output",
                                 "system", "document", "prefix_len", "error")}
    out.update(t_admit=None, t_first=None, t_done=None, tpot_s=None, new_tokens=0)
    if fut is None:
        out["error"] = out["error"] or "never submitted"
        return out
    s = fut.slo()
    at = lambda v: None if v is None else r["submitted"] + v
    out.update(t_admit=at(s["queue_wait_s"]), t_first=at(s["ttft_s"]), t_done=at(s["latency_s"]),
               tpot_s=s["tpot_s"], new_tokens=s["new_tokens"])
    if fut.done() and fut._error is not None:
        out["error"] = repr(fut._error)
    return out


def pick_sample(finished, n: int, seed: int):
    """The longest finished request, one of a first turn and one of a later turn
    where there are such, then others drawn from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    order = [int(i) for i in rng.permutation(len(finished))]
    longest = max(range(len(finished)), key=lambda i: finished[i]["prompt"] + finished[i]["output"])
    picked = [longest]
    for want in (0, 1):
        hit = next((i for i in order if min(finished[i]["turn"], 1) == want and i not in picked), None)
        if hit is not None and len(picked) < n:
            picked.append(hit)
    picked += [i for i in order if i not in picked][: max(0, n - len(picked))]
    return [finished[i] for i in picked]


def check_outputs(sample, ctx, control: str = ""):
    """[(name, value)]: the widest gap by which a served token's logit lies below
    the reference's best (over max|logit| at that position), and the count of
    outputs that lost their prompt or their length."""
    seqs, starts, broken = [], [], 0
    for r in sample:
        out = np.asarray(r["future"].result(timeout=1))
        if len(out) != r["prompt"] + r["output"] or not np.array_equal(out[: r["prompt"]], r["ids"]):
            broken += 1
            continue
        seqs.append(out)
        starts.append(r["prompt"])
    res = ctx.family.serve_reference(ctx.config, ctx.seed, seqs, starts, control=control)
    checks = [("token_gap", max((float(g.max()) for g in res["gap"]), default=float("inf"))),
              ("broken_outputs", float(broken))]
    if control:
        checks.append(("control_token_gap", max(float(g.max()) for g in res["control_gap"])))
    return checks, sum(len(g) for g in res["gap"])


def run(ctx) -> dict:
    from paddlepaddle_tpu.inference.serving import LOOP_PHASES      # the loop's own names for what it does between chunks

    cfg, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    clock = time.perf_counter
    engine = ctx.family.build_serve(cfg, seed)
    inner = engine._engine
    ctx.log("engine built")
    horizon = traffic["ramp_s"] + ctx.seconds + traffic["tail_s"]
    requests = loadgen.schedule(traffic, horizon)
    loadgen.fill_tokens(requests, seed, cfg["vocab_size"])
    warmed = warm_up(engine, requests, seed, cfg, traffic)
    ctx.log(f"{warmed} warm requests served")

    kv0, stats0, compile_before = inner.kv_stats(), dict(engine.stats), ctx.compile_stats()
    log = window.DeliveryLog(lambda: engine.stats["decode_tokens"], clock=clock)
    gen = loadgen.OpenLoop(requests, lambda r: _send(engine, r), clock=clock,
                           annotate=ctx.span if ctx.trace else None)
    gcs = window.GcWatch(clock=clock).start()
    log.start()
    gen.start()
    t_ready = gen.t0 + traffic["ramp_s"]
    if ctx.trace:
        time.sleep(max(0.0, t_ready - 1.5 - clock()))
        ctx.trace_open(at=t_ready)
    time.sleep(max(0.0, t_ready - clock()))
    ctx.mark_window_open()
    # this thread sleeps through the window: it wakes for the opening delivery
    # (a few times at most) and once after the closing edge
    opened = None
    while opened is None and clock() < t_ready + 120:
        time.sleep(0.1)
        opened = next((t for t, _ in log.events if t >= t_ready), None)
    if opened is not None:
        time.sleep(max(0.0, opened + ctx.seconds + 2 * window.POLL_S - clock()))
    ctx.mark_window_close()
    ctx.trace_close()
    gen.stop()
    i_open, i_close = window.delivery_window(log.events, t_ready, ctx.seconds)
    t_open, t_close = log.events[i_open][0], log.events[i_close][0]
    due_in = [r for r in requests if t_open <= r["due"] <= t_close]
    deadline = clock() + traffic["wait_after_close_s"]
    while clock() < deadline and not all(
            "future" not in r or r["future"].done() for r in due_in):
        time.sleep(0.05)
    log.stop()
    gcs.stop()
    kv1, stats1, compile_after = inner.kv_stats(), dict(engine.stats), ctx.compile_stats()
    recs = [record(r) for r in requests if r["due"] <= t_close]
    finished = [r for r in requests if "future" in r and r["future"].done()
                and r["future"]._error is None and t_open <= r["submitted"] + r["future"].slo()["latency_s"] <= t_close]
    for r in requests:                     # what is still queued or decoding is dropped
        if "future" in r and not r["future"].done():
            r["future"].cancel()
    ctx.read_memory_peak()
    sample = pick_sample(finished, int(traffic["check_requests"]), seed) if finished else []
    ctx.family.free_serve(engine)
    ctx.log("window closed, engine freed")
    slot_steps = cfg["engine"]["max_batch_size"] * cfg["engine"]["decode_chunk"]
    occupancy = window.delivery_occupancy(log.events, slot_steps)
    span = slice(i_open, i_close + 1)
    gaps = window.gap_summary([t for t, _ in log.events[span]])
    for line in window.stall_lines("deliveries", gaps, occupancy[span], log.late_wakes, gcs.long, t_open, t_close,
                                   ctx.at_open, ctx.at_close):
        ctx.log(line)
    checks, checked_tokens = check_outputs(sample, ctx, ctx.control) if sample else ([("token_gap", float("inf"))], 0)

    in_win = lambda t: t is not None and t_open <= t <= t_close
    tpots = [r["tpot_s"] * 1e3 for r in recs if in_win(r["t_done"]) and r["tpot_s"] is not None]
    ttfts = [x * 1e3 for x in window.ttft_from_due(recs, t_open, t_close)]
    e2e = {"out_tokens_per_s": window.delivery_rate(log.events, i_open, i_close)}
    if tpots:
        e2e["tpot_p50_ms"] = window.percentile(tpots, 50)
    got = [x for x in ttfts if math.isfinite(x)]      # a missed request counts in ``failed``, not in the mean
    if got:
        e2e["ttft_mean_ms"] = sum(got) / len(got)
    failed = sum(1 for r in recs if r["error"] is not None and "Cancelled" not in r["error"])
    if traffic["wait_after_close_s"] > 0:
        failed += sum(1 for r in recs if r["error"] is None and t_open <= r["due"] <= t_close
                      and r["t_first"] is None)
    return {
        "attempted": len(recs), "failed": failed, "end_to_end": e2e, "checks": checks,
        "obs": {"requests": recs, "events": log.events, "occupancy": occupancy, "gaps": gaps,
                "i_open": i_open, "i_close": i_close, "t_open": t_open, "t_close": t_close,
                "kv_before": kv0, "kv_after": kv1, "stats_before": stats0, "stats_after": stats1,
                "compile_before": compile_before, "compile_after": compile_after,
                "slots": cfg["engine"]["max_batch_size"], "decode_chunk": cfg["engine"]["decode_chunk"],
                "warm_requests": warmed, "checked_tokens": checked_tokens,
                "span_names": ("loadgen.submit", *LOOP_PHASES), "unattributed": "engine_thread"},
    }
