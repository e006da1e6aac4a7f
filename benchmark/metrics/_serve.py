"""Arithmetic shared by the serve readers, over the benchmark's own request records."""
import re
import statistics

from benchmark import work

# XLA module names of the engine's programs, as the trace gives them
ADMIT_MODULES = r"^jit__admit_paged_impl$|^jit_impl$"
DECODE_MODULES = r"^jit_run$"


def in_window(obs, key):
    return [r for r in obs["requests"] if r[key] is not None and obs["t_open"] <= r[key] <= obs["t_close"]]


def aligned_prefix(r, page):
    """Tokens of a declared prefix that whole pages can share (one tail token stays)."""
    n = ((r["prefix_len"] or 0) // page) * page
    return n - page if n == r["prompt"] else n


def computed_span(r, page):
    """(first, last) prompt positions the admission computes: the tail past a cached prefix on a later turn, else all."""
    first = aligned_prefix(r, page) if r["turn"] > 0 else 0
    return first, r["prompt"]


def prompt_ops(obs, requests):
    cfg, page = obs["config"], obs["config"]["engine"]["kv_page_size"]
    forward_ops = work.counts(obs).forward_ops
    return sum(forward_ops(cfg, *computed_span(r, page), with_head_tokens=1) for r in requests)


def module_seconds(obs, pattern):
    tr, rx = obs["trace"], re.compile(pattern)
    names = [k for k in tr["module_s"] if rx.search(k)]
    if not names:
        return None, 0
    return sum(tr["module_s"][k] for k in names), sum(tr["module_calls"].get(k, 0) for k in names)


def live_context(obs, t0, t1):
    """Mean over [t0, t1] of (tokens held in live slots, live slots), from each request's admit and done stamps."""
    tpots = [r["tpot_s"] for r in obs["requests"] if r["tpot_s"]]
    tpot = statistics.median(tpots) if tpots else None
    tokens = slots = 0.0
    for r in obs["requests"]:
        if r["t_admit"] is None:
            continue
        a, b = max(r["t_admit"], t0), min(r["t_done"] if r["t_done"] is not None else t1, t1)
        if b <= a:
            continue
        if r["t_done"] is not None and r["t_done"] > r["t_admit"]:
            per_s = r["new_tokens"] / (r["t_done"] - r["t_admit"])
        else:
            per_s = 1.0 / tpot if tpot else 0.0
        mid = (a + b) / 2.0 - r["t_admit"]
        tokens += (r["prompt"] + per_s * mid) * (b - a)
        slots += b - a
    return tokens / (t1 - t0), slots / (t1 - t0)


def whole_mfu(obs):
    """Operations of every prompt token computed and every token decoded in the window, over the window and the peak."""
    cfg = obs["config"]
    t0, t1 = obs["t_open"], obs["t_close"]
    decoded = obs["events"][obs["i_close"]][1] - obs["events"][obs["i_open"]][1]
    tokens, slots = live_context(obs, t0, t1)
    mean_ctx = tokens / slots if slots else 0.0
    per_token = work.counts(obs).forward_ops(cfg, int(mean_ctx), int(mean_ctx) + 1, with_head_tokens=1)
    ops = decoded * per_token + prompt_ops(obs, in_window(obs, "t_admit"))
    return 100.0 * ops / (t1 - t0) / work.peaks(obs["device_kind"])["flops_per_s"]
