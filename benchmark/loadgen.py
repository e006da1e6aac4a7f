"""One general traffic generator, driven by a traffic file's parameters.

A traffic file fixes the multiset of (prompt length, output length) pairs and
the arrival offsets: both come from the file's own ``schedule_seed`` and the
distributions it names, so every run of a cell offers the same tokens on the
same schedule. ``--seed`` changes the token ids (and the weights), never how
much work the window holds.

A session is an optional document plus ``turns`` requests; each prompt is
[system prefix] + [document] + question. ``initial_burst`` sessions are due at
t=0, the rest arrive at ``rate_per_s`` (``poisson`` or ``uniform`` gaps).
"""

from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, List

import numpy as np


def draw_length(spec: dict, rng: np.random.Generator) -> int:
    if "fixed" in spec:
        return int(spec["fixed"])
    if "choices" in spec:
        return int(spec["choices"][int(rng.integers(len(spec["choices"])))])
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return int(rng.integers(lo, hi + 1))
    if spec["dist"] == "lognormal":
        return int(min(hi, max(lo, round(spec["median"] * math.exp(spec["sigma"] * rng.standard_normal())))))
    raise ValueError(f"unknown length distribution {spec!r}")


def schedule(traffic: dict, horizon_s: float) -> List[dict]:
    """Every request due within ``horizon_s``, in due order. Sessions are drawn
    one after another, so a longer horizon only appends."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    sess = traffic.get("session", {"turns": 1, "gap_s": [0, 0]})
    out, t, s = [], 0.0, 0
    while True:
        if s >= int(traffic.get("initial_burst", 0)):
            gap = 1.0 / traffic["rate_per_s"]
            t += rng.exponential(gap) if traffic.get("arrivals", "poisson") == "poisson" else gap
        # every draw is made whether or not the request falls inside the
        # horizon, so the stream does not depend on it
        doc = draw_length(traffic["document_tokens"], rng) if traffic.get("document_tokens") else 0
        due = t
        reqs = []
        for turn in range(int(sess["turns"])):
            if turn:
                due += rng.uniform(*sess["gap_s"])
            reqs.append({"session": s, "turn": turn, "due": due,
                         "system": int(traffic.get("shared_prefix_tokens", 0)), "document": doc,
                         "question": draw_length(traffic["prompt_tokens"], rng),
                         "output": draw_length(traffic["output_tokens"], rng)})
        if t > horizon_s:
            break
        out += [r for r in reqs if r["due"] <= horizon_s]
        s += 1
    out.sort(key=lambda r: (r["due"], r["session"], r["turn"]))
    for r in out:
        r["prompt"] = r["system"] + r["document"] + r["question"]
        r["prefix_len"] = (r["system"] + r["document"]) if traffic.get("declare_prefix") else None
    return out


def fill_tokens(requests: List[dict], seed: int, vocab: int) -> None:
    """Token ids from ``--seed``: one system prefix for all, one document per
    session, a question per request."""
    def ids(n, *path):
        return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, *path]).integers(
            0, vocab, (n,)).astype(np.int32)

    for r in requests:
        r["ids"] = np.concatenate([ids(r["system"], 0), ids(r["document"], 1, r["session"]),
                                   ids(r["question"], 2, r["session"], r["turn"])])


def warm_classes(requests: List[dict], prompt_quantum: int, prefix_quantum: int) -> List[List[dict]]:
    """One short session for each class of shapes the schedule holds (prompt
    length up to ``prompt_quantum``, declared prefix in whole ``prefix_quantum``
    pages), first turn and a later turn where the mix has them: the set-up
    sends these so that every program the window drives is compiled before it."""
    seen: Dict[tuple, List[dict]] = {}
    for r in requests:
        pfx = (r["prefix_len"] or 0) // prefix_quantum
        tail = r["prompt"] - pfx * prefix_quantum
        key = (-(-r["prompt"] // prompt_quantum), pfx, -(-tail // prompt_quantum), min(r["turn"], 1))
        seen.setdefault(key, []).append(r)
    sessions: Dict[tuple, List[dict]] = {}
    for key, rs in sorted(seen.items()):
        sessions.setdefault((key[1], rs[0]["document"]), []).append(rs[0])
    return list(sessions.values())


class OpenLoop:
    """Submits each request when it is due, whatever the server does; records
    how late each submission ran."""

    def __init__(self, requests: List[dict], submit: Callable[[dict], object],
                 clock: Callable[[], float] = time.perf_counter, annotate=None):
        self.requests, self._submit, self._clock = requests, submit, clock
        self._annotate = annotate
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-loadgen", daemon=True)
        self.t0 = None

    def start(self):
        self.t0 = self._clock()
        for r in self.requests:
            r["due"] += self.t0
        self._thread.start()

    def _run(self):
        for r in self.requests:
            wait = r["due"] - self._clock()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            r["submitted"] = self._clock()
            try:
                if self._annotate is not None:
                    with self._annotate("loadgen.submit"):
                        r["future"] = self._submit(r)
                else:
                    r["future"] = self._submit(r)
            except Exception as e:  # a refusal is a failed request, not a crash
                r["error"] = repr(e)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=30)
