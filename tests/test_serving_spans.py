"""The continuous loop's phase clock: ``observability.recorder.phase`` spans
and the ``stats`` counters they feed (docs/serving.md "The loop's phases").

One tiny engine serves every test (two slots, chunks of four steps, requests
of four tokens, so a request lives in exactly one chunk); each test reads the
difference of two ``stats`` copies.
"""

import math
import time

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
import paddlepaddle_tpu.observability as obs
from paddlepaddle_tpu.inference import ServingEngine
from paddlepaddle_tpu.inference.serving import LOOP_PHASES
from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

WORKING = [p for p in LOOP_PHASES if p != "serve.wait_request"]


@pytest.fixture(scope="module")
def engine():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, layers=2, heads=4, kv_heads=2,
        max_len=96))
    with ServingEngine(model, max_batch_size=2, decode_chunk=4) as eng:
        _serve(eng, 2)          # compile the admission and decode programs
        yield eng


def _serve(eng, n, new_tokens=4, seed=0):
    rng = np.random.default_rng(seed)
    futs = [eng.submit(rng.integers(0, 64, (8,)).astype(np.int32),
                       max_new_tokens=new_tokens, temperature=0.0)
            for _ in range(n)]
    for f in futs:
        f.result(180)
    _settle(eng)


def _settle(eng):
    """Wait until the loop has closed the iteration that ran the last
    chunk, i.e. until it blocks for want of work."""
    n = eng.stats["span_n.serve.wait_request"]
    deadline = time.monotonic() + 30
    while eng.stats["span_n.serve.wait_request"] <= n:
        assert time.monotonic() < deadline
        time.sleep(0.01)


def _delta(eng, fn):
    before = dict(eng.stats)
    inner = dict(eng._engine.stats)
    fn()
    after = dict(eng.stats)
    return ({k: after[k] - before[k] for k in after},
            {k: eng._engine.stats[k] - inner[k] for k in inner})


def _per_chunk(d, key):
    return d[key] / d["span_n.serve.chunk_sync"]


def test_counts_and_cover(engine):
    d, inner = _delta(engine, lambda: _serve(engine, 7, new_tokens=9))
    assert d["batched_requests"] == 7
    assert d["span_n.serve.admit"] == d["batched_requests"]
    assert d["span_n.serve.chunk_sync"] == inner["decode_calls"] > 0
    assert d["span_n.serve.decode_dispatch"] == inner["decode_calls"]
    assert d["span_n.serve.deliver"] == inner["decode_calls"]
    assert 0 < d["span_n.serve.first_sync"] <= inner["decode_calls"]
    # two slots, seven requests: the head of the queue was refused a slot
    assert d["admit_deferred"] > 0
    for name in LOOP_PHASES:
        s = engine.stats["span_s." + name]
        assert math.isfinite(s) and s >= 0, name
    # disjoint and nearly exhaustive: the rest is the breaker, the hooks and
    # the bumps
    covered = sum(d["span_s." + p] for p in WORKING)
    assert 0.8 * d["loop_busy_s"] <= covered <= d["loop_busy_s"]
    assert d["turnaround_n"] <= inner["decode_calls"]
    assert engine.health()["stats"]["loop_busy_s"] == engine.stats["loop_busy_s"]


def test_a_copy_taken_on_a_result_is_not_torn(engine, monkeypatch):
    """A client that copies ``stats`` the moment its result arrives (the
    benchmark does, after its last warm request) stands inside the
    iteration that served it: the host's part of that iteration must
    already be in ``loop_busy_s`` beside its spans, or the next difference
    holds the seconds of a slow admission without its span."""
    real = engine._engine._admit

    def slow_admit(req):
        time.sleep(0.1)
        return real(req)

    monkeypatch.setattr(engine._engine, "_admit", slow_admit)
    rng = np.random.default_rng(3)
    fut = engine.submit(rng.integers(0, 64, (8,)).astype(np.int32),
                        max_new_tokens=4, temperature=0.0)
    fut.result(180)
    before = dict(engine.stats)
    monkeypatch.setattr(engine._engine, "_admit", real)
    _serve(engine, 4)
    d = {k: engine.stats[k] - before[k] for k in before}
    covered = sum(d["span_s." + p] for p in WORKING)
    assert d["span_s.serve.admit"] < 0.1
    assert 0.8 * d["loop_busy_s"] <= covered <= 1.05 * d["loop_busy_s"]


def test_slow_retire_is_turnaround_and_deliver(engine, monkeypatch):
    base, _ = _delta(engine, lambda: _serve(engine, 6))
    real = engine._engine._retire

    def slow_retire(slot):
        time.sleep(0.02)
        real(slot)

    monkeypatch.setattr(engine._engine, "_retire", slow_retire)
    slow, _ = _delta(engine, lambda: _serve(engine, 6))
    assert base["turnaround_n"] > 0 and slow["turnaround_n"] > 0
    gain = (slow["turnaround_s"] / slow["turnaround_n"]
            - base["turnaround_s"] / base["turnaround_n"])
    assert gain >= 0.02
    assert (_per_chunk(slow, "span_s.serve.deliver")
            - _per_chunk(base, "span_s.serve.deliver")) >= 0.02


class _SlowToHost:
    """A device array whose copy to the host takes 20 ms longer."""

    def __init__(self, inner):
        self.inner = inner

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.02)
        return np.asarray(self.inner)


def test_slow_sync_is_chunk_sync_not_turnaround(engine, monkeypatch):
    base, _ = _delta(engine, lambda: _serve(engine, 6))
    real = engine._engine._programs["decode"]

    def slow_decode(*args):
        out = real(*args)
        return (*out[:-1], _SlowToHost(out[-1]))

    monkeypatch.setitem(engine._engine._programs, "decode", slow_decode)
    slow, _ = _delta(engine, lambda: _serve(engine, 6))
    assert (_per_chunk(slow, "span_s.serve.chunk_sync")
            - _per_chunk(base, "span_s.serve.chunk_sync")) >= 0.02
    assert (slow["turnaround_s"] / slow["turnaround_n"]
            - base["turnaround_s"] / base["turnaround_n"]) < 0.01


def test_waiting_for_work_is_no_turnaround(engine):
    def one_at_a_time():
        for seed in range(3):
            _serve(engine, 1, seed=seed)
            time.sleep(0.12)        # the loop blocks, twice over, for want of work

    d, inner = _delta(engine, one_at_a_time)
    assert inner["decode_calls"] == 3
    assert d["turnaround_n"] == 0 and d["turnaround_s"] == 0
    assert d["span_n.serve.wait_request"] >= 3
    assert d["span_s.serve.wait_request"] >= 0.2
    # waiting is not busy time
    assert d["loop_busy_s"] < d["span_s.serve.wait_request"] + sum(
        d["span_s." + p] for p in WORKING)


def test_spans_on_the_profilers_clock(engine, tmp_path):
    import jax

    from benchmark import trace

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.window"):
            _serve(engine, 5, new_tokens=9)
    planes = trace.read_planes(trace.find_trace(str(tmp_path)))
    host = planes["/host:CPU"].get("python")
    if host is None:
        # read_planes keeps only the host lines named `python`, which is
        # the process's name (PERF.md section 7)
        pytest.skip("this interpreter does not run as `python`")
    (_, w0, w1), = [s for s in host if s[0] == "test.window"]
    phases = sorted((s for s in host if s[0] in LOOP_PHASES),
                    key=lambda s: s[1])
    assert {s[0] for s in phases} == set(LOOP_PHASES)
    for name, a, b in phases:
        if name in ("serve.admit", "serve.chunk_sync"):
            assert w0 <= a <= b <= w1, (name, a, b)
    for (n0, _, end), (n1, start, _) in zip(phases, phases[1:]):
        assert start >= end, (n0, n1)
    assert sum(1 for s in phases if s[0] == "serve.admit") >= 5


@pytest.mark.parametrize("tracing", [False, True])
def test_ring_follows_the_trace_flag(engine, tracing):
    rec = obs.get_recorder()
    if tracing:
        obs.enable(trace=True, metrics=False, watchdog_=False)
    try:
        rec.clear()
        _serve(engine, 2)
        names = {e.name for e in rec.events()}
    finally:
        obs.disable()
        rec.clear()
    if tracing:
        assert set(LOOP_PHASES) <= names
    else:
        assert names == set()
