"""Speculative decoding (inference/speculative.py + engine wiring).

The acceptance surface of ROADMAP item 1: greedy speculative decode is
token-EXACT vs the non-speculative engine (weak independent draft — the
heavy-rejection path — and self-draft — the full-acceptance path,
including the draft-cache catch-up deficit it creates), tokens per
target step > 1 at full acceptance, rejected runs leave ZERO leaked
pages and intact prefix-cache refcounts (the page-rewind rollback is an
index edit), the compile plan enumerates draft_admit/draft_k/verify_k as
first-class entries (warmup -> compile-free serve window; bundle round
trip with zero cold compiles; a draft-model swap fails the fingerprint
gate loudly), and multi-token steps report honest TPOT. The int8-draft
and k-sweep variants ride the `slow` marker (tier-1 budget)."""

import os
import time

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.inference import compile_plan as cp
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.robustness import (
    RequestCancelledError,
    RequestValidationError,
)
from paddlepaddle_tpu.inference.serving import GenerationResult, ServingEngine
from paddlepaddle_tpu.observability import watchdog


def _llama(hidden=64, layers=2, vocab=128, max_len=96, dtype="bfloat16"):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 3,
        num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=max_len,
        dtype=dtype))


@pytest.fixture(scope="module")
def target():
    paddle.seed(0)
    return _llama()


@pytest.fixture(scope="module")
def draft_weak():
    """An INDEPENDENT small draft: with random weights it almost never
    matches the target's greedy choice, so every verify step exercises
    the rejection/rollback path — the adversarial parity workload."""
    paddle.seed(7)
    return _llama(hidden=32)


@pytest.fixture(scope="module")
def workload():
    """Ragged prompts + budgets, one eos request, one shared prefix pair
    (page-aligned at page_size 16, MISS then HIT)."""
    rng = np.random.default_rng(3)
    reqs = []
    for plen, budget, eos in [(5, 8, None), (17, 4, None), (40, 6, None),
                              (9, 8, 3), (22, 5, None)]:
        reqs.append((rng.integers(0, 128, (plen,)).astype(np.int32),
                     budget, eos, None))
    system = rng.integers(0, 128, (16,)).astype(np.int32)
    for _ in range(2):
        tail = rng.integers(0, 128, (7,)).astype(np.int32)
        reqs.append((np.concatenate([system, tail]), 6, None, 16))
    return reqs


def _refs(target, workload):
    """Per-request greedy ground truth (generate_cached, trimmed the way
    the engine trims: up to and including eos, budget-bounded)."""
    outs = []
    for p, budget, eos, _ in workload:
        outs.append(target.generate_cached(
            p[None], max_new_tokens=budget, temperature=0.0,
            eos_token_id=eos).numpy()[0])
    return outs


@pytest.fixture(scope="module")
def spec_engine(target, draft_weak):
    eng = ServingEngine(target, max_batch_size=3, decode_chunk=8,
                        kv_page_size=16, draft=draft_weak, spec_k=2)
    yield eng
    eng.stop()


def _submit_all(eng, workload):
    return [eng.submit(p, max_new_tokens=budget, eos_token_id=eos,
                       prefix_len=pfx)
            for p, budget, eos, pfx in workload]


# -- units -------------------------------------------------------------------

def test_spec_plan_keys_parse_and_validation():
    assert cp.parse_key(cp.draft_admit_key(128)) == (
        "draft_admit", {"bucket": 128})
    assert cp.parse_key(cp.draft_key(4)) == ("draft", {"k": 4})
    assert cp.parse_key(cp.verify_key(4)) == ("verify", {"k": 4})
    with pytest.raises(ValueError, match="unrecognized"):
        cp.parse_key("verify_kx")


def test_spec_constructor_validation(target, draft_weak):
    with pytest.raises(ValueError, match="BOTH draft"):
        BatchDecodeEngine(target, max_slots=2, spec_k=2)
    with pytest.raises(ValueError, match="paged"):
        BatchDecodeEngine(target, max_slots=2, kv_layout="contiguous",
                          draft=draft_weak, spec_k=2)
    with pytest.raises(ValueError, match="vocab"):
        paddle.seed(11)
        BatchDecodeEngine(target, max_slots=2,
                          draft=_llama(hidden=32, vocab=64), spec_k=2)
    with pytest.raises(ValueError, match="spec_k"):
        BatchDecodeEngine(target, max_slots=2, draft=draft_weak, spec_k=0)


def test_tpot_divides_by_tokens_after_first_sync():
    """The multi-token honesty fix: TPOT must divide by tokens that
    arrived AFTER _t_first; the default (_n_at_first == 1) is
    bit-identical to the old one-token-per-step accounting."""
    r = GenerationResult()
    r._t_admit = r._t_submit
    r._t_first = r._t_submit + 1.0
    r._t_done = r._t_submit + 11.0
    r._n_new = 11
    assert r.slo()["tpot_s"] == pytest.approx(1.0)       # (11-1) tokens
    r._n_at_first = 6       # a speculative burst landed at the first sync
    assert r.slo()["tpot_s"] == pytest.approx(2.0)       # (11-6) tokens
    r._n_at_first = 11
    assert r.slo()["tpot_s"] is None                     # nothing after


# -- token exactness ---------------------------------------------------------

def test_spec_greedy_token_exact_weak_draft(spec_engine, target, workload):
    """Heavy-rejection parity: an independent random draft proposes,
    almost everything rolls back, and the emitted stream must STILL be
    token-for-token the non-speculative greedy output — acceptance only
    filters which step emits what, never what is emitted."""
    futs = _submit_all(spec_engine, workload)
    outs = [f.result(300) for f in futs]
    for out, ref in zip(outs, _refs(target, workload)):
        np.testing.assert_array_equal(out, ref)
    info = spec_engine.health()["spec"]
    assert info["enabled"] and info["k"] == 2
    assert info["rollbacks"] > 0, "weak draft must exercise rejection"
    assert info["proposed"] == info["target_steps"] * 2
    # accepted counts are stamped on the result futures at retirement
    assert all(getattr(f, "_spec_steps", 0) > 0 for f in futs)
    assert all(hasattr(f, "_spec_accepted") for f in futs)


def test_spec_full_accept_multiplies_tokens_per_step(target, workload):
    """Self-draft (draft == target) accepts every proposal: parity must
    hold through the full-accept path (which leaves the draft cache one
    position behind — the 2-token catch-up window repairs it) and each
    target weight-read must yield > 1 token."""
    with ServingEngine(target, max_batch_size=2, decode_chunk=6,
                       kv_page_size=16, draft=target, spec_k=2) as eng:
        futs = _submit_all(eng, workload[:4])
        outs = [f.result(300) for f in futs]
        info = eng.health()["spec"]
    for out, ref in zip(outs, _refs(target, workload[:4])):
        np.testing.assert_array_equal(out, ref)
    assert info["acceptance_rate"] == 1.0
    assert info["rollbacks"] == 0
    assert info["tokens_per_target_step"] > 1.5
    assert info["accept_run_p50"] == 2


def test_spec_rejects_sampled_requests(spec_engine):
    with pytest.raises(RequestValidationError, match="temperature"):
        spec_engine.submit(np.arange(5, dtype=np.int32), max_new_tokens=4,
                           temperature=0.8)


# -- rollback page accounting ------------------------------------------------

def test_spec_rollback_leaves_zero_leaked_pages(spec_engine, workload):
    """After a rejection-heavy serve (including prefix hits), every
    speculated page is back: pool.used equals exactly the refcount-0
    cached prefix pages, and no prefix entry holds a live ref."""
    futs = _submit_all(spec_engine, workload)
    for f in futs:
        f.result(300)
    eng = spec_engine._engine
    kv = eng.kv_stats()
    assert kv["pages_used"] == kv["prefix"]["cached_pages"]
    assert all(e.refcount == 0 for e in eng.prefix._entries.values())
    assert all(not pages for pages in eng._slot_pages)


def test_spec_cancel_mid_speculation_returns_pages(spec_engine):
    """A cancelled in-flight request's slot releases its reservation on
    the next scheduler sweep — the PR 2 cancellation seam composed with
    speculation."""
    eng = spec_engine._engine
    base_used = eng.pool.used
    rng = np.random.default_rng(9)
    f = spec_engine.submit(rng.integers(0, 128, (12,)).astype(np.int32),
                           max_new_tokens=60)
    deadline = time.time() + 30
    while time.time() < deadline and eng.busy_slots() == 0:
        time.sleep(0.005)
    assert eng.busy_slots() == 1
    f.cancel()
    with pytest.raises(RequestCancelledError):
        f.result(30)
    deadline = time.time() + 30
    while time.time() < deadline and (eng.busy_slots() or
                                      eng.pool.used > base_used):
        time.sleep(0.005)
    assert eng.busy_slots() == 0
    assert eng.pool.used <= base_used


# -- compile plan / warmup / bundles -----------------------------------------

def test_spec_plan_warmup_and_bundle_roundtrip(tmp_path, spec_engine,
                                               target, draft_weak,
                                               workload, request):
    """draft_admit/draft_k/verify_k are first-class plan entries: warmup
    leaves a compile-free serve window, a bundle round trip loads them
    with ZERO compiles through the fingerprint gate, and a draft-model
    swap falls back loudly (draft facts are in the fingerprint)."""
    watchdog.install(threshold=3)
    eng = spec_engine._engine
    # no "decode": the spec engine routes every chunk through draft/
    # verify, so the plain chunked-decode scan (the most expensive
    # compile in the plan) must not be warmed or bundled as dead weight
    assert set(eng.compile_plan.keys()) == {
        "admit_p96", "draft_admit_p96", "draft_k2", "verify_k2"}
    eng.warmup()
    before = sum(watchdog.compile_counts().values())
    futs = _submit_all(spec_engine, workload[:3])
    outs = [f.result(300) for f in futs]
    assert sum(watchdog.compile_counts().values()) == before, \
        "speculative serve window must be compile-free after warmup"

    request.getfixturevalue("needs_bundles")   # the rest saves a bundle
    path = str(tmp_path / "spec_bundle")
    manifest = eng.save_serving_bundle(path)
    keys = {e["key"] for e in manifest["entries"]}
    assert {"draft_admit_p96", "draft_k2", "verify_k2"} <= keys

    eng2 = BatchDecodeEngine(target, max_slots=3, chunk=8, page_size=16,
                             draft=draft_weak, spec_k=2, bundle=path)
    assert eng2._bundle_info["loaded"] is True
    b2 = sum(watchdog.compile_counts().values())
    from paddlepaddle_tpu.inference.serving import GenerationRequest

    reqs = [GenerationRequest(p, budget, 0.0, 0, eos)
            for p, budget, eos, _ in workload[:3]]
    eng2.serve(reqs, timeout=120)
    outs2 = [np.asarray(r.result.result(5)) for r in reqs]
    assert sum(watchdog.compile_counts().values()) == b2, \
        "bundle-loaded spec programs must serve with zero compiles"
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(a, b)

    # draft swap: arch facts differ -> fingerprint mismatch -> lazy path
    paddle.seed(21)
    eng3 = BatchDecodeEngine(target, max_slots=3, chunk=8, page_size=16,
                             draft=_llama(hidden=48), spec_k=2, bundle=path)
    assert eng3._bundle_info["loaded"] is False
    assert "spec" in eng3._bundle_info["error"]


def test_spec_warmup_with_perf_plane(target, draft_weak):
    """warmup() on a spec engine with the perf-attribution plane armed:
    draft_k/verify_k keys carry no admission bucket, so the perf capture
    must skip them (regression: KeyError 'bucket' aborted warmup) while
    still capturing the target admit program."""
    import paddlepaddle_tpu.observability as obs
    from paddlepaddle_tpu.observability import perf

    obs.reset()
    perf.enable()
    try:
        paddle.seed(11)
        eng = BatchDecodeEngine(target, max_slots=2, chunk=8, page_size=16,
                                draft=draft_weak, spec_k=2)
        info = eng.warmup()
        assert info["compiled"] == len(eng.compile_plan.keys())
        names = {r["program"] for r in perf.registry().table()}
        assert "serving.admit" in names          # target admit captured
        assert not any("draft" in n or "verify" in n for n in names)
    finally:
        perf.reset()
        perf.disable()
        obs.reset()


# -- chaos: breaker storm mid-speculation ------------------------------------

@pytest.mark.chaos
def test_chaos_decode_storm_mid_speculation(target, draft_weak):
    """A serving.decode fault storm against the SPECULATIVE engine: every
    future resolves (typed or completed), the breaker opens and recovers,
    and the failed slots' speculated pages all return to the pool."""
    from paddlepaddle_tpu.resilience import chaos

    eng = ServingEngine(target, max_batch_size=1, decode_chunk=6,
                        kv_page_size=16, draft=draft_weak, spec_k=2,
                        breaker_threshold=2, breaker_reset_s=0.2)
    rng = np.random.default_rng(5)
    p = rng.integers(0, 128, (8,)).astype(np.int32)
    try:
        ref = eng.submit(p, max_new_tokens=4).result(300)  # warm compiles
        chaos.configure("serving.decode:exc:x2",
                        seed=int(os.environ.get("PADDLE_CHAOS_SEED",
                                                "1234")))
        failed = [eng.submit(rng.integers(0, 128, (8,)).astype(np.int32),
                             max_new_tokens=4) for _ in range(2)]
        for f in failed:
            with pytest.raises(chaos.ChaosError):
                f.result(120)
        # the loop fails the futures BEFORE reset_slots() returns the
        # pages — poll briefly instead of racing it
        deadline = time.time() + 10
        while time.time() < deadline and eng._engine.pool.used:
            time.sleep(0.01)
        assert eng._engine.pool.used == 0, \
            "failed speculation must return every page"
        time.sleep(0.25)                  # storm exhausted + reset window
        out = eng.submit(p, max_new_tokens=4).result(120)
        np.testing.assert_array_equal(out, ref)   # still token-exact
        assert eng._engine.pool.used == 0
    finally:
        chaos.disable()
        eng.stop()


# -- slow tier: int8 draft + k sweep -----------------------------------------

@pytest.mark.slow
def test_spec_int8_draft_token_exact(target, draft_weak, workload):
    """Weight-only int8 DRAFT (the draft's weight reads are the
    speculation overhead): parity is structural — acceptance filters,
    the emitted tokens are always target-greedy."""
    with ServingEngine(target, max_batch_size=3, decode_chunk=8,
                       kv_page_size=16, draft=draft_weak, spec_k=2,
                       draft_quant="weight_only_int8") as eng:
        futs = _submit_all(eng, workload)
        outs = [f.result(300) for f in futs]
        assert eng.health()["spec"]["draft"]["quant"] == "weight_only_int8"
    for out, ref in zip(outs, _refs(target, workload)):
        np.testing.assert_array_equal(out, ref)


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 4])
def test_spec_k_sweep_token_exact(target, draft_weak, workload, k):
    with ServingEngine(target, max_batch_size=3, decode_chunk=8,
                       kv_page_size=16, draft=draft_weak, spec_k=k) as eng:
        futs = _submit_all(eng, workload)
        outs = [f.result(300) for f in futs]
    for out, ref in zip(outs, _refs(target, workload)):
        np.testing.assert_array_equal(out, ref)
