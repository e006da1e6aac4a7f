"""KV memory at scale (ROADMAP item 4): int8 KV pages + host-RAM
prefix-cache tier.

The acceptance surface: the page-slab wire format round-trips
byte-exactly (the same framing the disaggregated-prefill seam will
speak), the host tier's LRU/budget bookkeeping is exact, int8 KV holds
greedy top-1 agreement against full-precision KV (the page format itself
is pinned in tests/test_int8_kv_view.py), a spilled-then-restored prefix hit emits the
same tokens as one that never left the device, a corrupted slab degrades
to a full-prefill miss (never a wrong token), and the chaos drill leaks
zero pages on either tier."""

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.kv_pool import (
    HostPrefixTier,
    HostSlab,
    deserialize_page_slab,
    prefix_hash,
    serialize_page_slab,
)
from paddlepaddle_tpu.inference.serving import GenerationRequest


def _model(dtype="float32"):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=192,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=96, dtype=dtype))


def _req(ids, n, temp=0.0, top_k=0, eos=None, prefix_len=None):
    r = GenerationRequest(ids, n, temp, top_k, eos)
    r.prefix_len = prefix_len
    return r


def _serve(eng, reqs, timeout=240):
    eng.serve(reqs, timeout=timeout)
    return [np.asarray(r.result.result(5)) for r in reqs]


def _prompts(seed=0, lens=(12, 20, 7)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 127, size=(1, n)) for n in lens]


# -- page-slab wire format ----------------------------------------------------

def test_slab_roundtrip_byte_exact():
    rng = np.random.default_rng(3)
    arrays = [
        rng.standard_normal((4, 8, 2, 16)).astype(np.float32),
        rng.integers(-127, 128, (4, 8, 2, 16)).astype(np.int8),
        rng.standard_normal((4, 2)).astype(np.float32),
    ]
    meta = {"page_size": 8, "kv_quant": "int8", "length": 30}
    blob = serialize_page_slab(meta, arrays)
    m2, arrs2 = deserialize_page_slab(blob)
    assert m2 == meta
    assert len(arrs2) == len(arrays)
    for a, b in zip(arrays, arrs2):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def test_slab_roundtrip_bfloat16():
    # the serving dtype: bf16's numpy .str is an anonymous void — the
    # format must carry the NAME so the reader reconstructs the real type
    ml_dtypes = pytest.importorskip("ml_dtypes")
    x = np.arange(32, dtype=np.float32).astype(ml_dtypes.bfloat16)
    blob = serialize_page_slab({"dtype": "bfloat16"}, [x])
    _, (y,) = deserialize_page_slab(blob)
    assert y.dtype == x.dtype and y.tobytes() == x.tobytes()


def test_slab_rejects_corruption():
    blob = serialize_page_slab({"k": 1}, [np.zeros(4, np.float32)])
    with pytest.raises(ValueError):
        deserialize_page_slab(b"XXXX" + blob[4:])      # bad magic
    with pytest.raises(ValueError):
        deserialize_page_slab(blob[:-3])               # truncated payload
    with pytest.raises(ValueError):
        deserialize_page_slab(blob + b"\x00")          # trailing bytes


# -- host tier bookkeeping ----------------------------------------------------

def _slab(nbytes, stamp):
    return HostSlab(b"x" * nbytes, length=8, n_pages=1, stamp=stamp)


def test_host_tier_lru_budget_and_oversize():
    with pytest.raises(ValueError):
        HostPrefixTier(0)
    tier = HostPrefixTier(100)
    assert tier.put("a", _slab(40, stamp=1.0))
    assert tier.put("b", _slab(40, stamp=2.0))
    # over budget: oldest-stamp entry ("a") is the discard victim
    assert tier.put("c", _slab(40, stamp=3.0))
    assert tier.pop("a") is None and tier.discards == 1
    assert sorted(tier.keys()) == ["b", "c"]
    assert tier.used_bytes == 80
    # a slab larger than the whole budget is refused, not thrashed in
    assert not tier.put("big", _slab(200, stamp=4.0))
    assert tier.discards == 2 and sorted(tier.keys()) == ["b", "c"]
    # pop decrements, put_back restores without double-counting stats
    s = tier.pop("b")
    assert s is not None and tier.used_bytes == 40 and tier.restores == 1
    tier.put_back("b", s)
    assert tier.used_bytes == 80 and tier.restores == 0
    st = tier.stats()
    assert st["entries"] == 2 and st["budget_bytes"] == 100
    assert st["occupancy"] == pytest.approx(0.8)


# -- engine-level parity ------------------------------------------------------

def test_engine_int8_greedy_agreement_vs_full_precision():
    prompts = _prompts(seed=1)

    def run(**kw):
        eng = BatchDecodeEngine(_model(), max_slots=4, chunk=4,
                                page_size=8, **kw)
        return _serve(eng, [_req(p, 8) for p in prompts])

    base = run()
    quant = run(kv_quant="int8")
    agree = np.mean([np.mean(a[p.shape[1]:] == b[p.shape[1]:])
                     for a, b, p in zip(base, quant, prompts)])
    assert agree >= 0.9, f"greedy top-1 agreement {agree} < 0.9"


def test_kv_quant_validation_and_fingerprint():
    from paddlepaddle_tpu.inference import compile_plan as cp

    m = _model()
    with pytest.raises(ValueError, match="int4.*seam"):
        BatchDecodeEngine(m, max_slots=2, kv_quant="int4")
    with pytest.raises(ValueError):
        BatchDecodeEngine(m, max_slots=2, kv_quant="int3")
    with pytest.raises(ValueError, match="paged"):
        BatchDecodeEngine(m, max_slots=2, kv_layout="contiguous",
                          kv_quant="int8")
    # kv_quant changes every decode program AND the cache treedef — it
    # must be a compile-plan fact or an AOT bundle would cross-load
    base = BatchDecodeEngine(m, max_slots=2, chunk=4, page_size=8)
    quant = BatchDecodeEngine(m, max_slots=2, chunk=4, page_size=8,
                              kv_quant="int8")
    assert cp.CompilePlan.for_engine(base).fingerprint() \
        != cp.CompilePlan.for_engine(quant).fingerprint()
    assert base.kv_stats()["kv_quant"] == "off"
    assert quant.kv_stats()["kv_quant"] == "int8"
    # int8 pages are smaller than f32 pages at the same page_size
    assert quant.kv_stats()["page_bytes"] < base.kv_stats()["page_bytes"]


# -- tiered prefix cache ------------------------------------------------------

def _tiered_engine(num_pages=6, host_bytes=1 << 20, **kw):
    return BatchDecodeEngine(_model(), max_slots=1, chunk=4, page_size=8,
                             kv_quant="int8", prefix_cache=True, num_pages=num_pages,
                             kv_host_bytes=host_bytes, **kw)


def _prefix_reqs(seed=1):
    rng = np.random.default_rng(seed)
    pfx_a = rng.integers(1, 127, size=(1, 16))
    pfx_b = rng.integers(1, 127, size=(1, 16))
    tail = rng.integers(1, 127, size=(1, 4))
    mk = lambda p: _req(np.concatenate([p, tail], 1), 6, prefix_len=16)
    return pfx_a, pfx_b, mk


def test_spill_restore_token_parity():
    """A prefix evicted to the host tier and restored on re-hit must emit
    EXACTLY the tokens of (a) its own first run and (b) a pool big enough
    that it never left the device — the restore path re-materializes the
    same quantized bytes, so parity is byte-level, not approximate."""
    pfx_a, pfx_b, mk = _prefix_reqs()
    eng = _tiered_engine()                 # 5 usable pages: B evicts A
    a1 = _serve(eng, [mk(pfx_a)])
    _serve(eng, [mk(pfx_b)])
    st = eng.kv_host.stats()
    assert st["spills"] >= 1 and st["entries"] >= 1
    a2 = _serve(eng, [mk(pfx_a)])
    st = eng.kv_host.stats()
    assert st["restores"] >= 1
    np.testing.assert_array_equal(a1[0], a2[0])
    ks = eng.kv_stats()
    assert ks["host"]["enabled"]
    assert ks["host"]["restore_ms_p50"] is not None
    assert ks["host"]["restore_ms_p99"] >= ks["host"]["restore_ms_p50"]
    # never-evicted control: same prompts, pool big enough to keep A
    big = _tiered_engine(num_pages=32)
    _serve(big, [mk(pfx_a)])
    c2 = _serve(big, [mk(pfx_a)])
    assert big.kv_host.stats()["spills"] == 0
    np.testing.assert_array_equal(a2[0], c2[0])


def test_corrupt_slab_degrades_to_miss():
    pfx_a, pfx_b, mk = _prefix_reqs()
    eng = _tiered_engine()
    a1 = _serve(eng, [mk(pfx_a)])
    _serve(eng, [mk(pfx_b)])               # spills A's slab to host
    h = prefix_hash(pfx_a, 16)
    slab = eng.kv_host.pop(h)
    assert slab is not None
    # a slab whose meta doesn't match the engine (wrong page geometry,
    # different quant mode, foreign model) must be a loud miss — the
    # request full-prefills and still finishes with the right tokens
    bad = serialize_page_slab({"garbage": True}, [np.zeros(4, np.int8)])
    eng.kv_host.put_back(h, HostSlab(bad, slab.length, slab.n_pages,
                                     slab.stamp))
    a2 = _serve(eng, [mk(pfx_a)])
    np.testing.assert_array_equal(a1[0], a2[0])
    assert eng.prefix.misses >= 1


def test_host_tier_off_is_plain_eviction():
    pfx_a, pfx_b, mk = _prefix_reqs()
    eng = BatchDecodeEngine(_model(), max_slots=1, chunk=4, page_size=8,
                            prefix_cache=True, num_pages=6)
    assert eng.kv_host is None
    a1 = _serve(eng, [mk(pfx_a)])
    _serve(eng, [mk(pfx_b)])
    assert eng.prefix.evictions >= 1       # true discard, no tier to catch
    a2 = _serve(eng, [mk(pfx_a)])
    np.testing.assert_array_equal(a1[0], a2[0])


# -- observability ------------------------------------------------------------

def test_memledger_host_bucket_and_cross_tier_leak_check():
    from paddlepaddle_tpu.observability import memledger

    assert "kv_host_spill" in memledger.BUCKETS
    pfx_a, pfx_b, mk = _prefix_reqs()
    eng = _tiered_engine()
    _serve(eng, [mk(pfx_a)])
    _serve(eng, [mk(pfx_b)])               # A now lives on the host tier
    lc = memledger.leak_check(eng)
    assert lc["leaked_pages"] == 0
    assert lc["host_entries"] >= 1
    assert lc["host_bytes"] == eng.kv_host.used_bytes > 0
    assert lc["tier_overlap"] == 0         # device XOR host, never both
    led = memledger.MemoryLedger()
    sample = led.sample()
    assert sample["buckets"]["kv_host_spill"] >= eng.kv_host.used_bytes
    # host RAM must NOT be folded into the device-bytes reconciliation:
    # unattributed reconciles live DEVICE arrays against the device
    # buckets only, so it is exactly live - (params+kv+pinned+draft)
    attributed_device = (sample["buckets"]["params"]
                         + sample["buckets"]["kv_pages"]
                         + sample["buckets"]["prefix_pinned"]
                         + sample["buckets"]["draft"])
    assert sample["buckets"]["unattributed"] == max(
        sample["live_array_bytes"] - attributed_device, 0)


def test_alert_rule_kv_host_tier_full():
    from paddlepaddle_tpu.observability.alerts import default_rules

    rules = {r.name: r for r in default_rules()}
    rule = rules["kv_host_tier_full"]
    assert rule.severity == "warn"
    assert any(c.series == "paddle_serving_kv_host_occupancy"
               for c in rule.conditions)


def test_perf_gate_kv_memory_fields():
    import sys

    sys.path.insert(0, "tools")
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    body = {"aggregate_tok_s": 100.0, "prefix_restore_ms_p50": 3.0,
            "prefix_restore_ms_p99": 9.0,
            "kv_quant_ab": {"int8": {"aggregate_tok_s": 90.0,
                                     "concurrency_peak": 8}}}
    m = perf_gate.serving_metrics({"serving_bench": body})
    assert m["serving.prefix_restore_ms_p50"] == (3.0, perf_gate.LOWER)
    assert m["serving.prefix_restore_ms_p99"] == (9.0, perf_gate.LOWER)
    assert m["serving.kvq_mixed_tok_s"] == (90.0, perf_gate.HIGHER)
    assert m["serving.kvq_concurrency_peak"] == (8.0, perf_gate.HIGHER)


# -- chaos drill: zero leaked pages on either tier ----------------------------

@pytest.mark.chaos
def test_chaos_tiered_kv_zero_leak_both_tiers():
    """Churn a deliberately tiny two-tier config — spills, restores, host
    discards, failed restores all fire — then audit: every device page is
    owned by a slot or the prefix cache, no prefix hash is resident on
    both tiers, and the host tier's byte ledger matches its entries."""
    from paddlepaddle_tpu.observability import memledger

    rng = np.random.default_rng(11)
    prefixes = [rng.integers(1, 127, size=(1, 16)) for _ in range(4)]
    tail = rng.integers(1, 127, size=(1, 4))
    # host budget fits ONE ~2.6KB slab: concurrent spills force true
    # host-tier discards alongside the restores
    eng = _tiered_engine(num_pages=6, host_bytes=3000)
    order = rng.permutation(np.repeat(np.arange(4), 3))
    for i in order:
        _serve(eng, [_req(np.concatenate([prefixes[i], tail], 1), 4,
                          prefix_len=16)])
    st = eng.kv_host.stats()
    assert st["spills"] >= 3 and st["discards"] >= 1
    lc = memledger.leak_check(eng)
    assert lc["leaked_pages"] == 0, lc
    assert lc["tier_overlap"] == 0, lc
    # the host byte ledger must equal the sum of the resident slabs, and
    # popping every entry must drain it to exactly zero
    resident = sum(eng.kv_host.pop(h).nbytes
                   for h in list(eng.kv_host.keys()))
    assert lc["host_bytes"] == resident
    assert eng.kv_host.used_bytes == 0
