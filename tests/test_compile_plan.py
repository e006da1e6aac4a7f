"""Compile plan + persistent compile cache + AOT serving bundles
(inference/compile_plan.py, core/compile_cache.py, engine warmup/bundle
surfaces, router pre-warm).

The acceptance surface of the cold-start work: the plan enumerates exactly
what the engine compiles (watchdog-counted), warmup leaves ZERO compiles
in the serve window, a bundle save->load round trip is token-exact vs a
fresh engine with zero retraces on the bundle path, a manifest mismatch
falls back cleanly (never crashes), persistent-cache hits are labeled by
the recompile watchdog (warm restarts don't read as storms), and
rolling_restart pre-warms a replica before re-admission."""

import json
import os

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.core import compile_cache
from paddlepaddle_tpu.inference import compile_plan as cp
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.serving import GenerationRequest, ServingEngine
from paddlepaddle_tpu.observability import watchdog


def _model(dtype="bfloat16"):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=192,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=96, dtype=dtype))


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def warm_engine(model):
    """One warmed bf16 engine shared by the fast tests (params are
    read-only, so engines built over the same model are weight-identical
    — the bundle parity baseline)."""
    watchdog.install(threshold=3)
    eng = BatchDecodeEngine(model, max_slots=2, chunk=4, page_size=16)
    eng.warmup()
    return eng


def _reqs(n=2, toks=6):
    return [GenerationRequest([1, 2, 3, 4, 5], toks, 0.0, 0, None)
            for _ in range(n)]


def _serve(eng, reqs):
    eng.serve(reqs, timeout=120)
    return [np.asarray(r.result.result(5)) for r in reqs]


def _total_compiles():
    return sum(watchdog.compile_counts().values())


def _cold_compiles():
    return sum(watchdog.cold_compile_counts().values())


# -- units -------------------------------------------------------------------

def test_key_helpers_and_prompt_buckets():
    assert cp.prompt_buckets(96) == [96]
    assert cp.prompt_buckets(256) == [128, 256]
    assert cp.prompt_buckets(300) == [128, 256, 300]
    assert cp.parse_key(cp.decode_key()) == ("decode", {})
    assert cp.parse_key(cp.admit_key(128)) == ("admit", {"bucket": 128})
    assert cp.parse_key(cp.prefix_admit_key(2, 64)) == (
        "prefix", {"n_pfx": 2, "tail_bucket": 64})
    with pytest.raises(ValueError, match="unrecognized"):
        cp.parse_key("admit_banana")
    with pytest.raises(ValueError, match="unrecognized"):
        cp.parse_key("../../etc/passwd")


def test_plan_enumeration_and_fingerprint(warm_engine):
    plan = warm_engine.compile_plan
    assert plan.keys() == ["decode", "admit_p96"]
    facts = plan.facts
    assert facts["quant"] == "off" and facts["kv_layout"] == "paged"
    assert facts["page_size"] == 16 and facts["max_len"] == 96
    # stable: re-deriving the plan from the same engine fingerprints equal
    assert cp.CompilePlan.for_engine(warm_engine).fingerprint() \
        == plan.fingerprint()
    d = plan.describe()
    assert d["entries"] == 2 and len(d["fingerprint"]) == 16


# -- warmup: eager plan compile, compile-free serve window -------------------

def test_warmup_compiles_plan_and_serve_window_is_compile_free(warm_engine):
    # the module fixture already warmed; re-warm must be a no-op
    info = warm_engine.warmup()
    assert info["compiled"] == 0 and info["skipped"] == len(
        warm_engine.compile_plan.keys())
    assert set(warm_engine.compile_plan.keys()) <= set(
        warm_engine._programs)
    before = _total_compiles()
    outs = _serve(warm_engine, _reqs())
    assert all(len(o) == 11 for o in outs)          # 5 prompt + 6 new
    assert _total_compiles() == before, \
        "warmup must leave zero compiles in the serve window"
    # greedy determinism across engines is the parity baseline below
    assert (outs[0] == outs[1]).all()


def test_lazy_build_stays_inside_the_plan(model):
    eng = BatchDecodeEngine(model, max_slots=2, chunk=4, page_size=16)
    _serve(eng, _reqs())
    assert set(eng._programs) <= set(eng.compile_plan.keys()), \
        "the engine compiled a program its plan does not enumerate"


# -- bundles -----------------------------------------------------------------

def test_bundle_round_trip_token_exact_zero_retrace(needs_bundles,
                                                    warm_engine, model,
                                                    tmp_path):
    path = str(tmp_path / "bundle")
    manifest = warm_engine.save_serving_bundle(path)
    assert {e["key"] for e in manifest["entries"]} == {"decode",
                                                       "admit_p96"}
    assert os.path.exists(os.path.join(path, "manifest.json"))
    baseline = _serve(warm_engine, _reqs())
    before = _cold_compiles()
    eng2 = BatchDecodeEngine(model, max_slots=2, chunk=4, page_size=16,
                             bundle=path)
    assert eng2._bundle_info["loaded"] is True
    assert eng2._bundle_info["programs"] == 2
    outs = _serve(eng2, _reqs())
    assert _cold_compiles() == before, \
        "bundle path must serve with zero retraces/compiles"
    for a, b in zip(baseline, outs):
        assert (a == b).all(), "bundle-loaded engine diverged token-wise"
    info = eng2.compile_info()
    assert info["bundle"]["loaded"] and info["programs_built"] == 2
    assert info["plan"]["fingerprint"] == manifest["fingerprint"][:16]


def test_bundle_mismatch_and_corruption_fall_back(needs_bundles,
                                                  warm_engine, model,
                                                  tmp_path):
    path = str(tmp_path / "bundle_m")
    warm_engine.save_serving_bundle(path)
    # config mismatch (different page geometry) -> logged fallback, the
    # engine builds lazily and still serves
    eng = BatchDecodeEngine(model, max_slots=2, chunk=4, page_size=32,
                            bundle=path)
    assert eng._bundle_info["loaded"] is False
    assert "page_size" in eng._bundle_info["error"] \
        or "fingerprint" in eng._bundle_info["error"]
    assert eng._programs == {}            # nothing half-loaded
    outs = _serve(eng, _reqs(n=1))
    assert len(outs[0]) == 11
    # strict load surfaces the typed error
    with pytest.raises(cp.BundleMismatchError):
        eng.load_serving_bundle(path, strict=True)
    # corruption: flip bytes in one payload -> sha check rejects, engine
    # keeps its (already working) programs
    victim = next(f for f in os.listdir(path) if f.endswith(".xc"))
    with open(os.path.join(path, victim), "r+b") as f:
        f.write(b"\x00garbage\x00")
    eng3 = BatchDecodeEngine(model, max_slots=2, chunk=4, page_size=16,
                             bundle=path)
    assert eng3._bundle_info["loaded"] is False
    assert "sha256" in eng3._bundle_info["error"]


# -- persistent compile cache + watchdog labeling ----------------------------

def test_compile_cache_hits_and_watchdog_labels(model, tmp_path,
                                                can_serialize_executables):
    cache_dir = str(tmp_path / "ccache")
    watchdog.install(threshold=3)   # order-independent of the fixtures
    watchdog.reset()
    storms = []
    watchdog.set_storm_callback(lambda site, n: storms.append(site))
    assert compile_cache.install(cache_dir) is True
    try:
        e1 = BatchDecodeEngine(model, max_slots=2, chunk=4, page_size=16)
        w1 = e1.warmup()
        assert w1["compiled"] == 2 and w1["cache_hits"] == 0
        stats = compile_cache.stats()
        assert stats["enabled"] and stats["misses"] >= 2
        # a SECOND engine re-jits the same programs: persistent cache
        # serves them, the watchdog labels them hits, and no per-callsite
        # storm fires on this warm "restart"
        e2 = BatchDecodeEngine(model, max_slots=2, chunk=4, page_size=16)
        w2 = e2.warmup()
        assert w2["compiled"] == 2 and w2["cache_hits"] >= 2
        stats = compile_cache.stats()
        assert stats["hits"] >= 2 and stats["retrieval_s"] > 0
        assert sum(watchdog.cache_hit_counts().values()) >= 2
        assert sum(watchdog.cold_compile_counts().values()) \
            < sum(watchdog.compile_counts().values())
        assert not storms, f"warm restart tripped storm warnings: {storms}"
        log = watchdog.compile_log()
        assert any(e.get("cache_hit") for e in log)
        assert any(e.get("planned") == "warmup" for e in log)
        outs = _serve(e2, _reqs(n=1))
        assert len(outs[0]) == 11
        # a bundle saved from the HIT engine must load back: e2's
        # executables are cache-DESERIALIZED, and save_bundle proves
        # every payload's round trip before writing it
        if can_serialize_executables:
            hit_path = str(tmp_path / "hit_bundle")
            e2.save_serving_bundle(hit_path)
            e3 = BatchDecodeEngine(model, max_slots=2, chunk=4,
                                   page_size=16, bundle=hit_path)
            assert e3._bundle_info["loaded"] is True, \
                e3._bundle_info.get("error")
    finally:
        compile_cache.uninstall()
        watchdog.set_storm_callback(None)
    assert compile_cache.stats()["enabled"] is False
    # uninstall must DETACH, not just stop counting: jax latches its
    # cache handle + "cache used" decision at the first compile, and a
    # stale latch keeps the old directory serving hits and absorbing
    # writes for the rest of the process (the ordering bug that poisoned
    # later engines' bundle saves with cache-deserialized executables)
    from jax._src import compilation_cache as _jcc

    assert _jcc._cache is None, \
        "uninstall left jax's latched persistent-cache handle live"


def test_compile_cache_flag_family():
    from paddlepaddle_tpu.core import flags

    assert flags.flag_value("compile_cache_min_compile_secs") == 0.0
    # no directory from the environment or the caller -> install refuses
    # (cache stays off)
    assert compile_cache.install("") is False
    assert compile_cache.install() is False


# -- serving engine + health surfaces ----------------------------------------

def test_serving_health_compile_block_and_static_mode(model):
    eng = ServingEngine(model, mode="static", max_batch_size=2)
    h = eng.health()
    assert "compile" in h and "cache" in h["compile"]
    # static mode: warmup is a documented no-op, bundles are refused
    assert eng.warmup()["mode"] == "static"
    with pytest.raises(ValueError, match="continuous"):
        eng.save_serving_bundle("/tmp/nope")
    with pytest.raises(ValueError, match="continuous"):
        ServingEngine(model, mode="static", bundle="/tmp/nope")


def test_serving_engine_bundle_passthrough(needs_bundles, warm_engine, model,
                                           tmp_path):
    path = str(tmp_path / "bundle_se")
    warm_engine.save_serving_bundle(path)
    srv = ServingEngine(model, mode="continuous", max_batch_size=2,
                        decode_chunk=4, kv_page_size=16, bundle=path)
    h = srv.health()
    assert h["compile"]["bundle"]["loaded"] is True
    assert h["compile"]["plan"]["entries"] == 2
    before = _cold_compiles()
    with srv:
        out = srv.generate([1, 2, 3, 4, 5], max_new_tokens=4,
                           timeout=120)
    assert len(out) == 9 and _cold_compiles() == before


# -- router pre-warm ---------------------------------------------------------

def test_rolling_restart_prewarms_before_readmission(model, tmp_path):
    from paddlepaddle_tpu.inference.router import ServingRouter

    assert compile_cache.install(str(tmp_path / "rcache"))
    try:
        def factory():
            return ServingEngine(model, mode="continuous",
                                 max_batch_size=2, decode_chunk=4,
                                 kv_page_size=16)

        router = ServingRouter([factory], probe_interval_s=0.05)
        with router:
            out = router.generate([1, 2, 3, 4, 5], max_new_tokens=4,
                                  timeout=120)
            assert len(out) == 9
            res = router.rolling_restart(health_timeout=30.0)
            assert res["ok"] is True
            warm = res["replicas"][0]["warmup"]
            # the fresh engine's whole plan compiled OUT of rotation...
            assert warm is not None and warm["compiled"] == 2
            # ...so the first routed request finds only warm programs
            before = _total_compiles()
            out2 = router.generate([1, 2, 3, 4, 5], max_new_tokens=4,
                                   timeout=120)
            assert len(out2) == 9
            assert _total_compiles() == before, \
                "first request after rolling restart hit a cold program"
    finally:
        compile_cache.uninstall()


# -- perf gate ---------------------------------------------------------------

def test_perf_gate_coldstart_metrics(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import perf_gate

    base = {"coldstart": {
        "restart_to_first_token_s": 1.0, "compiles": 0,
        "cold": {"restart_to_first_token_s": 20.0},
        "bundle": {"restart_to_first_token_s": 1.0},
        "bundle_cache": {"restart_to_first_token_s": 0.6}}}
    good = json.loads(json.dumps(base))
    bad = {"coldstart": {
        "restart_to_first_token_s": 4.0, "compiles": 5,
        "cold": {"restart_to_first_token_s": 20.0},
        "bundle": {"restart_to_first_token_s": 4.0},
        "bundle_cache": {"restart_to_first_token_s": 4.0}}}
    bench = str(tmp_path / "bench.json")
    with open(bench, "w") as f:
        json.dump({"value": 100.0}, f)
    paths = {}
    for name, doc in (("base", base), ("good", good), ("bad", bad)):
        paths[name] = str(tmp_path / f"{name}.json")
        with open(paths[name], "w") as f:
            json.dump(doc, f)
    assert perf_gate.main(["--baseline", bench, "--serving",
                           paths["good"], paths["base"]]) == 0
    rc = perf_gate.main(["--baseline", bench, "--serving",
                         paths["bad"], paths["base"]])
    assert rc == 1          # slower restart AND compiles off the 0 floor
    # the metric extraction itself
    m = perf_gate.serving_metrics(bad)
    assert m["coldstart.restart_to_first_token_s"] == (4.0, "lower")
    assert m["coldstart.compiles"] == (5.0, "lower")
    assert m["coldstart.bundle.restart_to_first_token_s"][1] == "lower"


# -- full e2e: int8 + prefix variants (slow) ---------------------------------

@pytest.mark.slow
def test_bundle_full_e2e_int8_with_prefix_variant(needs_bundles, tmp_path):
    # BOTH phases in fresh subprocesses — the real deploy shape (a
    # bundle-save job, then a restarted serving process). In-process,
    # earlier suite tests that *executed* persistent-cache-retrieved
    # executables leave XLA CPU symbol state that makes executables
    # serialized afterwards non-portable (`Symbols not found` at
    # deserialize) — the graceful-fallback path, see docs/serving.md.
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = str(tmp_path / "bundle_int8")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # phase 1: build the int8 engine, drive prefix traffic so the
    # traffic-shaped admit_pfx program exists (not in the static plan,
    # but bundled once built), save the bundle
    saver = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "import tests.test_compile_plan as t\n"
        "from paddlepaddle_tpu.inference.decode_engine import "
        "BatchDecodeEngine\n"
        "from paddlepaddle_tpu.inference.serving import GenerationRequest\n"
        "from paddlepaddle_tpu.observability import watchdog\n"
        "watchdog.install()\n"
        "m = t._model()\n"
        "eng = BatchDecodeEngine(m, max_slots=2, chunk=4, page_size=16,\n"
        "    quant='weight_only_int8', quant_group_size=16)\n"
        "eng.warmup()\n"
        "prompt = list(range(1, 41))\n"
        "r1 = GenerationRequest(prompt, 5, 0.0, 0, None, prefix_len=20)\n"
        "r2 = GenerationRequest(prompt[:20] + list(range(50, 70)), 5, 0.0,"
        " 0, None, prefix_len=20)\n"
        "outs = t._serve(eng, [r1, r2])\n"
        "pfx = [k for k in eng._programs if k.startswith('admit_pfx')]\n"
        "assert pfx, 'prefix traffic did not build a prefix-HIT program'\n"
        "manifest = eng.save_serving_bundle(%r)\n"
        "saved = {e['key'] for e in manifest['entries']}\n"
        "assert set(eng.compile_plan.keys()) | set(pfx) <= saved\n"
        "print(json.dumps({'tokens': [o.tolist() for o in outs],\n"
        "                  'saved': sorted(saved)}))\n"
    ) % (root, path)
    proc = subprocess.run([sys.executable, "-c", saver], env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    base = json.loads(proc.stdout.strip().splitlines()[-1])["tokens"]
    # phase 2: fresh same-weights engine restarted from the bundle
    child = (
        "import json, sys, numpy as np\n"
        "sys.path.insert(0, %r)\n"
        "import tests.test_compile_plan as t\n"
        "from paddlepaddle_tpu.inference.decode_engine import "
        "BatchDecodeEngine\n"
        "from paddlepaddle_tpu.inference.serving import GenerationRequest\n"
        "from paddlepaddle_tpu.observability import watchdog\n"
        "watchdog.install()\n"
        "m = t._model()\n"
        "eng = BatchDecodeEngine(m, max_slots=2, chunk=4, page_size=16,\n"
        "    quant='weight_only_int8', quant_group_size=16, bundle=%r)\n"
        "eng.load_serving_bundle(%r, strict=True)  # loud on mismatch\n"
        "w = eng.warmup()  # flushes host-op fills; programs all loaded\n"
        "c0 = sum(watchdog.compile_counts().values())\n"
        "prompt = list(range(1, 41))\n"
        "r3 = GenerationRequest(prompt, 5, 0.0, 0, None, prefix_len=20)\n"
        "r4 = GenerationRequest(prompt[:20] + list(range(50, 70)), 5, 0.0,"
        " 0, None, prefix_len=20)\n"
        "outs = t._serve(eng, [r3, r4])\n"
        "print(json.dumps({\n"
        "    'loaded': eng._bundle_info['loaded'],\n"
        "    'warmup_compiled': w['compiled'],\n"
        "    'serve_window_compiles':\n"
        "        sum(watchdog.compile_counts().values()) - c0,\n"
        "    'prefix_hits': eng.prefix.hits,\n"
        "    'tokens': [o.tolist() for o in outs]}))\n"
    ) % (root, path, path)
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["loaded"] is True
    # zero retraces on the bundle path: every plan program came from the
    # bundle (warmup had nothing to compile) and the serve window —
    # including the bundled prefix-HIT program — is compile-free
    assert out["warmup_compiled"] == 0
    assert out["serve_window_compiles"] == 0
    assert out["prefix_hits"] >= 1
    assert out["tokens"] == base, \
        "bundle-restarted engine diverged token-wise from the saver"
