"""Performance attribution plane (observability/perf/): program cost
registry (exact XLA FLOPs -> measured MFU/roofline), step-time
decomposition, request-lifecycle SLO tracing, and the perf regression
gate."""

import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddlepaddle_tpu.observability as obs
from paddlepaddle_tpu.observability import perf

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture
def clean_perf():
    obs.disable()
    obs.reset()
    perf.enable()
    yield
    perf.disable()
    obs.disable()
    obs.reset()


def _tiny_llama(max_len=256):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=128, hidden_size=32, layers=1, heads=2, kv_heads=1,
        max_len=max_len))


# ---------------------------------------------------------------------------
# cost registry
# ---------------------------------------------------------------------------

def test_capture_known_matmul_exact_flops(clean_perf):
    """A known-shape matmul must report EXACTLY 2*M*K*N flops, and the
    returned Compiled must execute correctly (capture is not a shadow
    compile — it IS the executable)."""
    import jax
    import jax.numpy as jnp

    M, K, N = 128, 64, 32
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((M, K), jnp.float32)
    b = jnp.ones((K, N), jnp.float32)
    compiled = perf.capture_jit("t.matmul", f, (a, b), bucket="mkn")
    assert compiled is not None
    out = np.asarray(compiled(a, b))
    assert out.shape == (M, N) and float(out[0, 0]) == K
    rows = {(r["program"], r["bucket"]): r for r in perf.registry().table()}
    row = rows[("t.matmul", "mkn")]
    assert row["flops"] == 2 * M * K * N
    assert row["hbm_bytes"] and row["out_bytes"] == M * N * 4
    assert row["cost_source"] == "compiled"
    # same count from the no-backend-compile lowering path
    c = perf.cost_of_lowered("t.matmul_lowered", f, (a, b))
    assert c["flops"] == 2 * M * K * N


def test_roofline_classification_and_mfu(clean_perf):
    """Derived fields: MFU from (flops, min wall, peak), bandwidth util,
    and the intensity-vs-ridge compute/bandwidth classification."""
    specs = {"peak_flops": 100.0, "peak_hbm_bytes_per_s": 10.0,
             "ridge_flops_per_byte": 10.0}
    reg = perf.registry()
    reg.record("compute_prog", flops=100.0, bytes_accessed=1.0)
    reg.observe("compute_prog", 2.0)
    reg.record("bw_prog", flops=10.0, bytes_accessed=5.0)
    reg.observe("bw_prog", 1.0)
    reg.observe("bw_prog", 0.5)           # min wall wins
    rows = {r["program"]: r for r in reg.table(specs)}
    c, b = rows["compute_prog"], rows["bw_prog"]
    assert c["bound"] == "compute" and c["pct_of_peak"] == c["mfu"]
    assert c["mfu"] == pytest.approx(100.0 / (2.0 * 100.0))
    assert b["bound"] == "bandwidth"
    assert b["calls"] == 2 and b["wall_s_min"] == 0.5
    assert b["hbm_util"] == pytest.approx(5.0 / (0.5 * 10.0))
    assert b["pct_of_peak"] == b["hbm_util"]


def test_program_gauges_on_metrics_scrape(clean_perf):
    """/metrics must expose paddle_program_* roofline gauges (published
    lazily at scrape time)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a @ a)
    a = jnp.ones((64, 64))
    perf.capture_jit("t.sq", f, (a,), bucket="64")
    perf.observe("t.sq", 1e-3, bucket="64")
    text = obs.to_prometheus_text()
    assert 'paddle_program_flops{bucket="64",program="t.sq"}' in text
    assert "paddle_program_mfu" in text
    assert "paddle_program_compute_bound" in text
    # strict exposition: the round-trip parser must accept it
    from paddlepaddle_tpu.observability.metrics import parse_prometheus_text

    fams = parse_prometheus_text(text)
    assert "paddle_program_mfu" in fams


# ---------------------------------------------------------------------------
# step-time decomposition
# ---------------------------------------------------------------------------

def test_steptimeline_phases_sum_to_wall(clean_perf):
    """Phase seconds sum to the step wall by construction, and recorded
    comm/data spans inside the bracket land in their phases."""
    obs.enable(trace=True, metrics=True, watchdog_=False)
    tl = perf.timeline()
    rec = obs.get_recorder()
    with tl.step("s1"):
        rec.record_complete("fake_allreduce", "collective", 0.010)
        rec.record_complete("dataloader_wait", "dataloader", 0.005)
        time.sleep(0.03)
    assert tl.count == 1
    s = tl.snapshot()["last"][-1]
    total = sum(s["phases"].values())
    assert total == pytest.approx(s["wall_s"], rel=1e-6)
    assert s["phases"]["comm"] == pytest.approx(0.010)
    assert s["phases"]["data_wait"] == pytest.approx(0.005)
    assert s["phases"]["compute"] > 0
    # metrics: per-phase counters accumulated
    snap = obs.snapshot()
    phases = snap["paddle_step_phase_seconds_total"]
    assert phases[(("phase", "comm"),)] == pytest.approx(0.010)
    assert snap["paddle_steps_total"][()] == 1
    # summary renders the section
    assert "Step time decomposition" in obs.summary()


def test_steptimeline_counter_track_in_trace(clean_perf, tmp_path):
    """With tracing on, each step emits a chrome 'C' (counter) sample —
    Perfetto renders the stacked per-phase track."""
    obs.enable(trace=True, metrics=False, watchdog_=False)
    with perf.step("s"):
        time.sleep(0.002)
    doc = obs.get_recorder().to_chrome_trace()
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert counters and counters[-1]["name"] == "step_phases_ms"
    assert set(counters[-1]["args"]) == {"compute", "host", "comm",
                                         "data_wait"}
    # and the trace file is still valid JSON end-to-end
    p = tmp_path / "t.json"
    obs.export_chrome_trace(str(p))
    json.loads(p.read_text())


# ---------------------------------------------------------------------------
# compile-path hooks
# ---------------------------------------------------------------------------

def test_decode_engine_program_capture_and_walls(clean_perf):
    """The engine's bucketed prefill and chunked decode land in the cost
    registry; decode flops come from a 1-step lowering scaled by chunk
    (XLA counts a scan body once), and each chunk observes a wall."""
    from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
    from paddlepaddle_tpu.inference.serving import GenerationRequest

    eng = BatchDecodeEngine(_tiny_llama(), max_slots=2, chunk=4)
    rng = np.random.default_rng(0)
    reqs = [GenerationRequest(rng.integers(0, 128, (8,)), 6, 0.0, 0, None)
            for _ in range(2)]
    eng.serve(reqs)
    rows = {(r["program"], r["bucket"]): r for r in perf.registry().table()}
    admit = rows[("serving.admit", "p128")]
    decode = rows[("serving.decode", "s2c4")]
    assert admit["flops"] > 0 and admit["cost_source"] == "compiled"
    assert decode["flops"] > 0 and decode["cost_source"] == "lowered"
    assert decode["cost_scale"] == 4.0
    assert decode["calls"] >= 1 and decode["wall_s_min"] > 0
    assert decode["mfu"] is not None and decode["mfu"] > 0


def test_trainstep_and_static_run_program_capture(clean_perf):
    """TrainStep's first call and a static-graph run both register their
    program costs (lowering path — execution identical to perf-off)."""
    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.optimizer import SGD

    lin = paddle.nn.Linear(8, 8)
    opt = SGD(learning_rate=0.1, parameters=lin.parameters())
    step = TrainStep(lin, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
    x = np.ones((4, 8), np.float32)
    step(paddle.to_tensor(x), paddle.to_tensor(x))
    rows = {r["program"]: r for r in perf.registry().table()}
    assert rows["train.step"]["flops"] > 0
    assert rows["train.step"]["cost_source"] == "lowered"

    # static program
    paddle.enable_static()
    try:
        from paddlepaddle_tpu import static

        with static.program_guard(static.Program()):
            inp = static.data("x", [4, 8], "float32")
            out = inp * 2.0 + 1.0
            exe = static.Executor()
            res = exe.run(feed={"x": x}, fetch_list=[out])
        assert np.allclose(res[0], x * 2 + 1)
    finally:
        paddle.disable_static()
    rows = {r["program"]: r for r in perf.registry().table()}
    assert "static.run_program" in rows
    assert rows["static.run_program"]["calls"] >= 1


def test_static_run_program_survives_shape_change(clean_perf):
    """The exec cache keys on feed NAMES, not shapes — with perf armed
    the capture must stay on the lowering path so jit's transparent
    retrace on a new batch shape (e.g. a last partial batch) survives."""
    import paddlepaddle_tpu as paddle

    paddle.enable_static()
    try:
        from paddlepaddle_tpu import static

        with static.program_guard(static.Program()):
            inp = static.data("x", [-1, 4], "float32")
            out = inp * 3.0
            exe = static.Executor()
            a = exe.run(feed={"x": np.ones((2, 4), np.float32)},
                        fetch_list=[out])
            b = exe.run(feed={"x": np.ones((5, 4), np.float32)},
                        fetch_list=[out])
        assert np.asarray(a[0]).shape == (2, 4)
        assert np.asarray(b[0]).shape == (5, 4)
    finally:
        paddle.disable_static()


def test_bench_time_steps_reports_cost(clean_perf):
    """bench._time_steps returns the cost dict the mfu_measured fields
    are derived from (single-step lowering, not the scan chains)."""
    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.jit.train import TrainStep
    from paddlepaddle_tpu.optimizer import SGD

    sys.path.insert(0, os.path.dirname(_TOOLS))
    import bench

    lin = paddle.nn.Linear(16, 16)
    opt = SGD(learning_rate=0.1, parameters=lin.parameters())
    step = TrainStep(lin, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
    x = np.ones((4, 16), np.float32)
    dt, loss, cost = bench._time_steps(step, None, 3, batch=(x, x),
                                       tag="unit")
    assert dt > 0
    assert cost is not None and cost["flops_per_step"] > 0
    rows = {r["program"]: r for r in perf.registry().table()}
    assert rows["bench.unit"]["calls"] == 1   # per_step wall observed


# ---------------------------------------------------------------------------
# request-lifecycle SLO tracing
# ---------------------------------------------------------------------------

def test_slo_histograms_and_request_spans_continuous(clean_perf):
    """Continuous engine: TTFT / TPOT / queue-wait histograms populate,
    GenerationResult.slo() carries per-request numbers, and each request
    lands as a request#<id> span in the trace."""
    from paddlepaddle_tpu.inference.serving import ServingEngine

    obs.enable(trace=True, metrics=True, watchdog_=False)
    rng = np.random.default_rng(0)
    with ServingEngine(_tiny_llama(), max_batch_size=2,
                       decode_chunk=4) as eng:
        futs = [eng.submit(rng.integers(0, 128, (8,)).astype(np.int32),
                           max_new_tokens=6) for _ in range(3)]
        for f in futs:
            f.result(120)
    s = futs[0].slo()
    assert s["new_tokens"] == 6
    assert s["ttft_s"] is not None and 0 < s["ttft_s"] <= s["latency_s"]
    assert s["queue_wait_s"] is not None and s["queue_wait_s"] >= 0
    assert s["tpot_s"] is not None and s["tpot_s"] > 0
    snap = obs.snapshot()
    assert snap["paddle_serving_ttft_seconds"][()]["count"] == 3
    assert snap["paddle_serving_tpot_seconds"][()]["count"] == 3
    assert snap["paddle_serving_queue_wait_seconds"][()]["count"] == 3
    spans = [e for e in obs.get_recorder().events()
             if e.cat == "serving.request"]
    assert len(spans) == 3
    assert spans[0].name.startswith("request#")
    assert spans[0].args["tokens"] == 6
    assert "SLO: ttft p50=" in obs.summary()


class _FakeTensor:
    def __init__(self, a):
        self._a = a

    def numpy(self):
        return self._a


class _FakeModel:
    """generate_cached-shaped model for the static scheduler — decodes
    instantly, so the SLO surface is exercised without a real compile."""

    class config:
        max_position_embeddings = 64

    def generate_cached(self, ids, max_new_tokens=4, temperature=0.0,
                        top_k=0, eos_token_id=None):
        ids = np.asarray(ids)
        gen = np.tile(np.arange(max_new_tokens, dtype=np.int32),
                      (ids.shape[0], 1))
        return _FakeTensor(np.concatenate([ids, gen], axis=1))


def test_slo_static_mode_fake_engine(clean_perf):
    """Static mode: TTFT == full latency (no streaming), deadline margin
    observed, histograms fed through the same hook."""
    from paddlepaddle_tpu.inference.serving import ServingEngine

    obs.enable(trace=False, metrics=True, watchdog_=False)
    with ServingEngine(_FakeModel(), mode="static", max_batch_size=4,
                       max_wait_ms=5) as eng:
        futs = [eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=5,
                           deadline_s=30.0) for _ in range(4)]
        for f in futs:
            f.result(30)
    s = futs[0].slo()
    assert s["new_tokens"] == 5
    assert s["ttft_s"] == pytest.approx(s["latency_s"], rel=0.5)
    snap = obs.snapshot()
    assert snap["paddle_serving_ttft_seconds"][()]["count"] == 4
    margins = snap["paddle_serving_deadline_margin_seconds"][()]
    assert margins["count"] == 4 and margins["min"] > 0


def test_flight_dump_carries_requests_and_program_costs(clean_perf,
                                                        tmp_path):
    """The black box includes request-lifecycle ring events AND the live
    program-cost table (callable annotation resolved at dump time)."""
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.inference.serving import ServingEngine
    from paddlepaddle_tpu.observability import flight

    flight.enable(str(tmp_path), install_hooks=False)
    try:
        f = jax.jit(lambda a: a * 2)
        a = jnp.ones((8,))
        perf.capture_jit("t.double", f, (a,))
        with ServingEngine(_FakeModel(), mode="static",
                           max_batch_size=2, max_wait_ms=5) as eng:
            eng.submit(np.arange(4, dtype=np.int32),
                       max_new_tokens=3).result(30)
        path = flight.dump("perf_test")
        lines = [json.loads(ln) for ln in open(path)]
    finally:
        flight.disable()
    head = lines[0]
    progs = head["annotations"]["program_costs"]
    assert any(r["program"] == "t.double" for r in progs)
    req_events = [ln for ln in lines if ln.get("rec") == "event"
                  and ln.get("kind") == "request"]
    phases = {(e.get("data") or {}).get("phase") for e in req_events}
    assert "submit" in phases and "finish" in phases


# ---------------------------------------------------------------------------
# exporter endpoint + obsctl
# ---------------------------------------------------------------------------

def test_programs_endpoint_and_obsctl(clean_perf, capsys):
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.observability import exporter

    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((64, 32))
    b = jnp.ones((32, 16))
    perf.capture_jit("t.mm", f, (a, b), bucket="64")
    perf.observe("t.mm", 1e-4, bucket="64")
    served = exporter.TelemetryExporter(port=0).start()
    try:
        with urllib.request.urlopen(served.url("/programs"),
                                    timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["enabled"] is True
        assert doc["device"]["peak_flops"] > 0
        row = next(r_ for r_ in doc["programs"] if r_["program"] == "t.mm")
        assert row["flops"] == 2 * 64 * 32 * 16
        assert row["mfu"] > 0

        sys.path.insert(0, _TOOLS)
        import obsctl

        rc = obsctl.main(["programs", f"127.0.0.1:{served.port}"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "t.mm" in out and "Bound" in out
    finally:
        served.stop()


# ---------------------------------------------------------------------------
# perf_gate
# ---------------------------------------------------------------------------

def _gate(argv):
    sys.path.insert(0, _TOOLS)
    import perf_gate

    return perf_gate.main(argv)


def _bench_doc(tok_s=1000.0, mfu=0.5, ttft50=10.0, ttft99=20.0):
    return {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": tok_s,
        "detail": {"mfu": mfu, "configs": {
            "resnet50": {"images_per_sec": 100.0, "step_ms": 50.0},
        }},
    }, {
        "serving_bench": {"aggregate_tok_s": 500.0,
                          "ttft_p50_ms": ttft50, "ttft_p99_ms": ttft99,
                          "tpot_ms": 1.0},
    }


def test_perf_gate_synthetic(tmp_path):
    bench, serving = _bench_doc()
    base = tmp_path / "base.json"
    sbase = tmp_path / "sbase.json"
    base.write_text(json.dumps(bench))
    sbase.write_text(json.dumps(serving))

    # identical artifacts pass
    assert _gate(["--baseline", str(base), "--current", str(base),
                  "--serving", str(sbase), str(sbase)]) == 0

    # a 10% tokens/s drop fails at the default 5% tolerance
    worse, _ = _bench_doc(tok_s=900.0)
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(worse))
    assert _gate(["--baseline", str(base), "--current", str(cur)]) == 1
    # ... but --dry-run always exits 0
    assert _gate(["--baseline", str(base), "--current", str(cur),
                  "--dry-run"]) == 0
    # ... and a wider tolerance admits it
    assert _gate(["--baseline", str(base), "--current", str(cur),
                  "--tol", "0.15"]) == 0

    # latency is direction-aware: TTFT p99 doubling fails
    _, sworse = _bench_doc(ttft99=45.0)
    scur = tmp_path / "scur.json"
    scur.write_text(json.dumps(sworse))
    assert _gate(["--baseline", str(base), "--current", str(base),
                  "--serving", str(scur), str(sbase)]) == 1

    # missing metric: warns by default, fails under --strict
    partial = {"metric": "x", "value": 1000.0, "detail": {}}
    pcur = tmp_path / "partial.json"
    pcur.write_text(json.dumps(partial))
    assert _gate(["--baseline", str(base), "--current", str(pcur)]) == 0
    assert _gate(["--baseline", str(base), "--current", str(pcur),
                  "--strict"]) == 1

    # driver-format artifacts (a record wrapped as {"parsed": ...}) parse
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(json.dumps({"n": 5, "rc": 0, "parsed": bench}))
    assert _gate(["--baseline", str(wrapped), "--current", str(base)]) == 0

    # unusable input -> 2
    bad = tmp_path / "bad.json"
    bad.write_text("not json at all")
    assert _gate(["--baseline", str(bad)]) == 2


def test_perf_gate_paged_kv_serving_fields(tmp_path):
    """The paged-KV serving_bench columns gate direction-aware: hit rate /
    concurrency / mixed tokens/s falling is a regression, occupancy
    RISING is a regression (it's memory per workload, lower = better)."""
    bench, _ = _bench_doc()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(bench))

    def serving(hit=0.9, conc=8, occ=0.5, mixed=800.0, avail=1.0):
        return {"serving_bench": {
            "aggregate_tok_s": 500.0, "ttft_p50_ms": 10.0,
            "prefix_hit_rate": hit, "concurrency_peak": conc,
            "kv_occupancy_peak": occ, "mixed_tok_s": mixed,
            "availability": avail}}

    sbase = tmp_path / "sbase.json"
    sbase.write_text(json.dumps(serving()))
    good = tmp_path / "good.json"
    good.write_text(json.dumps(serving(hit=0.95, occ=0.4)))  # improvements
    assert _gate(["--baseline", str(base), "--current", str(base),
                  "--serving", str(good), str(sbase)]) == 0
    for bad_kw in ({"hit": 0.5}, {"conc": 4}, {"mixed": 600.0},
                   {"occ": 0.9}, {"avail": 0.8}):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(serving(**bad_kw)))
        assert _gate(["--baseline", str(base), "--current", str(base),
                      "--serving", str(bad), str(sbase)]) == 1, bad_kw


def test_perf_gate_driver_format_dry_run(tmp_path):
    """The run_tier1 smoke: a driver-format record (bench.py's line under
    ``parsed``) parses and the gate passes against itself."""
    bench, _ = _bench_doc()
    rec = tmp_path / "driver_record.json"
    rec.write_text(json.dumps({"n": 5, "rc": 0, "parsed": bench}))
    assert _gate(["--baseline", str(rec)]) == 0
    assert _gate(["--baseline", str(rec), "--dry-run"]) == 0
