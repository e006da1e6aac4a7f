"""Fleet telemetry plane (observability/exporter.py, aggregate.py,
flight.py + tools/obsctl.py): per-rank HTTP exporters, rank-0 store-based
aggregation with a rank label per series, cross-rank chrome-trace merge,
and the crash flight recorder ("black box").

Reference surface: fleet-wide monitor stats + multi-worker profile merge;
MegaScale-style crash-surviving diagnostics.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
import paddlepaddle_tpu.observability as obs
from paddlepaddle_tpu.observability import aggregate, exporter, flight
from paddlepaddle_tpu.observability.metrics import parse_prometheus_text

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OBSCTL = os.path.join(_REPO, "tools", "obsctl.py")


@pytest.fixture
def clean_obs():
    """Observability + flight recorder + exporter singleton fully reset
    before AND after — no telemetry state may leak across suites."""
    obs.disable()
    obs.reset()
    flight.disable()
    exporter.stop()
    yield obs
    obs.disable()
    obs.reset()
    flight.disable()
    exporter.stop()


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ---------------------------------------------------------------------------
# per-rank exporter
# ---------------------------------------------------------------------------

def test_exporter_serves_metrics_healthz_vars_trace(clean_obs):
    obs.enable(trace=True, metrics=True, watchdog_=False)
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    _ = paddle.add(x, x)
    with obs.RecordEvent("probe_region"):
        pass
    with exporter.TelemetryExporter(port=0) as e:
        status, body = _get(e.url("/metrics"))
        assert status == 200
        fams = parse_prometheus_text(body.decode())  # valid exposition
        assert "paddle_op_calls_total" in fams

        status, body = _get(e.url("/healthz"))
        assert status == 200
        health = json.loads(body)
        assert health["ok"] is True
        assert health["rank"] == 0 and health["world"] == 1
        assert health["obs"]["metrics"] is True
        assert health["obs"]["blackbox"] is False

        status, body = _get(e.url("/vars"))
        assert status == 200
        doc = json.loads(body)  # strict JSON (no Infinity), labeled rows
        rows = doc["paddle_op_calls_total"]
        assert any(r["labels"] == {"op": "add"} and r["value"] == 1
                   for r in rows)

        status, body = _get(e.url("/trace"))
        assert status == 200
        trace = json.loads(body)
        assert trace["displayTimeUnit"] == "ms"
        assert any(ev["name"] == "probe_region"
                   for ev in trace["traceEvents"])

        status, body = _get(e.url("/no/such/route"))
        assert status == 404
        assert "/metrics" in json.loads(body)["routes"]


def test_vars_stays_strict_json_with_nonfinite_observations(clean_obs):
    """A histogram that saw inf must not make /vars emit `Infinity` (which
    strict JSON parsers reject) — non-finite scalars become null."""
    obs.get_registry().histogram("paddle_degenerate_seconds",
                                 "probe").observe(float("inf"))
    obs.get_registry().gauge("paddle_degenerate_gauge",
                             "probe").set(float("nan"))
    with exporter.TelemetryExporter(port=0) as e:
        status, body = _get(e.url("/vars"))
        assert status == 200
        doc = json.loads(body.decode(), parse_constant=lambda c: (
            pytest.fail(f"non-strict JSON constant {c} in /vars")))
        (row,) = doc["paddle_degenerate_seconds"]
        assert row["value"]["sum"] is None
        assert row["value"]["min"] is None
        (grow,) = doc["paddle_degenerate_gauge"]
        assert grow["value"] is None


def test_exporter_health_providers_gate_the_503(clean_obs):
    with exporter.TelemetryExporter(port=0) as e:
        e.register_health("serving", lambda: {"ok": True, "state": "serving"})
        status, body = _get(e.url("/healthz"))
        assert status == 200
        assert json.loads(body)["providers"]["serving"]["state"] == "serving"

        e.register_health("serving", lambda: {"ok": False, "state": "open"})
        status, body = _get(e.url("/healthz"))
        assert status == 503
        assert json.loads(body)["ok"] is False

        def broken():
            raise RuntimeError("probe exploded")

        e.register_health("serving", broken)
        status, body = _get(e.url("/healthz"))
        assert status == 503
        assert "probe exploded" in json.loads(body)["providers"]["serving"]["error"]

        e.unregister_health("serving")
        status, _ = _get(e.url("/healthz"))
        assert status == 200


def test_exporter_falls_back_to_ephemeral_port_when_taken(clean_obs, capfd):
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    taken = blocker.getsockname()[1]
    try:
        with exporter.TelemetryExporter(port=taken) as e:
            assert e.port is not None and e.port != taken
            status, _ = _get(e.url("/healthz"))
            assert status == 200
    finally:
        blocker.close()
    assert "falling back" in capfd.readouterr().err


def test_serving_engine_registers_health_with_running_exporter(clean_obs):
    serving = pytest.importorskip("paddlepaddle_tpu.inference.serving")

    class _Out:
        def __init__(self, a):
            self._a = a

        def numpy(self):
            return self._a

    class FakeModel:
        def generate_cached(self, ids, max_new_tokens, **kw):
            return _Out(np.concatenate(
                [ids, np.zeros((ids.shape[0], max_new_tokens), np.int32)],
                axis=1))

    e = exporter.start(port=0)
    eng = serving.ServingEngine(FakeModel(), mode="static",
                                max_batch_size=2, max_wait_ms=5.0,
                                max_len=64)
    try:
        eng.submit(np.zeros((4,), np.int32), max_new_tokens=4).result(30)
        status, body = _get(e.url("/healthz"))
        assert status == 200
        prov = json.loads(body)["providers"]["serving"]
        assert prov["state"] == "serving" and prov["ok"] is True
    finally:
        eng.stop()
    # a deliberate stop unregisters: the process is not "unhealthy"
    status, body = _get(e.url("/healthz"))
    assert status == 200
    assert "serving" not in json.loads(body)["providers"]


# ---------------------------------------------------------------------------
# fleet aggregation (metric merge + trace merge)
# ---------------------------------------------------------------------------

_T0 = ('# HELP paddle_demo_total a demo counter\n'
       '# TYPE paddle_demo_total counter\n'
       'paddle_demo_total{op="add"} 3\n')
_T1 = ('# HELP paddle_demo_total a demo counter\n'
       '# TYPE paddle_demo_total counter\n'
       'paddle_demo_total{op="add"} 5\n'
       '# HELP paddle_demo_depth a demo gauge\n'
       '# TYPE paddle_demo_depth gauge\n'
       'paddle_demo_depth 2\n')


def test_merge_prometheus_texts_labels_every_sample_with_rank():
    merged = aggregate.merge_prometheus_texts({0: _T0, 1: _T1})
    assert 'paddle_demo_total{op="add",rank="0"} 3' in merged
    assert 'paddle_demo_total{op="add",rank="1"} 5' in merged
    assert 'paddle_demo_depth{rank="1"} 2' in merged
    # HELP/TYPE once per family, and the merge re-parses strictly
    assert merged.count("# TYPE paddle_demo_total counter") == 1
    fams = parse_prometheus_text(merged)
    assert {lab["rank"] for _, lab, _ in
            fams["paddle_demo_total"]["samples"]} == {"0", "1"}
    # an existing rank label is preserved, not clobbered
    pre = ('# HELP x_total h\n# TYPE x_total counter\n'
           'x_total{rank="9"} 1\n')
    assert 'rank="9"' in aggregate.merge_prometheus_texts({0: pre})


def test_merge_chrome_traces_one_pid_per_rank_with_clock_offsets():
    doc0 = {"traceEvents": [
        {"name": "step", "ph": "X", "ts": 1000, "dur": 10, "pid": 0,
         "tid": 1}], "displayTimeUnit": "ms"}
    doc1 = {"traceEvents": [
        {"name": "step", "ph": "X", "ts": 1000, "dur": 10, "pid": 0,
         "tid": 7}], "displayTimeUnit": "ms"}
    # rank 1's perf epoch started 2s "later" in wall terms: same wall
    # instant => its anchor (wall - perf) is 2s larger, shifting +2e6 us
    clocks = {0: {"wall": 100.0, "perf": 50.0},
              1: {"wall": 100.0, "perf": 48.0}}
    merged = aggregate.merge_chrome_traces({0: doc0, 1: doc1}, clocks)
    assert merged["displayTimeUnit"] == "ms"
    events = merged["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    by_pid = {e["pid"]: e for e in spans}
    assert by_pid[0]["ts"] == 1000
    assert by_pid[1]["ts"] == 1000 + 2_000_000
    assert by_pid[1]["tid"] == 7  # thread ids survive, only pid is rewritten
    meta = [e for e in events if e.get("ph") == "M"
            and e["name"] == "process_name"]
    assert {(m["pid"], m["args"]["name"]) for m in meta} == {
        (0, "rank 0"), (1, "rank 1")}
    json.loads(json.dumps(merged))  # Perfetto loads strict JSON


def test_fleet_publisher_and_rank0_merged_routes_over_store(clean_obs):
    """Two 'ranks' in one process: rank 1 publishes through a real TCPStore,
    rank 0's exporter serves the merged /metrics, /fleet/trace and
    /fleet/ranks — the in-process version of the 2-worker acceptance."""
    from paddlepaddle_tpu.distributed.store import TCPStore

    store = TCPStore("127.0.0.1", 0, is_master=True)
    trace1 = {"traceEvents": [{"name": "w1", "ph": "X", "ts": 5, "dur": 1,
                               "pid": 0, "tid": 2}], "displayTimeUnit": "ms"}
    pub = aggregate.FleetPublisher(
        store, rank=1, interval_s=0.1, text_fn=lambda: _T1,
        trace_fn=lambda: trace1).start()
    try:
        obs.enable(trace=True, metrics=True, watchdog_=False)
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        _ = paddle.add(x, x)
        with exporter.TelemetryExporter(port=0) as e:
            aggregate.install_fleet_routes(e, store, world=2, local_rank=0)
            deadline = time.time() + 10
            fams = {}
            while time.time() < deadline:
                status, body = _get(e.url("/metrics"))
                assert status == 200
                fams = parse_prometheus_text(body.decode())
                if "paddle_demo_total" in fams:
                    break
                time.sleep(0.05)
            # rank 0's live series and rank 1's published series, labeled
            assert any(lab.get("rank") == "0" for _, lab, _ in
                       fams["paddle_op_calls_total"]["samples"])
            assert any(lab.get("rank") == "1" for _, lab, _ in
                       fams["paddle_demo_total"]["samples"])
            (reporting,) = [v for _, _, v in
                            fams["paddle_fleet_ranks_reporting"]["samples"]]
            assert reporting == 2

            # the unmerged per-rank view stays reachable
            status, body = _get(e.url("/metrics/local"))
            assert status == 200
            assert "rank=" not in body.decode()

            status, body = _get(e.url("/fleet/trace"))
            merged = json.loads(body)
            pids = {ev["pid"] for ev in merged["traceEvents"]}
            assert pids == {0, 1}
            assert any(ev.get("name") == "w1" and ev["pid"] == 1
                       for ev in merged["traceEvents"])

            status, body = _get(e.url("/fleet/ranks"))
            ranks = json.loads(body)["ranks"]
            assert ranks["1"]["published"] is True
            assert ranks["1"]["age_s"] is not None
    finally:
        pub.stop(final_publish=False)


def test_fleet_publisher_restart_and_runtime_trace_gate(clean_obs):
    from paddlepaddle_tpu.distributed.store import TCPStore

    store = TCPStore("127.0.0.1", 0, is_master=True)
    seen = []
    pub = aggregate.FleetPublisher(store, rank=5, interval_s=60,
                                   text_fn=lambda: seen.append(1) or _T0)
    pub.start()
    pub.stop(final_publish=False)
    n_after_stop = len(seen)
    # restartable: stop() must not leave the publisher thread stillborn
    pub.start()
    deadline = time.time() + 5
    while len(seen) <= n_after_stop and time.time() < deadline:
        time.sleep(0.02)
    assert len(seen) > n_after_stop, "restarted publisher never published"
    pub.stop(final_publish=False)

    # trace publication follows the RUNTIME tracing state (enable(trace=..)
    # without any PADDLE_OBS_TRACE env), not the env flag alone
    obs.enable(trace=True, metrics=False, watchdog_=False)
    with obs.RecordEvent("fleet_trace_probe"):
        pass
    aggregate.FleetPublisher(store, rank=6, interval_s=60,
                             text_fn=lambda: _T0).publish()
    assert store.check(aggregate.trace_key(6))
    doc = json.loads(store.get(aggregate.trace_key(6)))["trace"]
    assert any(ev["name"] == "fleet_trace_probe" for ev in doc["traceEvents"])
    obs.disable()
    aggregate.FleetPublisher(store, rank=7, interval_s=60,
                             text_fn=lambda: _T0).publish()
    assert not store.check(aggregate.trace_key(7))  # tracing off: no trace

    # an UNCHANGED ring is not re-serialized/re-shipped every interval
    # (each store request holds the client's wire mutex)
    obs.enable(trace=True, metrics=False, watchdog_=False)
    with obs.RecordEvent("dedup_probe"):
        pass
    set_keys = []
    orig_set = store.set
    store.set = lambda k, v: (set_keys.append(k), orig_set(k, v))[1]
    try:
        pub8 = aggregate.FleetPublisher(store, rank=8, interval_s=60,
                                        text_fn=lambda: _T0)
        tk = aggregate.trace_key(8)
        pub8.publish()
        pub8.publish()  # no new spans in between: trace skipped
        assert set_keys.count(tk) == 1
        with obs.RecordEvent("dedup_probe2"):
            pass
        pub8.publish()
        assert set_keys.count(tk) == 2  # ring changed: republished
    finally:
        store.set = orig_set


def test_two_engines_get_distinct_health_providers(clean_obs):
    """Two providers under one exporter must not clobber each other, and a
    guarded unregister only removes its own entry."""
    with exporter.TelemetryExporter(port=0) as e:
        fn_a = lambda: {"ok": True, "who": "a"}   # noqa: E731
        fn_b = lambda: {"ok": True, "who": "b"}   # noqa: E731
        name_a = e.register_health("serving", fn_a, unique=True)
        name_b = e.register_health("serving", fn_b, unique=True)
        assert name_a == "serving" and name_b == "serving-2"
        _, body = _get(e.url("/healthz"))
        providers = json.loads(body)["providers"]
        assert providers["serving"]["who"] == "a"
        assert providers["serving-2"]["who"] == "b"
        # stale guarded unregister (wrong fn) is a no-op
        e.unregister_health(name_b, fn=fn_a)
        _, body = _get(e.url("/healthz"))
        assert "serving-2" in json.loads(body)["providers"]
        e.unregister_health(name_b, fn=fn_b)
        _, body = _get(e.url("/healthz"))
        assert "serving-2" not in json.loads(body)["providers"]


# ---------------------------------------------------------------------------
# flight recorder (black box)
# ---------------------------------------------------------------------------

def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def test_flight_ring_is_bounded_and_dump_has_stacks(tmp_path, clean_obs):
    rec = flight.enable(str(tmp_path), capacity=16)
    for i in range(50):
        flight.record("probe", f"e{i}", i=i)
    assert len(rec.events()) == 16
    assert rec.events()[0]["name"] == "e34"  # oldest fell off
    path = flight.dump("unit_test")
    recs = _read_jsonl(path)
    head = recs[0]
    assert head["rec"] == "header" and head["reason"] == "unit_test"
    assert head["rank"] == 0 and head["world"] == 1
    events = [r for r in recs if r["rec"] == "event"]
    assert len(events) == 16
    assert events[-1]["name"] == "e49"
    (stacks,) = [r for r in recs if r["rec"] == "stacks"]
    mains = [t for t in stacks["threads"] if t["name"] == "MainThread"]
    assert mains and any("test_flight_ring" in fr
                         for fr in mains[0]["frames"])
    assert recs[-1]["rec"] == "end"


def test_flight_open_step_survives_ring_eviction(tmp_path, clean_obs):
    flight.enable(str(tmp_path), capacity=16)
    flight.record("step", "train_step", phase="begin", ordinal=7)
    for i in range(40):  # push the begin event out of the ring
        flight.record("noise", f"n{i}")
    recs = _read_jsonl(flight.dump("evicted"))
    (open_step,) = [r for r in recs if r["rec"] == "in_flight_step"]
    assert open_step["name"] == "train_step"
    assert open_step["data"]["ordinal"] == 7
    # a closed step is not in-flight
    flight.record("step", "train_step", phase="end", ordinal=7, ok=True)
    recs = _read_jsonl(flight.dump("closed"))
    assert not [r for r in recs if r["rec"] == "in_flight_step"]


def test_flight_excepthook_dumps_then_chains(tmp_path, clean_obs):
    prev_hook = sys.excepthook
    flight.enable(str(tmp_path), capacity=16)
    assert sys.excepthook is not prev_hook
    flight.record("step", "train_step", phase="begin", ordinal=1)
    chained = []
    saved = flight._prev_excepthook
    flight._prev_excepthook = lambda *a: chained.append(a)
    try:
        raise RuntimeError("boom for the black box")
    except RuntimeError:
        sys.excepthook(*sys.exc_info())
    finally:
        flight._prev_excepthook = saved
    assert chained, "the previous excepthook must still run"
    files = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    assert len(files) == 1 and "unhandled_exception" in files[0]
    recs = _read_jsonl(os.path.join(tmp_path, files[0]))
    (exc,) = [r for r in recs if r["rec"] == "exception"]
    assert exc["type"] == "RuntimeError"
    assert "boom for the black box" in exc["value"]
    assert any(r["rec"] == "in_flight_step" for r in recs)
    flight.disable()
    assert sys.excepthook is prev_hook  # hooks restored


def test_runtime_seams_feed_the_flight_recorder(tmp_path, clean_obs):
    """step boundaries, retries, chaos injections, collective launches —
    the seams the ISSUE names — all land in the ring."""
    from paddlepaddle_tpu.distributed.watchdog import Watchdog
    from paddlepaddle_tpu.resilience import chaos
    from paddlepaddle_tpu.resilience.retry import RetryPolicy, call_with_retry

    rec = flight.enable(str(tmp_path), capacity=128)
    wd = Watchdog(timeout=60, abort=False)
    with wd.step("train_step"):
        pass
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 2:
            raise OSError("transient")
        return "ok"

    assert call_with_retry(flaky, policy=RetryPolicy(max_attempts=3,
                                                     base_delay=0.0),
                           sleep=lambda s: None) == "ok"
    chaos.configure("probe.seam:exc:x1")
    with pytest.raises(chaos.ChaosError):
        chaos.chaos_point("probe.seam")
    chaos.disable()
    grad = paddle.to_tensor(np.ones((4,), np.float32))
    paddle.distributed.all_reduce(grad)

    kinds = {(e["kind"], e["name"]) for e in rec.events()}
    assert ("step", "train_step") in kinds
    assert ("retry", "flaky") in kinds
    assert ("chaos", "probe.seam") in kinds
    assert ("collective", "all_reduce") in kinds
    steps = [e for e in rec.events() if e["kind"] == "step"]
    assert [e["data"]["phase"] for e in steps] == ["begin", "end"]
    assert steps[1]["data"]["ok"] is True

    # an exc injection AT the step seam aborts __enter__ before __exit__
    # exists — the flight span must still close, or a later unrelated dump
    # reports a phantom in-flight step
    chaos.configure("step:exc:x1")
    with pytest.raises(chaos.ChaosError):
        with wd.step("doomed_step"):
            pytest.fail("step body must not run when the seam raises")
    chaos.disable()
    recs = _read_jsonl(flight.dump("after_step_exc"))
    assert not [r for r in recs if r["rec"] == "in_flight_step"]
    doomed = [e for e in rec.events() if e["kind"] == "step"
              and e["name"] == "doomed_step"]
    assert [e["data"]["phase"] for e in doomed] == ["begin", "end"]
    assert doomed[1]["data"]["ok"] is False


def test_watchdog_timeout_dump_survives_via_flight(tmp_path, clean_obs):
    """Satellite: the step-watchdog timeout report is persisted by the
    flight recorder (not only stderr) and carries all-thread stacks."""
    from paddlepaddle_tpu.distributed.watchdog import Watchdog

    flight.enable(str(tmp_path), capacity=64)
    fired = threading.Event()
    wd = Watchdog(timeout=0.05, poll_interval=0.01, abort=False,
                  on_timeout=lambda *a: fired.set()).start()
    try:
        with wd.step("stalling_step"):
            assert fired.wait(5), "watchdog did not fire"
            time.sleep(0.05)  # let _dump finish writing
    finally:
        wd.stop()
    files = [f for f in os.listdir(tmp_path) if "step_timeout" in f]
    assert files, "timeout must leave a black box"
    recs = _read_jsonl(os.path.join(tmp_path, files[0]))
    (ev,) = [r for r in recs if r["rec"] == "event"
             and r["kind"] == "watchdog_timeout"]
    assert ev["name"] == "stalling_step"
    assert ev["data"]["elapsed_s"] >= 0.05
    (open_step,) = [r for r in recs if r["rec"] == "in_flight_step"]
    assert open_step["name"] == "stalling_step"
    (stacks,) = [r for r in recs if r["rec"] == "stacks"]
    assert len(stacks["threads"]) >= 2  # main + watchdog monitor at least
    all_frames = "".join(fr for t in stacks["threads"]
                         for fr in t["frames"])
    assert "stalling_step" in all_frames or "wait" in all_frames


def test_breaker_open_flushes_black_box(tmp_path, clean_obs):
    serving = pytest.importorskip("paddlepaddle_tpu.inference.serving")

    class _Sick:
        def generate_cached(self, ids, max_new_tokens, **kw):
            raise RuntimeError("decode keeps failing")

    flight.enable(str(tmp_path), capacity=64)
    eng = serving.ServingEngine(_Sick(), mode="static", max_batch_size=1,
                                max_wait_ms=1.0, max_len=64,
                                breaker_threshold=2)
    try:
        for _ in range(2):
            with pytest.raises(RuntimeError):
                eng.submit(np.zeros((4,), np.int32),
                           max_new_tokens=4).result(30)
        deadline = time.time() + 5
        while time.time() < deadline and not any(
                "breaker_open" in f for f in os.listdir(tmp_path)):
            time.sleep(0.05)
    finally:
        eng.stop()
    files = [f for f in os.listdir(tmp_path) if "breaker_open" in f]
    assert files, "an opening breaker must flush the flight recorder"
    recs = _read_jsonl(os.path.join(tmp_path, files[0]))
    transitions = [r for r in recs if r["rec"] == "event"
                   and r["kind"] == "breaker"]
    assert any(t["data"]["to"] == "open" for t in transitions)


# ---------------------------------------------------------------------------
# obsctl
# ---------------------------------------------------------------------------

def _load_obsctl():
    import importlib.util

    spec = importlib.util.spec_from_file_location("obsctl", _OBSCTL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obsctl_scrape_and_aggregate_over_http(clean_obs, capsys):
    obsctl = _load_obsctl()
    obs.enable(trace=False, metrics=True, watchdog_=False)
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    _ = paddle.add(x, x)
    with exporter.TelemetryExporter(port=0) as e:
        assert obsctl.main(["scrape", f"127.0.0.1:{e.port}"]) == 0
        assert "paddle_op_calls_total" in capsys.readouterr().out
        assert obsctl.main(["aggregate", f"127.0.0.1:{e.port}",
                            e.url()]) == 0
        captured = capsys.readouterr()
        fams = parse_prometheus_text(captured.out)
        # both targets are the same rank-0 exporter: colliding self-reported
        # ranks fall back to list-position labels (with a warning) instead
        # of one target silently clobbering the other
        assert "labeling targets by list position" in captured.err
        assert {lab["rank"] for _, lab, _ in
                fams["paddle_op_calls_total"]["samples"]} == {"0", "1"}
        # a dead target is skipped, not fatal to the merge
        assert obsctl.main(["aggregate", "127.0.0.1:9",
                            f"127.0.0.1:{e.port}", "--timeout", "2"]) == 0
        captured = capsys.readouterr()
        assert "skipping" in captured.err
        assert "paddle_op_calls_total" in captured.out


def test_obsctl_merge_trace_writes_perfetto_file(tmp_path, capsys):
    obsctl = _load_obsctl()
    for r in (0, 1):
        with open(tmp_path / f"trace{r}.json", "w") as f:
            json.dump({"traceEvents": [
                {"name": f"op{r}", "ph": "X", "ts": 10, "dur": 2,
                 "pid": 0, "tid": 1}], "displayTimeUnit": "ms"}, f)
    out = str(tmp_path / "merged.json")
    assert obsctl.main(["merge-trace", "-o", out,
                        str(tmp_path / "trace0.json"),
                        str(tmp_path / "trace1.json")]) == 0
    with open(out) as f:
        merged = json.load(f)
    assert {e["pid"] for e in merged["traceEvents"]
            if e.get("ph") == "X"} == {0, 1}


def test_obsctl_blackbox_tail_renders_newest_dump(tmp_path, clean_obs):
    flight.enable(str(tmp_path), capacity=32)
    flight.record("step", "train_step", phase="begin", ordinal=3)
    flight.record("retry", "store.get", attempt=1)
    flight.dump("drill")
    # obsctl blackbox tail is stdlib-only: run it as a real subprocess
    out = subprocess.run(
        [sys.executable, _OBSCTL, "blackbox", "tail", "--dir",
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "reason=drill" in out.stdout
    assert "step" in out.stdout and "train_step" in out.stdout
    assert "retry" in out.stdout
    assert "IN-FLIGHT STEP" in out.stdout
    assert "stacks:" in out.stdout


def test_obsctl_scrape_dead_target_is_one_line_error(tmp_path):
    out = subprocess.run(
        [sys.executable, _OBSCTL, "scrape", "127.0.0.1:9", "--timeout", "2"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 1
    assert "Traceback" not in out.stderr
    assert "127.0.0.1:9" in out.stderr


def test_obsctl_blackbox_tail_empty_dir(tmp_path):
    out = subprocess.run(
        [sys.executable, _OBSCTL, "blackbox", "tail", "--dir",
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 1
    assert "no black-box dumps" in out.stderr


# ---------------------------------------------------------------------------
# end-to-end drills (slow: real distributed.launch subprocesses)
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_FLEET_WORKER = r"""
import os, sys, time
sys.path.insert(0, os.environ["REPO_DIR"])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_OBS_TRACE", "1")   # publish traces too
import numpy as np
import paddlepaddle_tpu as paddle
import paddlepaddle_tpu.observability as obs

rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
stop_file = os.environ["DRILL_STOP"]
x = paddle.to_tensor(np.ones((2, 2), np.float32))
deadline = time.time() + 120
while not os.path.exists(stop_file) and time.time() < deadline:
    _ = paddle.add(x, x)      # keeps per-rank op counters moving
    time.sleep(0.05)
print(f"FLEET_RANK{rank}_DONE", flush=True)
"""


@pytest.mark.slow
def test_launch_two_workers_rank0_serves_fleet_metrics_and_trace(tmp_path):
    """Acceptance: distributed.launch with 2 workers -> rank 0's merged
    /metrics has per-rank-labeled series from BOTH workers; the merged
    trace is Perfetto-valid JSON with one pid per rank."""
    script = tmp_path / "worker.py"
    script.write_text(_FLEET_WORKER)
    stop_file = str(tmp_path / "stop")
    base_port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPO_DIR=_REPO, DRILL_STOP=stop_file,
               PADDLE_OBS_PUBLISH_INTERVAL_S="0.3",
               # env-based enablement on the LAUNCHER too: its own
               # import-time exporter binds base_port first, and launch()
               # must release it for the real rank 0 (regression: launcher
               # squatting the deterministic port)
               PADDLE_OBS_EXPORT="1", PADDLE_OBS_PORT=str(base_port))
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddlepaddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--obs_export",
         "--obs_port", str(base_port), str(script)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=_REPO)
    try:
        fams = {}
        deadline = time.time() + 120
        while time.time() < deadline:
            assert proc.poll() is None, proc.stdout.read()[-3000:]
            try:
                status, body = _get(
                    f"http://127.0.0.1:{base_port}/metrics", timeout=5)
            except (OSError, urllib.error.URLError):
                time.sleep(0.3)
                continue
            if status != 200:
                time.sleep(0.3)
                continue
            fams = parse_prometheus_text(body.decode())
            samples = fams.get("paddle_op_calls_total", {}).get("samples", [])
            if {lab.get("rank") for _, lab, _ in samples} >= {"0", "1"}:
                break
            time.sleep(0.3)
        samples = fams.get("paddle_op_calls_total", {}).get("samples", [])
        ranks = {lab.get("rank") for _, lab, _ in samples}
        assert ranks >= {"0", "1"}, f"merged series from {ranks}, want both"
        (reporting,) = [v for _, _, v in
                        fams["paddle_fleet_ranks_reporting"]["samples"]]
        assert reporting == 2

        # per-rank exporters answer on base+rank too
        status, body = _get(f"http://127.0.0.1:{base_port + 1}/healthz")
        assert status == 200 and json.loads(body)["rank"] == 1

        status, body = _get(f"http://127.0.0.1:{base_port}/fleet/trace",
                            timeout=30)
        assert status == 200
        merged = json.loads(body)  # Perfetto-valid strict JSON
        assert merged["displayTimeUnit"] == "ms"
        span_pids = {ev["pid"] for ev in merged["traceEvents"]
                     if ev.get("ph") == "X"}
        assert span_pids == {0, 1}, f"one pid per rank, got {span_pids}"
    finally:
        with open(stop_file, "w") as f:
            f.write("stop")
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
    assert rc == 0, proc.stdout.read()[-3000:]


_KILL_WORKER = r"""
import os, sys, time
sys.path.insert(0, os.environ["REPO_DIR"])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.distributed.watchdog import Watchdog

wd = Watchdog(timeout=300, abort=False)
x = paddle.to_tensor(np.ones((2, 2), np.float32))
for step in range(10):
    with wd.step("train_step"):   # chaos seam "step" + flight step events
        _ = paddle.add(x, x)
print("KILL_WORKER_SURVIVED", flush=True)
"""


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_kill_leaves_blackbox_and_obsctl_renders_it(tmp_path):
    """Acceptance: PADDLE_CHAOS_POINTS=step:kill:@N leaves a black-box
    JSONL whose final records include the in-flight step event and thread
    stacks; `obsctl blackbox tail` renders it."""
    script = tmp_path / "worker.py"
    script.write_text(_KILL_WORKER)
    bb_dir = str(tmp_path / "blackbox")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPO_DIR=_REPO,
               PADDLE_OBS_BLACKBOX="1",
               PADDLE_OBS_BLACKBOX_DIR=bb_dir,
               PADDLE_CHAOS_POINTS="step:kill:@4:77",
               PADDLE_CHAOS_SEED="1234")
    out = subprocess.run(
        [sys.executable, "-m", "paddlepaddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restarts", "0", str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    assert out.returncode == 77, (out.returncode, out.stderr[-2000:])
    assert "KILL_WORKER_SURVIVED" not in out.stdout

    files = [f for f in os.listdir(bb_dir) if f.endswith(".jsonl")]
    assert len(files) == 1, files
    assert "chaos_kill" in files[0]
    recs = _read_jsonl(os.path.join(bb_dir, files[0]))
    assert recs[0]["reason"].startswith("chaos_kill")
    # the in-flight step: step 4 began (flight event) but never ended —
    # surfaced both as the last step event and as an in_flight_step record
    step_events = [r for r in recs if r["rec"] == "event"
                   and r["kind"] == "step"]
    assert step_events[-1]["data"] == {"phase": "begin", "ordinal": 4}
    (open_step,) = [r for r in recs if r["rec"] == "in_flight_step"]
    assert open_step["data"]["ordinal"] == 4
    (chaos_ev,) = [r for r in recs if r["rec"] == "event"
                   and r["kind"] == "chaos"]
    assert chaos_ev["name"] == "step" and chaos_ev["data"]["mode"] == "kill"
    (stacks,) = [r for r in recs if r["rec"] == "stacks"]
    assert any(t["name"] == "MainThread" for t in stacks["threads"])

    tail = subprocess.run(
        [sys.executable, _OBSCTL, "blackbox", "tail", "--dir", bb_dir],
        capture_output=True, text=True, timeout=60)
    assert tail.returncode == 0, tail.stderr
    assert "reason=chaos_kill" in tail.stdout
    assert "IN-FLIGHT STEP" in tail.stdout
    assert "stacks:" in tail.stdout
