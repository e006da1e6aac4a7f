"""Work counts from shapes against hand-worked values for one Mistral-7B layer,
and the trace reducer on a small trace recorded on the TPU v5e."""

import os
from types import SimpleNamespace

import pytest

from benchmark import trace, work

MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768, "num_hidden_layers": 2}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_one_layer_by_hand():
    # q and o: 4096x4096 each; k and v: 4096x1024 each; gate, up, down: 4096x14336 each
    assert work.layer_matmul_params(MISTRAL) == 2 * 16777216 + 2 * 4194304 + 3 * 58720256 == 218103808
    assert work.head_params(MISTRAL) == 134217728
    # causal attention over 4,096 positions: sum of (t+1) = 4096*4097/2 keys, QK^T and PV, 32 heads of 128
    assert work.attention_ops(MISTRAL, 0, 4096) == 4 * 32 * 128 * (4096 * 4097 // 2) == 137472507904
    # the tail of a cached prefix: positions 3072..3199 attend to everything before them
    assert work.attention_ops(MISTRAL, 3072, 3200) == 4 * 32 * 128 * sum(t + 1 for t in range(3072, 3200))
    fwd = 2 * (2 * 218103808 * 4096 + 137472507904) + 2 * 134217728 * 4096
    assert work.forward_ops(MISTRAL, 0, 4096, 4096) == fwd
    assert work.train_ops_per_step(MISTRAL, 2, 4096) == 3 * 2 * fwd
    assert work.flash_backward_ops(MISTRAL, 2, 4096) == 2.5 * 2 * 137472507904
    assert work.kv_bytes_per_token(dict(MISTRAL, num_hidden_layers=16)) == 65536


def test_decode_floor_and_peaks():
    pk = work.peaks("TPU v5 lite")
    assert (pk["flops_per_s"], pk["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9")
    cfg = dict(MISTRAL, num_hidden_layers=16)
    weights = 16 * 218103808 + 134217728
    least = work.decode_step_least_s(cfg, live_tokens=20000, active_slots=32, pk=pk)
    assert least == pytest.approx((2 * weights + 20000 * 65536) / 819e9)    # bandwidth-bound
    assert work.roofline_least_s(197e12, 1.0, pk) == pytest.approx(1.0)


def test_self_times_and_gaps_on_made_up_planes():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(123)", 0, 1000), ("jit_step(123)", 2000, 3000)],
            "XLA Ops": [("%while.1 = () while()", 0, 1000), ("%fusion.2 = bf16[8,128]{1,0} fusion()", 100, 400),
                        ('%custom-call.3 = bf16[2,4096]{1,0} custom-call(), custom_call_target="tpu_custom_call", '
                         'metadata={op_name="jit(step)/pallas_call[name=_fwd_kernel]"}', 400, 900),
                        ("%fusion.2 = bf16[8,128]{1,0} fusion()", 2000, 3000)]},
        "/host:CPU": {"python": [("bench.window", 0, 4000), ("train.loss_fetch", 950, 2050),
                                 ("train.step_call", 3000, 3100)]},
    }
    r = trace.reduce(planes, host_spans=("train.loss_fetch", "train.step_call"))
    assert r["window_s"] == pytest.approx(4e-6) and r["busy_s"] == pytest.approx(2e-6)
    assert r["module_s"] == {"jit_step": pytest.approx(2e-6)} and r["module_calls"] == {"jit_step": 2}
    assert r["op_s"]["fusion.2 bf16[8,128]"] == pytest.approx(1.3e-6)
    assert "while.1" not in " ".join(r["op_s"])            # a wrapper is not counted over its body
    assert trace.matching(r, r"custom-call.*_fwd_kernel") == (pytest.approx(5e-7), 1)
    assert trace.matching(r, r"_dq_kernel") is None
    assert r["gap_s"] == {"train.loss_fetch": pytest.approx(1e-6), "unattributed": pytest.approx(1e-6)}
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] == "fusion.2 bf16[8,128]" and len(b["idle_gaps"]) == 2


def test_recorded_trace_from_the_chip():
    planes = trace.read_planes(os.path.join(DATA, "small.xplane.pb"))
    assert "bench.window" not in {n for n, _, _ in planes["/host:CPU"]["python"]}     # recorded before the span existed
    r = trace.reduce(planes, window_span=None, host_spans=("train.step_call", "train.loss_fetch"))
    assert r["devices"] == 1 and 0 < r["busy_s"] < r["window_s"]
    assert sum(r["module_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert sum(r["module_calls"].values()) >= 1
    assert sum(r["gap_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert set(r["gap_s"]) <= {"train.step_call", "train.loss_fetch", "unattributed"}
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce(planes)                       # the window's span was asked for and is not there: no silent fall-back


class _Line:
    def __init__(self, name, events):
        self.name = name
        self.events = [SimpleNamespace(name=n, start_ns=a, duration_ns=b - a) for n, a, b in events]


def _fake_profile(monkeypatch, host_lines):
    import jax.profiler

    planes = [SimpleNamespace(name="/device:TPU:0", lines=[
                  _Line("XLA Modules", [("jit_run(7)", 0, 1000)]),
                  _Line("XLA Ops", [("%fusion.1 = bf16[32,128]{1,0} fusion()", 0, 1000)])]),
              SimpleNamespace(name="/host:CPU", lines=[_Line(n, ev) for n, ev in host_lines]),
              SimpleNamespace(name="/host:metadata", lines=[])]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", staticmethod(lambda path: SimpleNamespace(planes=planes)))


@pytest.mark.parametrize("interpreter", ["python", "python3", "python3.12"])
def test_a_python_threads_line_is_read_under_the_name_the_client_gives_it(monkeypatch, interpreter):
    _fake_profile(monkeypatch, [(interpreter, [("bench.window", 0, 2000)]),          # the main thread
                                (interpreter, [("serve.deliver", 1000, 1600)]),       # the engine thread: same name
                                ("pjrt-tpu-tasks/332", [("D2H Dispatch", 0, 100)]),
                                ("pythonic-helper/9", [("bench.window", 0, 9000)])])
    planes = trace.read_planes("made-up.xplane.pb")
    assert planes["/host:CPU"] == {"python": [("bench.window", 0.0, 2000.0), ("serve.deliver", 1000.0, 1600.0)]}
    r = trace.reduce(planes, host_spans=("serve.deliver",), unattributed="engine_thread")
    assert r["window_s"] == pytest.approx(2e-6) and r["busy_s"] == pytest.approx(1e-6)
    assert r["gap_s"] == {"serve.deliver": pytest.approx(1e-6)}


def test_a_missing_window_raises_and_none_asks_for_the_fall_back():
    planes = {"/device:TPU:0": {"XLA Modules": [("jit_run(7)", 500, 1500)],
                                "XLA Ops": [("%fusion.1 = bf16[32,128]{1,0} fusion()", 500, 1500)]},
              "/host:CPU": {"python": [("serve.admit", 0, 400)]}}
    with pytest.raises(RuntimeError, match="no host span 'bench.window'"):
        trace.reduce(planes, host_spans=("serve.admit",))
    with pytest.raises(RuntimeError, match="no host span 'bench.window'"):
        trace.reduce(dict(planes, **{"/host:CPU": {}}))               # no Python line at all, as on the chip until PR 31
    r = trace.reduce(planes, window_span=None)
    assert r["window_s"] == pytest.approx(1e-6) and r["busy_s"] == pytest.approx(1e-6) and r["gap_s"] == {}


def test_a_call_cut_by_the_windows_edge_counts_by_its_part_inside():
    """Three decode calls of 1,000 ns; the window opens 750 ns into the first and closes 500 ns into the third."""
    calls = [(0, 1000), (1000, 2000), (2000, 3000)]
    planes = {"/device:TPU:0": {"XLA Modules": [("jit_run(7)", a, b) for a, b in calls],
                                "XLA Ops": [("%fusion.1 = bf16[32,128]{1,0} fusion()", a + 250 * i, a + 250 * (i + 1))
                                            for a, _ in calls for i in range(4)]},
              "/host:CPU": {"python": [("bench.window", 750, 2500)]}}
    r = trace.reduce(planes)
    assert r["module_calls"] == {"jit_run": pytest.approx(0.25 + 1 + 0.5)}
    assert r["module_s"]["jit_run"] / r["module_calls"]["jit_run"] == pytest.approx(1e-6)     # the time of a whole call
    whole = trace.reduce(planes, window_span=None)
    assert whole["module_calls"] == {"jit_run": 3} and whole["module_s"]["jit_run"] == pytest.approx(3e-6)


def test_idle_gaps_are_named_by_the_serving_loops_phases():
    busy = [(0, 1000), (2000, 3000), (4000, 5000), (6000, 7000), (8000, 9000)]
    planes = {
        "/device:TPU:0": {"XLA Modules": [("jit_run(7)", a, b) for a, b in busy],
                          "XLA Ops": [("%fusion.1 = bf16[32,128]{1,0} fusion()", a, b) for a, b in busy]},
        "/host:CPU": {"python": [
            ("bench.window", 0, 10000),
            ("serve.chunk_sync", 200, 1100), ("serve.deliver", 1100, 1600),     # half of the gap 1000-2000: named so
            ("serve.admit", 1650, 2050),                                         # 350 of the same gap: under half
            ("serve.deliver", 3100, 3400),                                       # 300 of the gap 3000-4000: nobody's
            ("serve.first_sync", 4900, 5700), ("loadgen.submit", 5000, 5900),    # 700 and 900 of 5000-6000: the larger
            ("serve.wait_request", 6500, 9990)]}}                                # all of 7000-8000 and of 9000-10000
    spans = ("loadgen.submit", "serve.sweep", "serve.wait_request", "serve.admit", "serve.decode_dispatch",
             "serve.first_sync", "serve.chunk_sync", "serve.deliver")
    r = trace.reduce(planes, host_spans=spans, unattributed="engine_thread")
    assert r["window_s"] == pytest.approx(1e-5) and r["busy_s"] == pytest.approx(5e-6)
    assert r["gap_s"] == {"serve.deliver": pytest.approx(1e-6), "engine_thread": pytest.approx(1e-6),
                          "loadgen.submit": pytest.approx(1e-6), "serve.wait_request": pytest.approx(2e-6)}
    assert [n for n, _ in trace.breakdown(r)["idle_gaps"]][0] == "serve.wait_request"
