"""Work counts from shapes against hand-worked values for one Mistral-7B layer,
and the trace reducer on a small trace recorded on the TPU v5e."""

import os

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
    r = trace.reduce(planes, host_spans=("train.step_call", "train.loss_fetch"))
    assert r["devices"] == 1 and 0 < r["busy_s"] < r["window_s"]
    assert sum(r["module_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert sum(r["module_calls"].values()) >= 1
    assert sum(r["gap_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert set(r["gap_s"]) <= {"train.step_call", "train.loss_fetch", "unattributed"}
