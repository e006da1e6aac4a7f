"""``decode.experts_ms_per_step`` on the CPU (nothing here is a measurement): its reader on a hand-made reduced trace,
the same kernel's calls in an admission left out by their name, ``None`` where no call carries the name (the programs
before the kernels named their calls), and its entry in the manifest."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "decode.experts_ms_per_step"


def reader():
    return run.load_reader(os.path.join(ROOT, "benchmark"), NAME)


def call(name):
    # a 128-slot decode step's call and a prefix hit's padded 128-row tail give the same result: only the name differs
    return (f'%{name} = f32[128,6144]{{1,0}} custom-call(s32[16]{{0}} %p.1, bf16[128,6144]{{1,0}} %p.2), '
            'custom_call_target="tpu_custom_call"')


def obs(ops, calls=25.4):
    return {"decode_chunk": 16, "trace": {
        "module_s": {"jit_run": 7.0, "jit_impl": 2.6}, "module_calls": {"jit_run": calls, "jit_impl": 85.0},
        "op_text_s": dict(ops), "op_text_n": {k: 64 for k in ops}}}


DECODE = {call("held_experts_decode.97"): 0.58, call("held_experts_decode.100"): 0.58}


def test_the_reader_is_the_kernels_decode_calls_over_the_decode_programs_steps():
    assert reader()(obs(DECODE)) == pytest.approx(1e3 * 1.16 / (25.4 * 16))


def test_the_reader_leaves_the_admissions_calls_out():
    admit = {call("held_experts_admit.12"): 0.9, call("held_experts_admit"): 0.1}
    assert reader()(obs(dict(DECODE, **admit))) == pytest.approx(reader()(obs(DECODE)))
    assert reader()(obs(admit)) is None


@pytest.mark.parametrize("ops, calls", [
    ({call("_unknown_.97"): 0.58, call("_unknown_.100"): 0.58}, 25.4),    # the parent's calls
    ({call("held_experts.3"): 0.58}, 25.4),                              # traced outside any engine program
    ({}, 25.4),                                                           # a dense form: no kernel call
    (DECODE, 0.0),                                                        # no decode run in the window
])
def test_the_reader_finds_nothing_where_there_is_nothing_to_read(ops, calls):
    assert reader()(obs(ops, calls)) is None


def test_the_manifest_reads_it_in_both_expert_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(p for p in m["per_layer"] if p["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "device_trace", "layer": "decode step",
                     "moves": "tpot_p50_ms", "workloads": ["serve-agent-saturated", "serve-longdoc-saturated"]}
    tpot = next(e for e in m["end_to_end"] if e["name"] == "tpot_p50_ms")
    assert set(entry["workloads"]) <= set(tpot["workloads"])
