"""``decode.attention_ms_per_step.steady`` on the CPU (nothing here is a measurement): its reader on a hand-made
reduced trace, ``None`` where no call carries the kernel's name (the programs before the kernels named their calls),
and its entry in the manifest."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "decode.attention_ms_per_step.steady"


def reader():
    return run.load_reader(os.path.join(ROOT, "benchmark"), NAME)


def call(name):
    return (f'%{name} = bf16[32,32,128]{{2,1,0}} custom-call(bf16[32,32,128]{{2,1,0}} %p.1), '
            'custom_call_target="tpu_custom_call"')


def obs(ops, calls=36.5):
    return {"decode_chunk": 16, "trace": {
        "module_s": {"jit_run": 6.7, "jit__admit_paged_impl": 2.5},
        "module_calls": {"jit_run": calls, "jit__admit_paged_impl": 11.0},
        "op_text_s": dict(ops), "op_text_n": {k: 16 for k in ops}}}


def test_the_reader_is_the_gqa_kernels_seconds_over_the_decode_programs_steps():
    ops = {call("paged_gqa_attention_decode.5"): 0.82, call("paged_gqa_attention_decode.6"): 0.01}
    assert reader()(obs(ops)) == pytest.approx(1e3 * 0.83 / (36.5 * 16))
    # the steady and the saturated reader read the same calls the same way
    saturated = run.load_reader(os.path.join(ROOT, "benchmark"), "decode.attention_ms_per_step")
    assert saturated(obs(ops)) == reader()(obs(ops))


@pytest.mark.parametrize("ops, calls", [
    ({call("_unknown_.31"): 0.82}, 36.5),                          # the parent's calls
    ({call("paged_gqa_attention.4"): 0.82}, 36.5),                 # traced outside any engine program
    ({call("paged_gqa_attention_decode.5"): 0.82}, 0.0),           # no decode run in the window
])
def test_the_reader_finds_nothing_where_there_is_nothing_to_read(ops, calls):
    assert reader()(obs(ops, calls)) is None


def test_the_manifest_reads_it_in_the_steady_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(p for p in m["per_layer"] if p["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "device_trace", "layer": "decode step",
                     "moves": "ttft_mean_ms", "workloads": ["serve-docqa-steady"]}
    ttft = next(e for e in m["end_to_end"] if e["name"] == "ttft_mean_ms")
    assert set(entry["workloads"]) <= set(ttft["workloads"])
