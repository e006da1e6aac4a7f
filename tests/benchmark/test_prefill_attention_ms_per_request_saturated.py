"""``prefill.attention_ms_per_request.saturated`` on the CPU (nothing here is a measurement): its reader on a hand-made
reduced trace, over the same admission runs as ``prefill.device_ms_per_request.saturated``, ``None`` where no call
carries the kernel's name (the programs before the kernels named their calls), and its entry in the manifest."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASE = os.path.join(ROOT, "benchmark")
NAME = "prefill.attention_ms_per_request.saturated"


def call(name, rows=16896):
    return (f'%{name} = bf16[64,{rows},128]{{2,1,0}} custom-call(s32[1]{{0}} %p.1, bf16[64,{rows},128]{{2,1,0}} '
            '%p.2), custom_call_target="tpu_custom_call"')


def obs(ops, whole=9.0, hits=28.0):
    return {"decode_chunk": 16, "trace": {
        "module_s": {"jit__admit_paged_impl": 4.4, "jit_impl": 1.5, "jit_run": 4.2},
        "module_calls": {"jit__admit_paged_impl": whole, "jit_impl": hits, "jit_run": 15.0},
        "op_text_s": dict(ops), "op_text_n": {k: 6 for k in ops}}}


ADMIT = {call("latent_prefill_attention_admit.2"): 1.0, call("latent_prefill_attention_admit.5", 8448): 0.64}


def test_the_reader_is_the_kernels_seconds_over_the_admissions_runs():
    assert run.load_reader(BASE, NAME)(obs(ADMIT)) == pytest.approx(1e3 * 1.64 / 37)
    # a part of the admissions' device time over the same runs: never more than the whole
    whole = run.load_reader(BASE, "prefill.device_ms_per_request.saturated")(obs(ADMIT))
    assert run.load_reader(BASE, NAME)(obs(ADMIT)) < whole


@pytest.mark.parametrize("ops, whole, hits", [
    ({call("_unknown_.4"): 1.0}, 9.0, 28.0),                        # the parent's calls
    ({call("latent_prefill_attention.4"): 1.0}, 9.0, 28.0),         # traced outside any engine program
    ({call("latent_prefill_attention_decode.4"): 1.0}, 9.0, 28.0),  # another program's calls
    (ADMIT, 0.0, 0.0),                                              # no admission in the window
])
def test_the_reader_finds_nothing_where_there_is_nothing_to_read(ops, whole, hits):
    assert run.load_reader(BASE, NAME)(obs(ops, whole, hits)) is None


def test_the_manifest_reads_it_in_the_longdoc_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(p for p in m["per_layer"] if p["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "device_trace", "layer": "prefill",
                     "moves": "out_tokens_per_s", "workloads": ["serve-longdoc-saturated"]}
    whole = next(p for p in m["per_layer"] if p["name"] == "prefill.device_ms_per_request.saturated")
    assert entry["workloads"] == whole["workloads"] and entry["moves"] == whole["moves"]
