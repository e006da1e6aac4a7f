"""``decode.attention_ms_per_step`` on the CPU (nothing here is a measurement): its reader on a hand-made reduced trace,
``None`` where no call carries the kernel's name (the programs before the kernels named their calls: ``%_unknown_.N``),
and its entry in the manifest."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "decode.attention_ms_per_step"


def reader():
    return run.load_reader(os.path.join(ROOT, "benchmark"), NAME)


def call(name, shape="bf16[128,64,512]"):
    return f'%{name} = {shape}{{2,1,0}} custom-call(bf16[128,64,512]{{2,1,0}} %p.1), custom_call_target="tpu_custom_call"'


def obs(ops, calls=12.5, chunk=16):
    """A reduced trace: ``ops`` {HLO text: seconds}, one event each; ``calls`` runs of the decode program."""
    return {"decode_chunk": chunk, "trace": {
        "module_s": {"jit_run": 4.0, "jit_impl": 1.0}, "module_calls": {"jit_run": calls, "jit_impl": 6.0},
        "op_text_s": dict(ops, **{"%fusion.7 = bf16[128,6144]{1,0} fusion(%p.2), kind=kLoop": 2.5}),
        "op_text_n": {k: 1 for k in [*ops, "%fusion.7 = bf16[128,6144]{1,0} fusion(%p.2), kind=kLoop"]}}}


NAMED = {call("paged_latent_attention_decode.12"): 0.6, call("paged_latent_attention_decode"): 0.2,
         call("paged_gqa_attention_decode.3", "bf16[32,32,128]"): 0.4}


def test_the_reader_is_the_kernels_seconds_over_the_decode_programs_steps():
    assert reader()(obs(NAMED)) == pytest.approx(1e3 * 1.2 / (12.5 * 16))
    assert reader()(obs(NAMED, calls=3.0, chunk=8)) == pytest.approx(1e3 * 1.2 / 24)


def test_the_reader_counts_neither_another_kernel_nor_another_programs_calls():
    others = {call("held_experts_decode.4", "f32[128,6144]"): 5.0, call("paged_latent_attention_admit.2"): 7.0,
              call("paged_latent_attention.9"): 9.0, call("paged_latent_attention_decode_x.1"): 11.0}
    assert reader()(obs(dict(NAMED, **others))) == pytest.approx(1e3 * 1.2 / (12.5 * 16))


@pytest.mark.parametrize("ops, calls", [
    ({call("_unknown_.97"): 0.6, call("_unknown_.100", "bf16[32,32,128]"): 0.4}, 12.5),    # the parent's calls
    ({}, 12.5),                                                                             # no kernel ran
    (NAMED, 0.0),                                                                           # no decode run in the window
])
def test_the_reader_finds_nothing_where_there_is_nothing_to_read(ops, calls):
    assert reader()(obs(ops, calls)) is None


def test_the_manifest_reads_it_in_the_saturated_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(p for p in m["per_layer"] if p["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "device_trace", "layer": "decode step",
                     "moves": "tpot_p50_ms",
                     "workloads": ["serve-chat-saturated", "serve-agent-saturated", "serve-longdoc-saturated"]}
    whole = next(p for p in m["per_layer"] if p["name"] == "decode.device_ms_per_step")
    assert entry["workloads"] == whole["workloads"]
