"""The ``mimo_v2`` family's benchmark files on the CPU at a tiny width (nothing here is a
measurement): a tiny MiMo-V2 configuration and a reasoning traffic mix written as files over
``tiny.write(root)``; ``correct`` is true for the program as it is and false for the float8
control and for each mechanism left out; the configuration's cut, deployment and weight
arithmetic at the published widths; the traffic inside ``max_len``; the family's counts of
operations and bytes by hand; the four new readers on hand-made reduced traces and ``None``
where the program has nothing for them; their entries in the manifest."""

import io
import json
import os

import numpy as np
import pytest

from benchmark import loadgen, run, tiny
from benchmark.families import mimo_v2 as family
from benchmark.families import mimo_v2_reference as reference
from benchmark.families import mimo_v2_work as mwork

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2 ** 31 + 23
CELL = "serve-reasoning-saturated"

CONFIG = {
    "family": "mimo_v2", "source": "tiny width for CPU tests", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 4, "num_attention_heads": 16,
    "num_key_value_heads": 4, "head_dim": 64, "v_head_dim": 32, "swa_num_attention_heads": 16,
    "swa_num_key_value_heads": 8, "swa_head_dim": 64, "swa_v_head_dim": 32, "sliding_window": 8, "rope_theta": 50000,
    "swa_rope_theta": 100, "partial_rotary_factor": 0.25, "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False, "hybrid_layer_pattern": [0, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1], "n_routed_experts": 4, "experts_first": 4, "num_experts_per_tok": 2,
    "routed_scaling_factor": None, "norm_topk_prob": True, "scoring_func": "sigmoid", "n_shared_experts": None,
    "max_position_embeddings": 512, "layernorm_epsilon": 1e-5, "published": {"n_routed_experts": 8},
    "sink_logit_init": {"mean": 1.5, "std": 1.0}, "torch_dtype": "float32",
    "engine": {"max_batch_size": 4, "max_len": 256, "decode_chunk": 2, "kv_page_size": 4, "prefix_cache": False},
    "engine_facts": {"prompt_bucket": 128, "page_tokens": 4},
}
TRAFFIC = dict(tiny.TRAFFIC["tiny-sessions"], document_tokens=None, declare_prefix=False,
               session={"turns": 1, "gap_s": [0, 0]}, prompt_tokens={"dist": "uniform", "min": 4, "max": 24},
               output_tokens={"dist": "uniform", "min": 20, "max": 40}, check_requests=3, ramp_s=0.3, tail_s=0.2)
# At hidden 64 a std of 0.1 gives a normalised state's projections a spread near the published widths' (0.02 x
# sqrt(4096) = 1.28 against 0.8), so that attention is no flat average and each mechanism moves the logits. In float32
# the program reads 0.0; the float8 control (0.070 on seed 11) and every fault read over three times the limit.
INIT_STD = 0.1
LIMITS = {"token_gap": 0.015, "broken_outputs": 0}


def write(root):
    """``tiny.write(root)`` plus the tiny MiMo configuration, its traffic, limits and cell, as files."""
    root = tiny.write(root)
    base = os.path.join(root, "benchmark")

    def dump(obj, *parts):
        with open(os.path.join(*parts), "w") as f:
            json.dump(obj, f)

    dump(CONFIG, base, "configs", "tiny-mimo.json")
    dump(TRAFFIC, base, "traffic", "tiny-reasoning.json")
    dump(LIMITS, base, "limits", "tiny-reasoning-serve.json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-mimo", "source": "none", "file": "benchmark/configs/tiny-mimo.json",
                         "reduced": [], "why": "CPU test"})
    m["workloads"].append({"name": "tiny-reasoning-serve", "config": "tiny-mimo", "traffic": "tiny-reasoning",
                           "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "out_tokens_per_s":
            e["workloads"].append("tiny-reasoning-serve")
    dump(m, root, "BENCHMARK.json")
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    std, reference.INIT_STD = reference.INIT_STD, INIT_STD
    yield write(str(tmp_path_factory.mktemp("bench-mimo")))
    reference.INIT_STD = std


def test_the_cell_runs_from_files_alone_and_is_correct(root):
    out = io.StringIO()
    res = run.run_cell("tiny-reasoning-serve", SEED, 0.4, 0, data_root=root, check_chip=False, out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 4
    assert res["checks"]["token_gap"]["value"] <= LIMITS["token_gap"] and res["checks"]["broken_outputs"]["value"] == 0


@pytest.mark.parametrize("control", ["fp8", *reference.FAULTS])
def test_the_float8_control_and_each_fault_are_not_correct(root, control):
    data = run.load_cell(root, "tiny-reasoning-serve")
    rng = np.random.default_rng(11)
    seqs = [rng.integers(0, 128, (64,)).astype(np.int32) for _ in range(3)]
    res = family.serve_reference(data.config, 11, seqs, [16] * 3, control=control)
    assert max(float(g.max()) for g in res["control_gap"]) > 3 * data.limits["token_gap"]
    assert sum(len(g) for g in res["gap"]) == 3 * 48 and res["controls"].keys() == {control}


def test_the_reference_shares_nothing_with_the_program():
    src = open(os.path.join(ROOT, "benchmark", "families", "mimo_v2_reference.py")).read()
    assert "paddlepaddle_tpu" not in src.split('"""', 2)[2]


def test_a_train_kind_on_this_family_raises_at_once_and_another_routing_is_refused():
    with pytest.raises(AttributeError, match="served, not trained"):
        family.build_train
    assert family.work is mwork and family.reference is reference
    with pytest.raises(ValueError, match="n_shared_experts"):
        family._model(dict(CONFIG, n_shared_experts=1), 1)


# -- the configuration: its cut, its deployment, its weights and its pages ----------

def published():
    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2-flash-serve-d7-ep32.json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cut_keeps_every_width_and_names_what_it_reduced():
    cfg = published()
    entry = next(c for c in manifest()["configs"] if c["name"] == "mimo-v2-flash-serve-d7-ep32")
    assert set(entry["reduced"]) == set(cfg["published"])
    assert cfg["published"] == {"num_hidden_layers": 48, "n_routed_experts": 256, "vocab_size": 152576}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (7, 8, 152576 // 8)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"], cfg["swa_head_dim"], cfg["swa_v_head_dim"]) == \
        (4096, 192, 128, 192, 128)
    assert (cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"], cfg["num_experts_per_tok"]) == (4, 8, 8)
    # the pattern's lists are kept whole; the program reads the first seven entries: layer 0 dense and full, then one
    # period of five window layers to one full
    assert len(cfg["hybrid_layer_pattern"]) == len(cfg["moe_layer_freq"]) == 48
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1] and cfg["moe_layer_freq"][:7] == [0] + [1] * 6
    assert cfg["engine"]["prefix_cache"] is False and "32" in cfg["deployment"]
    assert cfg["n_routed_experts"] * 32 == cfg["published"]["n_routed_experts"] and cfg["experts_first"] == 0


def test_the_weight_arithmetic_and_the_two_classes_of_pages():
    cfg = published()
    params = sum(int(np.prod(shape)) for _, shape, _ in reference.leaf_specs(cfg))
    h = 4096
    full = h * (64 * 192 + 4 * 192 + 4 * 128) + 64 * 128 * h
    window = h * (64 * 192 + 8 * 192 + 8 * 128) + 64 * 128 * h
    dense, expert, router = 3 * h * 16384, 3 * h * 2048, h * 256
    assert (full, window, dense, expert) == (89_128_960, 94_371_840, 201_326_592, 25_165_824)
    sinks = 5 * 64
    assert params == (full + dense) + 5 * (window + router + 8 * expert) + (full + router + 8 * expert) \
        + 2 * 19072 * h + 14 * h + h + sinks + 6 * 256          # norms, sink logits, routers' correction biases
    assert round(params / 1e6) == 2222 and round(2 * params / 1e9, 2) == 4.44
    # the full class: 2 layers of 4 x (192 + 128) x 2 bytes; the window class: 5 layers of 8 x 320 x 2, a ring of 3
    assert mwork.kv_bytes_per_token(cfg) == 5120
    assert sum(mwork.kv_row_bytes(cfg, i) for i in range(7) if cfg["hybrid_layer_pattern"][i]) == 25600
    from paddlepaddle_tpu.inference.kv_pool import ring_pages

    eng = cfg["engine"]
    ring = ring_pages(cfg["sliding_window"], eng["kv_page_size"])
    assert ring == 3 <= -(-(128 + eng["decode_chunk"] - 1) // 64) + 1
    full_pages = eng["max_batch_size"] * eng["max_len"] // 64 + 1
    window_pages = eng["max_batch_size"] * ring + 1
    assert round(full_pages * 64 * 5120 / 1e9, 2) == 5.37 and round(window_pages * 64 * 25600 / 1e9, 2) == 1.26


def test_the_traffic_stays_inside_max_len_and_declares_no_prefix():
    with open(os.path.join(ROOT, "benchmark", "traffic", "reasoning-saturated.json")) as f:
        traffic = json.load(f)
    reqs = loadgen.schedule(traffic, 120.0)
    assert max(r["prompt"] + r["output"] for r in reqs) <= published()["engine"]["max_len"]
    assert all(r["prefix_len"] is None and r["system"] == 0 and r["turn"] == 0 for r in reqs)
    assert sum(r["due"] == 0 for r in reqs) == traffic["initial_burst"] == 2 * published()["engine"]["max_batch_size"]
    out = sorted(r["output"] for r in reqs)
    assert 1400 < out[len(out) // 2] < 1700 and min(r["prompt"] for r in reqs) >= 64


# -- the family's counts, by hand ----------------------------------------------------

def test_counts_by_hand():
    cfg = published()
    assert mwork.held_pairs_per_token(cfg) == 0.25
    # a window layer's queries see min(t + 1, 128) keys
    assert mwork._keys(cfg, 1, 0, 100) == 100 * 101 / 2
    assert mwork._keys(cfg, 1, 0, 300) == 128 * 129 / 2 + (300 - 128) * 128
    assert mwork._keys(cfg, 1, 200, 300) == 100 * 128 and mwork._keys(cfg, 0, 200, 300) == (300 * 301 - 200 * 201) / 2
    pair = 2 * 64 * (192 + 128)
    assert mwork.attention_ops(cfg, 0, 300) == pair * (2 * 300 * 301 / 2 + 5 * (128 * 129 / 2 + 172 * 128))
    parts = mwork.decode_step_bytes(cfg, 400_000, 256)
    assert parts["full_kv"] == 400_000 * 5120 and parts["window_kv"] == 256 * 127 * 25600
    assert parts["experts"] == pytest.approx(6 * 8 * (1 - (31 / 32) ** 256) * 25_165_824 * 2)
    ops, nbytes = mwork.decode_walk_counts(cfg, True, 400_000, 256)
    assert nbytes == 256 * 127 * 5120 + 256 * (64 * 320 + 8 * 320) * 2 and ops == pair * 256 * 128
    ops, nbytes = mwork.decode_walk_counts(cfg, False, 400_000, 256)
    assert nbytes == 400_000 * 2560 + 256 * (64 * 320 + 4 * 320) * 2 and ops == pair * (400_000 + 256)


# -- the new readers ----------------------------------------------------------------

def reader(name):
    return run.load_reader(os.path.join(ROOT, "benchmark"), name)


def call(name, shape="bf16[256,64,128]"):
    return f'%{name} = {shape}{{2,1,0}} custom-call(bf16[256,64,192]{{2,1,0}} %p.1), custom_call_target="tpu_custom_call"'


WINDOW_CALLS = {call("paged_window_attention_decode.4"): 0.5, call("paged_window_attention_decode"): 0.3}
FULL_CALLS = {call("paged_gqa_attention_decode.7"): 0.6}
OTHERS = {call("paged_window_attention_admit.2"): 9.0, call("held_experts_decode.1", "f32[256,4096]"): 7.0,
          "%fusion.7 = bf16[256,4096]{1,0} fusion(%p.2), kind=kLoop": 2.5}


def obs(ops, runs=10.0, live=(400_000.0, 256.0)):
    """A reduced trace: ``ops`` {HLO text: seconds}, 40 events each; ``runs`` runs of the decode program; the live
    context held ``live`` (tokens, slots) all through the window."""
    req = {"t_admit": 0.0, "t_done": None, "prompt": live[0] / live[1], "new_tokens": 0, "tpot_s": None}
    return {"decode_chunk": 16, "config": published(), "device_kind": "TPU v5 lite", "work": mwork,
            "trace_t0": 1.0, "trace_t1": 2.0, "requests": [dict(req) for _ in range(int(live[1]))],
            "trace": {"module_s": {"jit_run": 4.0}, "module_calls": {"jit_run": runs},
                      "op_text_s": dict(ops), "op_text_n": {k: 40 for k in ops}}}


def test_the_window_attention_is_its_calls_seconds_over_the_decode_steps():
    got = reader("decode.window_attention_ms_per_step")(obs({**WINDOW_CALLS, **FULL_CALLS, **OTHERS}))
    assert got == pytest.approx(1e3 * 0.8 / (10.0 * 16))
    assert reader("decode.window_attention_ms_per_step")(obs({**FULL_CALLS, **OTHERS})) is None


@pytest.mark.parametrize("name, window, calls", [("window_decode_roofline", True, WINDOW_CALLS),
                                                 ("full_decode_roofline", False, FULL_CALLS)])
def test_a_walks_roofline_is_its_least_time_over_its_device_time(name, window, calls):
    o = obs({**WINDOW_CALLS, **FULL_CALLS, **OTHERS})
    ops, nbytes = mwork.decode_walk_counts(published(), window, 400_000.0, 256.0)
    least = max(ops / 197e12, nbytes / 819e9)
    want = 100.0 * 40 * len(calls) * least / sum(calls.values())
    assert reader(name)(o) == pytest.approx(want) and 0 < want < 100
    # the parent's program names no such call, and a family that counts no walk reads nothing
    assert reader(name)(obs(OTHERS)) is None
    assert reader(name)(dict(o, work=__import__("benchmark.work", fromlist=["work"]))) is None


def test_the_window_pages_peak_share_reads_the_decode_programs_counter():
    o = {"stats_after": {"window_pages_live_peak": 700}, "kv_after": {"window_classes": [{"pages_total": 768}]}}
    assert reader("kv.window_pages_peak_share")(o) == pytest.approx(100.0 * 700 / 768)
    assert reader("kv.window_pages_peak_share")({"stats_after": {}, "kv_after": {"pages_total": 9}}) is None


def test_the_manifest_reads_the_new_metrics_in_the_new_cell_alone():
    m = manifest()
    new = {"decode.window_attention_ms_per_step": ("decode step", "tpot_p50_ms"),
           "window_decode_roofline": ("kernels", "tpot_p50_ms"), "full_decode_roofline": ("kernels", "tpot_p50_ms"),
           "kv.window_pages_peak_share": ("KV pool", "out_tokens_per_s")}
    for name, (layer, moves) in new.items():
        entry = next(p for p in m["per_layer"] if p["name"] == name)
        assert (entry["layer"], entry["moves"], entry["workloads"]) == (layer, moves, [CELL])
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mimo-v2-flash-serve-d7-ep32", "reasoning-saturated", 1)
    reported = {e["name"] for e in m["end_to_end"] if CELL in e.get("workloads", [CELL])}
    assert reported == {"out_tokens_per_s", "tpot_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", ["moe.held_pick_share", "sched.admit_share"])
def test_the_new_cell_reads_the_held_picks_and_the_admissions_share(name):
    """The cell holds 8 of 256 experts behind a sigmoid router and admits whole prompts between 256 slots' chunks:
    both older readers hold for it, appended after the cell they were made for."""
    entry = next(p for p in manifest()["per_layer"] if p["name"] == name)
    assert entry["workloads"] == ["serve-longdoc-saturated", CELL]
