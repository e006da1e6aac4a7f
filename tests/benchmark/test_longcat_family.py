"""The ``longcat_flash`` family's benchmark files on the CPU at a tiny width
(nothing here is a measurement): a tiny LongCat configuration and an agent
traffic mix written as files over ``tiny.write(root)``; ``correct`` is true for
the program as it is and false for the float8 control and for each piece of the
mathematics left out or rounded (``test_longcat_faults.py``); the family's counts of operations and bytes
checked by hand at one small shape; each new reader on a hand-made ``obs`` and
``None`` where its counters are absent."""

import importlib
import io
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import run, tiny
from benchmark.families import longcat_flash as family
from benchmark.families import longcat_flash_reference as reference
from benchmark.families import longcat_flash_work as lwork

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2 ** 31 + 17

CONFIG = {
    "family": "longcat_flash", "source": "tiny width for CPU tests", "vocab_size": 128, "hidden_size": 64,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 1, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": 16,
    "zero_expert_num": 8, "moe_topk": 4, "n_routed_experts_held": 4, "experts_first": 4,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5, "rope_theta": 1e7, "attention_method": "MLA",
    "zero_expert_type": "identity", "torch_dtype": "bfloat16",
    "engine": {"max_batch_size": 4, "max_len": 256, "decode_chunk": 4, "kv_page_size": 16, "kv_num_pages": 80},
    "engine_facts": {"prompt_bucket": 128, "page_tokens": 16},
}
TRAFFIC = dict(tiny.TRAFFIC["tiny-sessions"], shared_prefix_tokens=32, document_tokens=None,
               session={"turns": 1, "gap_s": [0, 0]}, prompt_tokens={"dist": "uniform", "min": 4, "max": 40},
               output_tokens={"dist": "uniform", "min": 16, "max": 32}, check_requests=3, ramp_s=0.3, tail_s=0.2)
# The published widths multiply a normalised state by sqrt(6144) x 0.02 = 1.57; at hidden 64 a std of 0.2 does, so that
# an expert's part weighs here what it weighs there (at 0.02 it would be a hundredth of the identity part).
INIT_STD = 0.2
# three times above the program's largest reading at this width on the CPU (0 .. 0.0095 over three seeds) and three
# times under the least of the faults of test_longcat_faults.py (0.11, a cache row rounded to float8; the float8
# control reads 0.33 and 0.46)
LIMITS = {"token_gap": 0.033, "broken_outputs": 0}


def write(root):
    """``tiny.write(root)`` plus the tiny LongCat configuration, its traffic, limits and cell, as files."""
    root = tiny.write(root)
    base = os.path.join(root, "benchmark")

    def dump(obj, *parts):
        with open(os.path.join(*parts), "w") as f:
            json.dump(obj, f)

    dump(CONFIG, base, "configs", "tiny-longcat.json")
    dump(TRAFFIC, base, "traffic", "tiny-agent.json")
    dump(LIMITS, base, "limits", "tiny-agent-serve.json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["configs"].append({"name": "tiny-longcat", "source": "none", "file": "benchmark/configs/tiny-longcat.json",
                         "reduced": [], "why": "CPU test"})
    m["workloads"].append({"name": "tiny-agent-serve", "config": "tiny-longcat", "traffic": "tiny-agent", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] in ("out_tokens_per_s", "ttft_mean_ms"):
            e["workloads"].append("tiny-agent-serve")
    dump(m, root, "BENCHMARK.json")
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    std, reference.INIT_STD = reference.INIT_STD, INIT_STD
    yield write(str(tmp_path_factory.mktemp("bench-longcat")))
    reference.INIT_STD = std


def cell(root, seed=SEED):
    out = io.StringIO()
    res = run.run_cell("tiny-agent-serve", seed, 0.4, 0, data_root=root, check_chip=False, out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def test_the_cell_runs_from_files_alone_and_is_correct(root):
    res = cell(root)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) >= {"out_tokens_per_s", "setup_s"}
    assert res["checks"]["token_gap"]["value"] <= LIMITS["token_gap"] and res["checks"]["broken_outputs"]["value"] == 0


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5])
def test_the_float8_control_is_not_correct(root, seed):
    data = run.load_cell(root, "tiny-agent-serve")
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, 128, (96,)).astype(np.int32) for _ in range(3)]
    res = family.serve_reference(data.config, seed, seqs, [32] * 3, control="fp8")
    assert max(float(g.max()) for g in res["control_gap"]) > 3 * data.limits["token_gap"]
    assert sum(len(g) for g in res["gap"]) == 3 * 64


def test_the_reference_shares_nothing_with_the_program_and_its_share_is_a_share():
    src = open(os.path.join(ROOT, "benchmark", "families", "longcat_flash_reference.py")).read()
    assert "paddlepaddle_tpu" not in src.split('"""', 2)[2]
    # the held share plus the other three quarters of the routed experts is the whole layer
    cfg = dict(CONFIG, n_routed_experts_held=16, experts_first=0)
    w = reference.served_weights(reference.layer_specs(cfg, 0), 5, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64), jnp.float32)
    whole = reference.expert_share(cfg, w, "model.layers.0.", h, "f32")
    identity = reference.expert_share(dict(cfg, n_routed_experts_held=0), {**w, **{
        "model.layers.0.mlp." + k: w["model.layers.0.mlp." + k][:0] for k in ("gate_proj", "up_proj", "down_proj")}},
        "model.layers.0.", h, "f32")
    parts = 0
    for first in (0, 4, 8, 12):
        sub = {**w, **{"model.layers.0.mlp." + k: w["model.layers.0.mlp." + k][first:first + 4]
                       for k in ("gate_proj", "up_proj", "down_proj")}}
        parts = parts + reference.expert_share(dict(cfg, n_routed_experts_held=4, experts_first=first), sub,
                                               "model.layers.0.", h, "f32") - identity
    np.testing.assert_allclose(np.asarray(parts + identity), np.asarray(whole), atol=1e-5)


def test_a_train_kind_on_this_family_raises_at_once():
    with pytest.raises(AttributeError, match="served, not trained"):
        family.build_train
    assert family.work is lwork and family.reference is reference


# -- the family's counts, by hand at the published widths and one small shape ----

def published():
    with open(os.path.join(ROOT, "benchmark", "configs", "longcat-flash-omni-serve-d4-ep32.json")) as f:
        return json.load(f)


def test_counts_at_the_published_widths_by_hand():
    cfg = published()
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 64 * 128 * 6144          # without the kv up-projection
    up = 512 * 64 * 256
    assert mla + up == 90_570_752
    assert lwork.held_pairs_per_token(cfg) == 0.25
    assert lwork.layer_matmul_params(cfg) == 2 * mla + 2 * 3 * 6144 * 12288 + 6144 * 768 + 0.25 * 3 * 6144 * 2048
    assert lwork.head_params(cfg) == 6144 * 16384
    assert lwork.kv_bytes_per_token(cfg) == 9216
    assert lwork.experts_touched(cfg, 128) == pytest.approx(16 * (1 - (63 / 64) ** 128))
    # one query against 1,000 cached rows: absorbed; a 512-token prompt from nothing: expanded
    one = lwork.attention_forms(cfg, 1000, 1001)
    assert one["absorbed"] == 2 * up + 2 * 64 * (2 * 512 + 64) * 1001 and one["absorbed"] < one["expanded"]
    keys = 512 * 513 / 2
    first = lwork.attention_forms(cfg, 0, 512)
    assert first["expanded"] == 2 * up * 512 + 2 * 64 * (128 + 64 + 128) * keys and first["expanded"] < first["absorbed"]
    assert lwork.attention_ops(cfg, 0, 512) == 2 * first["expanded"]
    assert lwork.forward_ops(cfg, 0, 512, 1) == 4 * (2 * lwork.layer_matmul_params(cfg) * 512
                                                    + 2 * first["expanded"]) + 2 * 6144 * 16384


def test_the_decode_steps_least_time_by_hand():
    cfg = published()
    pk = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    parts = lwork.decode_step_bytes(cfg, 115_000, 128)
    touched = 16 * (1 - (63 / 64) ** 128)
    assert parts["experts"] == pytest.approx(4 * touched * 3 * 6144 * 2048 * 2)
    assert parts["attention_weights"] == 4 * 2 * 90_570_752 * 2 and parts["dense_mlps"] == 4 * 2 * 226_492_416 * 2
    assert parts["latent_rows"] == 115_000 * 9216 and parts["head"] == 6144 * 16384 * 2
    least = lwork.decode_step_least_s(cfg, 115_000, 128, pk)
    assert least == pytest.approx(sum(parts.values()) / 819e9) and 0.0125 < least < 0.0135      # bound by bytes


# -- the new readers ----------------------------------------------------------------

def reader(name):
    return run.load_reader(os.path.join(ROOT, "benchmark"), name)


OBS = {
    "stats_before": {"moe_picks_zero": 10, "moe_picks_held": 2, "moe_picks_absent": 12, "moe_experts_touched": 3,
                     "moe_layer_steps": 2, "moe_experts_held": 4, "moe_expert_pairs": (1, 0, 1, 0)},
    "stats_after": {"moe_picks_zero": 410, "moe_picks_held": 32, "moe_picks_absent": 782, "moe_experts_touched": 63,
                    "moe_layer_steps": 22, "moe_experts_held": 4, "moe_expert_pairs": (11, 5, 12, 4)},
    "kv_after": {"bytes_per_token": 9216},
}


def test_the_new_readers_on_a_hand_made_obs():
    assert reader("moe.zero_pick_share")(OBS) == pytest.approx(100 * 400 / (400 + 30 + 770))
    assert reader("moe.experts_touched_share")(OBS) == pytest.approx(100 * 60 / (20 * 4))
    assert reader("moe.load_max_over_mean")(OBS) == pytest.approx(11 * 4 / 30)
    assert reader("kv.latent_bytes_per_token")(OBS) == 9216


@pytest.mark.parametrize("name", ["moe.zero_pick_share", "moe.experts_touched_share", "moe.load_max_over_mean",
                                  "kv.latent_bytes_per_token"])
def test_a_new_reader_finds_nothing_on_a_program_without_the_counters(name):
    plain = {"stats_before": {"decode_view_pages": 1}, "stats_after": {"decode_view_pages": 2},
             "kv_after": {"pages_peak": 3}, "kv_before": {}}
    assert reader(name)(plain) is None and reader(name)({}) is None


def test_the_manifest_holds_the_new_cells_and_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells["serve-agent-saturated"]["config"] == "longcat-flash-omni-serve-d4-ep32"
    config = next(c for c in m["configs"] if c["name"] == "longcat-flash-omni-serve-d4-ep32")
    assert config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name, moves in (("moe.zero_pick_share", "tpot_p50_ms"), ("moe.experts_touched_share", "tpot_p50_ms"),
                        ("moe.load_max_over_mean", "out_tokens_per_s"), ("kv.latent_bytes_per_token", "out_tokens_per_s")):
        assert per_layer[name]["moves"] == moves and per_layer[name]["workloads"] == ["serve-agent-saturated"]
    for e in m["end_to_end"]:
        if e["name"] in ("out_tokens_per_s", "tpot_p50_ms"):
            assert "serve-agent-saturated" in e["workloads"]
    cfg = published()
    catalog = {"vocab_size": 131072, "num_layers": 28, "n_routed_experts": 512}
    assert cfg["published"] == catalog and cfg["n_routed_experts_held"] == 16 and cfg["experts_first"] == 0
    assert (cfg["hidden_size"], cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"], cfg["num_attention_heads"]) == \
        (6144, 12288, 2048, 64)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (cfg["n_routed_experts"] + cfg["zero_expert_num"], cfg["moe_topk"], cfg["routed_scaling_factor"],
            cfg["rope_theta"]) == (768, 12, 6, 10000000)
    assert importlib.import_module("benchmark.families." + cfg["family"]) is family
