"""chip_smoke.py and bench.py off the chip: the legs run at a tiny width on
the CPU with kernels interpreted (the same code the chip runs at the 254M
width), and both scripts refuse to produce a result without a TPU."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(_REPO, name)],
                          capture_output=True, text=True, timeout=240,
                          env=env, cwd=_REPO)


def test_smoke_legs_tiny_on_cpu():
    sys.path.insert(0, _REPO)
    import chip_smoke

    report = chip_smoke.run_legs(chip_smoke.TINY, interpret=True)
    legs = report["legs"]
    assert report["ok"] is True and report["device"]["platform"] == "cpu"
    # the verdict line is built from these: exactly three keys, typed
    dev = report["device"]
    assert set(dev) == {"platform", "kind", "count"}
    assert isinstance(dev["kind"], str) and type(dev["count"]) is int
    assert legs["train"]["status"] == "ok"
    losses = legs["train"]["losses"]
    assert losses == sorted(losses, reverse=True) and losses[-1] < losses[0]
    assert set(legs["serve"]) == {"status", "default", "int8_kv"}
    for name in ("default", "int8_kv"):
        row = legs["serve"][name]
        assert row["status"] == "ok" and row["prefix_hits"] >= 1
        assert row["worst_gap_over_max_logit"] <= row["margin"]
    assert legs["serve"]["default"]["kv_quant"] == "off"
    assert legs["serve"]["int8_kv"]["kv_quant"] == "int8"
    table = legs["kernels"]["table"]
    assert set(table) == {
        "flash", "flash_varlen", "gather_gemm", "paged_latent_attention",
        "paged_gqa_attention", "latent_prefill_attention",
        "held_experts.longdoc", "held_experts.agent"}
    assert all(r["mode"] == "interpret" for r in table.values())
    # conftest's 8 virtual CPU devices stand in for the four chips: the
    # sharding evidence is real, the allocator evidence is chip-only
    four = legs["four_chips"]
    assert four["status"] == "ok"
    assert len(set(four["train_dp2mp2"]["shard_elements"])) == 1
    assert four["decode_mp4"]["shard_elements"] == \
        [four["decode_mp4"]["shard_elements"][0]] * 4


def test_smoke_margin_rule_rejects_a_wrong_token():
    """The margin rule must be able to fail: a token the reference ranks
    far below its top logit is not a near-tie."""
    sys.path.insert(0, _REPO)
    import numpy as np

    import chip_smoke

    size = chip_smoke.TINY
    model = chip_smoke._build_model(size)
    ref = chip_smoke._Reference(model, size)
    prompts, _ = chip_smoke._prompts(size)
    outs = [np.concatenate([p, np.zeros(size.new_tokens, np.int32)])
            for p in prompts]            # "generated" token 0, every time
    with pytest.raises(AssertionError, match="not a near-tie"):
        ref.check("planted", prompts, outs, chip_smoke.MARGIN_BF16)


def test_chip_smoke_script_needs_a_tpu():
    proc = _run_script("chip_smoke.py")
    assert proc.returncode != 0
    assert "jax found none" in proc.stderr and "'cpu'" in proc.stderr
    # no result: nothing on stdout parses as the report
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_bench_script_needs_a_tpu():
    proc = _run_script("bench.py")
    assert proc.returncode != 0
    assert "jax found none" in proc.stderr and "'cpu'" in proc.stderr
    assert "no record written" in proc.stderr
    assert '"metric"' not in proc.stdout and '"error"' not in proc.stdout
