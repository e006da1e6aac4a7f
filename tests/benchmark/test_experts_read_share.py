"""``moe.experts_read_share`` on the CPU (nothing here is a measurement): its reader on a hand-made ``obs``, ``None``
where the copies of ``stats`` lack the counter (a program without it reads as absent, never as 0%), and its entry in
the manifest."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "moe.experts_read_share"


def reader():
    return run.load_reader(os.path.join(ROOT, "benchmark"), NAME)


BEFORE = {"moe_experts_touched": 3, "moe_experts_read": 5, "moe_layer_steps": 2, "moe_experts_held": 12}
AFTER = {"moe_experts_touched": 63, "moe_experts_read": 125, "moe_layer_steps": 22, "moe_experts_held": 12}


def test_the_reader_is_the_experts_read_a_layer_step_over_the_experts_held():
    assert reader()({"stats_before": BEFORE, "stats_after": AFTER}) == pytest.approx(100 * 120 / (20 * 12))


@pytest.mark.parametrize("before, after", [
    ({k: v for k, v in BEFORE.items() if k != "moe_experts_read"},
     {k: v for k, v in AFTER.items() if k != "moe_experts_read"}),          # a program without the counter
    (BEFORE, {k: v for k, v in AFTER.items() if k != "moe_experts_read"}),
    ({"decode_view_pages": 1}, {"decode_view_pages": 2}),                     # a model without expert layers
    (BEFORE, dict(AFTER, moe_layer_steps=2, moe_experts_read=5)),            # no live layer-step in the window
])
def test_the_reader_finds_nothing_where_there_is_nothing_to_read(before, after):
    assert reader()({"stats_before": before, "stats_after": after}) is None
    assert reader()({}) is None


def test_the_manifest_reads_it_in_both_expert_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(p for p in m["per_layer"] if p["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "expert layer", "moves": "tpot_p50_ms",
                     "workloads": ["serve-agent-saturated", "serve-longdoc-saturated"]}
    tpot = next(e for e in m["end_to_end"] if e["name"] == "tpot_p50_ms")
    assert set(entry["workloads"]) <= set(tpot["workloads"])
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", NAME + ".py"))
