"""The two readers of the decode step's view counters on made-up records: the ratio of two differences worked out by
hand, nothing on an ``obs`` from a program without the counters, nothing where no decode call ran between the copies."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASE = os.path.join(ROOT, "benchmark")

OLD_STATS = {"requests": 0, "decode_tokens": 0, "turnaround_s": 0.0, "turnaround_n": 0, "loop_busy_s": 0.0}
CELLS = {"decode.view_share": ("tpot_p50_ms", "serve-chat-saturated"),
         "decode.view_share.steady": ("ttft_mean_ms", "serve-docqa-steady")}


def stats(calls, view_pages):
    return dict(OLD_STATS, requests=3 * calls, decode_tokens=400 * calls,
                decode_view_pages=view_pages, decode_table_pages=64 * calls)


@pytest.fixture()
def obs():
    # 8 warm-up calls on the 16-page rung before the first copy; then 100 calls: 30 on 16 pages, 70 on 24
    return {"stats_before": stats(8, 8 * 16), "stats_after": stats(108, 8 * 16 + 30 * 16 + 70 * 24)}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_gives_the_ratio_of_two_differences(obs, name):
    assert run.load_reader(BASE, name)(obs) == pytest.approx(100.0 * (30 * 16 + 70 * 24) / (100 * 64)) == 33.75
    whole = dict(obs, stats_after=stats(108, 8 * 16 + 100 * 64))          # the whole table in every call
    assert run.load_reader(BASE, name)(whole) == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("lacking", ["both", "before", "after"])
def test_reader_gives_nothing_without_the_counters(obs, name, lacking):
    old = dict(obs)
    if lacking in ("both", "before"):
        old["stats_before"] = dict(OLD_STATS)
    if lacking in ("both", "after"):
        old["stats_after"] = dict(OLD_STATS, requests=50, decode_tokens=9000)
    assert run.load_reader(BASE, name)(old) is None
    assert run.load_reader(BASE, name)({}) is None


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_gives_nothing_where_no_decode_call_ran(obs, name):
    assert run.load_reader(BASE, name)(dict(obs, stats_after=obs["stats_before"])) is None


def test_manifest_gives_the_view_readers_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"] if m["name"] in CELLS}
    assert sorted(entries) == sorted(CELLS)
    for name, m in entries.items():
        moves, cell = CELLS[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == ("%", "program_counter", "decode step", moves), name
        assert cell in m["workloads"], name
    # each is read in its own cell's traced run and not in the other accepted cells'
    for cell in manifest["workloads"]:
        reported = {m["name"] for m in run.cell_metrics(manifest, cell, "end_to_end")}
        got = {m["name"] for m in run.cell_metrics(manifest, cell, "per_layer", reported)} & set(CELLS)
        if cell["name"] in ("train-4k-1chip", "serve-chat-saturated", "serve-docqa-steady"):
            assert got == {n for n, (_, c) in CELLS.items() if c == cell["name"]}, cell["name"]


@pytest.mark.parametrize("name", ["compile.cache_misses", "compile.backend_compile_s"])
def test_the_compile_readers_name_their_cells(name):
    """A later cell joins a metric by appending its name to ``workloads``: the two entries that had no such list now
    name the three accepted cells. A list names only cells that the manifest holds."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert {"train-4k-1chip", "serve-chat-saturated", "serve-docqa-steady"} <= set(entry["workloads"])
    cells = {w["name"] for w in manifest["workloads"]}
    assert all(set(m.get("workloads", ())) <= cells for m in manifest["per_layer"] + manifest["end_to_end"])
