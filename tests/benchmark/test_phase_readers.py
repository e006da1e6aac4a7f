"""The readers of the serving loop's phase counters on made-up records: each gives the value worked out by hand, and
nothing on an ``obs`` from a program without the counters (``ServingEngine.stats`` as it was: nine keys)."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASE = os.path.join(ROOT, "benchmark")

OLD_STATS = {"requests": 0, "batches": 0, "batched_requests": 0, "decode_tokens": 0, "batches_failed": 0, "shed": 0,
             "cancelled": 0, "deadline_expired": 0, "decode_failures": 0}


def stats(chunks, admits, turnaround_s, turnarounds, deliver_s, first_sync_s, chunk_sync_s, admit_s, busy_s):
    return dict(OLD_STATS, **{
        "span_s.serve.deliver": deliver_s, "span_n.serve.deliver": chunks,
        "span_s.serve.chunk_sync": chunk_sync_s, "span_n.serve.chunk_sync": chunks,
        "span_s.serve.first_sync": first_sync_s, "span_n.serve.first_sync": admits // 2,
        "span_s.serve.admit": admit_s, "span_n.serve.admit": admits, "admit_deferred": 3,
        "turnaround_s": turnaround_s, "turnaround_n": turnarounds, "loop_busy_s": busy_s})


def request(due, wait, first):
    return {"due": due, "submitted": due + 0.001, "t_admit": None if wait is None else due + wait,
            "t_first": None if first is None else due + first, "t_done": None}


@pytest.fixture()
def obs():
    # 100 chunks and 40 admissions between the two copies; 90 chunks were followed by a program, 10 by a wait for work
    return {"stats_before": stats(10, 4, 0.2, 8, 0.05, 1.0, 5.0, 0.04, 6.5),
            "stats_after": stats(110, 44, 1.82, 98, 0.45, 21.0, 45.0, 0.2, 68.5),
            "t_open": 10.0, "t_close": 55.0,
            "requests": [request(5.0, 0.1, 0.2),                  # due before the window: left out
                         request(12.0, 0.5, 1.2), request(20.0, 0.4, 1.3), request(30.0, 0.3, 0.8),
                         request(40.0, 0.2, None), request(50.0, None, None)]}


EXPECTED = {"sched.turnaround_ms_per_chunk": 1e3 * 1.62 / 90,
            "sched.deliver_ms_per_chunk": 1e3 * 0.40 / 100,
            "sched.sync_wait_share": 100 * (20.0 + 40.0) / 62.0,
            "sched.admit_host_ms_per_request": 1e3 * 0.16 / 40,
            "sched.first_token_wait_p50_ms": 700.0}               # median of 700, 900, 500


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_value_worked_out_by_hand(obs, name):
    assert run.load_reader(BASE, name)(obs) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_without_the_counters(obs, name):
    old = dict(obs, stats_before=dict(OLD_STATS), stats_after=dict(OLD_STATS, requests=50, decode_tokens=9000))
    for r in old["requests"]:
        r["t_first"] = None                                        # and no first-token stamp to read
    assert run.load_reader(BASE, name)(old) is None


@pytest.mark.parametrize("name", sorted(n for n in EXPECTED if n != "sched.first_token_wait_p50_ms"))
def test_counter_reader_gives_nothing_where_nothing_happened(obs, name):
    assert run.load_reader(BASE, name)(dict(obs, stats_after=obs["stats_before"])) is None


def test_sync_wait_share_cannot_read_over_100(obs):
    read = run.load_reader(BASE, "sched.sync_wait_share")
    obs["stats_after"]["loop_busy_s"] = obs["stats_before"]["loop_busy_s"] + 60.0
    assert read(obs) == pytest.approx(100.0)
    obs["stats_after"]["loop_busy_s"] -= 0.7                      # copies torn apart: the spans outrun the busy time
    assert read(obs) is None


def test_manifest_gives_the_phase_readers_their_cells():
    """The manifest's layout, not a measurement: the five entries exist, each with its source, layer and the end-to-end
    metric it moves, and the cell each was accepted in is among its ``workloads``. Where in ``per_layer`` they stand and
    which later cells joined them is the manifest's to grow: entries and cell names are appended, never moved."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"] if m["name"] in EXPECTED}
    assert sorted(entries) == sorted(EXPECTED)
    moves = {"sched.turnaround_ms_per_chunk": "out_tokens_per_s", "sched.deliver_ms_per_chunk": "out_tokens_per_s",
             "sched.sync_wait_share": "out_tokens_per_s", "sched.admit_host_ms_per_request": "ttft_mean_ms",
             "sched.first_token_wait_p50_ms": "ttft_mean_ms"}
    accepted_in = {"out_tokens_per_s": "serve-chat-saturated", "ttft_mean_ms": "serve-docqa-steady"}
    for name, m in entries.items():
        assert (m["source"], m["layer"], m["moves"]) == ("program_span", "scheduler", moves[name]), name
        assert accepted_in[m["moves"]] in m["workloads"], name
