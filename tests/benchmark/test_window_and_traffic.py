"""Window arithmetic (edges on events) and the traffic generator (work and
schedule fixed by the traffic file, never by ``--seed``)."""

import glob
import json
import math
import os

import numpy as np
import pytest

from benchmark import loadgen, window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRAFFIC = sorted(glob.glob(os.path.join(ROOT, "benchmark", "traffic", "*.json")))


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_train_window_edges_are_barriers():
    clock = FakeClock()

    def step(i):
        clock.t += 0.25

    stamps = window.run_steps(step, 1.1, clock)
    # closes at the first barrier at or after 1.1 s: five whole steps, no part of a sixth
    assert len(stamps) - 1 == 5 and stamps[-1] - stamps[0] == pytest.approx(1.25)
    assert window.rate(stamps, 8192) == pytest.approx(8192 / 0.25)


def test_stall_inside_the_window_lowers_the_rate_and_is_the_longest_gap():
    clock = FakeClock()

    def step(i):
        clock.t += 0.25 + (1.0 if i == 2 else 0.0)

    stamps = window.run_steps(step, 2.0, clock)
    assert window.rate(stamps, 8192) < 0.7 * 8192 / 0.25
    gaps = window.gap_summary(stamps)
    longest, offset, index = gaps["longest"][0]
    assert longest == pytest.approx(1.25) and offset == pytest.approx(1.75) and index == 3
    assert window.longest_gap_ms(gaps) == pytest.approx(1250.0) and window.longest_gap_ms(window.gap_summary([1.0])) is None
    assert gaps["median_s"] == pytest.approx(0.25) and gaps["over"] == 1 and gaps["over_s"] == pytest.approx(1.25)
    lines = window.stall_lines("loss scalars", gaps, None, [(stamps[3], 1.0)], [(stamps[2] + 0.1, 0.9, 2)],
                               stamps[0], stamps[-1],
                               ({"hits": 0, "misses": 0, "backend_compile_s": 0, "retrieval_s": 0},
                                {"cpu_steal_s": 1.0, "process_cpu_s": 2.0}),
                               ({"hits": 1, "misses": 2, "backend_compile_s": 3.5, "retrieval_s": 0.1},
                                {"cpu_steal_s": 2.5, "process_cpu_s": 3.0}))
    text = "\n".join(lines)
    assert "1250.0 ms ending 1.75 s in" in text and "1 gaps over 1.5 x median hold 1.250 s" in text
    assert "1000 ms at 1.75 s" in text and "900 ms (generation 2)" in text
    assert "compiled in the window 2 programs (3.50 s), retrieved 1" in text and "steal 1.50 s" in text
    quiet = window.stall_lines("deliveries", window.gap_summary([0.0, 0.6, 1.2, 1.8]), [math.nan, 0.9, 0.5, 0.9])
    assert "slots 50%" in quiet[0] and "0 gaps over" in quiet[1] and "never" in quiet[2] and "none" in quiet[3]
    assert window.stall_lines("deliveries", window.gap_summary([1.0])) == ["stalls: no two deliveries in the window"]


def test_occupancy_comes_from_the_counter_not_from_a_sample():
    events = [(0.0, 100), (0.6, 612), (1.2, 868), (1.8, 868 + 32)]
    occ = window.delivery_occupancy(events, slot_steps=32 * 16)
    assert math.isnan(occ[0]) and occ[1:] == [1.0, 0.5, 1 / 16]


def test_gc_watch_keeps_long_collections_only_and_leaves_no_callback():
    import gc

    clock = FakeClock()
    before = list(gc.callbacks)
    with window.GcWatch(at_least_s=0.05, clock=clock) as watch:
        watch._on_gc("start", {"generation": 2})
        clock.t += 0.2
        watch._on_gc("stop", {"generation": 2})
        watch._on_gc("start", {"generation": 0})
        clock.t += 0.001
        watch._on_gc("stop", {"generation": 0})
        assert len(gc.callbacks) == len(before) + 1
    assert gc.callbacks == before
    assert watch.long == [(100.0, pytest.approx(0.2), 2)]


def test_host_counters_are_monotonic_numbers():
    a, b = window.host_counters(), window.host_counters()
    assert a.keys() == b.keys() and "process_cpu_s" in a
    assert all(b[k] >= a[k] for k in a)


def test_delivery_window_opens_and_closes_on_deliveries():
    events = [(0.5, 10), (1.2, 20), (1.9, 30), (2.6, 40), (3.3, 50), (4.0, 60)]
    i0, i1 = window.delivery_window(events, t_ready=1.0, seconds=2.0)
    assert (events[i0], events[i1]) == ((1.2, 20), (2.6, 40))
    assert window.delivery_rate(events, i0, i1) == pytest.approx(20 / 1.4)
    with pytest.raises(RuntimeError):
        window.delivery_window(events, t_ready=5.0, seconds=2.0)


def test_ttft_counts_from_due_time_and_a_failure_is_a_miss():
    reqs = [{"due": 1.0, "t_first": 1.5, "error": None},
            {"due": 2.0, "t_first": 2.25, "error": None},
            {"due": 2.5, "t_first": None, "error": None},           # never got a token
            {"due": 2.6, "t_first": 2.7, "error": "refused"},       # failed
            {"due": 9.0, "t_first": 9.1, "error": None}]            # outside
    t = window.ttft_from_due(reqs, 0.0, 5.0)
    assert t[:2] == [0.5, 0.25] and t[2:] == [math.inf, math.inf]
    assert window.percentile(t, 50) == 0.5 and window.percentile(t, 90) == math.inf
    assert window.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9


def test_delivery_log_records_each_move_of_the_counter_and_polls_no_faster_than_20ms():
    import time

    assert window.POLL_S >= 0.02 and window.DeliveryLog(lambda: 0)._period == window.POLL_S
    box, reads = {"n": 0}, []

    def read():
        reads.append(time.perf_counter())
        return box["n"]

    log = window.DeliveryLog(read)
    log.start()
    for n in (5, 5, 9):
        box["n"] = n
        time.sleep(0.07)
    log.stop()
    assert [c for _, c in log.events] == [5, 9]
    assert min(b - a for a, b in zip(reads, reads[1:])) >= 0.019
    assert window.Heartbeat()._period >= window.POLL_S


@pytest.mark.parametrize("path", TRAFFIC, ids=[os.path.basename(p) for p in TRAFFIC])
def test_traffic_file_fixes_work_and_schedule(path):
    with open(path) as f:
        traffic = json.load(f)
    if traffic["kind"] != "open_loop_sessions":
        assert {"rows", "tokens_per_row", "warm_steps", "trace_seconds"} <= set(traffic)
        return
    a = loadgen.schedule(traffic, 30.0)
    b = loadgen.schedule(traffic, 30.0)
    assert a == b and len(a) > 20
    longer = loadgen.schedule(traffic, 40.0)
    key = lambda r: (r["session"], r["turn"], r["due"], r["prompt"], r["output"])
    assert {key(r) for r in a} <= {key(r) for r in longer}      # a longer horizon only appends
    for seed in (1, 2 ** 31 + 7):
        reqs = loadgen.schedule(traffic, 30.0)
        loadgen.fill_tokens(reqs, seed, 32768)
        assert [(r["due"], r["prompt"], r["output"]) for r in reqs] == \
            [(r["due"], r["prompt"], r["output"]) for r in a]
        assert all(len(r["ids"]) == r["prompt"] and r["ids"].max() < 32768 for r in reqs)
    assert max(r["prompt"] + r["output"] for r in longer) <= 4096


def test_seed_changes_ids_only_and_sessions_share_their_document():
    traffic = {"schedule_seed": 3, "arrivals": "uniform", "initial_burst": 1, "rate_per_s": 2.0,
               "shared_prefix_tokens": 8, "declare_prefix": True, "document_tokens": {"choices": [64]},
               "session": {"turns": 3, "gap_s": [0.1, 0.2]},
               "prompt_tokens": {"dist": "uniform", "min": 4, "max": 9},
               "output_tokens": {"fixed": 5}}
    reqs = loadgen.schedule(traffic, 4.0)
    other = loadgen.schedule(traffic, 4.0)
    loadgen.fill_tokens(reqs, 1, 1000)
    loadgen.fill_tokens(other, 2, 1000)
    s0 = [r for r in reqs if r["session"] == 0]
    assert len(s0) == 3 and all(r["prefix_len"] == 72 for r in s0)
    assert np.array_equal(s0[0]["ids"][:72], s0[2]["ids"][:72])
    assert not np.array_equal(s0[0]["ids"][72:], s0[1]["ids"][72:76])
    assert not np.array_equal(reqs[0]["ids"], other[0]["ids"])
    groups = loadgen.warm_classes(reqs, 128, 16)
    assert len(groups) == 1 and {min(r["turn"], 1) for r in groups[0]} == {0, 1}


def test_open_loop_submits_on_schedule_and_records_refusals():
    reqs = [{"due": 0.0}, {"due": 0.05}, {"due": 0.1}]
    seen = []

    def submit(r):
        if r["due"] - gen.t0 > 0.07:
            raise RuntimeError("queue full")
        seen.append(r)
        return object()

    gen = loadgen.OpenLoop(reqs, submit)
    gen.start()
    gen._thread.join(timeout=5)
    assert not gen._thread.is_alive() and len(seen) == 2
    assert all(r["submitted"] - r["due"] < 0.05 for r in reqs)
    assert "queue full" in reqs[2]["error"] and "future" not in reqs[2]
