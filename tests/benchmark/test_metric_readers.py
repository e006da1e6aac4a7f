"""Per-layer readers on what a chip run recorded (a reduced trace of the train
cell, cut to its Mosaic calls) and on made-up serve records: a share of a
roofline or of a peak lies in (0, 100], and a reader with nothing to read
returns nothing, never 0."""

import json
import os

import pytest

from benchmark import run, window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASE = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def config(name):
    with open(os.path.join(BASE, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture()
def train_obs():
    with open(os.path.join(DATA, "train_trace_reduced.json")) as f:
        reduced = json.load(f)
    stamps = [10.0 + 0.25 * i for i in range(13)]
    stamps[7:] = [t + 0.5 for t in stamps[7:]]           # one stall of half a second
    return {"trace": reduced, "config": config("mistral-7b-v0.3-train-d2"), "rows": 2, "tokens_per_row": 4096,
            "tokens_per_step": 8192, "chips": 1, "device_kind": "TPU v5 lite", "stamps": stamps,
            "gaps": window.gap_summary(stamps),
            "end_to_end": {"train_tokens_per_s": 8192 / 0.25}, "memory_peak_bytes": 11357361152,
            "compile_after": {"misses": 0, "backend_compile_s": 1.5}}


@pytest.mark.parametrize("name,lo,hi", [
    ("flash_fwd_roofline", 45, 60), ("flash_bwd_roofline", 40, 55), ("train.mfu", 55, 65),
    ("train.device_idle_share", 0, 1), ("train.step_ms_p50", 249.9, 250.1), ("train.longest_gap_ms", 749.9, 750.1), ("train.peak_hbm_gb", 11.3, 11.4),
    ("compile.cache_misses", 0, 0), ("compile.backend_compile_s", 1.5, 1.5)])
def test_train_readers_on_the_recorded_trace(train_obs, name, lo, hi):
    assert lo <= run.load_reader(BASE, name)(train_obs) <= hi


def test_a_kernel_off_the_path_reads_nothing(train_obs):
    tr = train_obs["trace"]
    tr["op_text_s"] = {k: v for k, v in tr["op_text_s"].items() if "tpu_custom_call" not in k}
    assert run.load_reader(BASE, "flash_fwd_roofline")(train_obs) is None
    assert run.load_reader(BASE, "flash_bwd_roofline")(train_obs) is None
    assert run.load_reader(BASE, "train.peak_hbm_gb")(dict(train_obs, memory_peak_bytes=0)) is None


@pytest.fixture()
def serve_obs():
    def req(i, turn, due, wait, first, done, n=64):
        return {"session": i, "turn": turn, "due": due, "submitted": due + 0.001, "prompt": 3200, "output": n,
                "system": 0, "document": 3072, "prefix_len": 3072, "error": None, "t_admit": due + wait,
                "t_first": first and due + first, "t_done": done and due + done,
                "tpot_s": 0.04 if done else None, "new_tokens": n if done else 0}
    reqs = [req(0, 0, 1.0, 0.3, 0.8, 3.4), req(0, 1, 2.5, 0.2, 0.5, 3.1), req(1, 0, 3.0, 0.4, 1.0, 3.6),
            req(1, 1, 5.0, 0.1, 0.3, None)]
    return {"requests": reqs, "t_open": 0.5, "t_close": 9.0, "trace_t0": 0.5, "trace_t1": 9.0,
            "events": [(0.5, 100), (4.0, 180), (9.0, 292)], "i_open": 0, "i_close": 2,
            "occupancy": window.delivery_occupancy([(0.5, 100), (4.0, 180), (9.0, 292)], 32 * 16),
            "gaps": window.gap_summary([0.5, 4.0, 9.0]), "slots": 32, "decode_chunk": 16,
            "kv_before": {"prefix": {"hits": 3}}, "kv_after": {"prefix": {"hits": 5}, "pages_peak": 400, "pages_total": 1151},
            "config": config("mistral-7b-v0.3-serve-d16"), "device_kind": "TPU v5 lite", "memory_peak_bytes": 12.7e9,
            "trace": {"busy_s": 6.0, "window_s": 8.5, "module_s": {"jit_impl": 0.5, "jit__admit_paged_impl": 0.4, "jit_run": 5.0, "jit_other": 0.1},
                      "module_calls": {"jit_impl": 2, "jit__admit_paged_impl": 2, "jit_run": 8, "jit_other": 3}}}


def test_serve_readers_on_made_up_records(serve_obs, monkeypatch):
    read = lambda name: run.load_reader(BASE, name)(serve_obs)
    assert read("loadgen.lateness_p99_ms") == pytest.approx(1.0)
    assert read("sched.queue_wait_p50_ms") == pytest.approx(199.0)
    assert read("sched.ttft_p90_ms.saturated") == pytest.approx(1000.0)
    assert read("sched.ttft_p90_ms.steady") == pytest.approx(1000.0)
    assert read("sched.slot_occupancy") == pytest.approx(100 * (80 + 112) / 2 / 512)
    assert read("serve.longest_gap_ms") == pytest.approx(5000.0)
    assert read("kv.pages_peak_share") == pytest.approx(100 * 400 / 1151)
    # two hits of a 3,072-token shareable prefix over four admitted prompts of 3,200
    assert read("kv.prefix_hit_token_share") == pytest.approx(100 * 2 * 3072 / (4 * 3200))
    assert read("serve.device_idle_share.steady") == pytest.approx(100 * 2.5 / 8.5)
    for name in ("serve.mfu", "prefill.mfu", "decode.step_roofline"):
        assert 0 < read(name) <= 100, name
    assert read("decode.device_ms_per_step") > 0 and read("prefill.device_ms_per_request") > 0
    serve_obs["trace"]["module_s"], serve_obs["trace"]["module_calls"] = {"jit_other": 0.1}, {"jit_other": 3}
    for name in ("prefill.mfu", "prefill.device_ms_per_request", "decode.step_roofline", "decode.device_ms_per_step"):
        assert read(name) is None, name
    serve_obs["requests"][1]["t_first"] = None      # a missed request in the tail leaves none, never an infinite one
    assert read("sched.ttft_p90_ms.steady") is None


class _CountingWork:
    """A family's counts handed over in ``obs["work"]``: ``benchmark.work``'s functions, each call noted."""

    def __init__(self):
        self.called = set()

    def __getattr__(self, name):
        from benchmark import work

        if name == "peaks":
            raise AssertionError("the peaks are benchmark.work's alone, never the family's")
        self.called.add(name)
        return getattr(work, name)


WORK_READERS = {"serve.mfu": "forward_ops", "serve.mfu.tpot": "forward_ops", "prefill.mfu": "forward_ops",
                "decode.step_roofline": "decode_step_least_s", "train.mfu": "train_ops_per_step",
                "flash_fwd_roofline": "flash_forward_ops", "flash_bwd_roofline": "flash_backward_ops"}


@pytest.mark.parametrize("name", sorted(WORK_READERS))
def test_a_reader_takes_its_counts_from_the_family_and_reads_the_same(train_obs, serve_obs, name):
    """``run_cell`` puts the family's ``work`` into ``obs``; for ``mistral`` that is ``benchmark.work``, so the stored
    records read the same to the last digit with the key and without it, and the count was asked of the family."""
    from benchmark import work
    from benchmark.families import mistral

    assert mistral.work is work
    obs = train_obs if name.startswith(("train.", "flash_")) else serve_obs
    read = run.load_reader(BASE, name)
    without = read(obs)
    family = _CountingWork()
    assert without is not None and read(dict(obs, work=family)) == without == read(dict(obs, work=work))
    assert WORK_READERS[name] in family.called
