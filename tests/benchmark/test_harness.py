"""The harness end to end at a tiny width on the CPU (nothing here is a
measurement): a configuration, a traffic mix and a metric added as files are
found by name; ``correct`` comes out true for the program as it is and false
for each fault the cells can have and for the lower-precision control;
``run.py`` gives no result without a TPU."""

import io
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import run, tiny
from benchmark.families import mistral
from benchmark.kinds import open_loop_sessions as ols
from benchmark.kinds import packed

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2 ** 31 + 11          # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("bench")))


def cell(root, workload, family=None, trace=0, seed=SEED):
    out = io.StringIO()
    res = run.run_cell(workload, seed, 0.6, trace, data_root=root, check_chip=False,
                       family=family, out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res      # the last line is the result
    return res


def faulty(**overrides):
    return types.SimpleNamespace(**{**{k: getattr(mistral, k) for k in dir(mistral)
                                       if not k.startswith("_")}, **overrides})


def test_train_cell_from_files_alone(root):
    res = cell(root, "tiny-train")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks" and set(res["checks"]) == {
        "loss_gap_mean", "grad_norm_gap", "change_norm_gap"}
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}


def test_serve_cell_from_files_alone(root):
    res = cell(root, "tiny-serve")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"out_tokens_per_s", "ttft_mean_ms", "setup_s"}
    assert res["checks"]["broken_outputs"]["value"] == 0


def test_a_new_configuration_and_traffic_are_new_files(root):
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny3.json"), "w") as f:
        json.dump(dict(tiny.CONFIG, num_hidden_layers=3), f)
    with open(os.path.join(base, "traffic", "tiny-packed-x4.json"), "w") as f:
        json.dump(dict(tiny.TRAFFIC["tiny-packed"], rows=4), f)
    with open(os.path.join(base, "limits", "tiny-train-x4.json"), "w") as f:       # no limit on the loss: read, not compared
        json.dump({k: v for k, v in tiny.LIMITS["tiny-train"].items() if k != "loss_gap_mean"}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "tiny3", "source": "none", "file": "benchmark/configs/tiny3.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny-train-x4", "config": "tiny3", "traffic": "tiny-packed-x4",
                                  "chips": 1, "why": "test"})
    manifest["end_to_end"][0]["workloads"].append("tiny-train-x4")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    res = cell(root, "tiny-train-x4")
    assert res["correct"] and "train_tokens_per_s" in res["metrics"]
    assert res["checks"]["loss_gap_mean"]["limit"] is None and res["checks"]["loss_gap_mean"]["value"] > 0


def test_a_new_metric_is_a_new_file(root):
    data = run.load_cell(root, "tiny-train")
    got = [m["name"] for m in run.cell_metrics(data.manifest, data.cell, "per_layer", {"setup_s"})]
    assert got == ["tiny.work_items"]
    assert run.load_reader(data.base, "tiny.work_items")({"stamps": [0.0, 1.0, 2.0]}) == 2


class Unchanged:
    """Fault: a step that returns its state unchanged."""

    def __init__(self, prog):
        self.prog = prog
        self.state = None

    def __call__(self, ids):
        import jax

        step = self.prog.step
        if self.state is None:
            self.state = jax.tree_util.tree_map(lambda x: x.copy(), (step.params, step.opt_state))
        loss = self.prog(ids)
        keep = jax.tree_util.tree_map(lambda x: x.copy(), self.state)
        step.params, step.opt_state = keep
        return loss

    def __getattr__(self, name):
        return getattr(self.prog, name)


class HalfBatch(Unchanged):
    """Fault: half of the batch left out, the mean taken over the rest."""

    def __call__(self, ids):
        return self.prog(ids[: len(ids) // 2])


@pytest.mark.parametrize("fault", [Unchanged, HalfBatch])
def test_a_broken_train_step_is_not_correct(root, fault):
    fam = faulty(build_train=lambda cfg, seed: fault(mistral.build_train(cfg, seed)))
    res = cell(root, "tiny-train", family=fam)
    assert not res["correct"]
    over = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert over and ("change_norm_gap" in over if fault is Unchanged else "grad_norm_gap" in over)


def test_an_altered_token_is_not_correct(root, monkeypatch):
    """Fault: a token altered where it is produced (the engine's retirement)."""
    from paddlepaddle_tpu.inference import decode_engine

    retire = decode_engine.BatchDecodeEngine._retire

    def altered(self, slot):
        s = self._host_slots[slot]
        if s.req is not None and len(s.emitted) > 1:
            s.emitted[1] = (s.emitted[1] + 1) % self.cfg.vocab_size
        return retire(self, slot)

    monkeypatch.setattr(decode_engine.BatchDecodeEngine, "_retire", altered)
    res = cell(root, "tiny-serve")
    assert not res["correct"] and res["checks"]["token_gap"]["value"] > res["checks"]["token_gap"]["limit"]


def test_a_lost_prompt_is_not_correct(root, monkeypatch):
    real = ols._send
    monkeypatch.setattr(ols, "_send", lambda engine, r: real(engine, dict(r, ids=r["ids"][::-1].copy())))
    res = cell(root, "tiny-serve")
    assert not res["correct"] and res["checks"]["broken_outputs"]["value"] > 0


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 5])
def test_the_train_control_is_not_correct(root, seed):
    """The control: the reference put in the program's place in float8."""
    data = run.load_cell(root, "tiny-train")
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    batches = [rng.integers(0, 128, (2, 64)).astype(np.int32) for _ in range(3)]
    ref = mistral.train_reference(data.config, seed, batches)
    ctl = mistral.train_reference(data.config, seed, batches, precision="fp8")
    got = dict(packed.compare(ctl["loss"], ctl["grad_norm"], ctl["change_norm"], ref))
    assert any(got[k] > data.limits[k] for k in got)


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 5])
def test_the_serve_control_is_not_correct(root, seed):
    data = run.load_cell(root, "tiny-serve")
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, 128, (160,)).astype(np.int32) for _ in range(4)]
    res = mistral.serve_reference(data.config, seed, seqs, [32] * 4, control="fp8")
    assert max(float(g.max()) for g in res["control_gap"]) > data.limits["token_gap"]
    assert sum(len(g) for g in res["gap"]) == 4 * 128


def test_reference_shares_nothing_with_the_program():
    src = open(os.path.join(ROOT, "benchmark", "families", "mistral_reference.py")).read()
    assert "paddlepaddle_tpu" not in src.split('"""', 2)[2]


def test_run_refuses_to_give_a_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
                        "train-4k-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=240)
    assert p.returncode != 0 and p.stdout.strip() == "" and "not a TPU" in p.stderr


def test_manifest_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    base = os.path.join(ROOT, m["paths"][0])
    for c in m["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in m["workloads"]:
        assert os.path.exists(os.path.join(base, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(base, "limits", w["name"] + ".json"))
    for p in m["per_layer"]:
        assert callable(run.load_reader(base, p["name"]))
        assert p["moves"] in {e["name"] for e in m["end_to_end"]}
