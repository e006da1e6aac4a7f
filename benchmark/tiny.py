"""A throw-away data root at a tiny width: a ``BENCHMARK.json`` with a train
and a serve cell, their configuration, traffic, limits and one per-layer metric,
all written as new files into a directory of the caller's. The CPU tests drive
the whole harness through it (nothing here is measured), and it shows that a
configuration, a traffic mix and a metric are added as files alone."""

from __future__ import annotations

import json
import os

CONFIG = {
    "family": "mistral", "source": "tiny width for CPU tests", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "train": {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8, "weight_decay": 0.01},
    "engine": {"max_batch_size": 4, "max_len": 256, "decode_chunk": 4, "kv_page_size": 16,
               "kv_num_pages": 80},
    "engine_facts": {"prompt_bucket": 128, "page_tokens": 16},
}
TRAFFIC = {
    "tiny-packed": {"kind": "packed", "rows": 2, "tokens_per_row": 64, "warm_steps": 1, "trace_seconds": 1},
    "tiny-sessions": {
        "kind": "open_loop_sessions", "schedule_seed": 5, "arrivals": "uniform", "initial_burst": 2,
        "rate_per_s": 4.0, "ramp_s": 1.0, "tail_s": 0.5, "wait_after_close_s": 30, "trace_seconds": 1,
        "warm_output_tokens": 2, "check_requests": 3, "shared_prefix_tokens": 0, "declare_prefix": True,
        "document_tokens": {"choices": [32, 48]}, "session": {"turns": 2, "gap_s": [0.1, 0.2]},
        "prompt_tokens": {"dist": "uniform", "min": 4, "max": 12},
        "output_tokens": {"dist": "uniform", "min": 3, "max": 6}},
}
LIMITS = {
    # between the program's readings on the CPU at this width (loss 3e-5, norms
    # 1.7e-3) and the float8 control's (norms from 3.4e-3): see tests/benchmark
    "tiny-train": {"loss_gap_mean": 1e-4, "grad_norm_gap": 2.5e-3, "change_norm_gap": 2.5e-3},
    "tiny-serve": {"token_gap": 0.01, "broken_outputs": 0},
}
METRIC = '''"""A throw-away per-layer metric: steps or requests the window held."""


def read(obs):
    return len(obs["stamps"]) - 1 if "stamps" in obs else len(obs["requests"])
'''


def write(root: str) -> str:
    base = os.path.join(root, "benchmark")
    for d in ("configs", "traffic", "limits", "metrics"):
        os.makedirs(os.path.join(base, d), exist_ok=True)

    def dump(obj, *parts):
        with open(os.path.join(*parts), "w") as f:
            json.dump(obj, f, indent=1)

    dump(CONFIG, base, "configs", "tiny.json")
    for name, t in TRAFFIC.items():
        dump(t, base, "traffic", name + ".json")
    for name, lim in LIMITS.items():
        dump(lim, base, "limits", name + ".json")
    with open(os.path.join(base, "metrics", "tiny.work_items.py"), "w") as f:
        f.write(METRIC)
    dump({
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"], "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "none", "file": "benchmark/configs/tiny.json",
                     "reduced": [], "why": "CPU test"}],
        "workloads": [
            {"name": "tiny-train", "config": "tiny", "traffic": "tiny-packed", "chips": 1, "why": "test"},
            {"name": "tiny-serve", "config": "tiny", "traffic": "tiny-sessions", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1,
             "source": "host_clock", "workloads": ["tiny-train"]},
            {"name": "out_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1,
             "source": "host_clock", "workloads": ["tiny-serve"]},
            {"name": "ttft_mean_ms", "unit": "ms", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": ["tiny-serve"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{"name": "tiny.work_items", "unit": "count", "better": "higher",
                       "source": "program_counter", "layer": "test", "moves": "setup_s"}],
    }, root, "BENCHMARK.json")
    return root
