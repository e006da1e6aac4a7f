"""Operations and bytes from shapes, and the table of peaks: the yardstick.

``Lowered.cost_analysis()`` is ``None`` on this TPU client, so every count here
is worked out from the configuration's sizes. Counts are what the mathematics
needs (a multiply-add is two operations; causal attention counts the lower
triangle; recomputation is not counted in an MFU), whatever implements it.

The counts below are a dense GQA decoder's (the ``mistral`` family's). A family
with another block brings a module with the same functions as a new file, names
it ``work`` in its builder, and the readers take it from ``obs["work"]``
(``counts(obs)``); the peaks stay here, for every family.
"""

from __future__ import annotations

import sys

# Google Cloud documentation, "TPU v5e" system architecture: per chip 197 TFLOP/s
# in bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM. Keyed by ``device_kind`` as
# jax reports it; a device that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def counts(obs: dict):
    """The module that counts operations and bytes for this run's family: what ``run.py`` put into ``obs``, or this
    one where a hand-made ``obs`` has none."""
    return obs.get("work") or sys.modules[__name__]


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * q + 2 * h * kv + q * h + 3 * h * i


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attention_ops(cfg: dict, first: int, last: int) -> float:
    """QK^T and PV of ONE layer for the query positions first..last-1, each
    attending causally to every earlier position and itself."""
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    keys = (last * (last + 1) - first * (first + 1)) / 2.0     # sum of (t + 1)
    return 4.0 * nh * d * keys


def forward_ops(cfg: dict, first: int, last: int, with_head_tokens: int) -> float:
    """Forward operations for computing positions first..last-1 of one sequence,
    the output head applied to ``with_head_tokens`` of them."""
    layers = cfg["num_hidden_layers"]
    return (layers * (2.0 * layer_matmul_params(cfg) * (last - first) + attention_ops(cfg, first, last))
            + 2.0 * head_params(cfg) * with_head_tokens)


def train_ops_per_step(cfg: dict, rows: int, tokens_per_row: int) -> float:
    """Forward plus backward (twice the forward) of a packed batch."""
    return 3.0 * rows * forward_ops(cfg, 0, tokens_per_row, tokens_per_row)


def flash_forward_ops(cfg: dict, rows: int, tokens: int) -> float:
    """One call of the flash forward kernel (one layer, causal)."""
    return rows * attention_ops(cfg, 0, tokens)


def flash_backward_ops(cfg: dict, rows: int, tokens: int) -> float:
    """One layer's flash backward: dV, dP, dQ, dK and the scores it has to
    rebuild, five matrix products against the forward's two."""
    return 2.5 * flash_forward_ops(cfg, rows, tokens)


def flash_bytes(cfg: dict, rows: int, tokens: int, itemsize: int = 2) -> float:
    """q and the output at every head, k and v at the KV heads, once."""
    nh, kvh, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return rows * tokens * d * (2 * nh + 2 * kvh) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize * cfg["num_hidden_layers"]


def decode_step_least_s(cfg: dict, live_tokens: float, active_slots: float, pk: dict,
                        itemsize: int = 2) -> float:
    """Least time of one decode step: every weight and the live KV read once, or
    the step's operations, whichever takes longer."""
    weights = cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)
    nbytes = weights * itemsize + live_tokens * kv_bytes_per_token(cfg, itemsize)
    ops = 2.0 * weights * active_slots + 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * live_tokens * cfg["num_hidden_layers"]
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["flops_per_s"])


def roofline_least_s(ops: float, nbytes: float, pk: dict) -> float:
    return max(ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
