"""Operations and bytes of the Kimi-K2 layer with one chip's share of the experts, from
the configuration's sizes: what ``counts(obs)`` hands the readers for the ``kimi_k2``
family (the peaks stay in ``benchmark/work.py``).

Counts are what the mathematics needs, whatever implements it (a multiply-add is two
operations; causal attention counts the lower triangle, once):

* **latent attention** has two forms of the same mathematics, and the count is the
  cheaper one for the shape at hand (``longcat_flash_work.attention_forms``, the same
  block): a prefill from an empty cache counts the expanded form, a decode step the
  absorbed one, a question behind a cached document whichever is less.
* **the first ``first_k_dense_replace`` layers** hold a dense SwiGLU of
  ``intermediate_size``; **every later layer** the router at its full width and the
  shared expert for every token, and the held experts at the expectation of their pairs,
  ``num_experts_per_tok held / router width`` a token (8 x 12 / 384 = 0.25).
* **a decode step's bytes**: attention weights, the dense MLP, the shared experts, the
  routers and the head once; of the held experts those that at least one live slot
  picked, at their expectation; the live latent rows once.
"""

from __future__ import annotations

from benchmark.families.longcat_flash_work import (_kv_up_params, _mla_projection_params, attention_forms,  # noqa: F401
                                                   head_params)


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _layers(cfg: dict) -> tuple:
    """(dense layers, expert layers)."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def _router_width(cfg: dict) -> int:
    """The router's published width; the file's ``n_routed_experts`` counts the experts HELD here."""
    return cfg["published"]["n_routed_experts"]


def held_pairs_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / _router_width(cfg)


def experts_touched(cfg: dict, slots: float) -> float:
    """Expected held experts with at least one pair among ``slots`` tokens."""
    return cfg["n_routed_experts"] * (1.0 - (1.0 - cfg["num_experts_per_tok"] / _router_width(cfg)) ** slots)


def _always_params(cfg: dict) -> float:
    """Parameters of the whole stack that every token multiplies outside the attention's
    own form and outside the routed experts: projections, the dense MLPs, the routers
    and the shared experts."""
    dense, sparse = _layers(cfg)
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * _mla_projection_params(cfg) + dense * _dense_mlp_params(cfg)
            + sparse * (h * _router_width(cfg) + cfg["n_shared_experts"] * _expert_params(cfg)))


def matmul_params(cfg: dict) -> float:
    """:func:`_always_params` and the held experts at the expectation of their pairs."""
    return _always_params(cfg) + _layers(cfg)[1] * held_pairs_per_token(cfg) * _expert_params(cfg)


def attention_ops(cfg: dict, first: int, last: int) -> float:
    """The one block of ONE layer, in its cheaper form."""
    return min(attention_forms(cfg, first, last).values())


def prefill_kernel_ops(cfg: dict, first: int, last: int) -> float:
    """ONE call of the long prefill's attention kernel (one layer, every head): 192-wide scores and 128-wide values for
    the query positions first..last-1 against every key at or before each, the lower triangle counted once; the
    expansion of keys and values from the latent is a matmul outside the kernel and is not counted here."""
    H = cfg["num_attention_heads"]
    keys = (last * (last + 1) - first * (first + 1)) / 2.0
    return 2.0 * H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) * keys


def prefill_kernel_bytes(cfg: dict, first: int, last: int, itemsize: int = 2) -> float:
    """What that call has to read and write once: queries and output for its rows, expanded keys and values and the
    one rotated key for every position up to ``last``."""
    H, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return itemsize * ((last - first) * H * (nope + rope + vd) + last * (H * (nope + vd) + rope))


def forward_ops(cfg: dict, first: int, last: int, with_head_tokens: int) -> float:
    """Forward operations for computing positions first..last-1 of one sequence, the
    output head applied to ``with_head_tokens`` of them."""
    return (2.0 * matmul_params(cfg) * (last - first) + cfg["num_hidden_layers"] * attention_ops(cfg, first, last)
            + 2.0 * head_params(cfg) * with_head_tokens)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One latent row in the one block of every layer: 6,912 at 6 layers."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize * cfg["num_hidden_layers"]


def decode_step_bytes(cfg: dict, live_tokens: float, active_slots: float, itemsize: int = 2) -> dict:
    """The bytes of one decode step by their parts."""
    dense, sparse = _layers(cfg)
    layers, h = cfg["num_hidden_layers"], cfg["hidden_size"]
    return {"experts": sparse * experts_touched(cfg, active_slots) * _expert_params(cfg) * itemsize,
            "shared_experts": sparse * cfg["n_shared_experts"] * _expert_params(cfg) * itemsize,
            "attention_weights": layers * (_mla_projection_params(cfg) + _kv_up_params(cfg)) * itemsize,
            "dense_mlps": dense * _dense_mlp_params(cfg) * itemsize,
            "router": sparse * h * _router_width(cfg) * itemsize,
            "head": head_params(cfg) * itemsize,
            "latent_rows": live_tokens * kv_bytes_per_token(cfg, itemsize)}


def decode_step_least_s(cfg: dict, live_tokens: float, active_slots: float, pk: dict, itemsize: int = 2) -> float:
    """Least time of one decode step: its bytes once, or its operations (absorbed
    attention over the live rows), whichever takes longer."""
    nbytes = sum(decode_step_bytes(cfg, live_tokens, active_slots, itemsize).values())
    H, kr, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    layers = cfg["num_hidden_layers"]
    per_slot = 2.0 * matmul_params(cfg) + layers * 2.0 * _kv_up_params(cfg) + 2.0 * head_params(cfg)
    ops = per_slot * active_slots + layers * 2.0 * H * (2 * kr + rope) * live_tokens
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["flops_per_s"])
