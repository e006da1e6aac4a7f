"""Operations and bytes of the LongCat-Flash double layer with one chip's share of
the experts, from the configuration's sizes: what ``counts(obs)`` hands the readers
for the ``longcat_flash`` family (the peaks stay in ``benchmark/work.py``).

Counts are what the mathematics needs, whatever implements it (a multiply-add is
two operations; causal attention counts the lower triangle):

* **latent attention** has two forms of the same mathematics, and the count is the
  cheaper one for the shape at hand. Expanded: every context row is expanded per
  head (``2 kv_lora_rank heads (nope + v)`` a row), then 192-wide scores and
  128-wide values a key. Absorbed: the same ``2 kv_lora_rank heads (nope + v)``
  once a QUERY (into the query and out of the output), then 576-wide scores and
  512-wide values a key. A decode step (one query against a long context) counts
  the absorbed form; a prefill from an empty cache the expanded one; a prefill
  behind a cached prefix whichever is less (about equal at a 256-token prefix
  and a 128-token tail, expanded beyond).
* **the expert share**: the router at its full width for every token, and the held
  experts at the expectation of their pairs, ``moe_topk held / (routed + zero)`` a
  token (12 x 16 / 768 = 0.25); identity picks cost nothing.
* **a decode step's bytes**: attention and dense-MLP weights, the router and the
  head once; of the held experts those that at least one live slot picked, at
  their expectation ``held (1 - (1 - moe_topk / (routed + zero)) ** slots)``; the
  live latent rows once.
"""

from __future__ import annotations


def _mla_projection_params(cfg: dict) -> int:
    """One block's matrices that every token passes whatever the attention's form."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return h * qr + qr * H * (nope + rope) + h * (kr + rope) + H * vd * h


def _kv_up_params(cfg: dict) -> int:
    return cfg["kv_lora_rank"] * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def _router_width(cfg: dict) -> int:
    return cfg["n_routed_experts"] + cfg["zero_expert_num"]


def held_pairs_per_token(cfg: dict) -> float:
    return cfg["moe_topk"] * cfg["n_routed_experts_held"] / _router_width(cfg)


def experts_touched(cfg: dict, slots: float) -> float:
    """Expected held experts with at least one pair among ``slots`` tokens."""
    return cfg["n_routed_experts_held"] * (1.0 - (1.0 - cfg["moe_topk"] / _router_width(cfg)) ** slots)


def layer_matmul_params(cfg: dict) -> float:
    """Parameters a token multiplies in one double layer outside the attention's
    own form: both blocks' projections, both dense MLPs, the router, and the held
    experts at the expectation of their pairs."""
    h = cfg["hidden_size"]
    return (2 * _mla_projection_params(cfg) + 2 * 3 * h * cfg["ffn_hidden_size"] + h * _router_width(cfg)
            + held_pairs_per_token(cfg) * _expert_params(cfg))


def layer_weight_params(cfg: dict, experts: float) -> float:
    """Parameters of one double layer read when ``experts`` of the held are touched."""
    h = cfg["hidden_size"]
    return (2 * (_mla_projection_params(cfg) + _kv_up_params(cfg)) + 2 * 3 * h * cfg["ffn_hidden_size"]
            + h * _router_width(cfg) + experts * _expert_params(cfg))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attention_forms(cfg: dict, first: int, last: int) -> dict:
    """Operations of ONE latent block for the query positions first..last-1, each
    attending causally to every earlier position and itself, in both forms."""
    H, kr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    keys = (last * (last + 1) - first * (first + 1)) / 2.0     # sum of (t + 1)
    up = 2.0 * _kv_up_params(cfg)
    return {"expanded": up * last + 2.0 * H * (nope + rope + vd) * keys,
            "absorbed": up * (last - first) + 2.0 * H * (2 * kr + rope) * keys}


def attention_ops(cfg: dict, first: int, last: int) -> float:
    """Both blocks of ONE double layer, each in its cheaper form."""
    return 2.0 * min(attention_forms(cfg, first, last).values())


def forward_ops(cfg: dict, first: int, last: int, with_head_tokens: int) -> float:
    """Forward operations for computing positions first..last-1 of one sequence, the
    output head applied to ``with_head_tokens`` of them."""
    return (cfg["num_layers"] * (2.0 * layer_matmul_params(cfg) * (last - first) + attention_ops(cfg, first, last))
            + 2.0 * head_params(cfg) * with_head_tokens)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One latent row in each of the two blocks of every layer: 9,216 at 4 layers."""
    return 2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize * cfg["num_layers"]


def decode_step_bytes(cfg: dict, live_tokens: float, active_slots: float, itemsize: int = 2) -> dict:
    """The bytes of one decode step by their parts."""
    layers = cfg["num_layers"]
    return {"experts": layers * experts_touched(cfg, active_slots) * _expert_params(cfg) * itemsize,
            "attention_weights": layers * 2 * (_mla_projection_params(cfg) + _kv_up_params(cfg)) * itemsize,
            "dense_mlps": layers * 2 * 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"] * itemsize,
            "router": layers * cfg["hidden_size"] * _router_width(cfg) * itemsize,
            "head": head_params(cfg) * itemsize,
            "latent_rows": live_tokens * kv_bytes_per_token(cfg, itemsize)}


def decode_step_least_s(cfg: dict, live_tokens: float, active_slots: float, pk: dict, itemsize: int = 2) -> float:
    """Least time of one decode step: its bytes once, or its operations (absorbed
    attention over the live rows), whichever takes longer."""
    nbytes = sum(decode_step_bytes(cfg, live_tokens, active_slots, itemsize).values())
    H, kr, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    per_slot = cfg["num_layers"] * (2.0 * layer_matmul_params(cfg) + 2 * 2.0 * _kv_up_params(cfg)) + 2.0 * head_params(cfg)
    ops = per_slot * active_slots + cfg["num_layers"] * 2 * 2.0 * H * (2 * kr + rope) * live_tokens
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["flops_per_s"])
