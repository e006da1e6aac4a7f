"""Operations and bytes of the MiMo-V2-Flash layer with one chip's share of the experts,
from the configuration's sizes: what ``counts(obs)`` hands the readers for the
``mimo_v2`` family (the peaks stay in ``benchmark/work.py``).

Counts are what the mathematics needs, whatever implements it (a multiply-add is two
operations; attention counts each query's keys once):

* **a full layer's** query sees every earlier position and itself; **a window
  layer's** at most ``sliding_window`` of them. Scores are ``head_dim`` wide and values
  ``v_head_dim``: ``2 x heads x (head_dim + v_head_dim)`` operations a (query, key).
* **a dense layer** (``moe_layer_freq`` 0) holds a SwiGLU of ``intermediate_size``;
  **an expert layer** the router at its full width for every token and the held
  experts at the expectation of their pairs, ``num_experts_per_tok held / router
  width`` a token (8 x 8 / 256 = 0.25); no shared expert.
* **a decode step's bytes**: every attention projection, dense MLP, router and the head
  once; of the held experts those at least one live slot picked, at their expectation;
  the full layers' K and V of every live token; the window layers' K and V of each live
  slot's last ``sliding_window - 1`` keys (the new row is an operand, not read).
* **one call of the decode walk** (one layer, every slot): the K and V rows it must
  read (those of :func:`decode_step_bytes`, one layer's part), each slot's query, new
  row and output; its operations those of that layer's attention for one query row a
  slot.
"""

from __future__ import annotations


def _windowed(cfg: dict, i: int) -> bool:
    return bool(cfg["hybrid_layer_pattern"][i])


def attention_shape(cfg: dict, i: int) -> tuple:
    """(heads, kv heads, qk width, v width) of layer ``i``."""
    if _windowed(cfg, i):
        return (cfg["swa_num_attention_heads"], cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                cfg["swa_v_head_dim"])
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["v_head_dim"]


def _layers(cfg: dict, window: bool):
    return [i for i in range(cfg["num_hidden_layers"]) if _windowed(cfg, i) == window]


def _router_width(cfg: dict) -> int:
    """The router's published width; the file's ``n_routed_experts`` counts the experts HELD here."""
    return cfg["published"]["n_routed_experts"]


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _attention_params(cfg: dict, i: int) -> int:
    H, kvh, hd, vd = attention_shape(cfg, i)
    h = cfg["hidden_size"]
    return h * (H * hd + kvh * hd + kvh * vd) + H * vd * h


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def held_pairs_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / _router_width(cfg)


def experts_touched(cfg: dict, slots: float) -> float:
    """Expected held experts with at least one pair among ``slots`` tokens."""
    return cfg["n_routed_experts"] * (1.0 - (1.0 - cfg["num_experts_per_tok"] / _router_width(cfg)) ** slots)


def _sparse(cfg: dict, i: int) -> bool:
    return bool(cfg["moe_layer_freq"][i])


def _always_params(cfg: dict) -> float:
    """Parameters every token multiplies outside the routed experts: attention
    projections, the dense MLPs and the routers."""
    h = cfg["hidden_size"]
    out = 0.0
    for i in range(cfg["num_hidden_layers"]):
        out += _attention_params(cfg, i)
        out += h * _router_width(cfg) if _sparse(cfg, i) else 3 * h * cfg["intermediate_size"]
    return out


def matmul_params(cfg: dict) -> float:
    """:func:`_always_params` and the held experts at the expectation of their pairs."""
    sparse = sum(_sparse(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return _always_params(cfg) + sparse * held_pairs_per_token(cfg) * _expert_params(cfg)


def _keys(cfg: dict, i: int, first: int, last: int) -> float:
    """Sum over the query positions first..last-1 of the keys each sees in layer ``i``."""
    full = (last * (last + 1) - first * (first + 1)) / 2.0
    if not _windowed(cfg, i):
        return full
    w = cfg["sliding_window"]
    if last <= w:
        return full
    lo = max(first, w)                      # from here on every query sees w keys
    head = (lo * (lo + 1) - first * (first + 1)) / 2.0 if first < lo else 0.0
    return head + (last - lo) * w


def _pair_ops(cfg: dict, i: int) -> float:
    H, _, hd, vd = attention_shape(cfg, i)
    return 2.0 * H * (hd + vd)


def attention_ops(cfg: dict, first: int, last: int) -> float:
    """Every layer's attention for the query positions first..last-1."""
    return sum(_pair_ops(cfg, i) * _keys(cfg, i, first, last) for i in range(cfg["num_hidden_layers"]))


def forward_ops(cfg: dict, first: int, last: int, with_head_tokens: int) -> float:
    """Forward operations for computing positions first..last-1 of one sequence, the
    output head applied to ``with_head_tokens`` of them."""
    return (2.0 * matmul_params(cfg) * (last - first) + attention_ops(cfg, first, last)
            + 2.0 * head_params(cfg) * with_head_tokens)


def kv_row_bytes(cfg: dict, i: int, itemsize: int = 2) -> int:
    """One token's K and V rows in layer ``i``."""
    _, kvh, hd, vd = attention_shape(cfg, i)
    return kvh * (hd + vd) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """The full layers' rows of one token (what the full class of pages holds a token)."""
    return sum(kv_row_bytes(cfg, i, itemsize) for i in _layers(cfg, False))


def _window_keys(cfg: dict, live_tokens: float, active_slots: float) -> float:
    """Keys a window layer's walk reads over the live slots: each slot's last
    ``sliding_window - 1`` (or all it holds, where fewer)."""
    if not active_slots:
        return 0.0
    return active_slots * min(cfg["sliding_window"] - 1, live_tokens / active_slots)


def decode_step_bytes(cfg: dict, live_tokens: float, active_slots: float, itemsize: int = 2) -> dict:
    """The bytes of one decode step by their parts."""
    layers, h = range(cfg["num_hidden_layers"]), cfg["hidden_size"]
    sparse = sum(_sparse(cfg, i) for i in layers)
    return {"experts": sparse * experts_touched(cfg, active_slots) * _expert_params(cfg) * itemsize,
            "attention_weights": sum(_attention_params(cfg, i) for i in layers) * itemsize,
            "dense_mlps": (len(layers) - sparse) * 3 * h * cfg["intermediate_size"] * itemsize,
            "router": sparse * h * _router_width(cfg) * itemsize,
            "head": head_params(cfg) * itemsize,
            "full_kv": live_tokens * kv_bytes_per_token(cfg, itemsize),
            "window_kv": _window_keys(cfg, live_tokens, active_slots)
            * sum(kv_row_bytes(cfg, i, itemsize) for i in _layers(cfg, True))}


def decode_step_least_s(cfg: dict, live_tokens: float, active_slots: float, pk: dict, itemsize: int = 2) -> float:
    """Least time of one decode step: its bytes once, or its operations, whichever takes longer."""
    nbytes = sum(decode_step_bytes(cfg, live_tokens, active_slots, itemsize).values())
    ops = (2.0 * (matmul_params(cfg) + head_params(cfg)) * active_slots
           + sum(_pair_ops(cfg, i) for i in _layers(cfg, False)) * live_tokens
           + sum(_pair_ops(cfg, i) for i in _layers(cfg, True)) * _window_keys(cfg, live_tokens, active_slots))
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["flops_per_s"])


def decode_walk_counts(cfg: dict, window: bool, live_tokens: float, active_slots: float, itemsize: int = 2):
    """(operations, bytes) of ONE call of the decode walk in a window layer (``window``)
    or a full one, every live slot: the keys it must read (every live token's, or each
    slot's window), each slot's query, new row and output."""
    i = _layers(cfg, window)[0]
    H, kvh, hd, vd = attention_shape(cfg, i)
    keys = _window_keys(cfg, live_tokens, active_slots) if window else live_tokens
    nbytes = keys * kv_row_bytes(cfg, i, itemsize) + active_slots * (H * (hd + vd) + kvh * (hd + vd)) * itemsize
    return _pair_ops(cfg, i) * (keys + active_slots), nbytes
