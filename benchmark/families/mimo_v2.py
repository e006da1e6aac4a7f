"""Builder of the ``mimo_v2`` family: puts a configuration file's MiMo-V2-Flash decoder,
one chip's share of its experts, into the program under test (``models.mimo_v2`` behind
the default ``inference.serving.ServingEngine``) with weights made on the device from
``--seed``. The family is served, not trained: there is no ``build_train``, and a train
kind on it raises at once.
"""

from __future__ import annotations

import importlib

import jax.numpy as jnp

from benchmark.families import mimo_v2_work as work  # noqa: F401  (``run.py`` hands it to the readers)
from benchmark.families.mistral import free_serve, stated_dtype  # noqa: F401  (an engine is freed, a dtype named, alike)

reference = importlib.import_module("benchmark.families.mimo_v2_reference")

# the configuration file's keys that the program's config takes under the same name (the file's ``n_routed_experts``
# counts the experts held here, the router's width stands under ``published``: ``reference.router_width``)
_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim", "swa_num_attention_heads",
         "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim", "sliding_window", "rope_theta",
         "swa_rope_theta", "partial_rotary_factor", "attention_value_scale", "add_swa_attention_sink_bias",
         "add_full_attention_sink_bias", "hybrid_layer_pattern", "moe_layer_freq", "num_experts_per_tok",
         "routed_scaling_factor", "experts_first", "max_position_embeddings", "layernorm_epsilon")
# what the program computes one way only: a configuration that states otherwise is refused
_FIXED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
          "hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False, "n_shared_experts": None}


def __getattr__(name):
    if name in ("build_train", "train_reference"):
        raise AttributeError(f"the mimo_v2 family has no {name}: it is served, not trained (16 bytes a parameter "
                             "do not fit two expert layers of it on a chip, and the expert share has no exchange)")
    raise AttributeError(name)


def _model(cfg: dict, seed: int):
    """The program's model object holding the seed's weights."""
    from paddlepaddle_tpu.models import MimoV2Config, MimoV2ForCausalLM
    from paddlepaddle_tpu.nn import initializer

    wrong = {k: cfg[k] for k, v in _FIXED.items() if k in cfg and cfg[k] != v}
    if cfg.get("sliding_window_size", cfg["sliding_window"]) != cfg["sliding_window"]:
        wrong["sliding_window_size"] = cfg["sliding_window_size"]
    if wrong:
        raise ValueError(f"models.mimo_v2 computes {_FIXED} only; the configuration states {wrong}")
    # the eager initialiser's values are overwritten at once: zeros cost no float32 copy of the experts
    initializer.set_global_initializer(initializer.Constant(0.0))
    try:
        model = MimoV2ForCausalLM(MimoV2Config(
            **{k: cfg[k] for k in _KEYS}, n_routed_experts=reference.router_width(cfg),
            n_routed_experts_held=cfg["n_routed_experts"], initializer_range=reference.INIT_STD,
            sink_init=reference.sink_init(cfg), dtype=cfg["torch_dtype"]))
    finally:
        initializer.set_global_initializer(None)
    handles = model.raw_state()
    specs = reference.leaf_specs(cfg)
    names = {n for n, _ in model.named_parameters()}
    if names != {n for n, _, _ in specs}:
        raise ValueError(f"weight names differ between the program and the spec: "
                         f"{sorted(names ^ {n for n, _, _ in specs})[:4]}")
    for name, shape, _ in specs:
        if tuple(handles[name].shape) != tuple(shape):
            raise ValueError(f"{name}: program {handles[name].shape} != spec {shape}")
        handles[name]._replace_data(jnp.zeros((), handles[name].dtype))     # or set-up would hold two models
    for name, value in reference.make_weights(cfg, specs, seed, stated_dtype(cfg)).items():
        handles[name]._replace_data(value)
    return model


def build_serve(cfg: dict, seed: int):
    """The default ``ServingEngine`` over the seed's weights, started, with the per-slot bookkeeping operations
    flushed; the cell's own traffic warms the programs it uses (one request of each prompt bucket)."""
    from paddlepaddle_tpu.inference.serving import ServingEngine

    engine = ServingEngine(_model(cfg, seed), **cfg["engine"])
    engine.start()
    engine._engine.warmup(keys=[])
    return engine


def serve_reference(cfg: dict, seed: int, sequences, first_new, control=""):
    return reference.serve_reference(cfg, seed, sequences, first_new, stated_dtype(cfg), control=control)
