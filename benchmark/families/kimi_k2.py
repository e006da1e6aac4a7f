"""Builder of the ``kimi_k2`` family: puts a configuration file's Kimi-K2 decoder, one
chip's share of its experts, into the program under test (``models.kimi_k2`` behind the
default ``inference.serving.ServingEngine``) with weights made on the device from
``--seed``. The family is served, not trained: there is no ``build_train``, and a train
kind on it raises at once.
"""

from __future__ import annotations

import importlib

import jax.numpy as jnp

from benchmark.families import kimi_k2_work as work  # noqa: F401  (``run.py`` hands it to the readers)
from benchmark.families.mistral import free_serve, stated_dtype  # noqa: F401  (an engine is freed, a dtype named, alike)

reference = importlib.import_module("benchmark.families.kimi_k2_reference")

# the configuration file's keys that the program's config takes under the same name (the file's ``n_routed_experts``
# counts the experts held here, the router's width stands under ``published``: ``reference.router_width``)
_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
         "first_k_dense_replace", "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
         "qk_nope_head_dim", "v_head_dim", "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
         "experts_first", "max_position_embeddings", "rms_norm_eps", "rope_theta", "rope_scaling")
# what the program computes one way only: a configuration that states otherwise is refused
_FIXED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
          "hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1, "tie_word_embeddings": False}


def __getattr__(name):
    if name in ("build_train", "train_reference"):
        raise AttributeError(f"the kimi_k2 family has no {name}: it is served, not trained (16 bytes a parameter "
                             "do not fit two expert layers of it on a chip, and the expert share has no exchange)")
    raise AttributeError(name)


def _model(cfg: dict, seed: int):
    """The program's model object holding the seed's weights."""
    from paddlepaddle_tpu.models import KimiK2Config, KimiK2ForCausalLM
    from paddlepaddle_tpu.nn import initializer

    wrong = {k: cfg[k] for k, v in _FIXED.items() if k in cfg and cfg[k] != v}
    if wrong:
        raise ValueError(f"models.kimi_k2 computes {_FIXED} only; the configuration states {wrong}")
    # the eager initialiser's values are overwritten at once: zeros cost no float32 copy of the experts
    initializer.set_global_initializer(initializer.Constant(0.0))
    try:
        model = KimiK2ForCausalLM(KimiK2Config(
            **{k: cfg[k] for k in _KEYS}, n_routed_experts=reference.router_width(cfg),
            n_routed_experts_held=cfg["n_routed_experts"], initializer_range=reference.INIT_STD, dtype=cfg["torch_dtype"]))
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
    for name, value in reference.make_weights(specs, seed, stated_dtype(cfg)).items():
        handles[name]._replace_data(value)
    return model


def build_serve(cfg: dict, seed: int):
    """The default ``ServingEngine`` over the seed's weights, started, with the per-slot bookkeeping operations
    flushed. The cell's own traffic warms what it uses, except the whole-prompt admissions the configuration lists
    under ``engine_facts.admit_buckets``, which are compiled here (and not run): the kind's warm-up sends a document's
    classes as ONE session, so the second question bucket of a first turn finds its document cached and never
    runs its whole-prompt program, which would then compile inside the ramp (minutes, on a cold cache)."""
    from paddlepaddle_tpu.inference import compile_plan
    from paddlepaddle_tpu.inference.serving import ServingEngine

    engine = ServingEngine(_model(cfg, seed), **cfg["engine"])
    engine.start()
    engine._engine.warmup(keys=[compile_plan.admit_key(b) for b in cfg["engine_facts"].get("admit_buckets", ())])
    return engine


def serve_reference(cfg: dict, seed: int, sequences, first_new, control=""):
    return reference.serve_reference(cfg, seed, sequences, first_new, stated_dtype(cfg), control=control)
