"""Builder of the ``longcat_flash`` family: puts a configuration file's LongCat-Flash
language model, one chip's share of its experts, into the program under test
(``models.longcat_flash`` behind the default ``inference.serving.ServingEngine``)
with weights made on the device from ``--seed``. The family is served, not trained:
there is no ``build_train``, and a train kind on it raises at once.
"""

from __future__ import annotations

import importlib

import jax.numpy as jnp

from benchmark.families import longcat_flash_work as work  # noqa: F401  (``run.py`` hands it to the readers)
from benchmark.families.mistral import free_serve, stated_dtype  # noqa: F401  (an engine is freed, a dtype named, alike)

reference = importlib.import_module("benchmark.families.longcat_flash_reference")

# the configuration file's keys that the program's config takes under the same name
_KEYS = ("vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
         "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim",
         "mla_scale_q_lora", "mla_scale_kv_lora", "routed_scaling_factor", "n_routed_experts", "zero_expert_num",
         "moe_topk", "experts_first", "n_routed_experts_held", "max_position_embeddings", "rms_norm_eps", "rope_theta")


def __getattr__(name):
    if name in ("build_train", "train_reference"):
        raise AttributeError(f"the longcat_flash family has no {name}: it is served, not trained (16 bytes a "
                             "parameter do not fit one layer of it on a chip, and the expert share has no exchange)")
    raise AttributeError(name)


def _model(cfg: dict, seed: int):
    """The program's model object holding the seed's weights."""
    from paddlepaddle_tpu.models import LongcatFlashConfig, LongcatFlashForCausalLM
    from paddlepaddle_tpu.nn import initializer

    if cfg.get("attention_method", "MLA") != "MLA" or cfg.get("zero_expert_type", "identity") != "identity":
        raise ValueError("models.longcat_flash computes latent attention and identity zero-compute experts only")
    # the eager initialiser's values are overwritten at once: zeros cost no float32 copy of 5 GB of experts
    initializer.set_global_initializer(initializer.Constant(0.0))
    try:
        model = LongcatFlashForCausalLM(LongcatFlashConfig(
            **{k: cfg[k] for k in _KEYS}, initializer_range=reference.INIT_STD, dtype=cfg["torch_dtype"]))
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
    flushed and no admission bucket compiled: the cell's own traffic warms what it uses."""
    from paddlepaddle_tpu.inference.serving import ServingEngine

    engine = ServingEngine(_model(cfg, seed), **cfg["engine"])
    engine.start()
    engine._engine.warmup(keys=[])
    return engine


def serve_reference(cfg: dict, seed: int, sequences, first_new, control=""):
    return reference.serve_reference(cfg, seed, sequences, first_new, stated_dtype(cfg), control=control)
