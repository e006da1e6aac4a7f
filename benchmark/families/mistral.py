"""Builder of the ``mistral`` family: puts a configuration file's Mistral decoder
into the program under test (``models.llama`` through ``jit.train.TrainStep``
or ``inference.serving.ServingEngine``) with weights made on the device from
``--seed``. A family with another block brings a builder, a reference and its
counts of operations and bytes (``work``) as new files beside this one;
``run.py`` finds them by the configuration's ``family`` key.
"""

from __future__ import annotations

import gc
import importlib

import jax
import jax.numpy as jnp

from benchmark import work  # noqa: F401  (the dense GQA decoder's counts are this family's; ``run.py`` hands them to the readers)

reference = importlib.import_module("benchmark.families.mistral_reference")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def stated_dtype(cfg: dict):
    return _DTYPES[cfg["torch_dtype"]]


def _model(cfg: dict, seed: int):
    """The program's model object holding the seed's weights."""
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("models.llama derives head_dim from hidden_size / heads")
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"]))
    handles = model.raw_state()
    specs = reference.leaf_specs(cfg)
    names = {n for n, _ in model.named_parameters()}
    if names != {n for n, _, _ in specs}:
        raise ValueError(f"weight names differ between the program and the spec: "
                         f"{sorted(names ^ {n for n, _, _ in specs})[:4]}")
    for name, shape, _ in specs:
        if tuple(handles[name].shape) != tuple(shape):
            raise ValueError(f"{name}: program {handles[name].shape} != spec {shape}")
        # the eager initialiser's weights go first, or set-up would hold two models
        handles[name]._replace_data(jnp.zeros((), handles[name].dtype))
    for name, value in reference.make_weights(specs, seed, stated_dtype(cfg)).items():
        handles[name]._replace_data(value)
    return model


class TrainProgram:
    """The compiled step with its state: the one object that set-up drives
    through its first steps and then hands to the window."""

    def __init__(self, cfg: dict, seed: int):
        from paddlepaddle_tpu.jit.train import TrainStep
        from paddlepaddle_tpu.optimizer import AdamW

        hp = cfg["train"]
        self.cfg, self.hp, self.seed = cfg, hp, seed
        model = _model(cfg, seed)
        opt = AdamW(learning_rate=hp["lr"], beta1=hp["beta1"], beta2=hp["beta2"],
                    epsilon=hp["epsilon"], weight_decay=hp["weight_decay"],
                    parameters=model.parameters(), multi_precision=True)
        self.step = TrainStep(model, opt, lambda m, ids, labels: m(ids, labels=labels))
        specs = reference.leaf_specs(cfg)
        dtype = stated_dtype(cfg)
        self._grad_norms = jax.jit(lambda slots: {
            n: jnp.sqrt(jnp.sum(jnp.square(s["moment1"]))) / (1.0 - hp["beta1"])
            for n, s in slots.items()})
        self._change_norms = jax.jit(lambda master, key: {
            n: jnp.sqrt(jnp.sum(jnp.square(
                master[n] - reference.init_leaf(key, n, s, k, dtype).astype(jnp.float32))))
            for n, s, k in specs})

    def __call__(self, ids):
        """One step on ``ids`` [rows, length]; the loss stays on the device."""
        return self.step(ids, ids)

    def grad_norms(self) -> dict:
        """Each leaf's gradient norm as the optimizer got it, from the first
        moment after ONE step (m1 = (1 - beta1) * g1)."""
        return {n: float(x) for n, x in
                self._grad_norms(self.step.opt_state["slots"]).items()}

    def change_norms(self) -> dict:
        """Norm of each master weight's change since the seed's weights."""
        return {n: float(x) for n, x in self._change_norms(
            self.step.opt_state["master"], reference.seed_key(self.seed)).items()}

    def free(self):
        self.step.params = self.step.opt_state = None
        for p in self.step.model.parameters():
            p._replace_data(jnp.zeros((), p.dtype))
        self.step = None
        gc.collect()


def build_train(cfg: dict, seed: int) -> TrainProgram:
    return TrainProgram(cfg, seed)


def build_serve(cfg: dict, seed: int):
    """The default ``ServingEngine`` over the seed's weights, started, with the
    per-slot bookkeeping operations flushed and no admission bucket compiled:
    the cell's own traffic warms what it uses."""
    from paddlepaddle_tpu.inference.serving import ServingEngine

    engine = ServingEngine(_model(cfg, seed), **cfg["engine"])
    engine.start()
    engine._engine.warmup(keys=[])
    return engine


def free_serve(engine):
    engine.stop()
    model = engine.model
    inner = engine._engine
    inner.params = inner.caches = None
    engine._engine = engine.model = None
    for p in model.parameters():
        p._replace_data(jnp.zeros((), p.dtype))
    gc.collect()


def train_reference(cfg: dict, seed: int, batches, precision="f32", half_batch=False):
    return reference.train_reference(cfg, seed, batches, cfg["train"], stated_dtype(cfg),
                                     precision=precision, half_batch=half_batch)


def serve_reference(cfg: dict, seed: int, sequences, first_new, control=""):
    return reference.serve_reference(cfg, seed, sequences, first_new, stated_dtype(cfg),
                                     control=control)
