"""GSPMD sharded training step — the multi-chip hot path.

This is the TPU-native replacement for the whole fleet hybrid-parallel engine
(reference: fleet.distributed_model wrap + HybridParallelOptimizer +
EagerReducer allreduce, python/paddle/distributed/fleet/): ONE jitted
function over a ``jax.sharding.Mesh`` whose in/out shardings express
DP (batch axis), FSDP/ZeRO-3 (param + optimizer-state sharding), TP (matmul
weight sharding) and SP (sequence-dim activation sharding). XLA inserts the
all-gathers / reduce-scatters / all-reduces over ICI that the reference issues
manually through NCCL process groups.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import random as prandom
from ..core.dispatch import unwrap
from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..optimizer.optimizer import Optimizer


def _as_jax_mesh(mesh) -> Mesh:
    if isinstance(mesh, Mesh):
        return mesh
    return mesh.to_jax()  # ProcessMesh


def _fit_spec(spec: Sequence[Optional[str]], shape, mesh: Mesh) -> P:
    """Drop axes that the mesh lacks or that don't divide the dim evenly.

    Mirrors the reference's dims_mapping validity rule
    (paddle/phi/core/distributed/auto_parallel/dist_attr.h: dims_mapping entry
    is -1 when a dim can't shard) so one rule table serves any mesh/model size.
    """
    out = []
    for i, ax in enumerate(spec):
        if i >= len(shape):
            break
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, (tuple, list)) else (ax,)
        axes = tuple(a for a in axes if a in mesh.shape)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if axes and size > 1 and shape[i] % size == 0:
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def match_sharding_rules(name: str, shape, rules, mesh: Mesh) -> P:
    for pattern, spec in rules:
        if re.match(pattern, name):
            return _fit_spec(spec, shape, mesh)
    return P()


def param_shardings(params: Dict[str, jax.Array], rules, mesh,
                    handles: Optional[dict] = None) -> Dict[str, NamedSharding]:
    """Per-param NamedSharding: an explicit ``Parameter.dist_spec`` (set by
    mpu/TP layers) wins over the regex rule table."""
    mesh = _as_jax_mesh(mesh)
    out = {}
    for n, p in params.items():
        spec = None
        h = handles.get(n) if handles else None
        if h is not None and getattr(h, "dist_spec", None) is not None:
            spec = _fit_spec(h.dist_spec, p.shape, mesh)
        if spec is None:
            spec = match_sharding_rules(n, p.shape, rules, mesh)
        out[n] = NamedSharding(mesh, spec)
    return out


class ShardedTrainStep:
    """pjit-compiled (params, opt_state, batch) -> (params', opt_state', loss).

    Args:
        model/optimizer/loss_fn: as jit.train.TrainStep.
        mesh: ProcessMesh or jax Mesh with named axes (e.g. dp/fsdp/tp/sp).
        rules: [(name_regex, spec_tuple)] placement table, e.g. from
            models.llama.llama_sharding_rules().
        data_axes: mesh axes the batch dim is sharded over (DP+FSDP together,
            the reference's dp×sharding product group).
        seq_axis: optional mesh axis to shard the sequence dim of the batch
            (SP/context parallelism's data layout).
    """

    def __init__(self, model: Layer, optimizer: Optimizer, loss_fn: Callable,
                 mesh=None, rules=None, data_axes=("dp", "fsdp"),
                 seq_axis: Optional[str] = None, donate: bool = True,
                 plan=None):
        if plan is not None:
            # a distributed.ShardingPlan carries mesh + rules + data axes
            # in one object; explicit args win where given
            mesh = mesh if mesh is not None else plan.mesh
            rules = rules if rules is not None else plan.rules
            if data_axes == ("dp", "fsdp") and plan.data_axes:
                data_axes = plan.data_axes
        if mesh is None or rules is None:
            raise ValueError("ShardedTrainStep needs mesh+rules or plan=")
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = _as_jax_mesh(mesh)
        self.rules = list(rules)

        params = model.functional_state(trainable_only=True)
        self.buffers = {k: v for k, v in model.functional_state().items()
                        if k not in params}
        self._param_sh = param_shardings(params, self.rules, self.mesh,
                                         handles=model.raw_state())
        repl = NamedSharding(self.mesh, P())

        # place params / buffers / optimizer state on the mesh (jnp.copy first:
        # device_put to an identical sharding can alias, and step params are
        # donated — the eager model's buffers must stay alive)
        self.params = {n: jax.device_put(jnp.copy(p), self._param_sh[n])
                       for n, p in params.items()}
        self.buffers = {n: jax.device_put(b, repl) for n, b in self.buffers.items()}
        opt_state = optimizer.init_state(self.params)
        self._opt_sh = self._opt_state_shardings(opt_state, repl)
        self.opt_state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), opt_state, self._opt_sh)

        batch_axes = tuple(a for a in data_axes if a in self.mesh.shape)
        self._batch_dim_spec = batch_axes if len(batch_axes) > 1 else (
            batch_axes[0] if batch_axes else None)
        self._seq_axis = seq_axis if (seq_axis in self.mesh.shape if seq_axis else False) else None

        donate_argnums = (0, 2) if donate else ()
        self._step = jax.jit(
            self._step_impl,
            in_shardings=(self._param_sh, None, self._opt_sh, None, repl, repl),
            out_shardings=(self._param_sh, self._opt_sh, repl),
            donate_argnums=donate_argnums,
        )
        self._step_count = 0

    def _opt_state_shardings(self, opt_state, repl):
        """Slots/master shard like their parameter (ZeRO: optimizer state is
        sharded wherever the param is); scalars replicated."""

        def like_param(name):
            def f(a):
                if a.shape == tuple(self.params[name].shape):
                    return self._param_sh[name]
                return repl
            return f

        return {
            "slots": {n: jax.tree_util.tree_map(like_param(n), s)
                      for n, s in opt_state["slots"].items()},
            "master": {n: (like_param(n)(m) if m is not None else None)
                       for n, m in opt_state["master"].items()},
            "step": repl,
        }

    def _batch_sharding(self, arr):
        spec = [self._batch_dim_spec]
        if self._seq_axis is not None and arr.ndim > 1:
            spec.append(self._seq_axis)
        return NamedSharding(self.mesh, _fit_spec(spec, arr.shape, self.mesh))

    def _step_impl(self, params, buffers, opt_state, batch, key, lr):
        from ..core import autograd as _ag

        def loss_of(p):
            # grads come from the outer jax.value_and_grad; the eager GradNode
            # tape is skipped (see jit/train.py).
            with _ag.no_grad(), prandom.key_scope(key):
                state = dict(p)
                state.update(buffers)
                with self.model.bind_state(state):
                    loss = self.loss_fn(self.model, *batch)
            return unwrap(loss)

        # tell the Pallas kernels inside which mesh they are traced for
        # (ops/kernels.partition_over): GSPMD cannot split a Mosaic call
        from ..ops.kernels import partition_over

        with partition_over(self.mesh, self._batch_dim_spec):
            loss, grads = jax.value_and_grad(loss_of)(params)
        new_params, new_opt = self.optimizer.apply(grads, opt_state, params, lr=lr)
        return new_params, new_opt, loss

    def __call__(self, *batch):
        batch_arrays = tuple(
            jax.device_put(
                b._data if isinstance(b, Tensor) else jnp.asarray(b),
                self._batch_sharding(b._data if isinstance(b, Tensor) else jnp.asarray(b)))
            for b in batch
        )
        key = prandom.next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        # enter the mesh context so activation sharding constraints inside
        # layer code (parallel.mpu._constraint) resolve axis names at trace
        with self.mesh:
            self.params, self.opt_state, loss = self._step(
                self.params, self.buffers, self.opt_state, batch_arrays, key, lr)
        self._step_count += 1
        return Tensor._from_data(loss)

    def sync_to_model(self):
        # copies: step params are donated on the next __call__ (see __init__)
        handles = self.model.raw_state()
        for name, val in self.params.items():
            if name in handles:
                handles[name]._replace_data(jnp.copy(val))
