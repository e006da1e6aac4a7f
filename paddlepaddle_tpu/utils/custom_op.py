"""Public custom-op extension API — register an op with autograd + SPMD.

Reference surface: python/paddle/utils/cpp_extension/ (load/setup compile a
C++ kernel and register it with the framework) and
paddle/phi/api/ext/op_meta_info.h (forward/backward/infer-meta
registration). TPU-native redesign: the "kernel language" of this framework
is jnp/lax/Pallas, so an extension op is a PURE FUNCTION of jax arrays — no
compiler toolchain, no ABI. ``register_op`` supplies the three integrations
the reference's registry provides:

* dispatcher routing — the returned callable goes through ``apply_op``, so
  the eager autograd tape, AMP cast hooks, NaN checks, and static-graph
  capture all see the op under its registered name;
* autograd — an optional ``backward`` becomes a ``jax.custom_vjp`` rule
  (otherwise jax differentiates the forward's body);
* SPMD — an optional ``sharding_rule`` (in_specs, out_specs) gives the op
  an explicit ``shard_map`` form over the active mesh via ``.shard()``,
  for bodies that carry their own collectives; ops built from ordinary
  jnp/Pallas code need none (GSPMD propagates through them).

Walkthrough: docs/custom_ops.md registers the fused rms-norm from
``incubate.nn.functional`` as if it lived outside the package, and
tests/test_custom_op.py exercises eager tape, jit, grad, and a sharded
train step against it.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax

from ..core.dispatch import apply_op

_REGISTRY: Dict[str, "CustomOp"] = {}


class CustomOp:
    """A registered op: call it like a function; ``.shard(mesh)`` returns
    the explicit-SPMD form when a sharding_rule was given."""

    def __init__(self, name: str, fn: Callable,
                 backward: Optional[Callable] = None,
                 sharding_rule: Optional[Tuple] = None):
        self.name = name
        self.backward = backward
        self.sharding_rule = sharding_rule
        if backward is not None:
            core = jax.custom_vjp(fn)

            def fwd(*args):
                out = fn(*args)
                return out, (args, out)

            def bwd(res, ct):
                args, out = res
                grads = backward(ct, *args, out=out)
                if not isinstance(grads, (tuple, list)):
                    grads = (grads,)
                if len(grads) != len(args):
                    raise ValueError(
                        f"custom op {name!r}: backward returned "
                        f"{len(grads)} gradients for {len(args)} inputs")
                return tuple(grads)

            core.defvjp(fwd, bwd)
            self._core = core
        else:
            self._core = fn

    def __call__(self, *args, **kwargs):
        return apply_op(self._core, *args, op_name=self.name, **kwargs)

    def raw(self, *args, **kwargs):
        """The unwrapped jax-level function (for composing inside other
        traced code without Tensor wrapping)."""
        return self._core(*args, **kwargs)

    def shard(self, mesh=None):
        """shard_map-wrapped form using the registered (in_specs, out_specs)
        over ``mesh`` (default: the active mesh) — for bodies containing
        their own collectives (psum/all_gather/...)."""
        if self.sharding_rule is None:
            raise ValueError(
                f"custom op {self.name!r} was registered without a "
                "sharding_rule; plain calls already propagate GSPMD "
                "shardings")
        from ..parallel.mpu import _current_mesh

        mesh = mesh or _current_mesh()
        if mesh is None:
            raise ValueError("no active mesh: enter `with mesh:` or pass one")
        in_specs, out_specs = self.sharding_rule
        inner = jax.shard_map(self._core, mesh=mesh,
                              in_specs=in_specs, out_specs=out_specs)

        def call(*args, **kwargs):
            return apply_op(inner, *args, op_name=f"{self.name}_sharded",
                            **kwargs)

        return call


def register_op(name: str, fn: Callable, backward: Optional[Callable] = None,
                sharding_rule: Optional[Tuple] = None,
                override: bool = False) -> CustomOp:
    """Register a custom op (reference role: utils/cpp_extension load()).

    Args:
        name: registry key; also the op name autograd/profiling see.
        fn: pure function of jax arrays -> array or pytree of arrays. Any
            jnp/lax/Pallas code works (pl.pallas_call bodies included).
        backward: optional VJP rule ``backward(ct, *inputs, out=...) ->
            tuple of input cotangents`` (None entries for non-diff inputs).
            Without it jax differentiates fn's body.
        sharding_rule: optional ``(in_specs, out_specs)`` PartitionSpecs
            enabling ``op.shard(mesh)`` for bodies with explicit
            collectives.
        override: allow replacing an existing registration.

    Returns the CustomOp (also retrievable via ``get_op(name)``).
    """
    if not callable(fn):
        raise TypeError(f"fn for custom op {name!r} must be callable")
    if name in _REGISTRY and not override:
        raise ValueError(f"custom op {name!r} already registered "
                         "(override=True to replace)")
    op = CustomOp(name, fn, backward=backward, sharding_rule=sharding_rule)
    _REGISTRY[name] = op
    return op


def get_op(name: str) -> CustomOp:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no custom op {name!r}; registered: {sorted(_REGISTRY)}") from None


def registered_ops():
    return dict(_REGISTRY)
