"""First-class sharding plans — named mesh axes + per-layer partition rules.

Reference surface: the auto-parallel distribution layer (PAPER.md L6 —
``ProcessMesh`` paddle/phi/core/distributed/auto_parallel/process_mesh.h:34,
``DistTensor`` dist_tensor.h:39, the SPMD rule tables under
paddle/phi/infermeta/spmd_rules/ and the reshard functions). The reference
attaches a dims_mapping to every tensor and runs rule-driven reshard passes;
the TPU-native design is ONE explicit plan object:

* a :class:`~.mesh.ProcessMesh` with NAMED axes (``"dp"`` data parallel,
  ``"mp"`` tensor/model parallel, plus ``"fsdp"``/``"ep"``/``"sp"`` where a
  strategy needs them) — parsed from a compact ``"dp2mp4"`` spec string or
  given directly;
* a per-layer PartitionSpec RULE TABLE (name-regex → spec tuple): attention
  heads and MLP hidden sharded on ``"mp"``, norms and embeddings explicitly
  replicated — the plan analogue of the reference's per-layer
  ColumnParallel/RowParallel markup (fleet/layers/mpu/mp_layers.py:336,543);
* ``plan.shard(params)`` placing a model-zoo pytree on the mesh (including
  :class:`~...nn.quant.qweight.QuantizedWeight` int8 leaves — the int8 ``q``
  and its scales shard TOGETHER, so a tensor-parallel decode reads only its
  own weight shard), ``plan.constrain`` for activation
  ``with_sharding_constraint``, and ``plan.shard_kv`` for the serving
  engine's KV pools (kv heads over ``"mp"``);
* a pjit-vs-shard_map COMPILE PATH (:meth:`ShardingPlan.compile`): explicit
  model-parallel specs prefer ``jax.jit`` with in/out shardings (pjit — the
  compiler partitions and inserts ICI collectives), a pure data-parallel
  plan takes the ``shard_map``-wrapped jit path (map-style per-device
  execution with explicit collectives, and no GSPMD partitioner pass to
  second-guess a trivially-replicated program).

Everything here is testable on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(tests/test_shard_plan.py; tools/run_tier1.sh).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import ProcessMesh

_SPEC_TOKEN = re.compile(r"([a-z_]+?)(\d+)")


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """``"dp2mp4"`` (optionally ``"dp2xmp4"``) → ``{"dp": 2, "mp": 4}``.

    Axis order in the string IS the mesh axis order (majorest first, the
    jax convention: the last axis is the fastest-varying — put ``mp``
    last so tensor-parallel peers are ICI neighbors)."""
    # 'x' is a separator ONLY between a size and the next axis name
    # ("dp2xmp4"); stripping it anywhere else would let typos like
    # "dp2x4" silently parse as a different mesh ({"dp": 24})
    s = re.sub(r"(?<=\d)x(?=[a-z])", "", spec.strip().lower())
    out: Dict[str, int] = {}
    pos = 0
    for m in _SPEC_TOKEN.finditer(s):
        if m.start() != pos:
            break
        name, size = m.group(1), int(m.group(2))
        if name == "x":
            # 'x' is the separator; an axis literally named "x" is a typo
            # ("dp2x4" = a forgotten second axis name), not a mesh
            raise ValueError(
                f"mesh spec {spec!r}: 'x' is the axis separator, not an "
                "axis name — did you drop an axis name after it?")
        if name in out:
            raise ValueError(f"mesh spec {spec!r}: duplicate axis {name!r}")
        if size < 1:
            raise ValueError(f"mesh spec {spec!r}: axis {name!r} size must "
                             f"be >= 1, got {size}")
        out[name] = size
        pos = m.end()
    if not out or pos != len(s):
        raise ValueError(
            f"mesh spec {spec!r} is not of the form '<axis><n>…' "
            "(e.g. 'dp2mp4', 'dp2ep4', 'mp2')")
    return out


def mesh_from_spec(spec) -> ProcessMesh:
    """Build a ProcessMesh from a ``"dp2mp4"`` string (or pass a
    ProcessMesh through). Raises when the spec needs more devices than
    the platform has — the caller decides whether to skip or force a
    host-device platform."""
    if isinstance(spec, ProcessMesh):
        return spec
    axes = parse_mesh_spec(spec)
    n = int(np.prod(list(axes.values())))
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"mesh {spec!r} needs {n} devices, only {avail} available "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "for CPU testing)")
    return ProcessMesh(shape=list(axes.values()),
                       dim_names=list(axes.keys()))


# -- rule tables -------------------------------------------------------------

def tp_decode_rules(mp_axis: str = "mp") -> List[Tuple[str, tuple]]:
    """Serving (tensor-parallel decode) placement table for llama-family
    names: attention q/k/v and MLP gate/up COLUMN-parallel on ``mp`` (heads
    / hidden out-dim sharded), o/down ROW-parallel (the contracted dim
    sharded — XLA inserts the all-reduce), lm_head vocab-sharded, and the
    REPLICATION POLICY EXPLICIT: embeddings and norms are replicated rows,
    not a fall-through."""
    return [
        (r".*embed_tokens\.weight$", ()),               # replicated: policy
        (r".*(q|k|v)_proj\.weight$", (None, mp_axis)),  # column (heads)
        (r".*o_proj\.weight$", (mp_axis, None)),        # row (heads in)
        (r".*(gate|up)_proj\.weight$", (None, mp_axis)),
        (r".*down_proj\.weight$", (mp_axis, None)),
        (r".*lm_head\.weight$", (None, mp_axis)),       # vocab-sharded logits
        (r".*(input_layernorm|post_attention_layernorm|\.norm)\.weight$",
         ()),                                           # norms: replicated
        (r".*", ()),
    ]


def dp_tp_train_rules(mp_axis: str = "mp",
                      fsdp_axis: Optional[str] = None):
    """Training placement: the llama 2D table with ``tp`` spelled
    ``mp_axis``; with no ``fsdp`` axis in the mesh those entries fit away
    and the plan is plain DP×TP (params sharded on mp only, batch on dp)."""
    from ..models.llama import llama_sharding_rules

    return llama_sharding_rules(tp_axis=mp_axis,
                                fsdp_axis=fsdp_axis or "fsdp")


def moe_train_rules(ep_axis: str = "ep", mp_axis: str = "mp"):
    """MoE placement: expert banks sharded on ``ep`` (expert parallelism),
    dense trunk as llama."""
    from ..parallel.moe import moe_sharding_rules

    return moe_sharding_rules(ep_axis=ep_axis, tp_axis=mp_axis)


def _is_quantized_weight(v) -> bool:
    # duck-typed (no import cycle into nn.quant): the int8 payload exposes
    # q / scale / group_size / wo_matmul
    return (hasattr(v, "wo_matmul") and hasattr(v, "q")
            and hasattr(v, "scale") and hasattr(v, "group_size"))


class ShardingPlan:
    """Named mesh + per-layer partition rules + compile-path choice.

    Args:
        mesh: ``"dp2mp4"`` spec string, a ProcessMesh, or a jax Mesh.
        rules: ``[(name_regex, spec_tuple)]`` placement table; default
            :func:`tp_decode_rules` over ``model_axis``.
        data_axes: mesh axes the batch dim shards over (present axes only
            are used).
        model_axis: the tensor/model-parallel axis name (``tp_degree`` is
            its size; 1 when the mesh lacks it).
        path: ``"auto"`` (pjit when the rules actually shard a param on a
            present mesh axis, else shard_map) | ``"pjit"`` | ``"shard_map"``.
    """

    def __init__(self, mesh, rules=None, data_axes: Sequence[str] = ("dp",),
                 model_axis: str = "mp", path: str = "auto"):
        if path not in ("auto", "pjit", "shard_map"):
            raise ValueError(
                f"path must be 'auto'|'pjit'|'shard_map', got {path!r}")
        if isinstance(mesh, Mesh):
            self.process_mesh = None
            self.mesh = mesh
        else:
            self.process_mesh = mesh_from_spec(mesh)
            self.mesh = self.process_mesh.to_jax()
        self.model_axis = model_axis
        self.data_axes = tuple(a for a in data_axes if a in self.mesh.shape)
        self.rules = list(rules) if rules is not None \
            else tp_decode_rules(model_axis)
        self._path = path

    # -- mesh facts ----------------------------------------------------------
    @property
    def axes(self) -> Dict[str, int]:
        return dict(self.mesh.shape)

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values()))) \
            if self.mesh.shape else 1

    @property
    def tp_degree(self) -> int:
        return int(self.mesh.shape.get(self.model_axis, 1))

    @property
    def dp_degree(self) -> int:
        d = 1
        for a in self.data_axes:
            d *= int(self.mesh.shape[a])
        return d

    def __repr__(self):
        axes = "x".join(f"{a}{s}" for a, s in self.mesh.shape.items())
        return f"ShardingPlan({axes}, path={self.compile_path!r})"

    # -- spec resolution -----------------------------------------------------
    def spec_for(self, name: str, shape) -> P:
        """Resolve the rule table for one named param; axes the mesh lacks
        or that don't divide the dim fit away (the reference's
        dims_mapping -1 rule), so one table serves any mesh/model size."""
        from ..parallel.sharded import match_sharding_rules

        return match_sharding_rules(name, tuple(shape), self.rules, self.mesh)

    def sharding_for(self, name: str, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(name, shape))

    def named_sharding(self, *spec) -> NamedSharding:
        """Literal spec → NamedSharding on this plan's mesh."""
        return NamedSharding(self.mesh, P(*spec))

    def uses_model_axis(self) -> bool:
        """True when any rule actually names the model axis — the signal
        that explicit shardings exist and pjit is the right compile path."""
        for _, spec in self.rules:
            for entry in spec:
                axes = entry if isinstance(entry, (tuple, list)) else (entry,)
                if self.model_axis in axes:
                    return self.model_axis in self.mesh.shape
        return False

    # -- placement -----------------------------------------------------------
    def _shard_quantized(self, name: str, w):
        """Place a QuantizedWeight: the int8 ``q`` takes the rule spec for
        its logical [in, out] layout; the scales shard TOGETHER with it —
        per-channel ``[out]`` rides q's out-dim axes, group-wise
        ``[in//g, out]`` rides both (axes that don't divide the scale's
        smaller dims fit away, never misalign)."""
        from ..parallel.sharded import _fit_spec

        qspec = self.spec_for(name, w.q.shape)
        ent = list(qspec) + [None] * (2 - len(qspec))
        if w.group_size == -1:
            sspec = _fit_spec((ent[1],), w.scale.shape, self.mesh)
        else:
            sspec = _fit_spec((ent[0], ent[1]), w.scale.shape, self.mesh)
        q = jax.device_put(w.q, NamedSharding(self.mesh, qspec))
        scale = jax.device_put(w.scale, NamedSharding(self.mesh, sspec))
        return type(w)(q, scale, group_size=w.group_size,
                       out_dtype=w.out_dtype)

    def shard(self, params: Dict[str, object]) -> Dict[str, object]:
        """Place a flat ``{name: array-or-QuantizedWeight}`` model state on
        the mesh per the rule table. Unmatched / unshardable leaves land
        replicated — every leaf is committed, so downstream jits never
        guess a placement."""
        out = {}
        for name, v in params.items():
            if _is_quantized_weight(v):
                out[name] = self._shard_quantized(name, v)
            else:
                out[name] = jax.device_put(
                    v, self.sharding_for(name, jnp.shape(v)))
        return out

    def replicate(self, x):
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def shard_batch(self, arr):
        """Batch placement: dim 0 over the (present) data axes."""
        from ..parallel.sharded import _fit_spec

        spec = self.data_axes if len(self.data_axes) > 1 else (
            self.data_axes[0] if self.data_axes else None)
        return jax.device_put(arr, NamedSharding(
            self.mesh, _fit_spec((spec,), jnp.shape(arr), self.mesh)))

    def kv_spec(self, shape, heads_axis: int = 2) -> P:
        """KV pool placement: kv heads over the model axis (axis 2 of both
        the paged ``[pages, page_size, kvh, hd]`` and contiguous
        ``[slots, max_len, kvh, hd]`` layouts)."""
        from ..parallel.sharded import _fit_spec

        spec = [None] * len(shape)
        spec[heads_axis] = self.model_axis
        return _fit_spec(spec, shape, self.mesh)

    def shard_kv(self, arr, heads_axis: int = 2):
        return jax.device_put(arr, NamedSharding(
            self.mesh, self.kv_spec(jnp.shape(arr), heads_axis)))

    def constrain(self, x, *spec):
        """``with_sharding_constraint`` inside traced code, spec in plan
        axis names; a no-op for axes the mesh lacks."""
        from ..parallel.sharded import _fit_spec

        fitted = _fit_spec(spec, jnp.shape(x), self.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, fitted))

    def validate_divisible(self, **dims) -> None:
        """Loud divisibility check for dims that MUST shard (a decode
        engine's kv heads): ``_fit_spec`` silently replicates a
        non-dividing dim, which for a TP serving engine means every chip
        holds the full pool — the failure must be an error, not a
        memory surprise."""
        tp = self.tp_degree
        bad = {k: v for k, v in dims.items() if int(v) % tp != 0}
        if bad:
            raise ValueError(
                f"tensor-parallel degree {self.model_axis}={tp} does not "
                f"divide " + ", ".join(f"{k}={v}" for k, v in bad.items())
                + " — pick a tp that divides the head/hidden counts")

    # -- compile path --------------------------------------------------------
    @property
    def compile_path(self) -> str:
        """``"pjit"`` when the rules put real shardings on a present mesh
        axis (explicit PartitionSpecs must be honoured — SNIPPETS.md [1]),
        else ``"shard_map"`` (pure data-parallel map-style execution)."""
        if self._path != "auto":
            return self._path
        return "pjit" if self.uses_model_axis() else "shard_map"

    def compile(self, fn, in_specs=None, out_specs=None,
                donate_argnums=(), static_argnums=()):
        """Compile ``fn`` under the plan's mesh.

        ``in_specs``/``out_specs`` are pytrees of PartitionSpecs (or None
        for "let the compiler infer from committed inputs"). The pjit path
        turns them into NamedShardings on ``jax.jit``; the shard_map path
        wraps ``fn`` in a map over the mesh first — there every spec is
        REQUIRED (map-style semantics have no inference)."""
        if self.compile_path == "pjit":
            kw = {}
            if in_specs is not None:
                kw["in_shardings"] = jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), in_specs,
                    is_leaf=lambda s: isinstance(s, P))
            if out_specs is not None:
                kw["out_shardings"] = jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), out_specs,
                    is_leaf=lambda s: isinstance(s, P))
            return jax.jit(fn, donate_argnums=donate_argnums,
                           static_argnums=static_argnums, **kw)
        if in_specs is None or out_specs is None:
            raise ValueError(
                "shard_map compile path requires explicit in_specs and "
                "out_specs (map-style execution cannot infer placements)")
        mapped = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        return jax.jit(mapped, donate_argnums=donate_argnums,
                       static_argnums=static_argnums)

    # -- observability -------------------------------------------------------
    def describe(self) -> Dict[str, object]:
        """The ``mesh`` block ``health()``/``/healthz`` reports and the
        ``paddle_mesh_*`` gauges are set from — what a fleet router needs
        to see a replica's parallelism."""
        return {
            "enabled": True,
            "axes": {a: int(s) for a, s in self.mesh.shape.items()},
            "devices": self.n_devices,
            "tp": self.tp_degree,
            "dp": self.dp_degree,
            "path": self.compile_path,
        }


def decode_plan(mesh, mp_axis: str = "mp") -> ShardingPlan:
    """Serving plan: tensor-parallel decode rules over ``mesh`` (commonly
    a 1-axis ``"mp2"``/``"mp4"`` spec — every chip serves every request,
    holding 1/tp of the weights and kv heads)."""
    return ShardingPlan(mesh, rules=tp_decode_rules(mp_axis),
                        data_axes=(), model_axis=mp_axis)


def train_plan(mesh, rules=None, data_axes=("dp", "fsdp"),
               mp_axis: str = "mp") -> ShardingPlan:
    """Training plan: llama DP(+FSDP)×TP rules by default; pass
    :func:`moe_train_rules` for expert-parallel MoE meshes."""
    return ShardingPlan(
        mesh, rules=rules if rules is not None else dp_tp_train_rules(mp_axis),
        data_axes=data_axes, model_axis=mp_axis)
