"""paddlepaddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built from scratch on JAX/XLA/Pallas/pjit.

The public namespace mirrors ``paddle.*`` (reference: python/paddle/__init__.py)
so reference users can switch with ``import paddlepaddle_tpu as paddle``.
Compute lowers to XLA HLO (MXU matmuls, fused elementwise) with Pallas kernels
for the fused hot ops; distribution is GSPMD mesh sharding over ICI/DCN.
"""

from __future__ import annotations

from . import version  # noqa: F401  (reference: paddle.version module)

__version__ = version.full_version

import jax as _jax

# paddle semantics: int64 indices / float64 on request. Floats still default
# to float32 (bfloat16 in AMP) — creation paths coerce explicitly, so enabling
# x64 does not leak f64 into compute.
_jax.config.update("jax_enable_x64", True)

from .core import (  # noqa: F401
    Parameter,
    Tensor,
    enable_grad,
    get_default_dtype,
    grad,
    no_grad,
    set_default_dtype,
    set_grad_enabled,
)
from .core.device import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    TPUPlace,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
)
from .core.dtype import (  # noqa: F401
    bfloat16,
    bool_ as bool8,
    complex64,
    complex128,
    float8_e4m3fn,
    float8_e5m2,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from .core.flags import get_flags, set_flags  # noqa: F401

# dtype class shim (reference: paddle.dtype — paddle.float32 etc. are its
# instances): our canonical dtype objects are jax/numpy scalar types, so
# the class is a constructor + isinstance gate over that set.


class _DTypeMeta(type):
    def __instancecheck__(cls, obj):
        # dtype OBJECTS only — not None, not string SPECS, and not VALUES
        # that merely carry a .dtype (tensors, arrays, numpy scalars), so
        # `isinstance(arg, paddle.dtype)` dispatch branches behave as in
        # the reference. Canonical dtypes here are numpy scalar TYPES
        # (paddle.float32 is a class) or np.dtype instances.
        import numpy as _np

        if not isinstance(obj, (type, _np.dtype)):
            return False
        from .core.dtype import convert_dtype as _cd

        try:
            return _cd(obj) is not None
        except (TypeError, ValueError, KeyError):
            return False


class dtype(metaclass=_DTypeMeta):
    """paddle.dtype: dtype('float32') -> the canonical dtype object
    (paddle.float32 itself); isinstance(paddle.float32, paddle.dtype) is
    True."""

    def __new__(cls, name):
        from .core import dtype as _dt

        d = _dt.convert_dtype(name)
        return getattr(_dt, {"bool": "bool_"}.get(d.name, d.name), d)


bool = bool8  # noqa: A001  (the reference exports `paddle.bool` likewise)


class _ExoticDType:
    """Placeholder dtypes the reference exposes for PIR string/raw tensors
    (paddle.pstring / paddle.raw) — not materializable as array dtypes on
    this backend; usable only as markers."""

    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return f"paddle.{self.name}"


pstring = _ExoticDType("pstring")
raw = _ExoticDType("raw")


def batch(reader, batch_size, drop_last=False):
    """Deprecated reader combinator (reference: paddle.batch,
    python/paddle/reader/decorator.py): wraps a sample reader into a
    batched reader. Kept for API parity; io.DataLoader is the real path."""

    batch_size = int(batch_size)
    if batch_size <= 0:
        raise ValueError(f"batch_size must be a positive int, got {batch_size}")

    def batched():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched
from .core.random import get_rng_state, seed, set_rng_state  # noqa: F401

# ops namespace (also patches Tensor methods)
from .ops import comparison as _cmp  # noqa: F401
from .ops import creation as _creation
from .ops import extras as _extras
from .ops import linalg as _linalg
from .ops import longtail as _longtail
from .ops import manipulation as _manip
from .ops import math as _math
from .ops import reduction as _reduction
from .ops import search as _search

_OP_MODULES = (_creation, _math, _reduction, _manip, _cmp, _linalg, _search,
               _extras, _longtail)
_globals = globals()
for _mod in _OP_MODULES:
    for _name in dir(_mod):
        if _name.startswith("_"):
            continue
        _obj = getattr(_mod, _name)
        if callable(_obj) and getattr(_obj, "__module__", "").startswith("paddlepaddle_tpu"):
            _globals.setdefault(_name, _obj)

# submodules (populated as the build progresses)


class _MissingModule:
    """Placeholder bound when an OPTIONAL submodule fails to import (its
    heavy dependency is absent from the environment): ``import
    paddlepaddle_tpu`` must never break on an extra the user isn't using.
    Any attribute access raises the original error with guidance."""

    def __init__(self, name, err):
        self.__name__ = "paddlepaddle_tpu." + name
        object.__setattr__(self, "_mm_name", name)
        object.__setattr__(self, "_mm_err", err)

    def __getattr__(self, attr):
        name, err = self._mm_name, self._mm_err
        if attr.startswith("__") and attr.endswith("__"):
            # dunder probes (hasattr/inspect/pickle) must see a normal
            # AttributeError, not an ImportError they won't catch
            raise AttributeError(attr)
        raise ImportError(
            f"paddlepaddle_tpu.{name} is unavailable: importing it failed "
            f"with {err!r}. Install the missing optional dependency to use "
            f"paddlepaddle_tpu.{name}.{attr}.") from err

    def __repr__(self):
        return f"<unavailable module {self.__name__} ({self._mm_err!r})>"


def _optional_import(name):
    import importlib

    try:
        return importlib.import_module("." + name, __name__)
    except (ImportError, OSError) as e:  # missing package / shared lib
        return _MissingModule(name, e)


from . import amp  # noqa: E402,F401
from . import autograd  # noqa: E402,F401
from . import distributed  # noqa: E402,F401
from . import distribution  # noqa: E402,F401
from . import fft  # noqa: E402,F401
from . import framework  # noqa: E402,F401
from . import geometric  # noqa: E402,F401
from . import hapi  # noqa: E402,F401
from . import hub  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import jit  # noqa: E402,F401
from . import linalg  # noqa: E402,F401
from . import metric  # noqa: E402,F401
from . import models  # noqa: E402,F401
from . import nn  # noqa: E402,F401
from . import observability  # noqa: E402,F401
from . import optimizer  # noqa: E402,F401
from . import profiler  # noqa: E402,F401
from . import resilience  # noqa: E402,F401

# optional extras: serving/deployment (inference), audio features, ONNX
# export — guarded so a missing heavy dep degrades to a clear error on
# first USE instead of breaking `import paddlepaddle_tpu`
audio = _optional_import("audio")
inference = _optional_import("inference")
onnx = _optional_import("onnx")
from . import quantization  # noqa: E402,F401
from . import regularizer  # noqa: E402,F401
from . import signal  # noqa: E402,F401
from . import callbacks  # noqa: E402,F401
from . import cost_model  # noqa: E402,F401
from . import sparse  # noqa: E402,F401
from . import static  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import utils  # noqa: E402,F401
from . import vision  # noqa: E402,F401
from .framework.io_api import load, save  # noqa: E402,F401
from .hapi import Model, summary  # noqa: E402,F401
from .jit.api import to_static  # noqa: E402,F401

# paddle.device package (cuda/xpu submodules + place API)
from . import device  # noqa: E402,F401

DataParallel = distributed.DataParallel


_static_mode = False


def disable_static(place=None):
    """Return to dygraph (the native mode)."""
    global _static_mode
    _static_mode = False
    from .core.dispatch import set_static_capture

    set_static_capture(False)


def enable_static():
    """Static-graph mode (reference: paddle.enable_static).

    TPU-native design (static/program.py): ops touching a static Variable
    are captured ABSTRACTLY into a real Program op graph at the dispatcher
    (shape inference via jax.eval_shape — the InferMeta role); transforms
    (append_backward, clone(for_test=True)) rewrite the op list, and
    ``static.Executor.run(prog, feed, fetch_list)`` lowers the graph into
    one pure function compiled by jax.jit per feed/fetch signature — the
    PirInterpreter's scheduling role is taken by XLA.
    """
    global _static_mode
    _static_mode = True
    from .core.dispatch import set_static_capture

    set_static_capture(True)


def in_dynamic_mode():
    return not _static_mode


def is_grad_enabled():
    from .core.autograd import is_grad_enabled as _ige

    return _ige()


# ---------------------------------------------------------------------------
# top-level namespace tail: constants, dtype inspectors, inplace variants
# (reference python/paddle/__init__.py exports)
# ---------------------------------------------------------------------------

import math as _py_math

import numpy as _np_mod

pi = _py_math.pi
e = _py_math.e
inf = float("inf")
nan = float("nan")
newaxis = None

finfo = _np_mod.finfo
iinfo = _np_mod.iinfo


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np_mod.set_printoptions(**kw)


from .nn.initializer import ParamAttr  # noqa: E402,F401
from .ops.longtail import (  # noqa: E402,F401
    binomial,
    cartesian_prod,
    column_stack,
    combinations,
    dstack,
    from_dlpack,
    hstack,
    log_normal,
    pdist,
    renorm,
    row_stack,
    standard_gamma,
    to_dlpack,
    vecdot,
    vstack,
)


class LazyGuard:
    """Deferred-init guard (reference framework LazyGuard): parameters here
    initialize eagerly, so the guard is a no-op context."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class CUDAPinnedPlace:
    """Accepted for API parity; host memory is always pinned-equivalent."""


def get_cuda_rng_state():
    return get_rng_state()


def set_cuda_rng_state(state):
    set_rng_state(state)


def disable_signal_handler():
    """No native signal handlers are installed; kept for parity."""


def check_shape(shape):
    for s in list(shape):
        if s is not None and int(s) < -1:
            raise ValueError(f"invalid dim {s} in shape {shape}")


def flops(net, input_size, custom_ops=None, print_detail=False):
    """APPROXIMATE FLOPs: 2 x parameter count (one MAC per weight per
    sample). The reference's per-operator counting (paddle.flops) is not
    reproduced; use the profiler for measured compute."""
    import builtins

    if print_detail:
        from .hapi.summary import summary as _summary

        try:
            _summary(net, input_size)
        except Exception:
            pass
    total = builtins.sum(int(_np_mod.prod(p.shape)) for p in net.parameters())
    return total * 2


# the inplace-wrapper factory lives in nn.functional (_inplace); reuse it so
# in-place semantics have exactly one implementation
from .nn.functional import _inplace as _make_inplace  # noqa: E402

# NOTE: random-fill ops (normal_, log_normal_, bernoulli_, cauchy_,
# geometric_) are NOT generated from their sampling functions — paddle's
# in-place fills take distribution PARAMS, not the tensor, as arguments.
_INPLACE_NAMES = [
    "acos", "acosh", "addmm", "asin", "asinh", "atan", "atanh",
    "bitwise_and", "bitwise_invert",
    "bitwise_left_shift", "bitwise_not", "bitwise_or", "bitwise_right_shift",
    "bitwise_xor", "cast", "copysign", "cosh", "cumprod", "cumsum",
    "digamma", "equal", "erf", "erfinv", "expm1", "flatten", "floor_divide",
    "floor_mod", "frac", "gammainc", "gammaincc", "gammaln", "gcd",
    "greater_equal", "greater_than", "hypot", "i0", "index_fill", "lcm",
    "ldexp", "less", "less_equal", "less_than", "lgamma", "log", "log10",
    "log1p", "log2", "logical_and", "logical_not", "logical_or",
    "logical_xor", "logit", "masked_fill", "masked_scatter", "mod",
    "multigammaln", "nan_to_num", "not_equal", "polygamma",
    "put_along_axis", "renorm", "sigmoid", "sinc", "sinh", "square",
    "squeeze", "t", "tan", "transpose", "tril", "triu", "trunc", "unsqueeze",
]
for _n in _INPLACE_NAMES:
    _fn = _globals.get(_n)
    if _fn is not None and callable(_fn) and _n + "_" not in _globals:
        _globals[_n + "_"] = _make_inplace(_fn)
del _n, _fn


def normal_(x, mean=0.0, std=1.0, name=None):
    """In-place fill with N(mean, std) samples (reference normal_)."""
    import jax

    from .core import random as _prandom

    vals = mean + std * jax.random.normal(_prandom.next_key(),
                                          tuple(x.shape))
    x._replace_data(vals.astype(x._data.dtype))
    return x


def log_normal_(x, mean=1.0, std=2.0, name=None):
    """In-place fill with LogNormal(mean, std) samples."""
    import jax
    import jax.numpy as _jnp

    from .core import random as _prandom

    vals = _jnp.exp(mean + std * jax.random.normal(_prandom.next_key(),
                                                   tuple(x.shape)))
    x._replace_data(vals.astype(x._data.dtype))
    return x


def bernoulli_(x, p=0.5, name=None):
    """In-place fill with Bernoulli(p) samples."""
    import jax

    from .core import random as _prandom

    vals = jax.random.bernoulli(_prandom.next_key(), p, tuple(x.shape))
    x._replace_data(vals.astype(x._data.dtype))
    return x


def cauchy_(x, loc=0, scale=1, name=None):
    """In-place fill with Cauchy samples (reference tensor.random cauchy_)."""
    import jax
    import jax.numpy as _jnp

    from .core import random as _prandom

    u = jax.random.uniform(_prandom.next_key(), tuple(x.shape))
    vals = loc + scale * _jnp.tan(_jnp.pi * (u - 0.5))
    x._replace_data(vals.astype(x._data.dtype))
    return x


def geometric_(x, probs, name=None):
    """In-place fill with Geometric samples (reference geometric_)."""
    import jax

    from .core import random as _prandom

    g = jax.random.geometric(_prandom.next_key(), probs, tuple(x.shape))
    x._replace_data(g.astype(x._data.dtype))
    return x


def uniform_(x, min=-1.0, max=1.0, seed=0, name=None):
    """In-place fill with U(min, max) samples (reference tensor/random.py
    uniform_)."""
    import jax

    from .core import random as _prandom

    key = jax.random.PRNGKey(seed) if seed else _prandom.next_key()
    vals = jax.random.uniform(key, tuple(x.shape), minval=min, maxval=max)
    x._replace_data(vals.astype(x._data.dtype))
    return x


def set_(x, source=None, shape=None, stride=None, offset=0, name=None):
    """Tensor.set_ (reference tensor/creation.py:3263): rebind ``x`` to a
    strided view over ``source``'s flat storage. XLA buffers cannot alias,
    so the view is materialized by gather — value semantics match; buffer
    sharing (meaningless on TPU) is not reproduced."""
    import jax.numpy as _jnp

    from .core.tensor import Tensor as _T

    if x.is_leaf and not x.stop_gradient:
        raise ValueError(
            "(InvalidArgument) Leaf Tensor that doesn't stop gradient "
            "can't use inplace strategy.")
    if source is None:
        x._replace_data(_jnp.zeros((0,), x._data.dtype))
        return x
    src = source._data if isinstance(source, _T) else _jnp.asarray(source)
    flat = src.reshape(-1)
    if shape is None:
        shape = list(src.shape)
    shape = [int(s) for s in shape]
    if not stride:
        acc, stride = 1, [0] * len(shape)
        for i in range(len(shape) - 1, -1, -1):
            stride[i] = acc
            acc *= shape[i]
    # reference offset is in BYTES into the storage (creation.py set_
    # example: offset=4 skips one float32 element)
    idx = _np_mod.zeros(tuple(shape), _np_mod.int64) \
        + offset // src.dtype.itemsize
    for d, st in enumerate(stride):
        ar = _np_mod.arange(shape[d], dtype=_np_mod.int64) * int(st)
        idx += ar.reshape((-1,) + (1,) * (len(shape) - 1 - d))
    if idx.size and int(idx.max()) >= flat.size:
        raise ValueError(
            f"set_: shape {shape} / stride {stride} / offset {offset} "
            f"reaches element {int(idx.max())} but source storage has only "
            f"{flat.size} elements")
    x._replace_data(flat[_jnp.asarray(idx)])
    return x


# attach the reference's tensor-method tail (plain + in-place + fills) now
# that the top-level namespace is fully assembled
import sys as _sys_mod  # noqa: E402

from .ops import _patch_tensor_method_tail as _pmtt  # noqa: E402

_pmtt(_sys_mod.modules[__name__])
del _pmtt

# reference nn.initializer package exposes LazyGuard via its lazy_init
# submodule (nn/initializer/lazy_init.py); initializer here is a single
# module, so mirror that path as attributes
import types as _types_mod  # noqa: E402

nn.initializer.LazyGuard = LazyGuard
nn.initializer.lazy_init = _types_mod.SimpleNamespace(LazyGuard=LazyGuard)

# persistent XLA compile cache: armed here iff JAX_COMPILATION_CACHE_DIR
# names a directory, so a fleet deploys warm-restart compile caching with an
# env var and no code change (core/compile_cache.py)
from .core import compile_cache as _compile_cache  # noqa: E402

_compile_cache.maybe_autoinstall()
