"""Persistent XLA compilation cache wiring — warm-disk restarts skip
backend compile.

Reference analogue: the reference framework's program/kernel caches that
``save_inference_model`` deployments rely on to avoid rebuilding per
process. JAX-native: XLA's persistent compilation cache
(``jax_compilation_cache_dir``) keyed by the optimized HLO, shared across
processes through a directory. This module arms it
(:func:`maybe_autoinstall` runs at package import, so arming a fleet is an
env var, no code change), counts hits/misses/seconds from the
``jax.monitoring`` cache events, and surfaces them as
``paddle_compile_cache_*`` metrics plus the ``cache`` block inside
``health()``/``/healthz``'s compile section.

Where the directory comes from, in one rule: ``JAX_COMPILATION_CACHE_DIR``
in the environment IS the cache — jax reads it itself, and nothing here
writes ``jax_compilation_cache_dir`` over it, not even to detach. Only
when it is unset does this module place the cache: at the directory
:func:`install` is given, and for the programs that run on the chip
(:func:`arm`: ``chip_smoke.py``, ``bench.py``,
``inference/replica_main.py``) at ``<checkout>/.jax_cache`` — a fixed
path, never one built from ``tempfile``, a pid or the clock: a directory
that moves never hits.

What the cache does and does not buy: a warm-disk restart still pays
python tracing and cache retrieval (tens of milliseconds per program)
but skips the backend compile (seconds to minutes) — the recompile
watchdog labels these fast-path compiles distinctly so a warm restart no
longer reads as a recompilation storm. AOT serving bundles
(:mod:`~..inference.compile_plan`) go further and skip the retrace too.

Listeners follow the watchdog's pattern: ``jax.monitoring`` listeners
cannot be unregistered, so one process-wide pair is installed on first
:func:`install` and gated by ``_active`` afterwards.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

from . import flags as _flags

_lock = threading.Lock()
_active = False
_listener_installed = False
_state: Dict[str, object] = {"enabled": False, "dir": None}
_counts: Dict[str, float] = {"hits": 0, "misses": 0, "retrieval_s": 0.0,
                             "saved_s": 0.0, "backend_compile_s": 0.0}

# event names shared with observability/watchdog.py's hit/miss labeling —
# defined once so a jax rename cannot desync the cache counters from the
# watchdog's storm suppression
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_COUNT_EVENTS = {
    CACHE_HIT_EVENT: "hits",
    CACHE_MISS_EVENT: "misses",
}
_DURATION_EVENTS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}


def _safe_metric(fn_name: str, *args, **kw) -> None:
    """Metrics are best-effort and gated by the obs family; cache
    accounting must never break a compile."""
    try:
        from .. import observability as _obs

        getattr(_obs, fn_name)(*args, **kw)
    except Exception:
        pass


def _on_event(event: str, **_kw) -> None:
    if not _active:
        return
    field = _COUNT_EVENTS.get(event)
    if field is None:
        return
    with _lock:
        _counts[field] += 1
    _safe_metric("safe_inc", f"paddle_compile_cache_{field}_total",
                 f"persistent compile cache {field}")


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if not _active:
        return
    field = _DURATION_EVENTS.get(event)
    if field is None:
        return
    with _lock:
        # saved_s can go NEGATIVE for tiny programs (retrieval costs more
        # than the compile it replaced) — keep the honest cumulative sum,
        # which is why these export as gauges, not counters
        _counts[field] += float(duration_secs)
        val = _counts[field]
    _safe_metric("safe_set", f"paddle_compile_cache_{field[:-2]}_seconds",
                 f"cumulative persistent-cache {field[:-2]} seconds", val)


def _reset_jax_cache_latch() -> None:
    """Drop jax's once-per-process compilation-cache latch AND its live
    cache object so the CURRENT ``jax_compilation_cache_dir`` value is
    re-read at the next compile. Without this, install() after the first
    compile is a no-op — and uninstall() leaves the old directory live:
    jax caches the "is the cache used" decision and the cache handle the
    first time any compile asks, and never re-reads the config."""
    from jax._src import compilation_cache as _jcc

    _jcc.reset_cache()


def _outside_dir() -> Optional[str]:
    """The cache directory the environment chose, if it chose one."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


# <checkout>/.jax_cache: beside the package, listed in .gitignore
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def arm() -> str:
    """Arm the persistent cache for a program that runs on the chip and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when the
    environment sets it, else ``<checkout>/.jax_cache``. The one call
    ``chip_smoke.py``, ``bench.py`` and ``inference/replica_main.py``
    make, so the three cannot disagree about where compiled programs
    live."""
    install(CHECKOUT_CACHE_DIR)
    return str(_state["dir"])


def install(cache_dir: Optional[str] = None,
            min_compile_secs: Optional[float] = None) -> bool:
    """Point jax at a persistent compilation cache directory and start
    counting its events. ``JAX_COMPILATION_CACHE_DIR`` wins when set: jax
    already reads it, and no other directory is written over it.
    Otherwise the cache goes to ``cache_dir``; with neither, it stays off.
    Returns True when armed."""
    global _active, _listener_installed
    import jax

    outside = _outside_dir()
    if outside:
        cache_dir = outside
    else:
        if not cache_dir:
            return False
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if min_compile_secs is None:
        min_compile_secs = _flags.flag_value("compile_cache_min_compile_secs")
    # default jax policy only persists compiles > 1s / large entries —
    # serving programs at small test scales would never cache, so the
    # flag default (0.0) persists everything and the flag raises the bar
    # on boxes where cache I/O matters
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax initializes its cache AT MOST ONCE, on the first compile — and
    # framework import itself compiles a few host ops before any user
    # code runs, latching "no cache" forever. Reset the latch so the
    # directory actually takes effect
    _reset_jax_cache_latch()
    with _lock:
        if not _listener_installed:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listener_installed = True
        _state["enabled"] = True
        _state["dir"] = cache_dir
    _active = True
    _safe_metric("safe_set", "paddle_compile_cache_enabled",
                 "persistent XLA compile cache armed (1 = on)", 1)
    return True


def uninstall() -> None:
    """Disarm: stop counting and detach the cache directory this module
    placed (existing entries stay on disk for the next install). A
    directory the environment chose stays attached — it is not ours to
    null."""
    global _active
    _active = False
    with _lock:
        _state["enabled"] = False
    if _outside_dir() is None:
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
        # drop jax's latched cache handle too: without the reset the OLD
        # directory keeps serving hits and absorbing writes for the rest
        # of the process — "detached" must mean detached
        _reset_jax_cache_latch()
    _safe_metric("safe_set", "paddle_compile_cache_enabled",
                 "persistent XLA compile cache armed (1 = on)", 0)


def maybe_autoinstall() -> bool:
    """Arm the cache iff the environment names a directory — called at
    package import so ``JAX_COMPILATION_CACHE_DIR=/path python serve.py``
    is the whole deployment story."""
    try:
        if _outside_dir():
            return install()
    except Exception as e:
        # never fatal at import — but an armed-by-env cache that silently
        # stays off means every restart pays full compiles with no signal
        import sys

        sys.stderr.write(
            "[compile-cache] JAX_COMPILATION_CACHE_DIR is set but the "
            "persistent compile cache could not be armed "
            f"({type(e).__name__}: {e}); "
            "restarts will pay full backend compiles\n")
        _safe_metric("safe_set", "paddle_compile_cache_enabled",
                     "persistent XLA compile cache armed (1 = on)", 0)
    return False


def reset_stats() -> None:
    with _lock:
        for k in _counts:
            _counts[k] = 0 if k in ("hits", "misses") else 0.0


def stats() -> Dict[str, object]:
    """Snapshot for ``health()`` compile blocks and benches."""
    with _lock:
        return {
            "enabled": bool(_state["enabled"]),
            "dir": _state["dir"],
            "hits": int(_counts["hits"]),
            "misses": int(_counts["misses"]),
            "retrieval_s": round(_counts["retrieval_s"], 4),
            "saved_s": round(_counts["saved_s"], 4),
            "backend_compile_s": round(_counts["backend_compile_s"], 4),
        }
