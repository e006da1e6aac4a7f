"""Global runtime flags registry.

Reference: paddle/common/flags.h:38 (PHI_DEFINE_EXPORTED_* macros; 185 flags in
paddle/common/flags.cc) + python paddle.set_flags/get_flags
(python/paddle/base/framework.py:132). Same semantics: typed flags, env-var
override at first read (FLAGS_xxx), settable at runtime from python.
"""

from __future__ import annotations

import os
from typing import Any, Dict


class _Flag:
    __slots__ = ("name", "default", "type", "help", "value", "env_read")

    def __init__(self, name, default, help_):
        self.name = name
        self.default = default
        self.type = type(default)
        self.help = help_
        self.value = default
        self.env_read = False


_registry: Dict[str, _Flag] = {}


def define_flag(name: str, default: Any, help_: str = "", env: str = None):
    """Register a typed flag. ``env`` names an alternate environment variable
    consulted (after the canonical FLAGS_xxx) for the initial value — used by
    flag families with an established env spelling (PADDLE_OBS_*)."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    if name in _registry:
        return _registry[name]
    f = _Flag(name, default, help_)
    raw = os.environ.get(name)
    if raw is None and env is not None:
        raw = os.environ.get(env)
    if raw is not None:
        f.value = _parse(raw, f.type)
        f.env_read = True
    _registry[name] = f
    return f


def _parse(s: str, t: type):
    if t is bool:
        return s.lower() in ("1", "true", "yes", "on")
    return t(s)


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        if k not in _registry:
            define_flag(k, v)
        else:
            f = _registry[k]
            f.value = _parse(v, f.type) if isinstance(v, str) and f.type is not str else f.type(v)


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        key = k if k.startswith("FLAGS_") else "FLAGS_" + k
        if key not in _registry:
            raise ValueError(f"Unknown flag {k}")
        out[k] = _registry[key].value
    return out


def flag_value(name: str):
    key = name if name.startswith("FLAGS_") else "FLAGS_" + name
    return _registry[key].value


# Core flags (subset of paddle/common/flags.cc relevant to this runtime).
define_flag("check_nan_inf", False, "scan op outputs for NaN/Inf (debugging)")
define_flag("benchmark", False, "synchronize after each op for timing")
define_flag("use_pallas_kernels", True, "use Pallas TPU kernels for fused ops")
define_flag("flash_attn_block_q", 512, "pallas flash-attn q block")
define_flag("flash_attn_block_kv", 512, "pallas flash-attn kv block")
define_flag("eager_delete_tensor_gb", 0.0, "compat no-op (XLA owns memory)")
define_flag("allocator_strategy", "xla", "compat: allocation handled by XLA runtime")

# The fused gather-GEMM MoE dispatch (ops/kernels/gather_gemm.py), what
# MoELayer(dispatch_mode="fused") runs: a Pallas kernel, interpreted off the
# chip. Where it is unsupported (or this switch is off) the layer falls back
# to the 'sorted' formulation LOUDLY: one stderr line and a counter, never
# with other results.
define_flag("fused_gather_gemm", True,
            "per-kernel KILL SWITCH for the fused gather-GEMM MoE "
            "dispatch: 0 forces the reference 'sorted' formulation even "
            "for explicit dispatch_mode='fused' opt-ins (the incident "
            "lever)", env="PADDLE_FUSED_GATHER_GEMM")

# Observability family (observability/): each flag also reads its PADDLE_OBS_*
# env spelling; all default off so the hot paths carry no instrumentation.
define_flag("obs_trace", False,
            "record host spans (ops, regions, collectives) into the "
            "observability ring buffer for chrome-trace export",
            env="PADDLE_OBS_TRACE")
define_flag("obs_metrics", False,
            "aggregate per-op/per-collective counters, gauges and latency "
            "histograms in the observability metrics registry",
            env="PADDLE_OBS_METRICS")
define_flag("obs_recompile_watch", False,
            "watch jax.jit compilations and warn on recompilation storms "
            "(same callsite compiling repeatedly)",
            env="PADDLE_OBS_RECOMPILE_WATCH")
define_flag("obs_buffer_size", 100000,
            "observability ring buffer capacity (events)",
            env="PADDLE_OBS_BUFFER_SIZE")
define_flag("obs_recompile_threshold", 3,
            "compiles from one callsite before the recompilation watchdog "
            "flags a storm", env="PADDLE_OBS_RECOMPILE_THRESHOLD")

# Fleet telemetry plane (observability/exporter.py, aggregate.py, flight.py):
# per-rank HTTP exporter, rank-0 store-based aggregation, crash flight
# recorder. All off by default like the rest of the obs family.
define_flag("obs_export", False,
            "start the per-rank HTTP telemetry exporter (/metrics /healthz "
            "/vars /trace) when observability is imported; "
            "distributed.launch --obs_export sets this for every worker",
            env="PADDLE_OBS_EXPORT")
define_flag("obs_port", 9470,
            "base port for the telemetry exporter; a worker listens on "
            "obs_port + rank (falls back to an ephemeral port if taken)",
            env="PADDLE_OBS_PORT")
define_flag("obs_export_host", "127.0.0.1",
            "bind address for the telemetry exporter (0.0.0.0 to scrape "
            "across hosts)", env="PADDLE_OBS_EXPORT_HOST")
define_flag("obs_publish_interval_s", 2.0,
            "seconds between fleet snapshot publications from each worker "
            "into the TCPStore control plane",
            env="PADDLE_OBS_PUBLISH_INTERVAL_S")
define_flag("obs_blackbox", False,
            "arm the crash flight recorder: a bounded ring of structured "
            "runtime events dumped as JSONL + all-thread stacks on "
            "unhandled exception, watchdog timeout, preemption, breaker "
            "open, or chaos kill", env="PADDLE_OBS_BLACKBOX")
define_flag("obs_blackbox_dir", "",
            "directory for black-box dumps (empty = <tmpdir>/paddle_blackbox)",
            env="PADDLE_OBS_BLACKBOX_DIR")
define_flag("obs_blackbox_events", 2048,
            "flight recorder ring capacity (structured events)",
            env="PADDLE_OBS_BLACKBOX_EVENTS")
define_flag("obs_reqtrace", False,
            "arm request-journey tracing (observability/reqtrace.py): one "
            "stitched trace per serving request — router pick, failover "
            "attempts, queue wait, paged admission, decode chunks, "
            "speculative rounds — served at /requests and by obsctl "
            "requests", env="PADDLE_OBS_REQTRACE")
define_flag("obs_reqtrace_ring", 256,
            "completed request journeys kept in the bounded reqtrace ring",
            env="PADDLE_OBS_REQTRACE_RING")
define_flag("obs_reqtrace_spans", 256,
            "span cap per request journey (overflow counts dropped_spans "
            "instead of growing)", env="PADDLE_OBS_REQTRACE_SPANS")
define_flag("obs_tsdb", False,
            "arm the in-process metric history plane (observability/"
            "tsdb.py): a sampler thread diffs the metrics registry every "
            "obs_tsdb_interval_s into bounded per-series rings (counters "
            "as rates, gauges as values, histograms as window quantiles), "
            "served at /query and merged fleet-wide at /fleet/query; also "
            "arms the burn-rate alert engine (observability/alerts.py)",
            env="PADDLE_OBS_TSDB")
define_flag("obs_tsdb_interval_s", 2.0,
            "seconds between metric-history samples (and alert-rule "
            "evaluations)", env="PADDLE_OBS_TSDB_INTERVAL_S")
define_flag("obs_tsdb_points", 512,
            "raw-tier ring capacity per series; the coarse tier keeps the "
            "same point count at 10x the spacing, so total history = "
            "points * interval * 11", env="PADDLE_OBS_TSDB_POINTS")
define_flag("obs_tsdb_publish_points", 64,
            "most-recent points per series (each tier) published into the "
            "TCPStore fleet plane for rank-0 /fleet/query merging; bounds "
            "the per-rank payload", env="PADDLE_OBS_TSDB_PUBLISH_POINTS")
define_flag("obs_perf", False,
            "arm the performance-attribution plane (observability/perf/): "
            "capture XLA cost_analysis FLOPs/bytes per compiled program "
            "(train step, decode engine, static run_program), derive "
            "measured MFU + roofline classification, and serve them as "
            "paddle_program_* gauges and the exporter's /programs endpoint",
            env="PADDLE_OBS_PERF")
define_flag("obs_prof", False,
            "arm the always-on sampling wall-clock profiler "
            "(observability/profiler.py): a daemon thread samples "
            "sys._current_frames() at obs_prof_hz into bounded per-second "
            "folded-stack rings, categorized by serving seam (decode / "
            "admission / router / wire / gc), served at /profile and "
            "rank-merged at /fleet/profile", env="PADDLE_OBS_PROF")
define_flag("obs_prof_hz", 50.0,
            "sampling-profiler rate in samples per second; the overhead "
            "gate (tools/check_obs_overhead.py gate 7) holds the default "
            "under 5% on the dispatch microloop and serving fast path",
            env="PADDLE_OBS_PROF_HZ")
define_flag("obs_prof_window_s", 120.0,
            "seconds of per-second folded-stack aggregation the profiler "
            "keeps (bounded ring; flight-recorder dumps attach the last "
            "~10s as hot_stacks)", env="PADDLE_OBS_PROF_WINDOW_S")
define_flag("obs_memledger", False,
            "arm the live memory ledger (observability/memledger.py): a "
            "daemon thread attributes jax.live_arrays() into named buckets "
            "(params, KV page pool, prefix-pinned, draft, workspace, "
            "unattributed) every obs_memledger_interval_s, publishes "
            "paddle_mem_* gauges (headroom rides the tsdb plane) and "
            "reconciles PagePool accounting for page-leak detection",
            env="PADDLE_OBS_MEMLEDGER")
define_flag("obs_memledger_interval_s", 5.0,
            "seconds between memory-ledger samples",
            env="PADDLE_OBS_MEMLEDGER_INTERVAL_S")

# Compile-cache family (core/compile_cache.py + inference/compile_plan.py):
# persistent XLA compilation cache so warm-disk restarts skip backend
# compile. The directory is jax's own JAX_COMPILATION_CACHE_DIR (armed at
# package import when set — env alone deploys it fleet-wide) or, for the
# programs that run on the chip, <checkout>/.jax_cache; hit/miss/seconds
# surface as paddle_compile_cache_*.
define_flag("compile_cache_min_compile_secs", 0.0,
            "only compiles at least this long are persisted to the compile "
            "cache (0 = persist everything; raise it where cache I/O costs "
            "more than small recompiles)",
            env="PADDLE_COMPILE_CACHE_MIN_SECS")

# SLO targets (observability/reqtrace.py burn tracker): sliding-window
# violation rates against these targets surface as paddle_slo_burn_{ttft,
# tpot} gauges and the health() "slo_burn" block — the input signal of the
# SLO-driven autoscaler control loop (ROADMAP item 5). 0 = target off.
define_flag("slo_ttft_ms", 0.0,
            "TTFT SLO target in milliseconds; nonzero arms the sliding-"
            "window burn-rate gauge paddle_slo_burn_ttft",
            env="PADDLE_SLO_TTFT_MS")
define_flag("slo_tpot_ms", 0.0,
            "TPOT SLO target in milliseconds; nonzero arms the sliding-"
            "window burn-rate gauge paddle_slo_burn_tpot",
            env="PADDLE_SLO_TPOT_MS")
define_flag("slo_burn_window_s", 60.0,
            "sliding window (seconds) the SLO burn rate is computed over",
            env="PADDLE_SLO_BURN_WINDOW_S")
define_flag("slo_error_budget", 0.01,
            "allowed SLO violation fraction; burn = violation_rate / "
            "budget (1.0 = spending the budget exactly as it accrues)",
            env="PADDLE_SLO_ERROR_BUDGET")

# Resilience family (resilience/): checkpoint integrity verification; the
# chaos engine reads its PADDLE_CHAOS_* env vars directly (lazily at the
# first seam hit, so launcher-spawned workers pick them up per process).
define_flag("ckpt_verify_crc", True,
            "verify per-shard CRC32 (checkpoint format v3) when loading; "
            "corrupted shards raise CheckpointCorruptionError instead of "
            "loading silently-wrong weights", env="PADDLE_CKPT_VERIFY")
define_flag("watchdog_rearm", True,
            "re-arm the step watchdog after a timed-out step retires, so "
            "every hung step is reported (not only the first)")

# Serving robustness family (inference/serving.py + inference/robustness.py):
# fleet-wide defaults for the ServingEngine's overload/failure protection.
# 0 means "off" for the bound-style flags; constructor arguments win.
define_flag("serving_max_queue", 0,
            "bound on queued generation requests; submits past it shed with "
            "ServerOverloadedError (0 = unbounded, the seed behavior)",
            env="PADDLE_SERVING_MAX_QUEUE")
define_flag("serving_max_queue_wait_s", 0.0,
            "shed submits whose estimated queue wait (EWMA of decode-attempt "
            "time x depth) exceeds this many seconds (0 = off)",
            env="PADDLE_SERVING_MAX_QUEUE_WAIT_S")
define_flag("serving_default_deadline_s", 0.0,
            "default per-request deadline applied when submit() passes none "
            "(0 = no deadline)", env="PADDLE_SERVING_DEADLINE_S")
define_flag("serving_breaker_threshold", 5,
            "consecutive decode failures that open the serving circuit "
            "breaker (submits then fail fast with CircuitOpenError)",
            env="PADDLE_SERVING_BREAKER_THRESHOLD")
define_flag("serving_breaker_reset_s", 30.0,
            "seconds an open serving breaker waits before letting one "
            "half-open probe request through",
            env="PADDLE_SERVING_BREAKER_RESET_S")
define_flag("serving_decode_timeout_s", 0.0,
            "engine-thread watchdog: a decode attempt in flight longer than "
            "this trips the breaker open (0 = watchdog off)",
            env="PADDLE_SERVING_DECODE_TIMEOUT_S")
define_flag("serving_drain_timeout_s", 30.0,
            "default drain(timeout): how long a draining engine lets "
            "in-flight slots finish before shedding the remainder",
            env="PADDLE_SERVING_DRAIN_TIMEOUT_S")

# KV-memory family (ROADMAP item 4): int8 KV pages + host-RAM prefix tier.
define_flag("serving_kv_quant", "",
            "KV-cache quantization for the paged pool: 'int8' stores K/V "
            "pages as int8 codes with per-page-per-head scales (about 2x "
            "pages at a fixed byte budget); '' = full-precision KV (the "
            "seed behavior). Constructor arguments win.",
            env="PADDLE_SERVING_KV_QUANT")
define_flag("serving_kv_host_bytes", 0,
            "byte budget for the host-RAM prefix-cache spill tier: "
            "refcount-0 prefix entries evicted from the device pool are "
            "serialized to host RAM and restored into fresh device pages "
            "on the next hit; LRU spans both tiers and host-tier discard "
            "is the true eviction (0 = tier off, eviction discards)",
            env="PADDLE_SERVING_KV_HOST_BYTES")
