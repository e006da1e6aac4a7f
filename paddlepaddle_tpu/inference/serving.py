"""Serving engine — request queue + batched KV-cache decode, wrapped in an
overload-and-failure protection layer.

Reference surface: the Predictor/predictor-pool deployment layer
(paddle/fluid/inference/api/paddle_inference_api.h:52,229 — config,
zero-copy handles, a pool of predictors serving concurrent callers) and the
serving-grade batched attention
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu via
python/paddle/incubate/nn/functional/block_multihead_attention.py).

TPU-native: one engine thread owns the chip; concurrent callers submit
GenerationRequests into a queue; futures deliver per-request results. Two
schedulers:

* ``mode="continuous"`` (default) — slot-based continuous batching over
  the BatchDecodeEngine (decode_engine.py): ragged prompt lengths, mixed
  sampling params and budgets share ONE compiled multi-step decode program
  with per-slot cache positions; finished slots retire and free slots admit
  queued requests mid-flight. KV lives in a PAGED pool by default
  (``kv_layout="paged"``): a device page table gathers each slot's
  logical cache, admission reserves pages for the request's REAL
  prompt+budget (not ``max_len``), and ``submit(prefix_len=…)`` shares
  page-aligned system-prompt prefixes across requests through a
  ref-counted prompt cache. The TPU-native equivalent of the reference's
  paged block_multi_head_attention serving path.
* ``mode="static"`` — groups compatible requests (same prompt-length
  bucket and sampling params) into one batched ``generate_cached`` call;
  simpler, kept for models without the cache-vector-position path.

Robustness layer (robustness.py), all opt-in except the circuit breaker:

* admission control — ``max_queue`` bounds the queue and sheds with a typed
  :class:`~.robustness.ServerOverloadedError` (queue depth + retry-after
  hint); ``max_queue_wait_s`` sheds on estimated wait; prompt/budget are
  validated against ``max_len`` at submit;
* deadlines & cancellation — per-request ``deadline_s`` sheds expired
  requests before they're decoded; ``GenerationResult.cancel()`` frees an
  in-flight slot so a departed client stops burning chip time;
* circuit breaker — N consecutive decode failures open it (submits fail
  fast, slots reset), half-open probe recovery, optional hung-decode
  watchdog (``decode_timeout_s``) that trips it;
* graceful drain — ``drain(timeout)`` stops admission, finishes in-flight
  slots, sheds the rest; ``install_preemption_hook()`` registers the drain
  with :mod:`~..resilience.preemption` so SIGTERM drains before exit 143;
* ``health()`` — readiness snapshot (queue depth, busy slots, breaker
  state, last-decode age), also served as the ``_OP_HEALTH`` frame by
  :class:`~.c_api_server.CApiServer`.

Chaos seams (resilience.chaos): ``serving.admit`` fires inside submit after
admission checks pass; ``serving.decode`` fires before each decode attempt,
so an armed fault storm exercises the breaker exactly like a sick model.
"""

from __future__ import annotations

import itertools
import queue
import sys
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..core import flags as _flags
from ..observability.recorder import phase, phase_counters
from ..resilience.chaos import chaos_point
from .decode_engine import ADMIT_COUNTERS, CHUNK_PHASES, SAMPLE_COUNTERS
from .kv_pool import pages_needed
from .robustness import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    EngineDrainingError,
    KVCapacityError,
    QueueWaitEstimator,
    RequestCancelledError,
    RequestValidationError,
    ServerOverloadedError,
)
from .robustness import safe_inc as _rob_safe_inc
from .robustness import safe_set as _rob_safe_set

# observability hook: _obs_srv(event, value) with events "latency" (seconds
# submit-to-result for one completed request), "error"/"cancelled" (a request
# failed / was cancelled), "batch_size" (decode slots / requests active in
# the current batch), "queue_depth" (requests waiting, queue + deferred),
# "batch" (value "ok"|"error": one decode attempt's outcome).
# None when observability is off.
_obs_srv = None

_BREAKER_STATE_NUM = {"closed": 0, "half_open": 1, "open": 2}

# the continuous loop's phases: disjoint, on the engine thread; the first
# three are timed in _loop_continuous, CHUNK_PHASES inside the decode engine
LOOP_PHASES = ("serve.sweep", "serve.wait_request", "serve.admit",
               *CHUNK_PHASES)
# what the decode engine counts for itself and the loop copies after a chunk
_ENGINE_SPAN_KEYS = ("turnaround_s", "turnaround_n", "decode_view_pages",
                     "decode_table_pages", "decode_calls", *ADMIT_COUNTERS,
                     *SAMPLE_COUNTERS,
                     *phase_counters(CHUNK_PHASES))

# process-wide request ids: the join key across SLO metrics, trace spans
# (request#<id>) and flight-recorder lifecycle events
_REQ_IDS = itertools.count(1)


def _flight_record(kind: str, name: str, **data) -> None:
    """Request-lifecycle feed into the crash flight recorder; one global
    check when the black box is disarmed, never raises."""
    try:
        from ..observability import flight

        flight.record(kind, name, **data)
    except Exception:
        pass


# cold-path metric wrappers shared with decode_engine (robustness.py):
# always record, never raise, cost nothing on the serve path
_safe_inc = _rob_safe_inc
_safe_set = _rob_safe_set


def _goodput_account(kind: str, n: int) -> None:
    """Goodput-ledger attribution for the serving-layer waste paths the
    engine cannot see (a failed decode chunk's partial output, drain/stop
    abandonment, static-batch delivery). Never raises."""
    if n <= 0:
        return
    try:
        from ..observability import goodput

        goodput.account(kind, n)
    except Exception:
        pass


class GenerationResult:
    """Future for one request. Carries the request's lifecycle timestamps
    (submit -> admit -> first token -> finish), stamped by the engine, so
    TTFT / TPOT / queue-wait are measured per request — :meth:`slo`
    returns them, and completed requests feed the
    ``paddle_serving_{ttft,tpot,queue_wait,deadline_margin}_seconds``
    histograms plus a ``request#<id>`` span in the trace."""

    def __init__(self):
        self._event = threading.Event()
        self._lock = threading.Lock()  # one-writer-wins arbitration: the
        #   router adds ROUTINE concurrent writers (client cancel() vs the
        #   winning replica's delivery) — check-then-act alone could tear
        #   the outcome (error=None AND output=None observed by a waiter)
        self._output = None
        self._error: Optional[BaseException] = None
        self._cancelled = False
        self._cancel_kind = "cancel"   # goodput kind a cancel wastes as
        self._callbacks: List = []     # run once, after the outcome is set
        self._obs_emit = True          # False: a wrapper future (router)
        #           whose replica-side inner future already feeds the SLO
        #           histograms + flight ring — one request, one record
        self._t_submit = time.perf_counter()
        self._t_admit: Optional[float] = None     # decode-slot admission
        self._t_first: Optional[float] = None     # first token on host
        self._t_done: Optional[float] = None
        self._n_new = 0                           # tokens generated
        self._n_at_first = 1     # tokens already delivered at _t_first: 1
        #   on the one-token-per-step path (bit-identical TPOT), stamped
        #   higher by multi-token (speculative) engines whose first host
        #   sync lands a burst — TPOT must divide by tokens that arrived
        #   AFTER _t_first, not assume one token per decode chunk
        self._req_id: Optional[int] = None
        self._deadline: Optional[float] = None    # absolute monotonic
        self._streaming = True                    # False: tokens arrive as
        #                       one batch (static mode) — TPOT meaningless
        self._trace = None       # reqtrace Journey riding this request
        self._trace_owner = False  # True on the future whose _set closes
        #   the journey (the router wrapper, or an engine-direct future);
        #   replica-side inner futures carry the journey but never close it
        self._t_dispatch: Optional[float] = None  # winning attempt's own
        #   submit time (router failover): queue wait is measured per
        #   attempt, not from the first submit across every retry

    def done(self) -> bool:
        return self._event.is_set()

    def cancelled(self) -> bool:
        return self._cancelled

    def cancel(self, reason: str = "cancel") -> bool:
        """Cancel the request: the future fails with
        :class:`RequestCancelledError` immediately, a queued request is
        dropped at pop time, and an in-flight decode slot is released on
        the next scheduler cycle (the chip stops spending on it). Returns
        True if the request had not already finished. ``reason`` names
        the goodput kind the abandoned tokens are attributed to (the
        router passes ``"hedge_loser"`` when reaping a hedge's loser);
        it rides the future because the slot sweep that releases the
        decode slot runs later, on the engine thread."""
        self._cancelled = True
        self._cancel_kind = reason
        if self._event.is_set():
            return False
        self._set(error=RequestCancelledError("request cancelled by client"))
        return True

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self._error is not None:
            raise self._error
        return self._output

    def _add_done_callback(self, fn) -> None:
        """Run ``fn(self)`` exactly once when the outcome lands (now, if it
        already has). The router's failover path hangs off this — a failed
        replica future re-dispatches without a waiter thread per request.
        Callbacks run on whichever thread sets the outcome (usually the
        engine loop), must not block, and never raise into the engine."""
        self._callbacks.append(fn)
        if self._event.is_set():
            self._drain_callbacks()

    def _drain_callbacks(self) -> None:
        # pop-one-at-a-time: a concurrent _set/_add_done_callback race may
        # drain in parallel, but each callback is popped (and so run) once
        while True:
            try:
                fn = self._callbacks.pop(0)
            except IndexError:
                return
            try:
                fn(self)
            except Exception:
                pass

    def slo(self) -> Dict[str, object]:
        """Per-request SLO numbers (None where the lifecycle point was
        never reached — e.g. a shed request has no TTFT). TPOT is the
        per-output-token average after the first token; in static serving
        mode there is no streaming, so TTFT equals full latency."""
        end = self._t_done
        t_first = self._t_first
        # queue wait is PER ATTEMPT: after a router failover the winning
        # attempt's own submit time (_t_dispatch) is the base — measuring
        # from the first submit would book the failed attempt's decode and
        # the backoff as "queue wait". TTFT/latency stay client-relative.
        t_base = (self._t_dispatch if self._t_dispatch is not None
                  else self._t_submit)
        return {
            "req_id": self._req_id,
            "new_tokens": self._n_new,
            "queue_wait_s": (None if self._t_admit is None
                             else self._t_admit - t_base),
            "ttft_s": (None if t_first is None
                       else t_first - self._t_submit),
            "tpot_s": (None if (t_first is None or end is None
                                or self._n_new <= self._n_at_first
                                or not self._streaming)
                       else (end - t_first)
                       / (self._n_new - self._n_at_first)),
            "latency_s": None if end is None else end - self._t_submit,
        }

    def _set(self, output=None, error=None):
        with self._lock:
            if self._event.is_set():
                return  # first outcome wins: a late writer (a retiring
            #   slot racing stop(), a delivery racing cancel()) must not
            #   flip — or tear — a result
            self._output = output
            self._error = error
            self._t_done = now = time.perf_counter()
            self._event.set()
        obs = _obs_srv if self._obs_emit else None
        outcome = ("ok" if error is None
                   else "cancelled" if isinstance(error, RequestCancelledError)
                   else "error")
        if obs is not None:
            if error is None:
                obs("latency", now - self._t_submit)
                s = self.slo()
                obs("slo", {
                    "id": self._req_id,
                    "latency": s["latency_s"],
                    "ttft": s["ttft_s"],
                    "tpot": s["tpot_s"],
                    "queue_wait": s["queue_wait_s"],
                    "deadline_margin": (None if self._deadline is None
                                        else self._deadline
                                        - time.monotonic()),
                    "tokens": self._n_new,
                })
            elif isinstance(error, RequestCancelledError):
                obs("cancelled", 1)
            else:
                obs("error", 1)
        if self._obs_emit:
            _flight_record(
                "request", str(self._req_id or "?"), phase="finish",
                outcome=outcome, tokens=self._n_new,
                latency_ms=round((now - self._t_submit) * 1e3, 3),
                **({} if self._t_first is None else
                   {"ttft_ms": round((self._t_first - self._t_submit)
                                     * 1e3, 3)}))
        try:
            if (error is None and self._obs_emit
                    and (_flags.flag_value("slo_ttft_ms") > 0
                         or _flags.flag_value("slo_tpot_ms") > 0)):
                from ..observability import reqtrace as _rt

                s = self.slo()
                _rt.slo_observe(s["ttft_s"], s["tpot_s"])
            tr = self._trace
            if tr is not None and self._trace_owner:
                from ..observability import reqtrace as _rt

                _rt.finish_future(tr, self, outcome)
        except Exception:
            pass       # observability must never break request delivery
        self._drain_callbacks()


def slo_summary(results) -> Dict[str, Optional[float]]:
    """TTFT p50/p99, TPOT and queue-wait percentiles over completed
    :class:`GenerationResult` futures — per-request lifecycle timestamps,
    no metrics plane needed. The SLO block ``tools/serving_bench.py`` and
    ``tools/quant_ab.py`` print beside tokens/s, and the numbers the
    continuous-batching work (ROADMAP item 1) must not regress: aggregate
    throughput that costs 10x TTFT is not a win."""
    slos = [r.slo() for r in results]
    ttfts = sorted(s["ttft_s"] for s in slos if s["ttft_s"] is not None)
    tpots = sorted(s["tpot_s"] for s in slos if s["tpot_s"] is not None)
    waits = sorted(s["queue_wait_s"] for s in slos
                   if s["queue_wait_s"] is not None)

    def pct(vals, q):
        if not vals:
            return None
        return vals[min(len(vals) - 1, int(q * (len(vals) - 1) + 0.5))]

    def ms(v):
        return None if v is None else round(v * 1e3, 2)

    return {
        "ttft_p50_ms": ms(pct(ttfts, 0.50)),
        "ttft_p99_ms": ms(pct(ttfts, 0.99)),
        "tpot_ms": ms(pct(tpots, 0.50)),
        "tpot_p99_ms": ms(pct(tpots, 0.99)),
        "queue_wait_p50_ms": ms(pct(waits, 0.50)),
        "queue_wait_p99_ms": ms(pct(waits, 0.99)),
    }


class GenerationRequest:
    def __init__(self, prompt_ids, max_new_tokens, temperature, top_k,
                 eos_token_id, deadline: Optional[float] = None,
                 prefix_len: Optional[int] = None):
        arr = np.asarray(prompt_ids, np.int32)
        if arr.ndim == 2 and arr.shape[0] == 1:
            arr = arr[0]
        if arr.ndim != 1:
            raise ValueError(
                f"submit() takes ONE prompt (1-D ids or [1, L]); got shape "
                f"{arr.shape} — submit a batch as separate requests, the "
                "engine batches compatible ones itself")
        self.prompt_ids = arr.reshape(1, -1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_token_id = eos_token_id
        self.deadline = deadline            # absolute time.monotonic(), or None
        # leading prompt tokens forming a SHARED prefix (system prompt) —
        # the paged engine content-hashes its page-aligned head so N
        # requests with one system prompt pay one prefill plus N tails
        self.prefix_len = None if prefix_len is None else int(prefix_len)
        self.id = next(_REQ_IDS)
        self.result = GenerationResult()
        self.result._req_id = self.id
        self.result._deadline = deadline

    def batch_key(self):
        # static-shape batching: same prompt length and sampling config share
        # one compiled decode program
        return (self.prompt_ids.shape[1], self.temperature, self.top_k,
                self.eos_token_id)


def _flag_or(value, flag_name, off_value=0):
    """Constructor default plumbing: explicit argument wins, else the
    FLAGS_serving_* flag. The "off" sentinel (0 / 0.0) maps to None from
    BOTH sources — an explicit ``max_queue=0`` means unbounded exactly like
    the flag's documented default, not a queue that sheds everything."""
    if value is None:
        value = _flags.flag_value(flag_name)
    return None if value == off_value else value


class ServingEngine:
    """Batched generation server over a model exposing ``generate_cached``."""

    def __init__(self, model, max_batch_size: int = 8,
                 max_wait_ms: float = 5.0, mode: str = "continuous",
                 max_len: Optional[int] = None, decode_chunk: int = 16,
                 max_queue: Optional[int] = None,
                 max_queue_wait_s: Optional[float] = None,
                 default_deadline_s: Optional[float] = None,
                 breaker_threshold: Optional[int] = None,
                 breaker_reset_s: Optional[float] = None,
                 decode_timeout_s: Optional[float] = None,
                 drain_timeout_s: Optional[float] = None,
                 drain_on_sigterm: bool = False,
                 quant: Optional[str] = None,
                 quant_group_size: int = -1,
                 kv_layout: str = "paged",
                 kv_page_size: int = 64,
                 kv_num_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 mesh=None,
                 plan=None,
                 bundle: Optional[str] = None,
                 draft=None,
                 spec_k: int = 0,
                 draft_quant: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 kv_host_bytes: Optional[int] = None):
        if mode not in ("continuous", "static"):
            raise ValueError(f"mode must be 'continuous' or 'static', got {mode!r}")
        if (kv_quant not in (None, "", "off")
                or kv_host_bytes) and mode != "continuous":
            raise ValueError(
                "kv_quant/kv_host_bytes require the continuous engine — "
                "the paged KV pool (int8 pages, host spill tier) lives "
                "there; static mode decodes through generate_cached")
        if (draft is not None or spec_k) and mode != "continuous":
            raise ValueError(
                "speculative decoding (draft=/spec_k=) requires the "
                "continuous engine — static mode decodes through the "
                "model's own generate_cached")
        if bundle is not None and mode != "continuous":
            raise ValueError(
                "bundle= requires the continuous engine (static mode "
                "decodes through the model's own generate_cached; AOT "
                "bundles serialize the decode engine's compiled programs)")
        if quant is not None and mode != "continuous":
            raise ValueError(
                "quant mode requires the continuous engine (static mode "
                "decodes through the model's own generate_cached, whose "
                "bound params are full precision)")
        if (mesh is not None or plan is not None) and mode != "continuous":
            raise ValueError(
                "tensor-parallel serving (mesh=/plan=) requires the "
                "continuous engine — static mode decodes through the "
                "model's own generate_cached, whose bound params are "
                "single-chip")
        self.model = model
        self.mode = mode
        self.max_batch_size = max_batch_size
        self.max_wait = max_wait_ms / 1e3
        self._queue: "queue.Queue[GenerationRequest]" = queue.Queue()
        self._deferred: "deque[GenerationRequest]" = deque()  # FIFO, drained
        # ahead of the queue — a batch-incompatible request parks here and
        # becomes a later leader instead of rotating behind newer arrivals
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._drained = threading.Event()
        self._drain_reason = "drain"   # metric label for drain-shed
        #   requests: "drain" unless the caller marked the drain
        #   deliberate ("scale_down", "sigterm", ...)
        self._thread: Optional[threading.Thread] = None
        self._watchdog_thread: Optional[threading.Thread] = None
        self._stats_lock = threading.Lock()
        self.stats = {"requests": 0, "batches": 0, "batched_requests": 0,
                      "decode_tokens": 0, "batches_failed": 0, "shed": 0,
                      "cancelled": 0, "deadline_expired": 0,
                      "decode_failures": 0,
                      # the continuous loop's phase clock (docs/serving.md)
                      **phase_counters(LOOP_PHASES), "admit_deferred": 0,
                      "turnaround_s": 0.0, "turnaround_n": 0,
                      "loop_busy_s": 0.0,
                      # pages the decode steps gathered, over the table's
                      "decode_view_pages": 0, "decode_table_pages": 0,
                      # admissions by kind, tokens computed and taken cached
                      **dict.fromkeys(ADMIT_COUNTERS, 0),
                      # decode calls, and by the sampler branch they asked for
                      "decode_calls": 0, **dict.fromkeys(SAMPLE_COUNTERS, 0)}
        # robustness limits: explicit args win, else FLAGS_serving_* (whose
        # 0 default means "off"), so a fleet can arm them by env alone
        self.max_queue = _flag_or(max_queue, "serving_max_queue")
        self.max_queue_wait_s = _flag_or(max_queue_wait_s,
                                         "serving_max_queue_wait_s", 0.0)
        self.default_deadline_s = _flag_or(default_deadline_s,
                                           "serving_default_deadline_s", 0.0)
        self.decode_timeout_s = _flag_or(decode_timeout_s,
                                         "serving_decode_timeout_s", 0.0)
        self.drain_timeout_s = (drain_timeout_s if drain_timeout_s is not None
                                else _flags.flag_value("serving_drain_timeout_s"))
        self._breaker = CircuitBreaker(
            threshold=(breaker_threshold if breaker_threshold is not None
                       else _flags.flag_value("serving_breaker_threshold")),
            reset_s=(breaker_reset_s if breaker_reset_s is not None
                     else _flags.flag_value("serving_breaker_reset_s")),
            on_transition=self._on_breaker_transition)
        self._estimator = QueueWaitEstimator()
        self._static_inflight = 0     # static scheduler's current batch size
        self._decode_started_at: Optional[float] = None
        self._hang_tripped = False
        self._last_decode_ok: Optional[float] = None
        self._drain_on_sigterm = bool(drain_on_sigterm)
        self._limits_armed = (self.max_queue is not None
                              or self.max_queue_wait_s is not None)
        self._engine = None
        self.quant = quant
        if mode == "continuous":
            from .decode_engine import BatchDecodeEngine

            self._engine = BatchDecodeEngine(
                model, max_slots=max_batch_size, max_len=max_len,
                chunk=decode_chunk, quant=quant,
                quant_group_size=quant_group_size, kv_layout=kv_layout,
                page_size=kv_page_size, num_pages=kv_num_pages,
                prefix_cache=prefix_cache, mesh=mesh, plan=plan,
                bundle=bundle, draft=draft, spec_k=spec_k,
                draft_quant=draft_quant, kv_quant=kv_quant,
                kv_host_bytes=kv_host_bytes)
            self._spec_enabled = self._engine.spec is not None
            if self._spec_enabled:
                self._announce_spec()
            self._max_len = self._engine.L
            self._top_k_cap = self._engine.TOP_K_CAP
            # page-pool capacity admission facts (None = contiguous): a
            # request needing more pages than the pool HOLDS must be shed
            # at submit, not deadlock at the head of the queue
            self._kv_page_size = (self._engine.page_size
                                  if kv_layout == "paged" else None)
            self._kv_capacity = (self._engine.pool.usable
                                 if kv_layout == "paged" else None)
            try:
                from ..observability import flight

                # CALLABLE annotation (resolved at dump time): a crash
                # dump carries the pool occupancy / prefix-hit state at
                # the moment of death, not at construction. Weakly bound:
                # the module-global annotation dict must not pin a
                # dropped engine's device buffers (params + KV pools)
                # alive for the life of the process
                eng_ref = weakref.ref(self._engine)

                def _kv_annotation():
                    eng = eng_ref()
                    return (eng.kv_stats() if eng is not None
                            else {"layout": "engine-released"})

                flight.annotate("serving_kv", _kv_annotation)
            except Exception:
                pass
            if quant is not None:
                self._announce_quant(self._engine.quant_meta)
            if (self._engine.kv_quant is not None
                    or self._engine.kv_host is not None):
                self._announce_kv_memory()
        else:
            self._max_len = max_len or getattr(
                getattr(model, "config", None), "max_position_embeddings",
                None)
            self._top_k_cap = None
            self._kv_page_size = None
            self._kv_capacity = None
            self._spec_enabled = False

    def _bump(self, key, n=1):
        with self._stats_lock:
            self.stats[key] += n

    def _announce_quant(self, meta: Dict[str, object]) -> None:
        """One-time (construction, cold path) observability for quant mode:
        paddle_serving_quant_* metrics, the flight-recorder header
        annotation, and a stderr line. With quant off NONE of this runs —
        the off path stays zero-overhead (check_serving_overhead.py)."""
        _safe_set("paddle_serving_quant_enabled",
                  "serving weight-only quantization armed (1 = on)", 1,
                  mode=self.quant)
        _safe_set("paddle_serving_quant_weights",
                  "matmul weights quantized by the serving engine",
                  len(meta.get("quantized", ())))
        _safe_set("paddle_serving_quant_bytes_saved",
                  "HBM weight bytes a decode step no longer reads",
                  meta.get("bytes_saved", 0))
        try:
            from ..observability import flight

            flight.annotate("serving_quant", {
                "mode": self.quant,
                "group_size": meta.get("group_size", -1),
                "weights": len(meta.get("quantized", ())),
                "bytes_saved": meta.get("bytes_saved", 0)})
        except Exception:
            pass
        sys.stderr.write(
            f"[serving] weight-only quant armed: {self.quant}, "
            f"{len(meta.get('quantized', ()))} weights, "
            f"{meta.get('bytes_saved', 0) / 1e6:.1f} MB HBM reads saved "
            "per full weight pass\n")

    def _announce_kv_memory(self) -> None:
        """One-time (construction, cold path) observability for the KV
        memory levers (ROADMAP item 4): int8 KV pages and/or the host-RAM
        prefix tier. Off path runs none of this."""
        eng = self._engine
        parts = []
        if eng.kv_quant is not None:
            parts.append(f"kv_quant={eng.kv_quant}")
        if eng.kv_host is not None:
            _safe_set("paddle_serving_kv_host_budget_bytes",
                      "byte budget of the host-RAM prefix spill tier",
                      eng.kv_host.max_bytes)
            parts.append(
                f"host tier {eng.kv_host.max_bytes / 1e6:.1f} MB")
        sys.stderr.write(
            f"[serving] KV memory: {', '.join(parts)} "
            f"({eng.pool.usable} device pages x "
            f"{eng.kv_stats()['page_bytes']} B)\n")

    def _announce_spec(self) -> None:
        """One-time (construction, cold path) observability for
        speculative decoding: gauges + a stderr line. The flight-recorder
        ``serving_spec`` header annotation (draft arch, k, live
        acceptance at dump time) is installed by the decoder itself. With
        speculation off none of this runs — the off path stays
        zero-overhead."""
        spec = self._engine.spec
        draft = spec.describe_draft()
        _safe_set("paddle_serving_spec_enabled",
                  "speculative decoding armed (1 = on)", 1,
                  k=spec.k, draft_quant=spec.draft_quant or "off")
        _safe_set("paddle_serving_spec_k",
                  "draft proposals per speculative target step", spec.k)
        sys.stderr.write(
            f"[serving] speculative decoding armed: k={spec.k}, draft "
            f"{draft['params_m']}M params ({draft['hidden_size']}h x "
            f"{draft['num_hidden_layers']}L, quant {draft['quant']})\n")

    # -- admission control ---------------------------------------------------
    def _on_breaker_transition(self, old: str, new: str) -> None:
        sys.stderr.write(f"[serving] circuit breaker {old} -> {new}\n")
        _safe_inc("paddle_serving_breaker_transitions_total",
                  "serving circuit-breaker state transitions", to=new)
        _safe_set("paddle_serving_breaker_state",
                  "serving breaker state (0 closed, 1 half-open, 2 open)",
                  _BREAKER_STATE_NUM[new])
        try:
            from ..observability import flight

            flight.record("breaker", "serving",
                          **{"from": old, "to": new})
            if new == "open":
                # an opening breaker means the engine is sick; capture the
                # black box while the evidence (recent decode failures, the
                # engine thread's stack) is still in the ring. Deferred to
                # a thread: this callback runs UNDER the breaker lock, and
                # a dump fsync (possibly to network storage) must not
                # freeze every submit's allow() check behind it
                threading.Thread(
                    target=lambda: flight.dump("breaker_open"),
                    daemon=True, name="flight-breaker-dump").start()
        except Exception:
            pass

    def _shed(self, reason: str, exc: BaseException) -> None:
        self._bump("shed")
        _safe_inc("paddle_serving_shed_total",
                  "requests shed by serving admission control, by reason",
                  reason=reason)
        try:
            from ..observability import flight

            flight.record("shed", reason)
        except Exception:
            pass
        raise exc

    def _queue_depth(self) -> int:
        return self._queue.qsize() + len(self._deferred)

    def _check_admission(self, req: GenerationRequest) -> None:
        """Every reason a request may not enter the queue, cheapest first.
        With no limits configured this is a handful of attribute reads
        (breaker state is read lock-free while closed) —
        tools/check_serving_overhead.py holds that path under 5% vs seed."""
        if req.max_new_tokens < 1:
            raise RequestValidationError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens}")
        ml = self._max_len
        if ml is not None and req.prompt_ids.shape[1] + req.max_new_tokens > ml:
            raise RequestValidationError(
                f"prompt {req.prompt_ids.shape[1]} + {req.max_new_tokens} "
                f"new tokens exceeds engine max_len {ml} (model "
                f"max_position_embeddings caps the KV cache) — shorten the "
                "prompt or lower max_new_tokens")
        if self._top_k_cap is not None and req.top_k > self._top_k_cap:
            raise RequestValidationError(
                f"top_k {req.top_k} exceeds the continuous engine's static "
                f"filter cap {self._top_k_cap} (use the static "
                "serving mode or lower top_k)")
        if self._spec_enabled and req.temperature > 0.0:
            raise RequestValidationError(
                f"temperature {req.temperature:g} with speculative "
                "decoding armed: greedy acceptance is token-exact for "
                "temperature 0 only (sampling-correct rejection "
                "resampling is a planned seam) — send temperature=0 or "
                "serve this engine without spec_k")
        if req.prefix_len is not None and not (
                0 <= req.prefix_len <= req.prompt_ids.shape[1]):
            raise RequestValidationError(
                f"prefix_len {req.prefix_len} must be within the prompt "
                f"(length {req.prompt_ids.shape[1]})")
        if self._kv_capacity is not None:
            # page-pool capacity, not just max_len: a pool sized below
            # slots x max_len can be too small for a request that passes
            # the length check — shed it typed instead of queueing
            # forever. Total need governs even on a prefix hit (the
            # pinned prefix pages occupy capacity too), so this check is
            # EXACT — the engine's own raise can only fire for direct
            # BatchDecodeEngine users
            ps = self._kv_page_size
            need = pages_needed(
                req.prompt_ids.shape[1] + req.max_new_tokens, ps)
            if need > self._kv_capacity:
                self._shed("kv_capacity", KVCapacityError(
                    f"prompt {req.prompt_ids.shape[1]} + "
                    f"{req.max_new_tokens} new tokens needs {need} KV pages "
                    f"(page_size {ps}) but the pool holds only "
                    f"{self._kv_capacity} even when empty — raise "
                    "kv_num_pages or shorten the request",
                    pages_needed=need, pages_capacity=self._kv_capacity))
        if self._draining.is_set():
            self._shed("draining", EngineDrainingError(
                "serving engine is draining; no new requests admitted"))
        breaker = self._breaker
        if breaker._state != "closed" and not breaker.allow():
            self._shed("breaker_open", CircuitOpenError(
                f"decode circuit breaker is open after "
                f"{breaker.consecutive_failures} consecutive "
                "failures; submits fail fast until a half-open probe "
                "succeeds",
                retry_after_s=breaker.retry_after_s()))
        if req.deadline is not None and time.monotonic() >= req.deadline:
            self._bump("deadline_expired")
            self._shed("deadline", DeadlineExceededError(
                "request deadline expired before admission"))
        if self._limits_armed:
            depth = self._queue_depth()
            est = self._estimator.estimate_wait_s(depth, self.max_batch_size)
            if self.max_queue is not None and depth >= self.max_queue:
                self._shed("queue_full", ServerOverloadedError(
                    f"serving queue full ({depth} >= max_queue "
                    f"{self.max_queue})", queue_depth=depth,
                    retry_after_s=max(est, self.max_wait)))
            if (self.max_queue_wait_s is not None
                    and est > self.max_queue_wait_s):
                self._shed("queue_wait", ServerOverloadedError(
                    f"estimated queue wait {est:.2f}s exceeds "
                    f"max_queue_wait_s {self.max_queue_wait_s:g}",
                    queue_depth=depth, retry_after_s=est))
        chaos_point("serving.admit")

    # -- client API ----------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=32, temperature=0.0,
               top_k=0, eos_token_id=None,
               deadline_s: Optional[float] = None,
               prefix_len: Optional[int] = None,
               trace=None) -> GenerationResult:
        """Queue one generation request; raises a typed
        :mod:`~.robustness` error instead of queueing when the request
        cannot (validation), or should not (overload, open breaker,
        draining, expired deadline), be served. ``prefix_len`` declares
        the leading shared prefix (system prompt) for the paged engine's
        prompt cache; ignored by the static scheduler and the contiguous
        layout. ``trace`` is a propagated request journey
        (:mod:`~..observability.reqtrace`) — the router passes its
        journey across the replica seam here; with none passed and
        tracing armed, the engine mints one (and this future owns it)."""
        dl = deadline_s if deadline_s is not None else self.default_deadline_s
        req = GenerationRequest(
            prompt_ids, max_new_tokens, temperature, top_k, eos_token_id,
            deadline=None if dl is None else time.monotonic() + dl,
            prefix_len=prefix_len)
        self._check_admission(req)
        tr = trace
        if tr is None:
            try:
                from ..observability import reqtrace as _rt

                if _rt.enabled():
                    tr = _rt.mint(req.id)
                    req.result._trace_owner = tr is not None
            except Exception:
                tr = None
        req.result._trace = tr
        if tr is not None:
            tr.event("engine.submit", prompt=req.prompt_ids.shape[1],
                     budget=req.max_new_tokens,
                     queue_depth=self._queue_depth())
        _flight_record("request", str(req.id), phase="submit",
                       prompt=req.prompt_ids.shape[1],
                       budget=req.max_new_tokens,
                       queue_depth=self._queue_depth())
        if self._thread is None:
            self.start()  # lazy start: a future must always have a server
        self._bump("requests")
        self._queue.put(req)
        if self._draining.is_set():
            # lost the race with a concurrent drain(): its shed sweep may
            # already have passed this request by, and a loop thread (re)
            # started above exits immediately while draining — fail the
            # future here so no caller blocks on a request no server owns
            t = self._thread
            if (t is None or not t.is_alive() or self._drained.is_set()) \
                    and not req.result.done():
                self._bump("shed")
                _safe_inc("paddle_serving_shed_total",
                          "requests shed by serving admission control, "
                          "by reason", reason="draining")
                req.result._set(error=EngineDrainingError(
                    "serving engine drained while the request was being "
                    "submitted"))
        return req.result

    def generate(self, prompt_ids, timeout: float = 300.0, **kw) -> np.ndarray:
        return self.submit(prompt_ids, **kw).result(timeout)

    # -- cold-start control --------------------------------------------------
    def warmup(self) -> Dict[str, object]:
        """Compile the engine's whole plan eagerly so the first request
        never lands on a cold program (the router pre-warms restarted
        replicas through this before re-admission). Static mode has no
        plan to walk — its programs belong to the model's own
        ``generate_cached`` — so it returns a no-op summary rather than
        raising: a fleet can warm heterogeneous replicas blindly."""
        if self._engine is None:
            return {"programs": 0, "compiled": 0, "skipped": 0,
                    "wall_s": 0.0, "mode": "static"}
        return self._engine.warmup()

    def save_serving_bundle(self, path: str) -> Dict[str, object]:
        """Serialize the decode engine's compiled programs + manifest to
        ``path`` — the artifact ``ServingEngine(..., bundle=path)`` then
        serves from with zero retraces (see docs/serving.md)."""
        if self._engine is None:
            raise ValueError(
                "save_serving_bundle requires the continuous engine")
        return self._engine.save_serving_bundle(path)

    def health(self) -> Dict[str, object]:
        """Readiness/liveness snapshot — what a probe endpoint (or the C
        protocol's ``_OP_HEALTH`` frame) reports."""
        now = time.monotonic()
        alive = self._thread is not None and self._thread.is_alive()
        state = ("draining" if self._draining.is_set() and alive
                 else "serving" if alive else "stopped")
        busy = self._engine.busy_slots() if self._engine is not None else 0
        started = self._decode_started_at
        breaker = self._breaker.state
        with self._stats_lock:
            stats = dict(self.stats)
        kv = (self._engine.kv_stats() if self._engine is not None
              else {"layout": "none"})
        mesh = (self._engine.mesh_info() if self._engine is not None
                else {"enabled": False})
        if self._engine is not None:
            compile_block = self._engine.compile_info()
        else:
            from ..core import compile_cache as _cc

            compile_block = {"cache": _cc.stats()}
        est = self._estimator.estimate_wait_s(self._queue_depth(),
                                              self.max_batch_size)
        try:
            from ..observability import reqtrace as _rt

            slo_burn = _rt.burn_snapshot()
        except Exception:
            slo_burn = {"enabled": False}
        try:
            from ..observability import goodput as _goodput

            goodput_block = _goodput.snapshot()
        except Exception:
            goodput_block = {"kinds": {}}
        return {
            "state": state,
            # useful-vs-wasted token ledger (observability.goodput): the
            # remote-fleet bench sums this across replica healths to get
            # fleet goodput_tok_s / waste_pct — a socket replica's ledger
            # lives in ITS process, not the router's
            "goodput": goodput_block,
            "mode": self.mode,
            # sliding-window SLO burn rate vs FLAGS_slo_{ttft,tpot}_ms —
            # the signal the SLO-driven autoscaler (ROADMAP item 5)
            # closes its scale-up/down loop on
            "slo_burn": slo_burn,
            "quant": self.quant or "off",
            "kv": kv,
            # speculative decoding: draft config, k, live acceptance rate
            # and tokens-per-target-step — what a deploy watches to know
            # the speculation is actually paying for its draft overhead
            "spec": (self._engine.spec_info() if self._engine is not None
                     else {"enabled": False}),
            # replica parallelism for the fleet router / /metrics: mesh
            # axes+devices and the tp degree this engine decodes at
            "mesh": mesh,
            # cold-start state: compile plan + warmup/bundle status +
            # persistent-cache counters — what a deploy watches to know a
            # restarted replica is warm before routing to it
            "compile": compile_block,
            "ok": alive and not self._draining.is_set()
                  and breaker != "open",
            "queue_depth": self._queue_depth(),
            "busy_slots": busy,
            # the fields the fleet router balances on, surfaced through
            # /healthz unchanged: estimated wait for a NEW request,
            # requests currently being decoded, KV headroom (None when
            # the engine has no paged pool)
            "est_wait_s": est,
            "inflight": busy if self.mode == "continuous"
                        else self._static_inflight,
            "pages_free": kv.get("pages_free"),
            "max_slots": self.max_batch_size,
            "max_queue": self.max_queue,
            "breaker": breaker,
            "breaker_consecutive_failures":
                self._breaker.consecutive_failures,
            "decode_inflight_s":
                0.0 if started is None else now - started,
            "last_decode_ok_age_s":
                None if self._last_decode_ok is None
                else now - self._last_decode_ok,
            "estimated_queue_wait_s": est,
            "stats": stats,
        }

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        if self._thread is None:
            if self._draining.is_set():
                # restart after a COMPLETED drain (thread gone): re-open
                # admission and re-arm the failure machinery — the drained
                # engine's breaker history and hang latch belong to the
                # previous serving epoch, not this one. Rolling restarts
                # (inference/router.py) depend on this: drain -> start
                # must yield a replica that admits again.
                self._draining.clear()
                self._breaker.reset()
                self._hang_tripped = False
                self._decode_started_at = None
            self._stop.clear()
            self._drained.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()
            if self.decode_timeout_s is not None \
                    and (self._watchdog_thread is None
                         or not self._watchdog_thread.is_alive()):
                self._watchdog_thread = threading.Thread(
                    target=self._watchdog_loop, daemon=True)
                self._watchdog_thread.start()
            if self._drain_on_sigterm:
                self.install_preemption_hook()
            # if this process runs a telemetry exporter, serve this
            # engine's readiness under /healthz (the HTTP analogue of the
            # C protocol's _OP_HEALTH frame)
            try:
                from ..observability import exporter as _exporter

                served = _exporter.get()
                if served is not None:
                    # unique: a second engine in this process must not
                    # clobber the first's provider entry
                    self._health_reg_name = served.register_health(
                        "serving", self.health, unique=True)
            except Exception:
                pass
        return self

    def install_preemption_hook(self, timeout: Optional[float] = None):
        """Register ``drain(timeout)`` as a preemption emergency callback:
        a SIGTERM'd serving host finishes in-flight requests (bounded by
        the drain timeout), sheds the rest with a typed error, and only
        then exits 143 — instead of futures dying mid-decode."""
        from ..resilience.preemption import install_preemption_handler

        return install_preemption_handler(
            lambda: self.drain(timeout, reason="sigterm"))

    def drain(self, timeout: Optional[float] = None,
              reason: str = "drain") -> Dict[str, object]:
        """Graceful shutdown: stop admission (submits raise
        :class:`EngineDrainingError`), let in-flight slots finish up to
        ``timeout`` seconds, shed everything still waiting with a typed
        error, then stop the engine thread. Idempotent. ``reason`` labels
        the shed/drain accounting — a DELIBERATE drain (the fleet
        controller's ``scale_down``, a preemption's ``sigterm``) must read
        as an operator action in the metrics, not as failure evidence."""
        timeout = self.drain_timeout_s if timeout is None else timeout
        t0 = time.monotonic()
        self._drain_reason = str(reason)
        self._draining.set()
        finished = True
        if self._thread is not None:
            finished = self._drained.wait(timeout)
        with self._stats_lock:
            shed_before = self.stats["shed"]
        try:
            self._shutdown(EngineDrainingError(
                "request shed: serving engine drained before it was served"))
        except RuntimeError:
            finished = False       # engine thread overran the stop join
        with self._stats_lock:
            shed = self.stats["shed"] - shed_before
        _safe_inc("paddle_serving_drains_total",
                  "graceful drains completed",
                  outcome="clean" if finished else "timeout",
                  reason=self._drain_reason)
        obs = _obs_srv
        if obs is not None:
            obs("queue_depth", 0)
        return {"clean": finished, "shed": shed,
                "wall_s": round(time.monotonic() - t0, 3)}

    def _shed_waiting(self, error: BaseException) -> int:
        """Fail everything queued or deferred (engine thread must be down
        or draining-idle; the deque is only touched by a live loop)."""
        n = 0
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if not req.result.done():
                req.result._set(error=error)
                n += 1
        while self._deferred:
            req = self._deferred.popleft()
            if not req.result.done():
                req.result._set(error=error)
                n += 1
        if n:
            self._bump("shed", n)
            _safe_inc("paddle_serving_shed_total",
                      "requests shed by serving admission control, by reason",
                      n, reason=self._drain_reason if isinstance(
                          error, EngineDrainingError) else "stop")
        return n

    def stop(self):
        # deliberate stop: a later /healthz must not keep reporting this
        # engine (a stopped-on-purpose engine is not an unhealthy process)
        try:
            from ..observability import exporter as _exporter

            served = _exporter.get()
            if served is not None:
                # guarded: only drop OUR entry, never a sibling engine's
                served.unregister_health(
                    getattr(self, "_health_reg_name", "serving"),
                    fn=self.health)
        except Exception:
            pass
        self._shutdown(RuntimeError("serving engine stopped"))

    def _shutdown(self, shed_error: BaseException):
        self._stop.set()
        overran = False
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # a mid-compile loop can overrun the join: keep the handle
                # so a later submit() cannot start a SECOND loop over the
                # same slot state; futures are still failed below so no
                # caller blocks, and we raise only after the cleanup
                overran = True
            else:
                self._thread = None
        if self._watchdog_thread is not None \
                and not self._watchdog_thread.is_alive():
            self._watchdog_thread = None
        # fail whatever is still queued or mid-decode: a caller must never
        # block on a future no server will serve
        self._shed_waiting(shed_error)
        if self._engine is not None:
            kind = ("drain" if isinstance(shed_error, EngineDrainingError)
                    else "stop")
            for i, s in enumerate(self._engine._host_slots):
                if s.req is not None and not s.req.result.done():
                    s.req.result._set(error=shed_error)
                    # mid-flight output abandoned by the shutdown
                    _goodput_account(kind, len(s.emitted))
                    self._engine._host_slots[i] = type(s)()
            self._engine.reset_slots()  # no phantom active device lanes
        if overran:
            raise RuntimeError(
                "serving engine thread did not stop within 30s (likely "
                "mid-compile); outstanding futures were failed; call "
                "stop() again to re-wait")

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        try:
            self.stop()
        except RuntimeError:
            if exc_type is None:
                raise  # don't mask the with-body's original exception
        return False

    # -- scheduler -----------------------------------------------------------
    def _precheck(self, req: GenerationRequest) -> bool:
        """True when a popped request should be served; cancelled/expired
        ones are failed (shed) here, BEFORE they cost any decode."""
        if req.result._event.is_set():  # cancel() already failed the future
            self._bump("cancelled")
            _safe_inc("paddle_serving_cancelled_total",
                      "requests cancelled by clients")
            return False
        if req.deadline is not None and time.monotonic() >= req.deadline:
            self._bump("deadline_expired")
            _safe_inc("paddle_serving_shed_total",
                      "requests shed by serving admission control, by reason",
                      reason="deadline")
            req.result._set(error=DeadlineExceededError(
                "request deadline expired while queued"))
            return False
        return True

    def _next_request(self, block: bool,
                      timeout: float = 0.05) -> Optional[GenerationRequest]:
        """Pop the next serveable request: the deferred FIFO drains ahead
        of the queue (no reordering behind newer arrivals)."""
        while self._deferred:
            req = self._deferred.popleft()
            if self._precheck(req):
                return req
        while True:
            try:
                req = (self._queue.get(timeout=timeout) if block
                       else self._queue.get_nowait())
            except queue.Empty:
                return None
            if self._precheck(req):
                return req

    def _requeue_expired_sweep(self) -> None:
        """While the breaker is open nothing is popped for decode — sweep
        the waiting set so expired/cancelled requests still shed promptly.
        Queue entries migrate to the deferred FIFO (which drains first), so
        arrival order is preserved."""
        while True:
            try:
                self._deferred.append(self._queue.get_nowait())
            except queue.Empty:
                break
        kept = deque(r for r in self._deferred if self._precheck(r))
        self._deferred = kept

    def _collect_batch(self) -> List[GenerationRequest]:
        """One leader request + everything compatible, up to max_batch_size:
        first from the deferred FIFO, then whatever arrives within the
        batching window. Incompatible queue arrivals are parked in the
        deferred FIFO — drained ahead of the queue next cycle, so a
        mismatched request becomes the next leader instead of starving
        behind a stream of compatible newer ones."""
        leader = self._next_request(block=True, timeout=0.1)
        if leader is None:
            return []
        if self._breaker.state == "half_open":
            return [leader]     # one-request probe decides the breaker
        batch = [leader]
        keep: "deque[GenerationRequest]" = deque()
        while self._deferred and len(batch) < self.max_batch_size:
            req = self._deferred.popleft()
            if not self._precheck(req):
                continue
            if req.batch_key() == leader.batch_key():
                batch.append(req)
            else:
                keep.append(req)
        self._deferred.extendleft(reversed(keep))  # keep FIFO order
        deadline = time.monotonic() + self.max_wait
        while len(batch) < self.max_batch_size:
            rest = deadline - time.monotonic()
            if rest <= 0:
                break
            try:
                req = self._queue.get(timeout=rest)
            except queue.Empty:
                break
            if not self._precheck(req):
                continue
            if req.batch_key() == leader.batch_key():
                batch.append(req)
            else:
                self._deferred.append(req)  # FIFO-parked, next cycle's leader
        return batch

    def _watchdog_loop(self):
        """Engine-thread watchdog: a decode attempt that exceeds
        ``decode_timeout_s`` trips the breaker — the hung thread cannot be
        interrupted (it may be inside XLA), but new submits fail fast and
        health() goes not-ok instead of the queue silently growing."""
        interval = max(0.005, min(1.0, self.decode_timeout_s / 4))
        while not self._stop.wait(interval):
            started = self._decode_started_at
            if (started is not None and not self._hang_tripped
                    and time.monotonic() - started > self.decode_timeout_s):
                self._hang_tripped = True
                sys.stderr.write(
                    f"[serving] decode in flight for more than "
                    f"{self.decode_timeout_s:g}s — tripping breaker\n")
                _safe_inc("paddle_serving_decode_hangs_total",
                          "decode attempts the watchdog declared hung")
                self._breaker.trip()

    def _decode_attempt(self, fn) -> bool:
        """Run one decode attempt (a static batch or a continuous chunk)
        under the chaos seam, the hang watchdog and the breaker. Returns
        True on success; on failure the caller has already been handed the
        exception via ``fn``'s own cleanup contract."""
        self._hang_tripped = False
        self._decode_started_at = time.monotonic()
        try:
            chaos_point("serving.decode")
            fn()
        finally:
            dt = time.monotonic() - self._decode_started_at
            self._decode_started_at = None
        self._estimator.observe(dt)
        return True

    def _loop(self):
        try:
            if self.mode == "continuous":
                self._loop_continuous()
            else:
                self._loop_static()
        finally:
            self._drained.set()

    def _loop_static(self):
        obs = None
        while not self._stop.is_set():
            if self._draining.is_set():
                return   # current batch finished; drain() sheds the rest
            obs = _obs_srv
            if obs is not None:
                obs("queue_depth", self._queue_depth())
            if not self._breaker.allow():
                self._requeue_expired_sweep()
                time.sleep(0.02)
                continue
            batch = self._collect_batch()
            if not batch:
                continue
            self._static_inflight = len(batch)
            try:
                self._decode_attempt(lambda: self._run_static_batch(batch))
            except BaseException as e:  # noqa: BLE001 — deliver to callers
                for req in batch:
                    req.result._set(error=e)
                self._bump("batches_failed")
                self._bump("decode_failures")
                self._breaker.record_failure()
                if obs is not None:
                    obs("batch", "error")
                continue
            finally:
                self._static_inflight = 0
            # outcome-tagged accounting AFTER the attempt: a failed batch
            # must not count as served
            self._breaker.record_success()
            self._last_decode_ok = time.monotonic()
            self._bump("batches")
            self._bump("batched_requests", len(batch))
            if obs is not None:
                obs("batch_size", len(batch))
                obs("batch", "ok")

    def _run_static_batch(self, batch: List[GenerationRequest]) -> None:
        ids = np.concatenate([r.prompt_ids for r in batch], axis=0)
        leader = batch[0]
        t_admit = time.perf_counter()
        for req in batch:
            req.result._t_admit = t_admit
            tr = req.result._trace
            if tr is not None:
                tr.event("queue.wait", t0=req.result._t_submit, t1=t_admit)
                tr.event("admit", mode="static", batch=len(batch),
                         plen=leader.prompt_ids.shape[1])
        out = self.model.generate_cached(
            ids,
            max_new_tokens=max(r.max_new_tokens for r in batch),
            temperature=leader.temperature, top_k=leader.top_k,
            eos_token_id=leader.eos_token_id)
        out = np.asarray(out.numpy())
        t_first = time.perf_counter()  # no streaming in static mode: the
        plen = leader.prompt_ids.shape[1]  # first token lands with the batch
        lockstep = max(r.max_new_tokens for r in batch)
        useful = overshoot = 0
        for i, req in enumerate(batch):
            row = out[i, : plen + req.max_new_tokens]
            req.result._t_first = t_first     # TTFT == full latency here
            req.result._streaming = False     # ... and TPOT is undefined,
            # not "microseconds/token" — slo() reports it as None
            gen = row[plen:]
            eos = req.eos_token_id
            if eos is not None and eos in gen:  # don't count post-eos pad
                gen = gen[: int(np.argmax(gen == eos)) + 1]
            req.result._n_new = len(gen)
            # static batches decode max(max_new_tokens) for EVERY row in
            # lockstep: the post-eos / past-budget tail is real decode
            # work the caller never sees. Summed across the batch, two
            # ledger calls total — accounting must not tax the fast path
            useful += len(gen)
            overshoot += lockstep - len(gen)
            tr = req.result._trace
            if tr is not None:
                tr.event("decode.batch", t0=t_admit, t1=t_first,
                         tokens=len(gen))
            req.result._set(output=row)
        _goodput_account("useful", useful)
        _goodput_account("overshoot", overshoot)

    def _sweep_slots(self) -> None:
        """Release in-flight slots whose client departed (cancel) or whose
        deadline passed — the chip stops spending on them mid-decode."""
        eng = self._engine
        now = time.monotonic()
        for i, s in enumerate(eng._host_slots):
            req = s.req
            if req is None:
                continue
            if req.result.done():       # cancelled (first outcome won)
                eng.release_slot(i, reason=getattr(
                    req.result, "_cancel_kind", "cancel"))
                self._bump("cancelled")
                _safe_inc("paddle_serving_cancelled_total",
                          "requests cancelled by clients")
            elif req.deadline is not None and now >= req.deadline:
                req.result._set(error=DeadlineExceededError(
                    "request deadline expired mid-decode"))
                eng.release_slot(i, reason="deadline")
                self._bump("deadline_expired")
                _safe_inc("paddle_serving_shed_total",
                          "requests shed by serving admission control, "
                          "by reason", reason="deadline")

    def _loop_continuous(self):
        """Continuous batching: admit queued requests into free decode slots,
        run multi-step decode chunks, retire finished slots mid-flight. The
        BatchDecodeEngine delivers each request's future on retirement."""
        eng = self._engine
        stats = self.stats
        while not self._stop.is_set():
            t_iter = time.perf_counter()
            waited = stats["span_s.serve.wait_request"]
            with phase("serve.sweep", stats):
                self._sweep_slots()
            busy = any(s.req is not None for s in eng._host_slots)
            draining = self._draining.is_set()
            if draining and not busy:
                return               # in-flight finished; drain() sheds rest
            admitted = False
            if not draining:
                if self._breaker.allow():
                    probe = self._breaker.state == "half_open"
                    while True:
                        req = self._next_request(block=False)
                        if req is None and not busy:
                            with phase("serve.wait_request", stats):
                                req = self._next_request(block=True)
                            # idle for want of work: what follows the last
                            # sync is no turn-around of the host's
                            eng._t_synced = None
                        if req is None:
                            break
                        # one count per admission; a refusal (no slot,
                        # no pages) is counted apart, as admit_deferred
                        try:
                            with phase("serve.admit", stats, n=0) as admit:
                                ok = eng._admit(req)
                                admit.n = int(ok)
                            if ok:
                                admitted = True
                                busy = True
                                self._bump("batched_requests")
                                if probe:
                                    break   # one-request half-open probe
                            else:
                                # no free slot: hold at the FIFO head, decode
                                # to free one — never rotated behind arrivals
                                self._deferred.appendleft(req)
                                stats["admit_deferred"] += 1
                                break
                        except BaseException as e:  # noqa: BLE001
                            req.result._set(error=e)
                elif not busy:
                    self._requeue_expired_sweep()
                    time.sleep(0.02)
                    continue
            obs = _obs_srv
            if obs is not None:
                obs("queue_depth", self._queue_depth())
            if not busy:
                continue
            if obs is not None:
                obs("batch_size",
                    sum(1 for s in eng._host_slots if s.req is not None))
            before = eng.stats["tokens_out"]
            # the host's part of the iteration is booked before the chunk
            # delivers anything, the chunk's part after it: whoever copies
            # stats on receiving a result finds the seconds and the spans
            # of the same work in them
            t_chunk = self._book_busy(
                t_iter, stats["span_s.serve.wait_request"] - waited)
            try:
                self._decode_attempt(eng._decode_chunk)
            except BaseException as e:  # noqa: BLE001 — fail the slots
                self._book_busy(t_chunk)
                for i, s in enumerate(eng._host_slots):
                    if s.req is not None:
                        s.req.result._set(error=e)
                        # partial output discarded with the failed chunk:
                        # wasted as retry_discard (the caller/router owns
                        # any retry; the tokens are gone either way)
                        _goodput_account("retry_discard", len(s.emitted))
                        eng._host_slots[i] = type(s)()
                eng.reset_slots()  # clear phantom device lanes too
                self._bump("batches_failed")
                self._bump("decode_failures")
                self._breaker.record_failure()
                if obs is not None:
                    obs("batch", "error")
                continue
            self._book_busy(t_chunk)
            self._breaker.record_success()
            self._last_decode_ok = time.monotonic()
            self._bump("decode_tokens", eng.stats["tokens_out"] - before)
            if obs is not None:
                obs("batch", "ok")
            if admitted:
                self._bump("batches")

    def _book_busy(self, since: float, waited: float = 0.0) -> float:
        """Add the wall time since ``since``, less the ``waited`` seconds
        of it that the loop blocked for want of work, to ``loop_busy_s``
        (called only in iterations that run a chunk), then copy the
        engine's own spans: in that order, so a reader between the two
        finds the spans short of the busy time, never over it. Returns
        now."""
        stats, eng = self.stats, self._engine.stats
        now = time.perf_counter()
        stats["loop_busy_s"] += (now - since) - waited
        for k in (*_ENGINE_SPAN_KEYS, *self._engine.pick_stat_keys):
            stats[k] = eng[k]
        return now
