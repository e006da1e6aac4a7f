"""Continuous-batching decode engine — paged KV pool, ragged lengths.

Reference surface: the serving-grade batched attention stack —
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu (paged,
blocked KV) surfaced via python/paddle/incubate/nn/functional/
block_multihead_attention.py, plus the fused-transformer decode loop.

TPU-native redesign: block tables and page indirection exist on GPU because
the allocator hands out scattered pages; the first engine here kept a
STATIC slot-contiguous KV pool [slots, max_len, kvh, hd] per layer instead
(zero gather indirection, every shape static). That shape has the
reference's ORIGINAL problem back: every admitted request reserves
``max_len`` worth of HBM whatever its real length, so mixed long/short
traffic caps concurrency at ``slots``, not at real KV bytes. The paged
layout (``kv_layout="paged"``, the default) fixes it the static-shape way:

* PAGED KV POOL: one ``[num_pages, page_size, kvh, hd]`` buffer per layer
  plus a device-resident page table ``[slots, max_len/page_size]`` int32.
  A decode step's attention reads each ACTIVE slot's pages where they
  lie, as far as the slot's own length goes, and gathers nothing: a
  Pallas kernel that walks the page table (ops/kernels/
  paged_gqa_attention.py for a K and a V pool, paged_latent_attention.py
  for a latent (MLA) row); the one newly written position is scattered to
  its physical page outside it. What the kernels do not take (int8 pages,
  the speculative verify's W-wide call, a tensor-parallel plan, heads
  narrower than a lane tile) GATHERS each layer's logical view through
  the page table (the XLA equivalent of the GPU block table — a gather
  index, not pointer chasing) and runs the UNCHANGED ragged-attention
  math over it; that view is as wide as the longest live context needs,
  not ``[slots, L]``: one rung of a short static ladder of page counts,
  chosen in-graph once per call (``_view_rung``).
  Admission allocates pages from a host-side free list
  (:mod:`~.kv_pool`), scatters the prefill prefix page-by-page, and slot
  retirement returns pages — so concurrency is bounded by total KV bytes
  in flight, not ``slots x max_len``. Pages are reserved for the FULL
  prompt+budget at admission (static-shape JAX favors upfront
  reservation over vLLM's lazy growth: no mid-flight OOM preemption
  path needed), which still kills the dominant waste — the
  ``max_len - (prompt+budget)`` tail every request used to hold.
* SHARED-PREFIX (PROMPT) CACHE: page-aligned prompt prefixes declared via
  ``prefix_len`` are content-hashed; a miss runs the normal full prefill
  and pins the prefix pages read-only (ref-counted), a hit prefills ONLY
  the tail against the cached prefix pages gathered as context — N
  requests sharing a system prompt pay one prefill plus N short tails.
  Refcount-0 entries stay cached and are LRU-evicted when the free list
  runs dry.
* PREFILL/DECODE SPLIT, DEVICE-RESIDENT BOOKKEEPING, CONTINUOUS
  BATCHING: unchanged from the slot-contiguous engine — admission is one
  compiled call per prompt-length bucket, decode is one compiled
  multi-step program over all slots with per-slot positions, the host
  syncs ONCE per decode chunk, finished slots retire and free slots admit
  mid-flight. ``kv_layout="contiguous"`` keeps the old pool byte-for-byte
  (the parity/A-B baseline).
"""

from __future__ import annotations

import contextlib
import functools
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd as _ag
from ..core.dispatch import unwrap
from ..observability.recorder import phase, phase_counters
from ..ops.kernels import traced_by
from ..ops.kernels.paged_gqa_attention import (paged_gqa_attention,
                                               reads_in_place, token_rows)
from ..ops.kernels.paged_latent_attention import (lane_whole,
                                                  paged_latent_attention,
                                                  pages_walked)
from ..parallel.moe import ExpertShareLayer, PickTap
from . import compile_plan as _cp
from .kv_pool import (PagePool, PrefixCache, cache_spec_of, page_classes,
                      pages_needed, prefix_hash, ring_pages,
                      spec_bytes_per_token)
from .robustness import KVCapacityError
from .robustness import safe_inc as _safe_inc
from .robustness import safe_set as _safe_set

# the phases of a decode chunk that the engine times itself (the serving
# loop adds serve.sweep, serve.wait_request and serve.admit around them)
CHUNK_PHASES = ("serve.decode_dispatch", "serve.first_sync",
                "serve.chunk_sync", "serve.deliver")
# what admissions did, by kind, cumulative in ``stats``: calls and the prompt
# tokens they COMPUTED (a whole prompt; the tail behind a cached prefix), and
# the tokens prefix hits took from the cache instead
ADMIT_COUNTERS = ("admit_n.whole", "admit_n.prefix_hit",
                  "admit_tokens_computed.whole",
                  "admit_tokens_computed.prefix_hit", "admit_tokens_cached")
# decode calls by the sampler branch their admitted requests ask for
# (BatchDecodeEngine._sample), cumulative in ``stats``: they sum to
# ``decode_calls``
SAMPLE_COUNTERS = ("sample_calls.greedy", "sample_calls.draw",
                   "sample_calls.filter")


def _head(model, hidden):
    """``model``'s output head over final hidden rows ``[..., hidden]``:
    ``lm_head``, or the tied embedding where there is none. Called inside
    the step's ``bind_state``."""
    if model.lm_head is None:
        return unwrap(hidden) @ unwrap(model.model.embed_tokens.weight).T
    return unwrap(model.lm_head(hidden))


def _bucket(n: int, q: int = 128) -> int:
    return -(-n // q) * q


# The view ladder, in eighths of the page table: 16, 24, 56 and 64 pages at
# max_len 4096 with pages of 64 tokens. It bounds the GATHERED view, which
# since PR 35 is what serves the calls no page-walk kernel takes: int8
# ``(codes, scales)`` pairs, a W-wide call (the speculative verify), an
# engine under a sharding plan (GSPMD cannot partition a Mosaic call) and
# pools whose rows are not whole lane tiles; a decode step over plain pools
# reads its pages in place and consults no rung (``_PagedView.attend``). Each
# rung is there for a property of the chip or of the engine's geometry, read
# on a TPU v5 lite at 32 slots, 8 kv heads of 128 in bf16, 16 layers, when
# plain pools were served here too (PERF.md section 6, PRs 29 and 30), where
# a view of n pages a slot is S*n*ps*kvh*hd*2 bytes = n * 4 MiB, once for K
# and once for V in a layer.
# * FOUR rungs, not eight: a rung is one more branch in every layer, and costs
#   START-UP by its count, not its width: about a second of every start-up a
#   rung at 16 layers, retrieved from the compile cache or compiled. All eight
#   eighths put 12-16% on a 60 s start-up.
# * 2 and 3 eighths, both low and close together: the v5e's compiler keeps a
#   view in the chip's fast memory (layout `S(1)`) up to 28 pages a slot,
#   112 MiB, and not from 30 pages, 120 MiB, on. A step whose views stay
#   there costs far less a page than one whose views do not (steps on rungs
#   16 and 24 average 14.6 ms, on 16 and 32 pages 20.2 ms), so a short
#   context gets two rungs under that limit and not one on it: what an int8
#   or a verify call of chat-length contexts runs. The limit is a count of
#   BYTES, not a share of the table: with more slots, a longer max_len or
#   wider heads the same eighths are wider views.
# * 7 eighths: the rung under the top. A table that is nearly full drops to
#   it in the calls where no live context has reached the last eighth (a
#   fifth of them with contexts of 2-3.7k tokens), an eighth of gather and
#   attention spared. A context between 3 and 7 eighths pays for 7: a rung
#   in between costs what every rung costs.
# * 8 eighths: the table itself; every context fits.
# None of the four has been re-read on the chip for the calls that are left
# to them (no cell serves int8 pages or drafts: ROADMAP 3.8).
VIEW_EIGHTHS = (2, 3, 7, 8)


def _view_ladder(pages: int) -> tuple:
    """The page counts a decode step's logical K/V view may take:
    ``VIEW_EIGHTHS`` of the table ``pages``, (16, 24, 56, 64) for 64."""
    return tuple(sorted({max(1, -(-pages * k // 8)) for k in VIEW_EIGHTHS}))


@functools.lru_cache(maxsize=None)
def _view_branches(attend, ladder: tuple, *static) -> tuple:
    """The ``lax.switch`` branches of one attention over a bounded view:
    ``attend(n, *static, *operands)`` for each page count ``n`` of the
    ladder. The SAME callables come back for the same arguments, and they
    close over nothing traced: jax keeps a branch's trace by the
    callable's identity, so a program traces each rung once, not once a
    layer (seconds of every start-up otherwise). Both reference
    formulations, bf16 and int8 KV, bound their gather through here."""
    return tuple(jax.jit(functools.partial(attend, n, *static))
                 for n in ladder)


def _attend_view(n, ps, n_rep, scale, q, k_new, v_new, kp, vp, page_table,
                 pos):
    """``_cached_attention`` against ``pool[page_table[:, :n]]``: the same
    write-then-attend order, bottom-right mask and f32 accumulation, over
    ``n * ps`` positions in place of ``max_len``."""
    from ..models.llama import _cached_attention

    S, table = q.shape[0], page_table[:, :n]
    kview = kp[table].reshape(S, n * ps, *kp.shape[2:])
    vview = vp[table].reshape(S, n * ps, *vp.shape[2:])
    return _cached_attention(q, k_new, v_new, kview, vview, pos, n_rep,
                             scale)[0]


def _attend_view_latent(n, ps, scale, q_abs, q_rope, c_new, r_new, c_pool,
                        r_pool, page_table, pos):
    """Absorbed latent attention against ``pool[page_table[:, :n]]``: every
    head of a slot attends the ONE row a token has, kept in two pools (the
    latent ``c`` and its rotated key): scores ``q_abs . c + q_rope . r``,
    values ``c``; the same write-then-attend order, bottom-right mask and f32
    accumulation as :func:`_attend_view`. ``q_abs [S, W, H, rank]``,
    ``q_rope [S, W, H, rope]``; returns ``[S, W, H, rank]``."""
    S, W, H, _ = q_abs.shape
    T = n * ps
    table = page_table[:, :n]
    cols = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    slots = jnp.arange(S, dtype=jnp.int32)[:, None]

    def view(pool, new):
        v = pool[table].reshape(S, T, pool.shape[-1])
        return v.at[slots, cols].set(new.astype(v.dtype))

    c, r = view(c_pool, c_new), view(r_pool, r_new)
    # the W x H queries of a slot are the rows of one matrix against its view
    att = (jnp.einsum("smr,str->smt", q_abs.reshape(S, W * H, -1), c,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("smr,str->smt", q_rope.reshape(S, W * H, -1), r,
                        preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(T, dtype=jnp.int32)[None, None, :] <= cols[:, :, None]
    p = jax.nn.softmax(
        jnp.where(jnp.repeat(valid, H, axis=1), att, -1e30), axis=-1)
    out = jnp.einsum("smt,str->smr", p.astype(c.dtype), c,
                     preferred_element_type=jnp.float32)
    return out.reshape(S, W, H, -1).astype(q_abs.dtype)


def _ref_gqa_attention(q, kview, vview, lens, *, rep, scale):
    """Reference gather-dequant attention over a materialized logical
    view [S, T, kvh, hd]: the same bottom-right causal rule, GQA
    grouping (q head g*rep+r reads kv head g) and f32 accumulation as
    :func:`~paddlepaddle_tpu.models.llama._cached_attention`, in float32
    throughout: what an int8 pool's attention is, and what a kernel that
    reads int8 pages in place would be held to."""
    S, W, h, hd = q.shape
    kvh = kview.shape[2]
    T = kview.shape[1]
    qg = q.astype(jnp.float32).reshape(S, W, kvh, rep, hd) * scale
    att = jnp.einsum("swgrd,stgd->swgrt", qg,
                     kview.astype(jnp.float32))
    k_pos = jnp.arange(T, dtype=jnp.int32)
    q_pos = lens[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    mask = k_pos[None, None, :] <= q_pos[:, :, None]      # [S, W, T]
    att = jnp.where(mask[:, :, None, None, :], att, -1e30)
    p = jax.nn.softmax(att, axis=-1)
    out = jnp.einsum("swgrt,stgd->swgrd", p,
                     vview.astype(jnp.float32))
    return out.reshape(S, W, h, hd).astype(q.dtype)


def _attend_view_int8(n, ps, dtype, rep, scale, q, kq, ksc, vq, vsc,
                      page_table, lens):
    """An int8 pair's attention over the same bounded table: gather-dequant
    ``n`` pages a slot, then :func:`_ref_gqa_attention` (the new rows are
    in the pool already)."""
    S, table = q.shape[0], page_table[:, :n]
    kview = _kv_dequant_gather(kq, ksc, table, dtype).reshape(
        S, n * ps, *kq.shape[2:])
    vview = _kv_dequant_gather(vq, vsc, table, dtype).reshape(
        S, n * ps, *vq.shape[2:])
    return _ref_gqa_attention(q, kview, vview, lens, rep=rep, scale=scale)


# -- int8 KV-page quantization (kv_quant="int8") ----------------------------
# KVQuant/KIVI-style symmetric absmax: each K/V page carries one f32 scale
# per kv head ([num_pages, kvh] riding the pool as a parallel buffer), codes
# are int8 in [-127, 127]. Dequant is exactly ``codes * scale`` in f32 —
# the one product a later kernel's read has to make as well, to stay
# token-exact with the gathered view at identical pool bytes.

def _kv_quant_pages(x):
    """Quantize whole pages ``x [npg, ps, kvh, hd]`` (f32) at admission:
    per-(page, head) absmax scale. Positions past the prefill length must
    already be zeroed by the caller so padding never inflates a scale."""
    amax = jnp.max(jnp.abs(x), axis=(1, 3))                  # [npg, kvh]
    scale = amax / 127.0
    codes = jnp.clip(
        jnp.round(x / jnp.maximum(scale, 1e-20)[:, None, :, None]),
        -127, 127).astype(jnp.int8)
    return codes, scale


def _kv_dequant_gather(codes, scale, idx, dtype):
    """Gather-dequant pages ``idx`` from an int8 pool: the reference (non-
    kernel) read path. ``codes[idx] [..., ps, kvh, hd]`` times
    ``scale[idx] [..., kvh]`` in f32, cast to the engine's KV dtype."""
    g = codes[idx].astype(jnp.float32)
    s = scale[idx][..., None, :, None]
    return (g * s).astype(dtype)


def _kv_quant_scatter(codes, scales, new_rows, phys, off):
    """Scatter ``new_rows [S, W, kvh, hd]`` (this step's K or V, already in
    the engine's KV dtype) into the int8 pool at physical page ``phys`` /
    in-page offset ``off`` ([S, W] each), quantizing at write time.

    The page scale is a RUNNING absmax: when a new row fits the existing
    scale the rescale factor is exactly 1.0 and ``round(q * 1.0) == q`` —
    existing codes are bit-identical, so steady-state decode appends are
    drift-free; only a genuine absmax growth requantizes the page (the
    standard running-scale tradeoff, documented in docs/quantization.md).
    W is static and small (1 for chunked decode, k+1 for spec verify), so
    the python loop unrolls into W gather/scatter pairs per pool. Duplicate
    physical targets across slots only occur on the sacrificial null page
    0, where last-write-wins garbage is by design never read unmasked."""
    S, W = phys.shape
    sl = jnp.arange(S)
    new_rows = new_rows.astype(jnp.float32)
    for w in range(W):
        pw, ow = phys[:, w], off[:, w]
        new = new_rows[:, w]                                 # [S, kvh, hd]
        old_scale = scales[pw]                               # [S, kvh]
        new_scale = jnp.maximum(old_scale,
                                jnp.max(jnp.abs(new), axis=-1) / 127.0)
        safe = jnp.maximum(new_scale, 1e-20)
        q_new = jnp.clip(jnp.round(new / safe[..., None]),
                         -127, 127).astype(jnp.int8)
        page = codes[pw].astype(jnp.float32)                 # [S, ps, kvh, hd]
        factor = old_scale / safe                            # == 1.0 no-grow
        page = jnp.clip(jnp.round(page * factor[:, None, :, None]),
                        -127, 127).astype(jnp.int8)
        page = page.at[sl, ow].set(q_new)
        codes = codes.at[pw].set(page)
        scales = scales.at[pw].set(new_scale)
    return codes, scales


_perf_mod = None


def _perf():
    """Cached accessor for the perf-attribution plane; the off path costs
    one global read + attribute check per COLD call site (program build,
    chunk boundary) — never per token."""
    global _perf_mod
    if _perf_mod is None:
        try:
            from ..observability import perf as p
        except Exception:
            return None
        _perf_mod = p
    return _perf_mod


def _flight_record(kind: str, name: str, **data) -> None:
    """Request-lifecycle feed into the crash flight recorder (no-op one
    global check when the black box is disarmed)."""
    try:
        from ..observability import flight

        flight.record(kind, name, **data)
    except Exception:
        pass


def _expected_compiles(label: str):
    """Recompile-watchdog region for PLANNED compiles (warmup, bundle
    save): counted, never storm-flagged. Falls back to a no-op context."""
    try:
        from ..observability import watchdog

        return watchdog.expected_compiles(label)
    except Exception:
        import contextlib

        return contextlib.nullcontext()


def _trace_of(req):
    """The request's journey (observability.reqtrace), or None — the off
    path and engine-shaped foreign request objects (benches, tests)
    without a GenerationResult cost one getattr chain here."""
    return getattr(getattr(req, "result", None), "_trace", None)


def _stamp(req, attr: str, value=None) -> None:
    """Best-effort SLO timestamp on the request's result future —
    engine-shaped foreign request objects (tests, benches) without a
    GenerationResult simply don't get stamped."""
    try:
        setattr(req.result, attr,
                time.perf_counter() if value is None else value)
    except Exception:
        pass


def _account(kind: str, n: int) -> None:
    """Goodput-ledger attribution (observability.goodput). The engine is
    the SINGLE accounting point for decoded tokens: every token stamped
    into ``stats["tokens_out"]`` lands here exactly once — as ``useful``/
    ``overshoot`` at retirement or as a waste kind when the slot is
    released without delivering. Never raises into decode."""
    if n <= 0:
        return
    try:
        from ..observability import goodput

        goodput.account(kind, n)
    except Exception:
        pass


def _as_row_of(pool, new):
    """``new [..., width]`` as rows of ``pool``: its dtype, and zeros in the
    lanes a widened pool has beyond the row's own."""
    return lane_whole(new.astype(pool.dtype), pool.shape[-1])


class _PagedView:
    """What a layer gets as ``cache`` in the paged decode forward: the
    layer's pools (in the order of its cache spec) behind the page table,
    and where the call's new rows go (``phys``, ``off``). An attention
    calls :meth:`attend` (a K and a V pool: models/llama.py) or
    :meth:`attend_latent` (one pool of latent rows:
    models/longcat_flash.py) in place of writing through a dense cache:
    a decode step (one query row a head) over plain pools reads each slot's
    pages in place, as far as ``walk`` says, and gathers none; any other call
    gathers its view here, as wide as ``rung`` says. What comes back beside
    the output is for :meth:`stored`: the new rows, or, where the pool is an
    int8 ``(codes, scales)`` pair, the pair with the rows quantised into
    their pages. How a pool is stored and read is decided here, from the
    pools themselves and the static width of the call. ``walk [S]`` is how
    far each slot's pages are read in place: its length where it is active,
    0 where it is idle (a stale length over a zeroed table row would read
    the null page once a page); None where the caller wants the gathered
    view whatever the pools are (the speculative verify; an engine under a
    sharding plan, whose program GSPMD partitions)."""

    __slots__ = ("ladder", "page_size", "kv_dtype", "pools", "page_table",
                 "rung", "phys", "off", "walk", "window")

    def __init__(self, eng, pools, page_table, rung, phys, off, walk=None,
                 window=0):
        self.ladder, self.page_size = eng._ladder, eng.page_size
        self.kv_dtype = eng._kv_dtype
        self.pools, self.page_table, self.rung = pools, page_table, rung
        self.phys, self.off, self.walk = phys, off, walk
        self.window = window      # > 0: ``page_table`` is the class's ring

    def attend(self, q, k_new, v_new, pos, n_rep, scale, sink=None):
        """(out, K rows, V rows), the new rows in the pool's dtype. A decode
        step (one query row a head) over plain pools whose rows are whole
        lane tiles IS the kernel that walks each slot's pages in place
        (ops/kernels/paged_gqa_attention.py; no view, no rung), at every
        extent: on the chip it beat the gathered view at chat's lengths as
        at docqa's (PERF.md section 6, PR 35). A W-wide call, a call with no
        ``walk`` and a narrower head keep :func:`_attend_view` on the rung's
        branch. Over an int8 pair the rows are quantised into their pages
        FIRST, outside the switch (the donated pool is never carried through
        a branch), so that the attention reads the bytes the next step will
        read (:func:`_attend_view_int8`), and the updated pairs come back in
        place of rows. A layer of a window class (``window``: its table is
        the class's ring), a ``sink`` (``[H]`` logits) and pools of one row
        a token (``paged_gqa_attention.token_rows``, rows come back as the
        pool holds them) are the kernel's alone: the decode step's one query
        row with a walk, nothing else."""
        kp, vp = self.pools
        if isinstance(kp, tuple):
            kq, ksc = _kv_quant_scatter(*kp, k_new.astype(self.kv_dtype),
                                        self.phys, self.off)
            vq, vsc = _kv_quant_scatter(*vp, v_new.astype(self.kv_dtype),
                                        self.phys, self.off)
            out = jax.lax.switch(
                self.rung,
                _view_branches(_attend_view_int8, self.ladder,
                               self.page_size, self.kv_dtype, n_rep, scale),
                q, kq, ksc, vq, vsc, self.page_table, pos)
            return (out, (kq, ksc), (vq, vsc))
        if self.window or sink is not None or token_rows(kp):
            if self.walk is None or q.shape[1] != 1:
                raise NotImplementedError(
                    "a window, a sink or pools of one row a token are read "
                    "by the decode step's page walk alone (one query row, "
                    "walk lengths given)")
            out = paged_gqa_attention(
                q[:, 0], k_new[:, 0], v_new[:, 0], kp, vp, self.page_table,
                self.walk, scale=scale, window=self.window,
                sink=sink)[:, None]
            S, W = k_new.shape[:2]
            return (out, k_new.reshape(S, W, *kp.shape[2:]).astype(kp.dtype),
                    v_new.reshape(S, W, *vp.shape[2:]).astype(vp.dtype))
        if self.walk is not None and q.shape[1] == 1 and reads_in_place(kp):
            out = paged_gqa_attention(
                q[:, 0], k_new[:, 0], v_new[:, 0], kp, vp, self.page_table,
                self.walk, scale=scale)[:, None]
        else:
            out = jax.lax.switch(
                self.rung,
                _view_branches(_attend_view, self.ladder, self.page_size,
                               n_rep, scale),
                q, k_new, v_new, kp, vp, self.page_table, pos)
        return (out, k_new.astype(kp.dtype), v_new.astype(vp.dtype))

    def attend_latent(self, block, q_abs, q_rope, c_new, r_new, pos, scale):
        """(out, c rows, key rows) over the layer's ``block``-th pair of
        pools, and the new rows in the pools' dtype. A decode step (one query
        row a head) IS the kernel that walks each slot's pages in place as
        far as its own length (ops/kernels/paged_latent_attention.py; no
        view, no rung). A W-wide call (the speculative verify's shape) keeps
        :func:`_attend_view_latent` on the rung's branch: the kernel takes
        one query row, and the static W of the call alone chooses."""
        c_pool, r_pool = self.pools[2 * block: 2 * block + 2]
        if q_abs.shape[1] == 1:
            out = paged_latent_attention(
                q_abs[:, 0], q_rope[:, 0], c_new[:, 0], r_new[:, 0], c_pool,
                r_pool, self.page_table, pos, scale=scale)[:, None]
        else:
            out = jax.lax.switch(
                self.rung,
                _view_branches(_attend_view_latent, self.ladder,
                               self.page_size, scale),
                q_abs, q_rope, c_new, r_new, c_pool, r_pool, self.page_table,
                pos)
        # as wide as the pools are held (the decode program holds them in
        # whole lane tiles: :meth:`BatchDecodeEngine._lane_whole_pools`)
        return (out, _as_row_of(c_pool, c_new), _as_row_of(r_pool, r_new))

    def stored(self, kept):
        """The layer's pools with what its attention handed back in place:
        a row is written to its page at ``phys, off``; an int8 pair holds
        its rows already."""
        return tuple(
            tuple(unwrap(a) for a in k) if isinstance(p, tuple)
            else p.at[self.phys, self.off].set(unwrap(k))
            for p, k in zip(self.pools, kept))


class _WindowClass:
    """The pages of the layers whose cache declares one window
    (``kv_pool.page_classes``): a pool of their own, ``ring`` pages a slot
    (``kv_pool.ring_pages``), and a ``[slots, ring]`` table whose row is a
    ring: a slot's logical page ``j`` lives in entry ``j % ring``. A slot's
    pages are taken at admission and given back at retirement; in between
    the ring hands the entry of a page that fell wholly behind the window to
    the page a new position opens."""

    __slots__ = ("window", "ring", "layers", "pool", "table", "slot_pages")

    def __init__(self, window, ring, layers, slots, page_size):
        self.window, self.ring, self.layers = int(window), int(ring), layers
        self.pool = PagePool(slots * self.ring + 1, page_size)
        self.table = jnp.zeros((slots, self.ring), jnp.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]

    def first_page(self, held, page_size: int):
        """The logical page of the first key a query reads after ``held``
        keys: that of position ``held - window + 1``."""
        return jnp.maximum(held - self.window + 1, 0) // page_size

    def pages_read(self, held, page_size: int):
        """Pages of a slot's ring that hold a key the query after ``held``
        keys reads (0 for an empty slot)."""
        return jnp.where(held > 0, -(-held // page_size)
                         - self.first_page(held, page_size), 0)


class _Slot:
    __slots__ = ("req", "emitted", "budget", "temp", "top_k", "spec_steps",
                 "spec_accepted")

    def __init__(self, req=None, budget=0, temp=0.0, top_k=0):
        self.req = req
        self.emitted: List[int] = []
        self.budget = budget
        self.temp = temp          # what the request was admitted with
        self.top_k = top_k
        self.spec_steps = 0       # speculative verify steps this request saw
        self.spec_accepted = 0    # draft tokens the verifier accepted for it


class BatchDecodeEngine:
    """Slot-based continuous-batching decoder for LlamaForCausalLM-shaped
    models (anything exposing ``.model(ids, caches=…, pos=…)``, ``.config``
    and ``.functional_state()``)."""

    def __init__(self, model, max_slots: int = 16, max_len: Optional[int] = None,
                 chunk: int = 16, quant: Optional[str] = None,
                 quant_group_size: int = -1, kv_layout: str = "paged",
                 page_size: int = 64, num_pages: Optional[int] = None,
                 prefix_cache: bool = True, mesh=None, plan=None,
                 bundle: Optional[str] = None, draft=None, spec_k: int = 0,
                 draft_quant: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 kv_host_bytes: Optional[int] = None):
        cfg = model.config
        if kv_layout not in ("paged", "contiguous"):
            raise ValueError(
                f"kv_layout must be 'paged' or 'contiguous', got {kv_layout!r}")
        self.model = model
        self.cfg = cfg
        # the cache the model declares: per layer, the pools a cached token
        # has a row in. Pools, admission scratch, page accounting and the
        # decode forward are built from it, whatever the rows are.
        self.cache_spec = cache_spec_of(model)
        self._latent = any(p.role == "latent"
                           for layer in self.cache_spec for p in layer)
        self._windowed = any(p.window for layer in self.cache_spec
                             for p in layer)
        if mesh is not None or plan is not None:
            self._refuse_latent(
                "a tensor-parallel plan or mesh",
                "the plan shards a pool on its kv heads and a latent row has "
                "one; sharding the up-projections by head is not built")
            self._refuse_window(
                "a tensor-parallel plan or mesh",
                "GSPMD cannot partition the page walk that is the window "
                "class's only reader, and the gathered view reads no ring")
        if prefix_cache and kv_layout == "paged":
            self._refuse_window(
                "prefix sharing (prefix_cache=True)",
                "a prefix's window-class pages are the ring of the slot that "
                "computed them, overwritten as it decodes; keeping a "
                "prefix's window tail with its entry is not built: pass "
                "prefix_cache=False")
        self.S = int(max_slots)
        self.L = int(max_len or cfg.max_position_embeddings)
        self.chunk = int(chunk)
        self.kv_layout = kv_layout
        self.params = model.functional_state()
        # weight-only quantization: params quantized ONCE here; every
        # compiled program after this point (admission prefill + the
        # scan-decode body) reads int8 weight buffers through the
        # QuantizedWeight pytree leaves — cache layout, donation
        # (caches only) and bucketed shapes are untouched. Single-chip
        # decode is HBM-bandwidth-bound, so halving weight bytes read per
        # step is the serving perf lever (tools/quant_ab.py measures it).
        self.quant = quant
        self.quant_meta: Dict[str, object] = {}
        if quant is not None:
            if quant != "weight_only_int8":
                raise ValueError(
                    f"quant={quant!r}: 'weight_only_int8' is the supported "
                    "decode-engine scheme (int4/PTQ honestly absent — "
                    "PARITY.md)")
            from ..nn.quant import quantize_param_tree

            self.params, self.quant_meta = quantize_param_tree(
                self.params, algo=quant, group_size=quant_group_size)
        # tensor-parallel decode: a sharding plan (distributed.shard_plan)
        # places params — including the int8 QuantizedWeight leaves, whose
        # q and scales shard together — column/row-parallel on its "mp"
        # axis and the KV pools on kv heads, so a model bigger than one
        # chip serves through the same compiled programs (XLA partitions
        # them and inserts the ICI collectives). Order matters: quantize
        # first (host-side, whole tensors), shard second.
        self.plan = plan
        if self.plan is None and mesh is not None:
            from ..distributed.shard_plan import ShardingPlan, decode_plan

            self.plan = (mesh if isinstance(mesh, ShardingPlan)
                         else decode_plan(mesh))
        if self.plan is not None:
            # loud, not silent: a head count tp doesn't divide would fit
            # away to a FULLY REPLICATED pool on every chip — the exact
            # memory surprise tensor parallelism exists to avoid
            self.plan.validate_divisible(
                num_attention_heads=cfg.num_attention_heads,
                num_key_value_heads=cfg.num_key_value_heads,
                intermediate_size=cfg.intermediate_size,
                vocab_size=cfg.vocab_size)  # lm_head is typically the
            #   largest serving weight; a vocab tp doesn't divide would
            #   silently replicate it on every chip
            self.params = self.plan.shard(self.params)
            self._mesh_gauges()
        # KV-cache quantization (ROADMAP item 4a): int8 codes + per-page-
        # per-head scales riding the pool. Resolved AFTER the plan so the
        # tp seam can be rejected loudly; argument wins over the flag,
        # ""/"off" are the explicit off spellings.
        from ..core.flags import flag_value as _flag_value

        if kv_quant is None:
            kv_quant = _flag_value("serving_kv_quant") or None
        if kv_quant in ("", "off"):
            kv_quant = None
        if kv_quant is not None:
            self._refuse_window(
                "kv_quant", "the int8 pages keep one layout of [kv heads, "
                "head size] rows and one table; a ring of them is not built")
            self._refuse_latent(
                "kv_quant",
                "an int8 page carries one scale a kv head, and a latent row "
                "has no heads and mixes a normalised latent with rotated "
                "keys: it needs a scheme of its own")
            if kv_quant == "int4":
                raise ValueError(
                    "kv_quant='int4': the int8 page format (codes + "
                    "per-page-per-head scales) is the shipped scheme; "
                    "int4 packing is the named follow-up seam on the same "
                    "scale buffers (docs/quantization.md) — honestly "
                    "absent, not silently served as int8")
            if kv_quant != "int8":
                raise ValueError(
                    f"kv_quant={kv_quant!r}: 'int8' is the supported "
                    "KV-cache scheme ('int4' is the named seam)")
            if kv_layout != "paged":
                raise ValueError(
                    "kv_quant='int8' needs kv_layout='paged' — scales "
                    "ride the page pool; the contiguous layout is the "
                    "full-precision parity baseline")
            if self.plan is not None:
                raise ValueError(
                    "kv_quant with a tensor-parallel plan: sharding the "
                    "(codes, scale) pair per layer is a named follow-up "
                    "seam (shard_kv places plain pools only) — serve "
                    "int8 KV single-chip or drop the plan")
        self.kv_quant = kv_quant
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self._kv_dtype = dtype     # compute dtype for scratch/dequant even
        #   when the pool itself stores int8 codes
        self.kv_host = None        # host-RAM prefix spill tier (item 4b)
        self._restore_ms: List[float] = []
        if kv_layout == "paged":
            self.page_size = int(page_size)
            if self.page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            self.P = pages_needed(self.L, self.page_size)   # pages per slot
            # default capacity: every slot can hold max_len, ceil'd to
            # whole pages — the contiguous pool's admission CONTRACT, and
            # its exact bytes when page_size divides max_len (otherwise
            # each slot's share rounds up to a whole page, worst case
            # page_size-1 tokens/slot; plus the null page). Size
            # num_pages BELOW S*P to serve more slots than the worst
            # case could ever fit contiguously
            n_pages = (self.S * self.P + 1 if num_pages is None
                       else int(num_pages))
            self.pool = PagePool(n_pages, self.page_size)
            self.prefix = PrefixCache()
            self.prefix_enabled = bool(prefix_cache)
            self.page_table = jnp.zeros((self.S, self.P), jnp.int32)
            self._ladder = _view_ladder(self.P)
            self.window_classes = [
                _WindowClass(w, min(ring_pages(w, self.page_size), self.P),
                             layers, self.S, self.page_size)
                for w, layers in sorted(page_classes(self.cache_spec).items())
                if w]
            if self.kv_quant == "int8":
                # each pool entry is (codes int8, scale f32 [pages, kvh]):
                # a nested pytree, so program args / scan carries /
                # donation / bundle templates thread it unchanged
                kvh, hd = self.cache_spec[0][0].row
                self.caches = [
                    ((jnp.zeros((n_pages, self.page_size, kvh, hd),
                                jnp.int8),
                      jnp.zeros((n_pages, kvh), jnp.float32)),
                     (jnp.zeros((n_pages, self.page_size, kvh, hd),
                                jnp.int8),
                      jnp.zeros((n_pages, kvh), jnp.float32)))
                    for _ in range(cfg.num_hidden_layers)]
            else:
                pages_of = {i: wc.pool.num_pages for wc in self.window_classes
                            for i in wc.layers}
                self.caches = [
                    tuple(jnp.zeros((pages_of.get(i, n_pages),
                                     self.page_size, *p.row), dtype)
                          for p in layer)
                    for i, layer in enumerate(self.cache_spec)]
            if kv_host_bytes is None:
                kv_host_bytes = int(
                    _flag_value("serving_kv_host_bytes") or 0)
            if kv_host_bytes and prefix_cache:
                self._refuse_latent(
                    "the host spill tier (kv_host_bytes)",
                    "a spilled slab is laid out and checked as K/V pairs of "
                    "[kv heads, head size] rows; the latent layout is not "
                    "built")
                from .kv_pool import HostPrefixTier

                self.kv_host = HostPrefixTier(int(kv_host_bytes))
            self._slot_pages: List[List[int]] = [[] for _ in range(self.S)]
            self._slot_prefix: List[Optional[str]] = [None] * self.S
            self._kv_gauges(total=True)
            if self.kv_quant is not None:
                _safe_set("paddle_serving_kv_quant_enabled",
                          "KV-cache quantization live on this engine "
                          "(1 = yes)", 1, mode=self.kv_quant)
        else:
            self.page_size = 0
            self.P = 0
            self.pool = None
            self.prefix = None
            self.prefix_enabled = False
            self.page_table = None
            self._ladder = ()
            # a window's layers mask their dense caches themselves
            self.window_classes = []
            self.caches = [
                tuple(jnp.zeros((self.S, self.L, *p.row), dtype)
                      for p in layer)
                for layer in self.cache_spec]
        # whether a decode step's K/V attention reads its pages in place
        # (:meth:`_PagedView.attend`): the decode program then hands the view
        # its walk lengths and reports each slot's walked pages
        self._walks_pairs = (
            kv_layout == "paged" and not self._latent and self.plan is None
            and all(reads_in_place(p) for p in self.caches[0]))
        if self.window_classes and not all(
                reads_in_place(p) for layer in self.caches for p in layer):
            raise ValueError(
                "a window class's pools must be read in place by the page "
                "walk (rows of whole lane tiles: kv_pool.PoolSpec); the "
                "gathered view reads no ring")
        if self.plan is not None:
            # commit the pools (kv heads on "mp") and every host-rebuilt
            # array (replicated): deterministic placements, so the jitted
            # programs never re-specialize on a sharding-inference guess
            self.caches = [(self.plan.shard_kv(k), self.plan.shard_kv(v))
                           for k, v in self.caches]
            if self.page_table is not None:
                self.page_table = self.plan.replicate(self.page_table)
        # device-resident per-slot state: [lens, tokens, active, budgets]
        self.lens = self._repl(jnp.zeros((self.S,), jnp.int32))
        self.tokens = self._repl(jnp.zeros((self.S,), jnp.int32))  # last tok
        self.active = self._repl(jnp.zeros((self.S,), bool))
        self.temps = self._repl(jnp.zeros((self.S,), jnp.float32))
        self.eos_ids = self._repl(jnp.full((self.S,), -1, jnp.int32))
        self.budgets = self._repl(jnp.zeros((self.S,), jnp.int32))  # left
        self.top_ks = self._repl(jnp.zeros((self.S,), jnp.int32))  # 0 = off
        self.key = self._repl(jax.random.PRNGKey(0))
        # program registry: every compiled program the engine serves with,
        # keyed by compile-plan key ("decode" / "admit_p<bucket>" /
        # "admit_pfx<n>t<bucket>"). Values are lazy jax.jit wrappers until
        # first use, warmup, or a bundle load replaces them with AOT
        # Compiled executables; _warmed tracks keys whose compile already
        # happened so warmup never double-compiles
        self._programs: Dict[str, object] = {}
        self._warmed: set = set()
        self._warm_info: Optional[Dict[str, object]] = None
        self._bundle_info: Optional[Dict[str, object]] = None
        self._decode_captured = False
        self._host_slots = [_Slot() for _ in range(self.S)]
        self._first_pending: Dict[int, object] = {}  # slot -> device scalar
        self.stats = {"tokens_out": 0, "requests": 0, "decode_calls": 0,
                      "peak_busy": 0, "turnaround_s": 0.0,
                      "turnaround_n": 0, "decode_view_pages": 0,
                      "decode_table_pages": 0,
                      **dict.fromkeys(ADMIT_COUNTERS, 0),
                      **dict.fromkeys(SAMPLE_COUNTERS, 0),
                      **phase_counters(CHUNK_PHASES)}
        # expert shares (parallel.moe.ExpertShareLayer) count their picks in
        # the decode program; the counters ride its one packed payload
        shares = [m for m in model.sublayers()
                  if isinstance(m, ExpertShareLayer)]
        self._experts_held = shares[0].count if shares else 0
        picks = {} if not shares else dict(
            moe_picks_zero=0, moe_picks_held=0, moe_picks_absent=0,
            moe_picks_total=0, moe_experts_touched=0, moe_experts_read=0,
            moe_layer_steps=0, moe_experts_held=self._experts_held,
            moe_expert_pairs=(0,) * self._experts_held)
        self.stats.update(picks)
        # a window class's counters (:meth:`_window_columns`) ride the same
        # payload
        windows = {} if not self.window_classes else dict(
            window_pages_walked_max=0, window_pages_live_peak=0,
            window_pages_released=0)
        self.stats.update(windows)
        # the serving loop copies these
        self.pick_stat_keys = tuple(picks) + tuple(windows)
        # perf_counter at the return of the last chunk's sync, until the
        # next compiled program is called (or the loop waits for work)
        self._t_synced: Optional[float] = None
        # speculative decoding: a draft model proposes spec_k greedy
        # tokens per slot and ONE batched target forward verifies all
        # k+1 positions — same emitted stream (greedy acceptance is
        # token-exact by construction), >1 token per target weight-read
        # at any nonzero acceptance rate. See inference/speculative.py.
        self.spec = None
        if draft is not None or spec_k:
            self._refuse_window(
                "speculative drafts (draft=/spec_k=)",
                "the verify's W-wide call reads a gathered view, and the "
                "window class is read by the page walk alone")
            self._refuse_latent(
                "speculative drafts (draft=/spec_k=)",
                "the draft decoder builds K/V pair caches from its own kv "
                "heads and shares the target's admission; pairing it with a "
                "latent target is not built")
            if draft is None or not spec_k:
                raise ValueError(
                    "speculative decoding needs BOTH draft= (a small "
                    "model or its config) and spec_k= (proposals per "
                    "target step)")
            from .speculative import SpeculativeDecoder

            self.spec = SpeculativeDecoder(self, draft, spec_k,
                                           draft_quant=draft_quant)
            self._spec_steps_per_chunk = max(
                1, self.chunk // (self.spec.k + 1))
        self.compile_plan = _cp.CompilePlan.for_engine(self)
        try:
            # weak registration: the memory ledger attributes this
            # engine's params/KV/draft buckets and reconciles its page
            # pool for leaks — it must never extend the engine's lifetime
            from ..observability import memledger as _memledger

            _memledger.register_engine(self)
        except Exception:
            pass
        if bundle is not None:
            # never fatal: a stale/foreign bundle logs and falls back to
            # the lazy build path — a deploy with a bad artifact serves
            # slow, it does not crash-loop
            self.load_serving_bundle(bundle)

    def _refuse_window(self, what: str, why: str) -> None:
        """What a window class of pages cannot do yet refuses at
        construction, with its reason, as :meth:`_refuse_latent` does."""
        if self._windowed:
            raise ValueError(f"{what} with a window class of KV pages: {why}")

    def _refuse_latent(self, what: str, why: str) -> None:
        """What a latent cache row cannot do yet refuses at construction,
        with its reason, rather than serving something else in silence."""
        if self._latent:
            raise ValueError(f"{what} with a latent (MLA) cache row: {why}")

    def _repl(self, x):
        """Replicate-commit under a plan (identity single-chip)."""

        return x if self.plan is None else self.plan.replicate(x)

    def mesh_info(self) -> Dict[str, object]:
        """Mesh/sharding snapshot for ``health()``/``/healthz`` — the
        parallelism block the fleet router and ``/metrics`` see."""
        if self.plan is None:
            return {"enabled": False}
        return self.plan.describe()

    def _mesh_gauges(self) -> None:
        """One-time (construction, cold path) mesh gauges."""
        axes = "x".join(f"{a}{s}" for a, s in self.plan.axes.items())
        _safe_set("paddle_mesh_devices",
                  "devices in the serving engine's mesh",
                  self.plan.n_devices, axes=axes)
        _safe_set("paddle_mesh_axes",
                  "named axes in the serving engine's mesh",
                  len(self.plan.axes), axes=axes)
        _safe_set("paddle_tp_degree",
                  "tensor-parallel degree of the decode engine",
                  self.plan.tp_degree)

    # -- paged-pool observability -------------------------------------------
    def _kv_gauges(self, total: bool = False) -> None:
        """Pool occupancy gauges — refreshed on the per-request host paths
        (admit/retire), never per token."""
        if self.kv_layout != "paged":
            return
        if total:
            _safe_set("paddle_serving_kv_pages_total",
                      "allocatable KV pages in the paged pool",
                      self.pool.usable)
            _safe_set("paddle_serving_kv_bytes_per_token",
                      "bytes one cached token takes over every pool of "
                      "every layer (the cache spec's rows)",
                      self._bytes_per_token(),
                      rows="+".join(f"{p.role}{list(p.row)}"
                                    for p in self.cache_spec[0]))
        _safe_set("paddle_serving_kv_pages_free",
                  "KV pages currently on the free list",
                  self.pool.free_count)
        if self.kv_host is not None:
            _safe_set("paddle_serving_kv_host_bytes",
                      "bytes of spilled prefix slabs resident in the "
                      "host-RAM tier", self.kv_host.used_bytes)
            _safe_set("paddle_serving_kv_host_occupancy",
                      "host-tier bytes used over its byte budget "
                      "(the kv_host_tier_full alert input)",
                      round(self.kv_host.occupancy, 4))

    def _restore_percentile(self, q: float) -> Optional[float]:
        """p-th percentile of recent host-tier restore latencies (ms)."""
        if not self._restore_ms:
            return None
        xs = sorted(self._restore_ms)
        return round(xs[min(len(xs) - 1, int(q * len(xs)))], 3)

    def _bytes_per_token(self) -> int:
        """Bytes a cached token takes over all pools (int8 codes are one
        byte; their page scales are counted in ``page_bytes``)."""
        itemsize = (1 if self.kv_quant == "int8"
                    else np.dtype(self._kv_dtype).itemsize)
        return spec_bytes_per_token(self._class_spec(0), itemsize)

    def _class_spec(self, window: int):
        """The cache spec's layers of one class of pages (0: the full one)."""
        if not self.window_classes:
            return self.cache_spec
        return [layer for layer in self.cache_spec
                if layer[0].window == window]

    def kv_stats(self) -> Dict[str, object]:
        """KV-pool snapshot for ``health()``/``/healthz`` and the serving
        bench: layout, page accounting, prefix-cache hit data, host-tier
        spill/restore counters."""
        per_tok = self._bytes_per_token()
        rows = {"bytes_per_token": per_tok,
                "row_shapes": [list(p.row) for p in self.cache_spec[0]],
                "row_roles": [p.role for p in self.cache_spec[0]]}
        if self.kv_layout != "paged":
            return {"layout": "contiguous",
                    "kv_bytes": int(self.S * self.L * per_tok), **rows}
        pool, pfx = self.pool, self.prefix
        # per-page scale overhead in int8 mode: one f32 per (page, head)
        # per K and V per layer — the honest page_bytes the memledger's
        # pinned-prefix reconciliation multiplies by
        scale_bytes = (2 * self.cache_spec[0][0].row[0] * 4
                       * len(self.cache_spec)
                       if self.kv_quant == "int8" else 0)
        page_bytes = int(self.page_size * per_tok + scale_bytes)
        host = {"enabled": False}
        if self.kv_host is not None:
            host = dict(self.kv_host.stats(), enabled=True,
                        restore_ms_p50=self._restore_percentile(0.50),
                        restore_ms_p99=self._restore_percentile(0.99))
        return {
            "layout": "paged",
            "kv_quant": self.kv_quant or "off",
            "page_size": self.page_size,
            "pages_total": pool.usable,
            "pages_free": pool.free_count,
            "pages_used": pool.used,
            "pages_peak": pool.peak_used,
            "occupancy": round(pool.used / max(pool.usable, 1), 4),
            "page_bytes": page_bytes,
            "kv_bytes": int(pool.num_pages * page_bytes),
            **rows,
            "prefix": {
                "enabled": self.prefix_enabled,
                "entries": len(pfx),
                "cached_pages": pfx.cached_pages,
                "hits": pfx.hits,
                "misses": pfx.misses,
                "evictions": pfx.evictions,
            },
            "host": host,
            **({} if not self.window_classes else
               {"window_classes": [self._window_stats(wc)
                                   for wc in self.window_classes]}),
        }

    def _window_stats(self, wc) -> Dict[str, object]:
        """One window class's block of :meth:`kv_stats`: its pool's pages,
        a slot's ring and what a page of it holds (``pages_peak`` counts pages
        taken for rings; ``stats`` carries the walk's counters)."""
        itemsize = np.dtype(self._kv_dtype).itemsize
        per_tok = spec_bytes_per_token(self._class_spec(wc.window), itemsize)
        return {"window": wc.window, "layers": list(wc.layers),
                "ring_pages": wc.ring, "pages_total": wc.pool.usable,
                "pages_used": wc.pool.used, "pages_peak": wc.pool.peak_used,
                "bytes_per_token": per_tok,
                "kv_bytes": int(wc.pool.num_pages * self.page_size * per_tok)}

    def spec_info(self) -> Dict[str, object]:
        """The ``spec`` block of ``health()``/``/healthz``: draft config,
        k, and live acceptance — ``{"enabled": False}`` when speculative
        decoding is off."""
        return {"enabled": False} if self.spec is None else self.spec.info()

    # -- compiled pieces ----------------------------------------------------
    def _forward(self, params, toks, caches, pos, last=None):
        """One model step: toks [b, s] -> (logits, caches'). An admission
        passes ``last``: its logits are then those of hidden row ``last``
        of its one prompt alone, ``[1, vocab]``, the only row it samples
        (the final norm is per row, so the row is the one the whole bucket
        would give). That row enters the head widened to float32 and its
        logits leave in the row's dtype: XLA rewrites a one-row matmul as
        a multiply and a reduce in the operands' dtype, which in bfloat16
        rounds every product and sums the rounded ones, where the matmul
        over many rows sums exact products in float32."""
        with _ag.no_grad(), self.model.bind_state(params):
            hidden, new_caches = self.model.model(toks, caches=caches, pos=pos)
            if last is None:
                logits = _head(self.model, hidden)
            else:
                row = jax.lax.dynamic_index_in_dim(unwrap(hidden)[0], last,
                                                   keepdims=True)
                logits = _head(self.model,
                               row.astype(jnp.float32)).astype(row.dtype)
        return logits, [tuple(unwrap(c) for c in nc) for nc in new_caches]

    def _view_rung(self, lens, active, span: int):
        """Which rung of ``self._ladder`` (an int32 index) is the narrowest
        view that holds every position an ACTIVE slot touches while its
        ``lens`` advances by ``span``. A retired slot's ``lens`` is stale
        and does not count."""
        top = len(self._ladder) - 1
        extent = jnp.max(jnp.where(active, lens, 0)) + span
        holds = jnp.asarray(self._ladder, jnp.int32) * self.page_size
        return jnp.minimum(jnp.sum(holds < extent), top).astype(jnp.int32)

    def _view_pages_column(self, rung):
        """The rung's page count as an ``[S, 1]`` column of a program's
        packed host-sync payload: how the host learns what was gathered."""
        pages = jnp.asarray(self._ladder, jnp.int32)[rung]
        return jnp.broadcast_to(pages, (self.S, 1))

    def _lane_whole_pools(self, pools):
        """A latent row's pools as its decode kernel copies pages from them:
        every row in whole lane tiles (Mosaic copies no slice of a narrower
        array: ops/kernels/paged_latent_attention.py). The decode program
        widens them ONCE a call, where the 64-wide pool changes its device
        layout anyway, carries them wide through its steps, and hands them
        back at the spec's widths (:meth:`_spec_wide_pools`); widened inside
        the step it would be a copy of every narrow pool in every block of
        every step."""
        return [tuple(lane_whole(p) for p in layer) for layer in pools]

    def _spec_wide_pools(self, pools):
        """:meth:`_lane_whole_pools` undone: every pool at its spec's width."""
        return [tuple(p[..., :spec.row[-1]] for p, spec in zip(layer, specs))
                for layer, specs in zip(pools, self.cache_spec)]

    def _walk_pages_column(self, lens, active, span: int):
        """:meth:`_view_pages_column` of a decode call whose attention is a
        kernel that walks each slot's own pages and consults no rung (a
        latent row's, a plain K/V pair's): per slot the pages its walk
        copies by the call's last step (those of its table row that hold a
        key), 0 for an inactive slot."""
        pages = pages_walked(lens + span, self.page_size, self.P)
        return jnp.where(active, pages, 0).astype(jnp.int32)[:, None]

    def _window_columns(self, wc, lens, lens_, active, active_,
                        n_steps: int):
        """A window class's ``[S, 3]`` columns of a decode call's payload,
        per slot: the most pages of its ring one step's walk read (over the
        steps it was live: held keys ``lens .. lens_ - 1``), the pages that
        hold a key the next query reads (0 where the slot ended), and the
        pages that fell wholly behind the window during the call (the ring
        hands their entries on)."""
        ps = self.page_size
        held = lens[:, None] + jnp.arange(n_steps, dtype=jnp.int32)[None, :]
        stepped = held < lens_[:, None]
        walked = jnp.max(jnp.where(stepped, wc.pages_read(held, ps), 0),
                         axis=1)
        live = jnp.where(active_, wc.pages_read(lens_, ps), 0)
        released = jnp.where(active, wc.first_page(lens_, ps)
                             - wc.first_page(lens, ps), 0)
        return jnp.stack([walked, live, released], axis=1).astype(jnp.int32)

    def _count_windows(self, columns) -> None:
        """Host half of :meth:`_window_columns`, every class's three."""
        st = self.stats
        for c in range(len(self.window_classes)):
            walked, live, released = columns[:, 3 * c:3 * c + 3].T
            st["window_pages_walked_max"] = max(
                st["window_pages_walked_max"], int(walked.max()))
            st["window_pages_live_peak"] = max(
                st["window_pages_live_peak"], int(live.sum()))
            st["window_pages_released"] += int(released.sum())

    def _pick_columns(self, counts):
        """The expert shares' counters of a decode call (``pick_counts`` and
        the live layer-steps, summed over its steps) as whole ``[S, n]``
        columns of the packed payload, zero padded: they come to the host
        with the chunk's one sync."""
        n = -(-counts.shape[0] // self.S)
        flat = jnp.zeros((n * self.S,), jnp.int32).at[:counts.shape[0]].set(
            counts.astype(jnp.int32))
        return flat.reshape(n, self.S).T

    def _count_picks(self, columns) -> None:
        """Host half of :meth:`_pick_columns`: add a call's counters to
        ``stats`` (``moe_expert_pairs`` is replaced, never mutated, so a
        shallow copy of ``stats`` stays whole)."""
        E = self._experts_held
        v = columns.T.reshape(-1)
        st = self.stats
        st["moe_expert_pairs"] = tuple(
            int(a) + int(b) for a, b in zip(st["moe_expert_pairs"], v[:E]))
        st["moe_picks_held"] += int(v[:E].sum())
        st["moe_picks_zero"] += int(v[E])
        st["moe_picks_absent"] += int(v[E + 1])
        st["moe_picks_total"] += int(v[:E + 2].sum())
        st["moe_experts_touched"] += int(v[E + 2])
        st["moe_experts_read"] += int(v[E + 3])
        st["moe_layer_steps"] += int(v[E + 4])

    def _forward_paged(self, params, toks, pools, page_table, lens, rung,
                       tap=None, walk=None):
        """One forward over ``toks [S, W]`` at per-slot positions
        ``lens..lens+W-1`` through the page table: each layer is handed
        its pools behind the table (a :class:`_PagedView`, whatever rows
        the cache spec gives them) and hands back the new rows, one per
        pool (an int8 pair comes back whole, the rows quantised into it:
        :meth:`_PagedView.stored` keeps either); ``tap`` (a
        ``parallel.moe.PickTap``) collects the picks of the expert shares
        the layers run; ``walk`` (``where(active, lens, 0)``, from the
        decode program alone) lets a decode step's attention read its pages
        in place (:class:`_PagedView`). Without it each attention gathers its
        logical K/V view (the page table IS the gather index), runs the
        unchanged ragged-attention math against it, and scatters all W
        newly written positions back to their physical pages. The view is
        LENGTH-BOUNDED: ``[S, n*page_size]`` gathered through
        ``page_table[:, :n]``, ``n`` the page count of ``rung``
        (:meth:`_view_rung`, taken once per program call from the longest
        ACTIVE context), not the whole ``[S, P*page_size]`` table — the
        positions left out were masked before the softmax and contributed
        exact zeros, so tokens are unchanged while gather and attention
        cost follows what is live. W=1 is the chunked decode step; the
        speculative verify program runs W=k+1 through the SAME
        implementation, so the two paths cannot diverge. Retired slots'
        table rows are zeroed and positions past ``max_len`` are
        redirected explicitly, so out-of-stream writes land in the
        sacrificial null page — never in another slot's pages; a stale
        ``lens`` past the view finds its write into the view dropped, and
        the pool takes the new rows as projected, not read back from the
        view."""
        S, ps, P, L = self.S, self.page_size, self.P, self.L
        rings = ()
        if self.window_classes:      # (the full table, each class's ring)
            page_table, rings = page_table
        W = toks.shape[1]
        rows = jnp.arange(S, dtype=jnp.int32)[:, None]         # [S, 1]
        pos = lens[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        page_idx = jnp.minimum(pos // ps, P - 1)
        phys = jnp.where(
            pos < L,
            page_table[jnp.broadcast_to(rows, pos.shape), page_idx], 0)
        off = pos % ps
        views = {}
        for wc, ring in zip(self.window_classes, rings):
            at = ring[jnp.broadcast_to(rows, pos.shape), (pos // ps) % wc.ring]
            for i in wc.layers:
                views[i] = (ring, jnp.where(pos < L, at, 0), wc.window)
        with _ag.no_grad(), self.model.bind_state(params):
            mdl = self.model.model
            x = mdl.embed_tokens(toks)
            cos, sin = mdl.rope_cos, mdl.rope_sin
            new_pools = []
            with tap if tap is not None else contextlib.nullcontext():
                for i, (layer, layer_pools) in enumerate(zip(mdl.layers,
                                                             pools)):
                    table, at, window = views.get(i, (page_table, phys, 0))
                    view = _PagedView(self, layer_pools, table, rung, at, off,
                                      walk, window)
                    x, kept = layer(x, cos, sin, None, pos=lens, cache=view)
                    # the write to the physical pool stays outside the
                    # switch: the donated pool is updated in place, never
                    # carried through a branch
                    new_pools.append(view.stored(kept))
            logits = _head(self.model, mdl.norm(x))
        return logits, new_pools

    # static bound of the in-graph per-slot top-k filter: one lax.top_k of
    # the cap serves every slot's k, and only calls with a live slot that
    # both draws and filters pay for it (:meth:`_sample`)
    TOP_K_CAP = 128

    @classmethod
    def _sample(cls, rows, temps, top_ks, key, live):
        """Per-slot sampling of ``rows`` (``[slots, vocab]`` logits, read as
        float32): temp==0 -> greedy, else categorical at temp, optionally
        restricted to the slot's top_k logits (k <= TOP_K_CAP).
        The call runs only what its ``live`` slots ask for, chosen in the
        program from its own arguments: all greedy -> the argmax alone; a
        slot draws and none of those filters -> the draw without the
        top_k; else the whole body. A slot's token is the same on every
        branch it may take under the same ``key`` (the filter never
        removes a row's maximum, and with k == 0 it is the identity); a
        slot outside ``live`` reads an unspecified token.
        ``rows`` enter in the head's own dtype and each branch widens them:
        a float32 operand would let the compiler fuse the widening into the
        head's matmul and drop its rounding, and tokens would no longer be
        those of the rounded logits."""
        draws = live & (temps > 0.0)
        branch = (jnp.any(draws).astype(jnp.int32)
                  + jnp.any(draws & (top_ks > 0)).astype(jnp.int32))

        def greedy(rows, temps, top_ks, key):
            return jnp.argmax(rows, axis=-1).astype(jnp.int32)

        def draw(rows, temps, top_ks, key):
            scaled = rows / jnp.maximum(temps[:, None], 1e-6)
            sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
            return jnp.where(temps <= 0.0, greedy(rows, temps, top_ks, key),
                             sampled)

        def filtered(rows, temps, top_ks, key):
            kcap = min(cls.TOP_K_CAP, rows.shape[-1])
            topv = jax.lax.top_k(rows, kcap)[0]           # [slots, kcap] desc
            kth = jnp.take_along_axis(
                topv, jnp.clip(top_ks[:, None] - 1, 0, kcap - 1), axis=1)
            rows = jnp.where((top_ks[:, None] > 0) & (rows < kth), -jnp.inf,
                             rows)
            return draw(rows, temps, top_ks, key)

        def widened(fn):
            return lambda rows, *a: fn(rows.astype(jnp.float32), *a)

        return jax.lax.switch(
            branch, [widened(f) for f in (greedy, draw, filtered)], rows,
            temps, top_ks, key)

    def _set_slot_state(self, caches, lens, tokens, active, temps, eos_ids,
                        budgets, top_ks, key, slot, plen, temp, eos, budget,
                        top_k, first):
        """Shared admission epilogue: every per-slot state element set
        in-graph; the slot is born inactive when its first token already
        ends it."""
        done = ((eos >= 0) & (first == eos)) | (budget <= 1)
        return (caches,
                lens.at[slot].set(plen),
                tokens.at[slot].set(first),
                active.at[slot].set(~done),
                temps.at[slot].set(temp),
                eos_ids.at[slot].set(eos),
                budgets.at[slot].set(budget - 1),
                top_ks.at[slot].set(top_k),
                key, first)

    def _scratch(self, length: int, dtype):
        """A dense cache of ``length`` positions for one sequence, per layer
        one array per pool of the spec: what an admission prefills through."""
        return [tuple(jnp.zeros((1, length, *p.row), dtype) for p in layer)
                for layer in self.cache_spec]

    @traced_by("admit")
    def _admit_impl(self, params, caches, lens, tokens, active, temps,
                    eos_ids, budgets, top_ks, ids, plen, slot, temp, eos,
                    budget, top_k, key):
        """ONE compiled admission (contiguous layout): prefill ids[1, bucket]
        through a scratch cache, scatter the K/V prefix into pool slot
        ``slot``, sample the first token, set every per-slot state element.
        No host syncs."""
        bucket = ids.shape[1]
        scratch = self._scratch(bucket, caches[0][0].dtype)
        row, scratch = self._forward(params, ids, scratch, jnp.int32(0),
                                     last=plen - 1)
        key, sub = jax.random.split(key)
        first = self._sample(row, temp[None], top_k[None], sub, True)[0]
        zero = jnp.int32(0)
        out_caches = [
            tuple(jax.lax.dynamic_update_slice(
                c, s, (slot,) + (zero,) * (c.ndim - 1))
                for c, s in zip(layer, scr))
            for layer, scr in zip(caches, scratch)]
        return self._set_slot_state(out_caches, lens, tokens, active, temps,
                                    eos_ids, budgets, top_ks, key, slot,
                                    plen, temp, eos, budget, top_k, first)

    @traced_by("admit")
    def _admit_paged_impl(self, params, pools, page_table, lens, tokens,
                          active, temps, eos_ids, budgets, top_ks, ids, plen,
                          slot, temp, eos, budget, top_k, key):
        """Paged admission: same scratch prefill, but the K/V prefix is
        scattered PAGE-BY-PAGE to the physical pages the host wrote into
        this slot's page-table row before the call. Scratch positions past
        the slot's reservation hit row entries of 0 — the null page."""
        rings = ()
        if self.window_classes:      # (the full table, each class's ring)
            page_table, rings = page_table
        bucket = ids.shape[1]
        ps = self.page_size
        npg = pages_needed(bucket, ps)
        pad = npg * ps - bucket
        scratch = self._scratch(bucket, self._kv_dtype)
        row, scratch = self._forward(params, ids, scratch, jnp.int32(0),
                                     last=plen - 1)
        key, sub = jax.random.split(key)
        first = self._sample(row, temp[None], top_k[None], sub, True)[0]
        dest = jax.lax.dynamic_slice(page_table, (slot, jnp.int32(0)),
                                     (1, npg))[0]
        # positions past the prompt hold prefill activations for the
        # bucket's zero-padding — mask them out of the int8 scale (the
        # attention mask already hides them; decode overwrites them)
        valid = (jnp.arange(npg * ps, dtype=jnp.int32)
                 < plen).reshape(npg, ps)[:, :, None, None]
        ringed = {i: (wc, ring) for wc, ring in zip(self.window_classes, rings)
                  for i in wc.layers}
        out_pools = [self._store_pages(layer, scr, dest, npg, pad, valid)
                     if i not in ringed else
                     self._store_window_tail(layer, scr, *ringed[i], slot,
                                             plen)
                     for i, (layer, scr) in enumerate(zip(pools, scratch))]
        return self._set_slot_state(out_pools, lens, tokens, active, temps,
                                    eos_ids, budgets, top_ks, key, slot,
                                    plen, temp, eos, budget, top_k, first)

    def _store_window_tail(self, layer_pools, rows, wc, ring, slot, plen):
        """One window layer's pools with the pages of a prefill's rows (per
        pool ``[1, tokens, *row]``) that hold its last ``window`` positions,
        each written to its entry of the slot's ring: logical page ``j`` to
        entry ``j % ring``, the latest such page at or before the prompt's
        last one. An entry whose page holds none of them (behind the window,
        or before position 0) writes the null page."""
        ps, B = self.page_size, wc.ring
        with jax.named_scope("window_tail_store"):
            last = (plen - 1) // ps
            j = last - (last - jnp.arange(B, dtype=jnp.int32)) % B
            keep = (j >= 0) & ((j + 1) * ps > plen - wc.window)
            row = jax.lax.dynamic_slice(ring, (slot, jnp.int32(0)),
                                        (1, B))[0]
            dest = jnp.where(keep, row, 0)
            out = []
            for p, r in zip(layer_pools, rows):
                n = pages_needed(r.shape[1], ps)
                r = jnp.pad(r[0], ((0, n * ps - r.shape[1]),)
                            + ((0, 0),) * (r.ndim - 2))
                pages = r.reshape(n, ps, *r.shape[1:])
                out.append(p.at[dest].set(pages[jnp.clip(j, 0, n - 1)]))
            return tuple(out)

    def _store_pages(self, layer_pools, rows, dest, npg: int, pad: int,
                     valid):
        """One layer's pools with ``rows`` (per pool ``[1, tokens, *row]``
        of a prefill) written page by page to the physical pages ``dest``;
        under int8 the K/V pair is quantized page by page, positions
        outside ``valid`` zeroed first."""
        ps = self.page_size
        pages = []
        for r in rows:
            if pad:
                r = jnp.pad(r, ((0, 0), (0, pad)) + ((0, 0),) * (r.ndim - 2))
            pages.append(r[0].reshape(npg, ps, *r.shape[2:]))
        if self.kv_quant == "int8":
            (kq, kscale), (vq, vscale) = layer_pools
            kc, ksc = _kv_quant_pages(
                jnp.where(valid, pages[0].astype(jnp.float32), 0.0))
            vc, vsc = _kv_quant_pages(
                jnp.where(valid, pages[1].astype(jnp.float32), 0.0))
            return ((kq.at[dest].set(kc), kscale.at[dest].set(ksc)),
                    (vq.at[dest].set(vc), vscale.at[dest].set(vsc)))
        return tuple(p.at[dest].set(pg) for p, pg in zip(layer_pools, pages))

    def _admit_prefix_program(self, n_pfx: int, tail_bucket: int):
        """Prefix-HIT admission factory (compiled per (prefix pages, tail
        bucket)): gather the cached prefix pages as read-only context,
        prefill ONLY the tail at positions [aligned, aligned+tail), scatter
        the tail's K/V to the slot's private pages, sample the first token.
        The prefix pages are never written — that is what makes them
        shareable across slots."""
        ps = self.page_size
        aligned = n_pfx * ps
        npg_tail = pages_needed(tail_bucket, ps)
        pad = npg_tail * ps - tail_bucket

        @traced_by("admit")
        def impl(params, pools, page_table, lens, tokens, active, temps,
                 eos_ids, budgets, top_ks, ids, tail_plen, slot, temp, eos,
                 budget, top_k, key):
            dtype = self._kv_dtype
            quant = self.kv_quant == "int8"
            row_pages = jax.lax.dynamic_slice(
                page_table, (slot, jnp.int32(0)), (1, self.P))[0]
            pfx = row_pages[:n_pfx]
            scratch = []
            for layer_pools in pools:
                if quant:
                    cached = [_kv_dequant_gather(q, sc, pfx, dtype)
                              for q, sc in layer_pools]
                else:
                    cached = [p[pfx] for p in layer_pools]
                scratch.append(tuple(
                    jnp.concatenate(
                        [c.reshape(1, aligned, *c.shape[2:]),
                         jnp.zeros((1, tail_bucket, *c.shape[2:]), dtype)],
                        axis=1)
                    for c in cached))
            row, scratch = self._forward(params, ids, scratch,
                                         jnp.int32(aligned),
                                         last=tail_plen - 1)
            key2, sub = jax.random.split(key)
            first = self._sample(row, temp[None], top_k[None], sub, True)[0]
            dest = row_pages[n_pfx:n_pfx + npg_tail]
            valid = (jnp.arange(npg_tail * ps, dtype=jnp.int32)
                     < tail_plen).reshape(npg_tail, ps)[:, :, None, None]
            out_pools = [
                self._store_pages(layer, [r[:, aligned:] for r in scr], dest,
                                  npg_tail, pad, valid)
                for layer, scr in zip(pools, scratch)]
            return self._set_slot_state(
                out_pools, lens, tokens, active, temps, eos_ids, budgets,
                top_ks, key2, slot, aligned + tail_plen, temp, eos, budget,
                top_k, first)

        return impl

    def _decode_program(self, n_steps: int):
        """``n_steps`` decode steps over all slots in one program; per-slot
        eos (-1 = none) and budget countdown in-graph. Returns the packed
        int32 host-sync payload: [slots, n_steps+1] (emitted tokens, -1
        where idle, then the active flag), and [slots, n_steps+2] in the
        paged layout, whose last column is the pages of the K/V view that
        every step of the call gathered (where a kernel reads the pages in
        place, those each slot's walk copies: :meth:`_walk_pages_column`). A
        factory so the
        perf plane can lower an ``n_steps=1`` variant for cost capture —
        XLA's cost analysis counts a scan body ONCE regardless of trip
        count, so the chunk program's own count would under-report by
        ~chunk.
        Paged layout threads the pool through the scan carry and reads the
        (loop-invariant) page table as a plain capture-free argument."""

        paged = self.kv_layout == "paged"
        latent = paged and self._latent
        walks = self._walks_pairs

        def step(caches, tokens, lens, active, temps, budgets, top_ks,
                 eos_ids, key, params, page_table, rung):
            picks = None
            if paged:
                tap = PickTap()
                logits, caches = self._forward_paged(
                    params, tokens[:, None], caches, page_table, lens, rung,
                    tap=tap,
                    walk=jnp.where(active, lens, 0) if walks else None)
                picks = tap.counts(mask=active)
                if picks is not None:
                    # ... and the expert-layer calls of a step with a live slot
                    picks = jnp.concatenate([picks, (
                        jnp.any(active) * len(tap.picks)).astype(
                            jnp.int32)[None]])
            else:
                logits, caches = self._forward(params, tokens[:, None],
                                               caches, lens)
            key, sub = jax.random.split(key)
            nxt = self._sample(logits[:, 0], temps, top_ks, sub, active)
            nxt = jnp.where(active, nxt, tokens)    # frozen when inactive
            lens = lens + active.astype(jnp.int32)
            emitted = jnp.where(active, nxt, -1)    # -1 = no token
            budgets = budgets - active.astype(jnp.int32)
            active = active & ~((eos_ids >= 0) & (nxt == eos_ids)) \
                & (budgets > 0)
            return caches, nxt, lens, active, budgets, key, (emitted, picks)

        @traced_by("decode")
        def run(params, caches, page_table, tokens, lens, active, temps,
                eos_ids, budgets, top_ks, key):
            # one rung for the whole call, from where the longest live
            # context will stand after its last step
            rung = self._view_rung(lens, active, n_steps) if paged else None
            if latent:
                caches = self._lane_whole_pools(caches)

            def body(carry, _):
                caches, tokens, lens, active, budgets, key = carry
                caches, tokens, lens, active, budgets, key, ys = step(
                    caches, tokens, lens, active, temps, budgets, top_ks,
                    eos_ids, key, params, page_table, rung)
                return (caches, tokens, lens, active, budgets, key), ys

            (caches_, tokens_, lens_, active_, budgets_, key_), (out, picks) \
                = jax.lax.scan(
                    body, (caches, tokens, lens, active, budgets, key), None,
                    length=n_steps)
            if latent:
                caches_ = self._spec_wide_pools(caches_)
            cols = [out.T, active_[:, None].astype(jnp.int32)]
            if paged:
                cols.append(self._walk_pages_column(lens, active, n_steps)
                            if latent or walks
                            else self._view_pages_column(rung))
            for wc in self.window_classes:
                cols.append(self._window_columns(wc, lens, lens_, active,
                                                 active_, n_steps))
            if picks is not None:
                cols.append(self._pick_columns(picks.sum(0)))
            packed = jnp.concatenate(cols, axis=1)  # [slots, n_steps+1(+1)]
            return caches_, tokens_, lens_, active_, budgets_, key_, packed

        if paged:
            return run

        def run_contiguous(params, caches, tokens, lens, active, temps,
                           eos_ids, budgets, top_ks, key):
            return run(params, caches, None, tokens, lens, active, temps,
                       eos_ids, budgets, top_ks, key)

        return run_contiguous

    # -- compile plan: program registry, warmup, bundles ---------------------
    def _build_program(self, key: str):
        """The lazy ``jax.jit`` wrapper for one plan key (no compile yet).
        The single construction seam: _admit, warmup() and bundle save all
        build through here, so the plan IS what the engine compiles."""
        kind, info = _cp.parse_key(key)
        if kind == "decode":
            return jax.jit(self._decode_program(self.chunk),
                           donate_argnums=(1,))
        if kind == "prefix":
            return jax.jit(
                self._admit_prefix_program(info["n_pfx"],
                                           info["tail_bucket"]),
                donate_argnums=(1,))
        if kind in ("draft_admit", "draft", "verify"):
            if self.spec is None:
                raise ValueError(
                    f"program key {key!r} needs speculative decoding "
                    "(draft=/spec_k=) armed on this engine")
            if kind == "draft_admit":
                return jax.jit(self.spec.draft_admit_impl,
                               donate_argnums=(1,))
            if kind == "draft":
                return jax.jit(self.spec.draft_program(info["k"]),
                               donate_argnums=(1,))
            return jax.jit(self.spec.verify_program(info["k"]),
                           donate_argnums=(1,))
        impl = (self._admit_paged_impl if self.kv_layout == "paged"
                else self._admit_impl)
        return jax.jit(impl, donate_argnums=(1,))

    def _program(self, key: str):
        """Registry lookup with lazy build — the serve-path accessor the
        spec chunk and draft admission share with warmup/bundles."""
        fn = self._programs.get(key)
        if fn is None:
            fn = self._build_program(key)
            self._programs[key] = fn
        return fn

    def _tables(self):
        """What the paged programs take as their page table: the table, or,
        with window classes, ``(table, (each class's ring table, ...))``."""
        if not self.window_classes:
            return self.page_table
        return (self.page_table, tuple(wc.table for wc in self.window_classes))

    def _decode_args(self) -> tuple:
        """THE decode program's argument tuple — shared by the serve path
        (_decode_chunk) and the plan seam (warmup/bundle lowering), so an
        AOT Compiled can never be specialized to avals the serve path
        doesn't pass."""
        if self.kv_layout == "paged":
            return (self.params, self.caches, self._tables(), self.tokens,
                    self.lens, self.active, self.temps, self.eos_ids,
                    self.budgets, self.top_ks, self.key)
        return (self.params, self.caches, self.tokens, self.lens,
                self.active, self.temps, self.eos_ids, self.budgets,
                self.top_ks, self.key)

    def _admit_args(self, key: str, ids, plen: int, slot: int, temp: float,
                    eos: int, budget: int, top_k: int) -> tuple:
        """THE admission argument tuple for one program key — shared by
        _admit (live request values) and the plan seam (zero examples:
        only avals matter for lowering and treedefs)."""
        kind, _ = _cp.parse_key(key)
        state = (self.lens, self.tokens, self.active, self.temps,
                 self.eos_ids, self.budgets, self.top_ks)
        tail = (ids, jnp.int32(plen), jnp.int32(slot), jnp.float32(temp),
                jnp.int32(eos), jnp.int32(budget), jnp.int32(top_k),
                self.key)
        head = ((self.params, self.caches, self._tables())
                if kind == "prefix" or self.kv_layout == "paged"
                else (self.params, self.caches))
        return head + state + tail

    def _example_args(self, key: str) -> tuple:
        """Concrete arguments with the EXACT avals (shape/dtype/sharding)
        the serve path passes for ``key`` — used to AOT-lower in warmup()/
        save, and to rebuild bundle pytree structures at load. Never
        executed, so live state buffers double as examples."""
        kind, info = _cp.parse_key(key)
        if kind == "decode":
            return self._decode_args()
        if kind == "draft_admit":
            return (self.spec.draft_params, self.spec.draft_caches,
                    self.spec.prev_tokens,
                    jnp.zeros((1, info["bucket"]), jnp.int32),
                    jnp.int32(1), jnp.int32(0))
        if kind == "draft":
            return (self.spec.draft_params, self.spec.draft_caches,
                    self.spec.prev_tokens, self.tokens, self.lens,
                    self.active)
        if kind == "verify":
            return (self.params, self.caches, self.page_table, self.lens,
                    self.tokens, self.spec.prev_tokens, self.active,
                    self.budgets, self.eos_ids,
                    jnp.zeros((self.S, info["k"]), jnp.int32))
        width = (info["tail_bucket"] if kind == "prefix"
                 else info["bucket"])
        return self._admit_args(key, jnp.zeros((1, width), jnp.int32),
                                plen=1, slot=0, temp=0.0, eos=-1, budget=1,
                                top_k=0)

    def _out_template(self, key: str) -> tuple:
        """A pytree with the program's OUTPUT structure (leaves are
        placeholders — treedefs carry structure only). Lets a bundle load
        reconstruct out_trees from the live engine instead of pickling
        treedefs with custom (QuantizedWeight) nodes."""
        kind, info = _cp.parse_key(key)
        if kind == "decode":
            return (self.caches, self.tokens, self.lens, self.active,
                    self.budgets, self.key, jnp.int32(0))
        if kind == "draft_admit":
            return (self.spec.draft_caches, self.spec.prev_tokens)
        if kind == "draft":
            return (self.spec.draft_caches,
                    jnp.zeros((self.S, info["k"]), jnp.int32))
        if kind == "verify":
            return (self.caches, self.lens, self.tokens,
                    self.spec.prev_tokens, self.active, self.budgets,
                    jnp.zeros((self.S, info["k"] + 4), jnp.int32))
        return (self.caches, self.lens, self.tokens, self.active,
                self.temps, self.eos_ids, self.budgets, self.top_ks,
                self.key, jnp.int32(0))

    def warmup(self, keys: Optional[List[str]] = None) -> Dict[str, object]:
        """Compile the plan EAGERLY (AOT lower+compile, nothing executed)
        so no request ever lands on a cold program — the explicit form of
        what the first requests used to pay implicitly. Idempotent per
        program; already-served or bundle-loaded keys are skipped. With a
        persistent compile cache armed, a warm-disk restart's warmup is
        retrieval, not compilation. Returns the warmup summary also kept
        in ``compile_info()``."""
        from ..core import compile_cache as _cc

        if keys is None:
            keys = self.compile_plan.keys()
        t0 = time.perf_counter()
        cache0 = _cc.stats()
        compiled_n = skipped = 0
        p = _perf()
        perf_on = p is not None and p.enabled()
        with _expected_compiles("warmup"):
            for key in keys:
                if key in self._warmed:
                    skipped += 1
                    continue
                fn = self._programs.get(key)
                if fn is None:
                    fn = self._build_program(key)
                if not hasattr(fn, "lower"):    # already an AOT Compiled
                    self._warmed.add(key)
                    skipped += 1
                    continue
                compiled = None
                kind, info = _cp.parse_key(key)
                if perf_on and kind in ("admit", "prefix"):
                    # same capture the lazy path does: the Compiled
                    # replaces the jit entry, one compile total, exact
                    # costs recorded. Only the TARGET admission kinds:
                    # draft_admit under "serving.admit" would collide
                    # with the target's bucket label in the cost
                    # registry, and draft/verify keys carry no bucket
                    bucket = (f"pfx{info['n_pfx']}t{info['tail_bucket']}"
                              if kind == "prefix" else f"p{info['bucket']}")
                    compiled = p.capture_jit(
                        "serving.admit", fn, self._example_args(key),
                        bucket=bucket, quant=self.quant or "off")
                if compiled is None:
                    compiled = fn.lower(*self._example_args(key)).compile()
                self._programs[key] = compiled
                self._warmed.add(key)
                compiled_n += 1
            self._warm_bookkeeping_ops()
        cache1 = _cc.stats()
        self._warm_info = {
            "programs": len(keys),
            "compiled": compiled_n,
            "skipped": skipped,
            "wall_s": round(time.perf_counter() - t0, 3),
            "cache_hits": cache1["hits"] - cache0["hits"],
        }
        _safe_set("paddle_serving_warmup_seconds",
                  "wall seconds the last engine warmup spent compiling",
                  self._warm_info["wall_s"])
        _safe_set("paddle_serving_warmup_programs",
                  "programs compiled by the last engine warmup",
                  compiled_n)
        _flight_record("compile", "warmup", **self._warm_info)
        return dict(self._warm_info)

    def _warm_bookkeeping_ops(self) -> None:
        """Flush the tiny host-side op compiles the first requests would
        otherwise pay (page-table row writes use STATIC slot indices, so
        each slot is its own ~10 ms program; likewise the first-token
        stack per pending count). Pure copies — engine state untouched.
        Without this, a fully warmed/bundled engine still shows a handful
        of ms-scale compiles in its first serve window."""
        try:
            if self.kv_layout == "paged":
                pt = self.page_table
                zrow = jnp.zeros((self.P,), jnp.int32)
                for slot in range(self.S):
                    pt = pt.at[slot].set(zrow)
                pt.block_until_ready()
                for wc in self.window_classes:
                    pt, zrow = wc.table, jnp.zeros((wc.ring,), jnp.int32)
                    for slot in range(self.S):
                        pt = pt.at[slot].set(zrow)
                    pt.block_until_ready()
            act = self.active
            for slot in range(self.S):
                act = act.at[slot].set(False)
            act.block_until_ready()
            firsts = [jnp.int32(0)] * self.S
            for k in range(1, self.S + 1):
                np.asarray(jnp.stack(firsts[:k]))
            if self.spec is not None and self._spec_steps_per_chunk > 1:
                # the spec chunk's payload concat is the one host-level op
                # its serve path adds — flush its ~ms compile here too
                parts = [jnp.zeros((self.S, self.spec.k + 4), jnp.int32)
                         ] * self._spec_steps_per_chunk
                np.asarray(jnp.concatenate(parts, axis=1))
        except Exception:
            pass          # best-effort: a miss here costs ms, not minutes

    def save_serving_bundle(self, path: str,
                            keys: Optional[List[str]] = None
                            ) -> Dict[str, object]:
        """Serialize the engine's compiled programs + manifest to ``path``
        (every plan entry plus traffic-built prefix variants; programs not
        yet compiled are AOT-compiled first). A process built with
        ``bundle=path`` then serves without a single retrace or backend
        compile. See :mod:`~.compile_plan` for format and commit rules."""
        with _expected_compiles("bundle_save"):
            manifest = _cp.save_bundle(self, path, keys=keys)
        _flight_record("compile", "bundle_save", path=str(path),
                       programs=len(manifest["entries"]),
                       wall_s=manifest.get("save_wall_s"))
        return manifest

    def load_serving_bundle(self, path: str, strict: bool = False) -> bool:
        """Load an AOT bundle into the program registry. Non-strict (the
        constructor path) NEVER raises: any mismatch/corruption logs one
        stderr line, bumps ``paddle_serving_bundle_fallbacks_total`` and
        leaves the engine on the normal lazy-build path."""
        try:
            manifest = _cp.load_bundle(self, path)
        except Exception as e:
            if strict:
                raise
            sys.stderr.write(
                f"[serving] bundle {path} not loaded "
                f"({type(e).__name__}: {e}); falling back to lazy program "
                "builds\n")
            _safe_inc("paddle_serving_bundle_fallbacks_total",
                      "serving bundles rejected at load (engine fell back "
                      "to compiling)", reason=type(e).__name__)
            self._bundle_info = {"loaded": False, "path": str(path),
                                 "error": f"{type(e).__name__}: {e}"}
            _flight_record("compile", "bundle_fallback", path=str(path),
                           error=f"{type(e).__name__}: {str(e)[:200]}")
            return False
        self._bundle_info = {
            "loaded": True,
            "path": str(path),
            "programs": len(manifest.get("entries", [])),
            "fingerprint": str(manifest.get("fingerprint"))[:16],
            # the version identity the fleet deploy pipeline rolls back
            # by — health() surfaces which artifact this engine serves
            "version": manifest.get("version") or _cp.bundle_version_id(
                manifest.get("fingerprint", "?"),
                manifest.get("created_unix", 0) or 0),
        }
        _safe_set("paddle_serving_bundle_loaded",
                  "an AOT serving bundle is live in this engine (1 = yes)",
                  1)
        _safe_set("paddle_serving_bundle_programs",
                  "programs loaded from the serving bundle",
                  self._bundle_info["programs"])
        _flight_record("compile", "bundle_load", path=str(path),
                       programs=self._bundle_info["programs"])
        return True

    def compile_info(self) -> Dict[str, object]:
        """The ``compile`` block of ``health()``/``/healthz``: plan size/
        fingerprint, how many programs are built/warm, bundle + warmup
        status, persistent-cache counters."""
        from ..core import compile_cache as _cc

        plan = self.compile_plan
        return {
            "plan": {"entries": len(plan.entries),
                     "fingerprint": plan.fingerprint()[:16]},
            "programs_built": len(self._programs),
            "programs_warmed": len(self._warmed),
            "warmup": self._warm_info,
            "bundle": self._bundle_info or {"loaded": False},
            "cache": _cc.stats(),
        }

    # -- host orchestration --------------------------------------------------
    def _prefix_plan(self, req, ids, plen):
        """(aligned, n_pfx, hash, entry) for a request's declared shared
        prefix — only FULL pages are shareable, and at least one tail token
        must remain so the first sample has logits to read."""
        pfx_len = int(getattr(req, "prefix_len", 0) or 0)
        if (self.kv_layout != "paged" or not self.prefix_enabled
                or pfx_len <= 0):
            return 0, 0, None, None
        if pfx_len > plen:
            raise ValueError(
                f"prefix_len {pfx_len} exceeds the prompt length {plen}")
        ps = self.page_size
        aligned = (pfx_len // ps) * ps
        if aligned == plen:
            aligned -= ps            # keep >= 1 tail token to sample from
        if aligned < ps:
            return 0, 0, None, None  # too short to share a full page
        n_pfx = aligned // ps
        h = prefix_hash(ids, aligned)
        return aligned, n_pfx, h, self.prefix.lookup(h)

    def _reserve_pages(self, plen: int, budget: int, n_pfx_cached: int,
                       exclude: Optional[str] = None):
        """Allocate the request's private pages (full prompt+budget
        reservation minus cached prefix pages). Returns the page list, or
        None when the pool cannot satisfy it RIGHT NOW (caller waits for
        retirements); raises :class:`KVCapacityError` when it could never
        fit — judged on the TOTAL need (a hit's pinned prefix pages count
        against capacity too, so a hit that would fit privately but not
        alongside its own prefix is typed-rejected, not spun on). LRU
        refcount-0 prefixes are evicted when the free list runs dry;
        ``exclude`` protects the entry this request is about to hit."""
        total = pages_needed(plen + budget, self.page_size)
        need = total - n_pfx_cached
        if total > self.pool.usable:
            raise KVCapacityError(
                f"prompt {plen} + {budget} new tokens needs {total} KV "
                f"pages (page_size {self.page_size}) but the pool holds "
                f"only {self.pool.usable} even when empty — raise "
                "num_pages or shorten the request", pages_needed=total,
                pages_capacity=self.pool.usable)
        if self.pool.free_count < need:
            spill = (self._spill_prefix if self.kv_host is not None
                     else None)
            evicted = self.prefix.evict_until(self.pool, need,
                                              exclude=exclude, spill=spill)
            if evicted:
                _safe_inc("paddle_serving_kv_prefix_evictions_total",
                          "prefix-cache entries LRU-evicted for pages",
                          evicted)
            if self.pool.free_count < need:
                return None
        return self.pool.alloc(need)

    # -- host-RAM prefix spill tier (ROADMAP item 4b) ------------------------
    def _slab_meta(self) -> Dict[str, object]:
        """The engine-compatibility facts a slab must match to restore —
        a mismatch (config change across a restart, foreign slab) is a
        loud miss, never silently-wrong KV."""
        cfg = self.cfg
        return {"page_size": self.page_size,
                "kvh": cfg.num_key_value_heads, "hd": cfg.head_dim,
                "layers": cfg.num_hidden_layers,
                "kv_quant": self.kv_quant or "off",
                "dtype": np.dtype(self._kv_dtype).name}

    def _spill_prefix(self, h: str, entry) -> bool:
        """``evict_until``'s spill callback: serialize the entry's live
        device pages (+ scales under int8) into a slab and hand it to the
        host tier. Runs BEFORE the pages return to the free list. False
        (tier rejected it — bigger than the whole budget) means the
        eviction proceeds as a true discard."""
        from .kv_pool import HostSlab, serialize_page_slab

        idx = np.asarray(entry.pages, np.int32)
        arrays = []
        for kp, vp in self.caches:
            if self.kv_quant == "int8":
                (kq, ksc), (vq, vsc) = kp, vp
                arrays += [np.asarray(kq[idx]), np.asarray(ksc[idx]),
                           np.asarray(vq[idx]), np.asarray(vsc[idx])]
            else:
                arrays += [np.asarray(kp[idx]), np.asarray(vp[idx])]
        meta = dict(self._slab_meta(), length=entry.length,
                    n_pages=len(entry.pages))
        blob = serialize_page_slab(meta, arrays)
        slab = HostSlab(blob, entry.length, len(entry.pages),
                        entry.last_used)
        ok = self.kv_host.put(h, slab)
        if ok:
            _safe_inc("paddle_serving_kv_prefix_spills_total",
                      "prefix entries spilled to the host-RAM tier "
                      "instead of discarded")
            _flight_record("kv", "prefix_spill", hash=h[:16],
                           pages=len(entry.pages), bytes=len(blob))
        return ok

    def _restore_prefix(self, h: str, slab, pfx_pages: List[int]) -> bool:
        """Write a popped host slab back into freshly reserved device
        pages and re-register the prefix (refcount 0 — the hit path about
        to run takes the slot's ref). False on any mismatch/corruption:
        the caller folds the pages back into a full-prefill miss."""
        from .kv_pool import deserialize_page_slab

        try:
            meta, arrays = deserialize_page_slab(slab.blob)
            want = dict(self._slab_meta(), length=meta.get("length"),
                        n_pages=len(pfx_pages))
            if meta != want:
                raise ValueError(f"slab/engine mismatch: {meta} != {want}")
            idx = jnp.asarray(np.asarray(pfx_pages, np.int32))
            per = 4 if self.kv_quant == "int8" else 2
            out = []
            for li, (kp, vp) in enumerate(self.caches):
                a = arrays[li * per:(li + 1) * per]
                if self.kv_quant == "int8":
                    (kq, ksc), (vq, vsc) = kp, vp
                    out.append(((kq.at[idx].set(jnp.asarray(a[0])),
                                 ksc.at[idx].set(jnp.asarray(a[1]))),
                                (vq.at[idx].set(jnp.asarray(a[2])),
                                 vsc.at[idx].set(jnp.asarray(a[3])))))
                else:
                    out.append((kp.at[idx].set(jnp.asarray(a[0])),
                                vp.at[idx].set(jnp.asarray(a[1]))))
            self.caches = out
            entry = self.prefix.register(h, pfx_pages, int(meta["length"]))
            entry.refcount = 0
            _safe_inc("paddle_serving_kv_prefix_restores_total",
                      "prefix entries restored from the host tier into "
                      "device pages")
            _flight_record("kv", "prefix_restore", hash=h[:16],
                           pages=len(pfx_pages), bytes=len(slab.blob))
            return True
        except Exception as e:
            sys.stderr.write(
                f"[serving] host-tier slab {h[:16]} failed to restore "
                f"({type(e).__name__}: {e}); serving the request as a "
                "full-prefill miss\n")
            _safe_inc("paddle_serving_kv_host_restore_failures_total",
                      "host-tier slabs that failed validation/restore "
                      "(request served as a miss)",
                      reason=type(e).__name__)
            return False

    def _admit(self, req) -> bool:
        """Prefill ``req`` into a free slot (one compiled call, no host
        sync); False when no slot (or, paged, no pages) is free."""
        free = [i for i, s in enumerate(self._host_slots) if s.req is None]
        if not free:
            return False
        slot = free[0]
        ids = np.asarray(req.prompt_ids, np.int32).reshape(1, -1)
        plen = ids.shape[1]
        if plen + req.max_new_tokens > self.L:
            raise ValueError(
                f"prompt {plen} + {req.max_new_tokens} new tokens exceeds "
                f"engine max_len {self.L} (model max_position_embeddings "
                f"{self.cfg.max_position_embeddings})")
        bucket = min(_bucket(plen), self.L)
        temp = float(getattr(req, "temperature", 0.0) or 0.0)
        eos = getattr(req, "eos_token_id", None)
        top_k = int(getattr(req, "top_k", 0) or 0)
        if top_k > self.TOP_K_CAP:
            raise ValueError(
                f"top_k {top_k} exceeds the continuous engine's static "
                f"filter cap {self.TOP_K_CAP} (use the static serving mode "
                "or lower top_k)")
        if self.spec is not None and temp > 0.0:
            raise ValueError(
                f"temperature {temp:g} with speculative decoding armed: "
                "greedy acceptance is token-exact for temperature 0 only "
                "(sampling-correct rejection resampling is a planned "
                "seam) — send temperature=0 or serve without spec_k")
        aligned = n_pfx = 0
        h = entry = None
        pages_reserved = None
        restored = False
        if self.kv_layout == "paged":
            aligned, n_pfx, h, entry = self._prefix_plan(req, ids, plen)
            hit = entry is not None
            slab = None
            if not hit and h is not None and self.kv_host is not None:
                # device miss with a spilled copy: POP the slab before the
                # reservation below — its own spills could otherwise push
                # this very slab over the host budget's LRU edge. We own
                # it now: restore it, or put it back on every early exit.
                slab = self.kv_host.pop(h)
            try:
                private = self._reserve_pages(
                    plen, req.max_new_tokens, n_pfx if hit else 0,
                    exclude=h if hit else None)
            except BaseException:
                if slab is not None:
                    self.kv_host.put_back(h, slab)
                raise
            if private is None:
                if slab is not None:
                    self.kv_host.put_back(h, slab)
                return False          # pool dry: decode frees pages later
            if slab is not None:
                # the no-prefix reservation covers prompt+budget in full:
                # its first n_pfx pages become the restored prefix, the
                # rest stay private — exactly a hit's reservation split
                t0r = time.perf_counter()
                pfx_pages, rest = private[:n_pfx], private[n_pfx:]
                if self._restore_prefix(h, slab, pfx_pages):
                    entry = self.prefix.lookup(h)
                    hit = restored = True
                    private = rest
                    self._restore_ms.append(
                        (time.perf_counter() - t0r) * 1e3)
                    del self._restore_ms[:-512]
                else:
                    private = pfx_pages + rest   # bad slab: full miss
            pages_reserved = len(private)
            self._slot_pages[slot] = private
            row = np.zeros((self.P,), np.int32)
            if hit:
                # safe: the reservation above excluded this entry from
                # eviction, so the hash still resolves
                self.prefix.ref(h)
                row[:n_pfx] = entry.pages
                row[n_pfx:n_pfx + len(private)] = private
                self._slot_prefix[slot] = h
                _safe_inc("paddle_serving_kv_prefix_hits_total",
                          "prefix-cache hits (prefill work skipped)")
            else:
                row[:len(private)] = private
            self.page_table = self.page_table.at[slot].set(jnp.asarray(row))
            for wc in self.window_classes:
                # a free slot always finds its ring: the pool holds one a slot
                ring = wc.pool.alloc(min(wc.ring, pages_needed(
                    plen + req.max_new_tokens, self.page_size)))
                wc.slot_pages[slot] = ring
                wrow = np.zeros((wc.ring,), np.int32)
                wrow[:len(ring)] = ring
                wc.table = wc.table.at[slot].set(jnp.asarray(wrow))
            self._kv_gauges()
        if self.kv_layout == "paged" and entry is not None:
            # HIT: prefill only the tail against the cached prefix pages
            tail = plen - aligned
            tail_bucket = min(_bucket(tail),
                              self.cfg.max_position_embeddings - aligned,
                              self.P * self.page_size - aligned)
            padded = np.zeros((1, tail_bucket), np.int32)
            padded[0, :tail] = ids[0, aligned:]
            fn_key = _cp.prefix_admit_key(n_pfx, tail_bucket)
            prog_plen = tail
            perf_bucket = f"pfx{n_pfx}t{tail_bucket}"
        else:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = ids
            fn_key = _cp.admit_key(bucket)
            prog_plen = plen
            perf_bucket = f"p{bucket}"
        args = self._admit_args(
            fn_key, jnp.asarray(padded), plen=prog_plen, slot=slot,
            temp=temp, eos=-1 if eos is None else int(eos),
            budget=req.max_new_tokens, top_k=top_k)
        fn = self._programs.get(fn_key)
        if fn is None:
            fn = self._build_program(fn_key)
            p = _perf()
            if p is not None and p.enabled():
                # capture the bucketed prefill program's exact cost; the
                # AOT Compiled replaces the jit entry (one compile total)
                compiled = p.capture_jit("serving.admit", fn, args,
                                         bucket=perf_bucket, quant=self.quant
                                         or "off")
                if compiled is not None:
                    fn = compiled
            self._programs[fn_key] = fn
        self._close_turnaround()
        try:
            (self.caches, self.lens, self.tokens, self.active, self.temps,
             self.eos_ids, self.budgets, self.top_ks, self.key, first) = \
                fn(*args)
        except BaseException:
            # the reservation must not outlive a failed admission (a
            # compile/dispatch error here would otherwise leak the pages
            # until a full reset)
            self._release_kv(slot)
            raise
        # only AFTER the first call succeeds: a failed first admission
        # (chaos, OOM) must not mask this key from a later warmup()
        self._warmed.add(fn_key)
        if self.kv_layout == "paged" and h is not None and entry is None:
            # MISS with a declared prefix: the full prefill just wrote the
            # prefix pages — pin them shared (this slot holds the first
            # ref); the slot keeps only its private tail/decode pages
            self.prefix.register(h, self._slot_pages[slot][:n_pfx], aligned)
            self.prefix.misses += 1
            self._slot_pages[slot] = self._slot_pages[slot][n_pfx:]
            self._slot_prefix[slot] = h
        if self.spec is not None:
            # draft prefill rides every admission: the draft keeps no
            # prefix cache, so it prefills the FULL prompt at the plain
            # bucket even when the target admission was a prefix HIT
            dpad = np.zeros((1, bucket), np.int32)
            dpad[0, :plen] = ids
            dkey = _cp.draft_admit_key(bucket)
            try:
                (self.spec.draft_caches, self.spec.prev_tokens) = \
                    self._program(dkey)(
                        self.spec.draft_params, self.spec.draft_caches,
                        self.spec.prev_tokens, jnp.asarray(dpad),
                        jnp.int32(plen), jnp.int32(slot))
            except BaseException:
                # the target-side admission already committed: deactivate
                # the device lane and return the pages, or a failed draft
                # prefill leaks the whole reservation
                self.reset_slots([slot])
                raise
            self._warmed.add(dkey)
        self._host_slots[slot] = _Slot(req, budget=int(req.max_new_tokens),
                                       temp=temp, top_k=top_k)
        self.stats["peak_busy"] = max(self.stats["peak_busy"],
                                      self.busy_slots())
        _stamp(req, "_t_admit")
        tr = _trace_of(req)
        if tr is not None:
            try:
                res = req.result
                tr.event("queue.wait", t0=res._t_submit, t1=res._t_admit)
                tr.event(
                    "admit", slot=slot, bucket=bucket, plen=plen,
                    **({} if pages_reserved is None
                       else {"pages": pages_reserved}),
                    **({} if h is None
                       else {"prefix": "restore" if restored
                             else ("hit" if entry is not None
                                   else "miss"),
                             "prefix_pages": n_pfx}))
                if self.spec is not None:
                    tr.event("spec.draft_prefill", bucket=bucket)
            except Exception:
                pass
        _flight_record("request", str(getattr(req, "id", "?")),
                       phase="admit", slot=slot, bucket=bucket, plen=plen,
                       **({"prefix_hit": entry is not None} if h else {}))
        self._first_pending[slot] = first   # device scalar, synced at collect
        self.stats["requests"] += 1
        kind = "whole" if entry is None else "prefix_hit"
        self.stats["admit_n." + kind] += 1
        self.stats["admit_tokens_computed." + kind] += prog_plen
        self.stats["admit_tokens_cached"] += plen - prog_plen
        return True

    def _close_turnaround(self) -> None:
        """The host's turn-around ends here: from the return of the last
        chunk's sync to this call of a compiled program the device had
        nothing of ours to run. Counted once per chunk."""
        t = self._t_synced
        if t is not None:
            self._t_synced = None
            self.stats["turnaround_s"] += time.perf_counter() - t
            self.stats["turnaround_n"] += 1

    def _count_decode_call(self) -> None:
        """Once per decode call, at its dispatch: the call itself, and the
        sampler branch (:meth:`_sample`) that the requests holding a slot
        ask for, read from what each was admitted with. The program decides
        for itself, step by step, from the slots still live; this is the
        host's count of the same choice and costs no sync."""
        drawing = [s.top_k for s in self._host_slots
                   if s.req is not None and s.temp > 0.0]
        branch = 1 + any(k > 0 for k in drawing) if drawing else 0
        self.stats["decode_calls"] += 1
        self.stats[SAMPLE_COUNTERS[branch]] += 1

    def _count_view(self, column) -> None:
        """Once per decode call: the pages of the table its steps read,
        beside the whole table's. ``column`` is the program's report, one
        number a slot: the rung's page count in every row where a view was
        gathered, each slot's own walk where a kernel read the pages in
        place; their mean over the slots, rounded up, is what is counted."""
        self.stats["decode_view_pages"] += -(-int(column.sum()) // self.S)
        self.stats["decode_table_pages"] += self.P

    def _release_kv(self, slot: int, zero_row: bool = True) -> None:
        """Return a slot's private pages to the free list, drop its prefix
        ref, and (by default) zero its page-table row so in-flight decode
        writes land in the null page. Idempotent."""
        if self.kv_layout != "paged":
            return
        pages = self._slot_pages[slot]
        if pages:
            self.pool.free(pages)
            self._slot_pages[slot] = []
        h = self._slot_prefix[slot]
        if h is not None:
            self.prefix.unref(h)
            self._slot_prefix[slot] = None
        for wc in self.window_classes:
            if wc.slot_pages[slot]:
                wc.pool.free(wc.slot_pages[slot])
                wc.slot_pages[slot] = []
            if zero_row:
                wc.table = wc.table.at[slot].set(
                    jnp.zeros((wc.ring,), jnp.int32))
        if zero_row:
            self.page_table = self.page_table.at[slot].set(
                jnp.zeros((self.P,), jnp.int32))
        self._kv_gauges()

    def _retire(self, slot: int):
        s = self._host_slots[slot]
        if s.req is not None:
            prompt = np.asarray(s.req.prompt_ids, np.int32).reshape(-1)
            gen = s.emitted[: s.budget]
            eos = getattr(s.req, "eos_token_id", None)
            if eos is not None and eos in gen:
                gen = gen[: gen.index(eos) + 1]   # trim past eos, keep it
            res = getattr(s.req, "result", None)
            if res is not None and getattr(res, "_event", None) is not None \
                    and res._event.is_set():
                # the future already has an outcome (a client cancel
                # raced this chunk's retirement): the _set below will
                # lose, nobody receives these tokens — attribute ALL of
                # them to the cancel kind, not to useful
                _account(getattr(res, "_cancel_kind", "cancel"),
                         len(s.emitted))
            else:
                _account("useful", len(gen))
                # tokens emitted past eos/budget and trimmed here: real
                # decode work nobody receives (the spec chunk's tail,
                # the chunk that overshot the budget)
                _account("overshoot", len(s.emitted) - len(gen))
            _stamp(s.req, "_n_new", len(gen))
            if self.spec is not None:
                # accepted counts ride the result future so slo()
                # consumers and benches can report tokens-per-target-step
                # per request, not just engine-wide
                _stamp(s.req, "_spec_steps", s.spec_steps)
                _stamp(s.req, "_spec_accepted", s.spec_accepted)
            s.req.result._set(output=np.concatenate(
                [prompt, np.asarray(gen, np.int32)]))
        self._release_kv(slot)
        self._host_slots[slot] = _Slot()

    def _collect_firsts(self):
        """ONE host sync for every first token admitted since the last
        collect (stacked on device, then a single transfer). Returns the
        slots whose ``_t_first`` was stamped by THIS collect — the spec
        chunk uses it to count tokens that landed at the same sync."""
        if not self._first_pending:
            return []
        with phase("serve.first_sync", self.stats):
            slots = sorted(self._first_pending)
            vals = np.asarray(
                jnp.stack([self._first_pending[i] for i in slots]))
            now = time.perf_counter()
            stamped = []
            for i, slot in enumerate(slots):
                s = self._host_slots[slot]
                if s.req is not None:
                    s.emitted.append(int(vals[i]))
                    self.stats["tokens_out"] += 1
                    # the prefill's sampled token reaching the HOST is the
                    # honest first-token time (TTFT numerator)
                    if getattr(s.req.result, "_t_first", 1) is None:
                        _stamp(s.req, "_t_first", now)
                        stamped.append(slot)
                        tr = _trace_of(s.req)
                        if tr is not None:
                            tr.event("first_token", t0=now)
            self._first_pending.clear()
        return stamped

    def reset_slots(self, slots=None):
        """Deactivate device-side slot state (all slots, or the given list)
        — REQUIRED after a failed decode or engine stop, or retired rows
        keep consuming compute as phantom active lanes in every chunk.
        Paged layout also returns the slots' pages to the free list."""
        if slots is None:
            self.active = self._repl(jnp.zeros((self.S,), bool))
            self._first_pending.clear()
            if self.kv_layout == "paged":
                for i in range(self.S):
                    self._release_kv(i, zero_row=False)
                self.page_table = self._repl(
                    jnp.zeros((self.S, self.P), jnp.int32))
                for wc in self.window_classes:
                    wc.table = jnp.zeros((self.S, wc.ring), jnp.int32)
        else:
            for i in slots:
                self.active = self.active.at[int(i)].set(False)
                # only THIS slot's pending first token: other slots' pending
                # syncs must survive a single-slot reset
                self._first_pending.pop(int(i), None)
                self._release_kv(int(i))

    def release_slot(self, slot: int, reason: str = "cancel"):
        """Free one slot without delivering a result — the cancellation /
        deadline path: the device lane goes inactive (no phantom compute),
        the host slot is recycled, and the next admission may reuse it. The
        caller owns failing the request's future. ``reason`` names the
        goodput kind the slot's already-decoded tokens are wasted as."""
        s = self._host_slots[int(slot)]
        if s.req is not None:
            _account(reason, len(s.emitted))
        self.reset_slots([slot])
        self._host_slots[int(slot)] = _Slot()

    def busy_slots(self) -> int:
        """Host-visible count of slots holding an in-flight request."""
        return sum(1 for s in self._host_slots if s.req is not None)

    def _spec_chunk(self):
        """The speculative serve step: per outer step, ONE draft program
        call (k greedy proposals) then ONE verify call (batched target
        forward + masked accept/reject); the chunk's payloads stay on
        device and sync to the host as a single transfer, exactly the
        non-spec chunk's cadence. Rejected tokens cost nothing to roll
        back — ``lens`` simply didn't advance past them."""
        spec = self.spec
        k = spec.k
        steps = self._spec_steps_per_chunk
        t0 = time.perf_counter()
        dkey, vkey = _cp.draft_key(k), _cp.verify_key(k)
        dfn = self._program(dkey)
        vfn = self._program(vkey)
        parts = []
        self._close_turnaround()
        with phase("serve.decode_dispatch", self.stats):
            for _ in range(steps):
                spec.draft_caches, props = dfn(
                    spec.draft_params, spec.draft_caches, spec.prev_tokens,
                    self.tokens, self.lens, self.active)
                (self.caches, self.lens, self.tokens, spec.prev_tokens,
                 self.active, self.budgets, payload) = vfn(
                    self.params, self.caches, self.page_table, self.lens,
                    self.tokens, spec.prev_tokens, self.active,
                    self.budgets, self.eos_ids, props)
                parts.append(payload)
        # post-success, exactly like the non-spec chunk: a failed first
        # call must not mask these keys from a later warmup()
        self._warmed.add(dkey)
        self._warmed.add(vkey)
        self._count_decode_call()
        stamped = self._collect_firsts()
        with phase("serve.chunk_sync", self.stats):
            pk = np.asarray(parts[0] if steps == 1
                            else jnp.concatenate(parts, axis=1))
        self._t_synced = time.perf_counter()
        with phase("serve.deliver", self.stats):
            self._deliver_spec(pk, stamped, t0)

    def _deliver_spec(self, pk, stamped, t0):
        """Host half of a speculative chunk: append what each slot
        emitted, account the rejected proposals, retire what finished."""
        spec = self.spec
        k = spec.k
        steps = self._spec_steps_per_chunk
        blocks = pk.reshape(self.S, steps, k + 4)
        em = blocks[:, :, : k + 1]           # emitted tokens, -1 padded
        acc = blocks[:, :, k + 1]            # raw accepted-run lengths
        act = blocks[:, -1, k + 2].astype(bool)
        # the widest view among the call's verify steps
        self._count_view(blocks[:, :, k + 3].max(axis=1))
        chunk_emitted = 0
        for slot, s in enumerate(self._host_slots):
            if s.req is None:
                continue
            toks = [int(t) for t in em[slot].ravel() if t >= 0]
            s.emitted.extend(toks)
            chunk_emitted += len(toks)
            self.stats["tokens_out"] += len(toks)
            live = acc[slot][acc[slot] >= 0]
            s.spec_steps += int(live.size)
            s.spec_accepted += int(live.sum())
            # drafted-but-rejected proposals: k drafted per live verify
            # step minus the accepted run — real draft work the target
            # never advanced past (outside the tokens_out identity)
            _account("spec_rejected", int(k * live.size - live.sum()))
            tr = _trace_of(s.req)
            if tr is not None and live.size:
                tr.event("spec.round", t0=t0, t1=time.perf_counter(),
                         tokens=len(toks), **spec.round_summary(acc[slot]))
            if slot in stamped and toks:
                # this sync delivered the admission's first token AND the
                # chunk's tokens at the same instant — record how many, so
                # slo()'s TPOT divides by tokens that arrived AFTER
                # _t_first instead of fabricating a k-times-faster stream
                _stamp(s.req, "_n_at_first", 1 + len(toks))
            if not act[slot] or len(s.emitted) >= s.budget:
                self._retire(slot)
        spec.record_chunk(acc, chunk_emitted)

    def _decode_chunk(self):
        if self.spec is not None:
            return self._spec_chunk()
        args = self._decode_args()
        p = _perf()
        perf_on = p is not None and p.enabled()
        cost_bucket = f"s{self.S}c{self.chunk}"
        if perf_on and not self._decode_captured:
            self._decode_captured = True    # capture attempted once only
            # lower (no backend compile) a 1-step variant and scale by
            # chunk: XLA cost analysis counts the scan body once, so the
            # chunk program's own count would under-report by ~chunk
            p.cost_of_lowered(
                "serving.decode", jax.jit(self._decode_program(1)), args,
                bucket=cost_bucket, scale=float(self.chunk),
                quant=self.quant or "off", slots=self.S, chunk=self.chunk)
        # chunks right after an admission also pay the _collect_firsts
        # readback inside this window; only PURE decode chunks are folded
        # into the program's wall, so wall_min measures the decode
        # program, not an extra link roundtrip
        pure_decode = not self._first_pending
        fn = self._programs.get("decode")
        if fn is None:
            fn = self._build_program("decode")
            self._programs["decode"] = fn
        self._close_turnaround()
        t0 = time.perf_counter()
        with phase("serve.decode_dispatch", self.stats):
            (self.caches, self.tokens, self.lens, self.active, self.budgets,
             self.key, packed) = fn(*args)
        # post-success: a failed first chunk must not mask the key from a
        # later warmup()
        self._warmed.add("decode")
        self._count_decode_call()
        self._collect_firsts()
        with phase("serve.chunk_sync", self.stats):
            pk = np.asarray(packed)             # the ONE sync per chunk
        t_sync = self._t_synced = time.perf_counter()
        with phase("serve.deliver", self.stats):
            if perf_on and pure_decode:
                # the packed readback IS this chunk's host sync, so the
                # wall is real device time (plus the per-call link floor)
                p.observe("serving.decode", t_sync - t0, bucket=cost_bucket)
            em, act = pk[:, :self.chunk], pk[:, self.chunk].astype(bool)
            if self.kv_layout == "paged":
                self._count_view(pk[:, self.chunk + 1])
                picks_at = self.chunk + 2 + 3 * len(self.window_classes)
                if self.window_classes:
                    self._count_windows(pk[:, self.chunk + 2:picks_at])
                if self._experts_held:
                    self._count_picks(pk[:, picks_at:])
            for slot, s in enumerate(self._host_slots):
                if s.req is None:
                    continue
                toks = [int(t) for t in em[slot] if t >= 0]
                s.emitted.extend(toks)
                self.stats["tokens_out"] += len(toks)
                tr = _trace_of(s.req)
                if tr is not None and toks:
                    tr.event("decode.chunk", t0=t0, t1=t_sync,
                             tokens=len(toks))
                if not act[slot] or len(s.emitted) >= s.budget:
                    self._retire(slot)

    def flush(self):
        """Deliver results for slots that finished during admission (first
        token hit eos / budget 1) without waiting for a decode chunk."""
        self._collect_firsts()
        act = np.asarray(self.active)
        for slot, s in enumerate(self._host_slots):
            if s.req is not None and (not act[slot]
                                      or len(s.emitted) >= s.budget):
                self._retire(slot)

    def serve(self, requests, timeout: float = 600.0):
        """Run a list of GenerationRequest-shaped objects to completion with
        continuous batching. Returns aggregate stats (the card number)."""
        pending = list(requests)
        t0 = time.perf_counter()
        n_out0 = self.stats["tokens_out"]
        deadline = t0 + timeout
        while (pending or any(s.req is not None for s in self._host_slots)) \
                and time.perf_counter() < deadline:
            while pending:
                try:
                    if not self._admit(pending[0]):
                        break                  # no slot/pages free: decode
                except ValueError as e:
                    # unservable request (max_len / top_k / KV capacity):
                    # fail ITS future and keep serving the rest — one bad
                    # request must not abandon the whole list
                    try:
                        pending[0].result._set(error=e)
                    except Exception:
                        pass
                pending.pop(0)
            if any(s.req is not None for s in self._host_slots):
                self._decode_chunk()
        self.flush()
        dt = time.perf_counter() - t0
        toks = self.stats["tokens_out"] - n_out0
        return {"wall_s": round(dt, 3),
                "new_tokens": toks,
                "agg_tokens_per_sec": round(toks / max(dt, 1e-9), 1),
                "decode_calls": self.stats["decode_calls"]}
