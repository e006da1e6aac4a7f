"""Grouped-query (GQA) decode attention that reads a long context's pages in
place — a Pallas TPU kernel walking the page table in chunks of pages (+ the
Pallas interpreter off the chip). The walk is paged_latent_attention.py's; what
differs is what a page is.

A K and a V pool a layer, ``[pages, page_size, kvh, hd]``; query head ``h``
attends the keys of kv head ``h // n_rep``. The gathered formulation
(``decode_engine._attend_view``) materialises ``pool[page_table[:, :n]]`` for
one extent ``n`` that holds the LONGEST of the slots' contexts, once for K and
once for V in every layer, whatever is live. Here a slot's pages are read once,
where they lie, as far as ITS OWN length goes, and an idle slot reads nothing.

Shape contract (one decode step, one query row a head):

* ``q [S, H, hd]``;
* ``k_new, v_new [S, kvh, hd]``: the step's new row. It is an operand and
  takes part as the LAST key; the pools are read only (the engine writes the
  row to its page outside, so a donated pool never passes through a branch or
  a kernel's output);
* ``page_table [S, P]`` int32, ``lens [S]`` int32: the keys the slot holds in
  the pool, so pool positions ``< lens`` are keys and the new row stands at
  position ``lens`` (the reference's bottom-right rule, ``k_pos <= lens``).
  ``lens`` is also how far the walk goes: a caller hands an IDLE slot 0 (its
  stale length over a zeroed table row would read the null page once a page),
  and that slot copies nothing and returns its new row's values, discarded.

A page is seen as ``[page_size * kvh, hd]``: row ``t * kvh + g`` is token
``t``'s key for kv head ``g``, the order the pool has in memory, so the view is
free and a page is one contiguous copy into its rows of a ``[K * page_size *
kvh, hd]`` buffer. A chunk is then ONE matmul operand for all heads: ``q [H,
hd]`` against it gives every head's score with every (token, kv head) row, and
the rows of another kv head than the query's own are masked with the positions
past the length. The MXU does ``kvh`` times the products the heads need and is
idle at ``H`` rows all the same; nothing is transposed, sliced by head or
stacked in registers (the design that regroups a page by kv head first lost on
the chip: docs/kernels.md). The values follow the same way: ``P [H, rows]``,
zero in the masked rows, times the V chunk.

The walk, the double buffer, the hand-over of the next slot's first chunk and
the treatment of a last chunk's dead pages are the latent kernel's, as is the
arithmetic: operands in the pools' dtype straight into the MXU, scores, online
softmax and accumulation in float32, ``P`` cast to the pools' dtype for the
values as ``_cached_attention`` casts it.

What Mosaic asks of the layout: ``hd`` in whole 128-lane tiles (no slice of a
narrower array can be the source of a copy; :func:`reads_in_place` says whether
a pool qualifies, and the engine keeps the gathered view where it does not),
and the new rows repeated to one a query head outside (``[S, H, hd]``, 8 KB a
slot), so that the last key's score is a product and a lane sum on the vector
unit with nothing regrouped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import call_name, interpret_mode
from .paged_latent_attention import LANES, NEG_INF

# tokens a loop step attends: K = CHUNK_TOKENS / page_size pages. Read on a
# TPU v5 lite at 32 slots x 32 query over 8 kv heads of 128, pages of 64, bf16
# (PERF.md section 6, PR 35; ms a call at 128 / 256 / 512 / 1,024 tokens a
# chunk): 12 live slots of 2.2-3.7k tokens and 20 idle 0.288 / 0.249 / 0.254 /
# 0.264, 32 slots of 80-1,500 tokens (median 450) 0.178 / 0.157 / 0.150 /
# 0.158, 32 of 3,500 tokens 0.802 / 0.661 / 0.656 / 0.661. Short chunks pay
# for their steps; long ones attend a short context's masked tail.
CHUNK_TOKENS = 512


def pages_per_chunk(page_size: int, table_pages: int) -> int:
    """K, the pages one step of the walk reads."""
    return max(1, min(table_pages, CHUNK_TOKENS // page_size))


def reads_in_place(pool) -> bool:
    """Whether the kernel can copy pages out of ``pool`` as it lies: a plain
    array (not an int8 ``(codes, scales)`` pair) whose rows are whole lane
    tiles, a row a (token, kv head) or a row a token (:func:`token_rows`).
    The same answer on and off the chip, so what the CPU tests serve through
    the kernel is what the chip serves through it."""
    return not isinstance(pool, tuple) and pool.shape[-1] % LANES == 0


def token_rows(pool) -> bool:
    """Whether ``pool`` keeps one row a token, ``[pages, page_size, kvh *
    width]``, every kv head's side by side: how a model declares keys of a
    width that is no whole number of lane tiles (192), whose ``[kvh, 192]``
    rows the TPU would lay out with the kv heads padded to a tile and the
    width to two (the kernel's view of a page as rows would then be a copy
    of the whole pool at every call)."""
    return pool.ndim == 3


def _kernel(pt_ref, lens_ref, q_ref, kn_ref, vn_ref, *refs, K, ps, kvh,
            rep, scale, slots, table_pages, window=0, sink=False,
            tokens=False):
    """Grid (slot,): online softmax over the slot's live chunks of K pages,
    then over the new row. With a ``window`` the table row is a ring and the
    walk starts at the page of the window's first key; with a ``sink`` each
    head's softmax starts from its sink logit (``m = b_h``, ``l = 1``);
    with ``tokens`` a page is a row a token (:func:`token_rows`)."""
    if sink:
        sink_ref, *refs = refs
    (k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, first_ref, m_ref, l_ref,
     acc_ref) = refs
    s = pl.program_id(0)
    span = K * ps                         # tokens a chunk
    page_rows = ps if tokens else ps * kvh    # rows a page
    held = lens_ref[s]                    # keys in the pool: positions < held

    def base(keys):
        """Position of the first row of a slot's chunk 0: the page of the
        window's first key (the query at ``keys`` sees ``keys - window + 1``
        on)."""
        return jnp.maximum(keys - window + 1, 0) // ps * ps

    if window:
        n = (held - base(held) + span - 1) // span
    else:
        n = (held + span - 1) // span     # live chunks

    def copies(slot, chunk, buf, k):
        """The two copies of page ``k`` of ``slot``'s ``chunk`` into its rows
        of buffer ``buf``."""
        if window:                        # a ring: logical page j in entry j % entries
            page = pt_ref[slot, (base(lens_ref[slot]) // ps + chunk * K + k)
                          % table_pages]
        else:
            page = pt_ref[slot, jnp.minimum(chunk * K + k, table_pages - 1)]
        rows = pl.ds(pl.multiple_of(k * page_rows, page_rows), page_rows)
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, rows],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, rows],
                                      sem.at[1, buf]))

    def each_page(act):
        """``act`` on every copy of a chunk's pages that hold a key: all K
        but in a slot's last chunk (a loop, not K copies of it in the
        program text)."""
        def page(k, carry):
            for copy in copies(*carry, k):
                act(copy)
            return carry

        def run(slot, chunk, buf):
            keys = lens_ref[slot] - chunk * span
            if window:
                keys = keys - base(lens_ref[slot])
            jax.lax.fori_loop(0, jnp.minimum(K, (keys + ps - 1) // ps), page,
                              (slot, chunk, buf))
        return run

    start = each_page(lambda copy: copy.start())
    wait = each_page(lambda copy: copy.wait())

    @pl.when(s == 0)
    def _():
        first_ref[0] = 0
        # rows that no copy fills are masked keys, and must hold numbers
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

        @pl.when(n > 0)
        def _():
            start(s, 0, 0)

    first = first_ref[0]                  # the buffer this slot's chunk 0 is in
    after = jnp.minimum(s + 1, slots - 1)
    after_live = (s + 1 < slots) & (lens_ref[after] > 0)

    if sink:
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.ones_like(l_ref)
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(scores, weigh):
        """One online-softmax update with ``scores [H, n]`` (f32);
        ``weigh(p)`` is ``p``'s ``[H, hd]`` sum of value rows."""
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + weigh(p)
        m_ref[...] = m_new

    q = q_ref[0]                                              # [H, hd]
    H, hd = q.shape
    if tokens:
        # kv head g's key is lanes g * hd .. of a token's row: each kv head's
        # query heads against its own lanes, one matmul a kv head
        hv = vn_ref.shape[-1]
        heads = [q_ref[0, g * rep:(g + 1) * rep, :] for g in range(kvh)]
        row = jax.lax.broadcasted_iota(jnp.int32, (H, span), 1)
        own, per_row = None, 1
    else:
        # row t * kvh + g of a chunk is token t's key for kv head g: a query
        # head keeps the rows of its own kv head
        row = jax.lax.broadcasted_iota(jnp.int32, (H, span * kvh), 1)
        own = (row % kvh) == (jax.lax.broadcasted_iota(
            jnp.int32, (H, span * kvh), 0) // rep)
        per_row = kvh

    def chunk(i, carry):
        cur = (first + i) % 2

        @pl.when(i + 1 < n)
        def _():
            start(s, i + 1, 1 - cur)

        @pl.when((i + 1 == n) & after_live)
        def _():
            start(after, 0, 1 - cur)

        wait(s, i, cur)
        k, v = kbuf[cur], vbuf[cur]      # [span * kvh, hd]; tokens: [span, kvh * hd]
        if tokens:
            sc = jnp.concatenate([jax.lax.dot_general(
                heads[g], k[:, g * hd:(g + 1) * hd], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) for g in range(kvh)], 0)
        else:
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        sc = sc * scale
        # a chunk that holds a key holds its first: no head's row is all
        # masked, so every masked p is exp(-1e30 - m) = 0 exactly
        if window:
            at = base(held) + i * span    # position of the chunk's row 0
            live = ((row < (held - at) * per_row)
                    & (row >= (held - window + 1 - at) * per_row))
        else:
            live = row < (held - i * span) * per_row
        if own is not None:
            live = own & live

        def weigh(p):
            if not tokens:
                return jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return jnp.concatenate([jax.lax.dot_general(
                p[g * rep:(g + 1) * rep].astype(v.dtype),
                v[:, g * hv:(g + 1) * hv], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) for g in range(kvh)], 0)

        fold(jnp.where(live, sc, NEG_INF), weigh)
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)

    @pl.when((n == 0) & after_live)
    def _():
        start(after, 0, first)

    first_ref[0] = (first + n) % 2
    # the step's own row, the last key: one row a head, so products and sums
    # on the vector unit; never masked, so l > 0
    f32 = jnp.float32
    kn, vn = kn_ref[0].astype(f32), vn_ref[0].astype(f32)     # [H, hd], [H, hv]
    sc = jnp.sum(q.astype(f32) * kn, axis=-1, keepdims=True) * scale
    fold(sc, lambda p: p.astype(vn_ref.dtype).astype(f32) * vn)
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_gqa_attention(q, k_new, v_new, k_pool, v_pool, page_table, lens, *,
                        scale, window=0, sink=None, chunk_pages=None,
                        interpret=None):
    """``out [S, H, hdv]`` in ``q``'s dtype: head ``h`` of slot ``s`` attends
    kv head ``h // (H // kvh)`` at pool positions ``< lens[s]`` through
    ``page_table[s]`` and then the new row ``(k_new[s], v_new[s])``.
    ``window`` > 0: only the keys at ``lens[s] - window + 1`` and after, the
    table row a ring (``kv_pool.ring_pages``), the call named
    ``paged_window_attention``. ``sink [H]`` (float32): one more logit a
    head in every softmax's denominator, with no value. ``chunk_pages``
    overrides K (the tests walk several chunks of a tiny table with it)."""
    K = (pages_per_chunk(k_pool.shape[1], page_table.shape[1])
         if chunk_pages is None else chunk_pages)
    if interpret is None:
        interpret = interpret_mode()
    if window == 1:
        raise ValueError("a window of 1 holds no key of the pool: attend the "
                         "new row alone")
    name = call_name("paged_window_attention" if window
                     else "paged_gqa_attention")
    return _program(float(scale), int(K), bool(interpret), name, int(window))(
        q, k_new, v_new, k_pool, v_pool, page_table, lens,
        *(() if sink is None else (sink,)))


@functools.lru_cache(maxsize=None)
def _program(scale: float, K: int, interpret: bool, name: str, window: int):
    """The jitted call for one static choice and call name. The SAME
    callable comes back for every layer, so a decode program traces and
    lowers the kernel once and calls it, not once a layer (seconds of every
    start-up otherwise, as for ``decode_engine._view_branches``)."""
    return jax.jit(functools.partial(_attend, scale=scale, K=K,
                                     interpret=interpret, name=name,
                                     window=window))


def _attend(q, k_new, v_new, k_pool, v_pool, page_table, lens, sink=None, *,
            scale, K, interpret, name, window=0):
    S, H, hd = q.shape
    tokens = token_rows(k_pool)
    if tokens:
        (pages, ps, _), kvh = k_pool.shape, k_new.shape[1]
    else:
        pages, ps, kvh, _ = k_pool.shape
    hv = v_new.shape[-1]
    rep, P = H // kvh, page_table.shape[1]
    dtype = k_pool.dtype
    rows = K * ps * kvh

    def per_slot(s, pt, lens):
        return (s, 0, 0)

    def whole(s, pt, lens):
        return (0, 0)

    def per_head(new):
        """``new [S, kvh, hd]`` with each kv head's row once a query head."""
        return jnp.repeat(new.astype(dtype), rep, axis=1)

    extra_specs, extra = [], []
    if sink is not None:
        extra_specs.append(pl.BlockSpec((H, 1), whole))
        extra.append(jnp.asarray(sink, jnp.float32).reshape(H, 1))
    if tokens:
        bufs = [pltpu.VMEM((2, K * ps, kvh * hd), dtype),       # K chunks
                pltpu.VMEM((2, K * ps, kvh * hv), dtype)]       # V chunks
        flat = ((pages, ps, kvh * hd), (pages, ps, kvh * hv))
    else:
        bufs = [pltpu.VMEM((2, rows, hd), dtype),      # K chunks
                pltpu.VMEM((2, rows, hd), dtype)]      # V chunks
        flat = ((pages, ps * kvh, hd), (pages, ps * kvh, hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, hd), per_slot),
                  pl.BlockSpec((1, H, hd), per_slot),
                  pl.BlockSpec((1, H, hv), per_slot),
                  *extra_specs,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, hv), per_slot),
        scratch_shapes=[*bufs,
                        pltpu.SemaphoreType.DMA((2, 2)),       # [pool, buffer]
                        pltpu.SMEM((1,), jnp.int32),   # chunk 0's buffer
                        pltpu.VMEM((H, 1), jnp.float32),       # running max
                        pltpu.VMEM((H, 1), jnp.float32),       # denominator
                        pltpu.VMEM((H, hv), jnp.float32)])     # accumulator
    kernel = functools.partial(_kernel, K=K, ps=ps, kvh=kvh, rep=rep,
                               scale=scale, slots=S, table_pages=P,
                               window=window, sink=sink is not None,
                               tokens=tokens)
    # Mosaic has no 64-bit types and the package turns x64 on at import:
    # trace the call (index maps and body) with it off. The slots run in
    # order: a slot starts the next one's first copies.
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, H, hv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lens, jnp.int32),
          q.astype(dtype), per_head(k_new), per_head(v_new), *extra,
          *(p.reshape(shape) for p, shape in zip((k_pool, v_pool), flat)))
