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
    tiles. The same answer on and off the chip, so what the CPU tests serve
    through the kernel is what the chip serves through it."""
    return not isinstance(pool, tuple) and pool.shape[-1] % LANES == 0


def _kernel(pt_ref, lens_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, first_ref, m_ref, l_ref, acc_ref, *, K, ps, kvh,
            rep, scale, slots, table_pages):
    """Grid (slot,): online softmax over the slot's live chunks of K pages,
    then over the new row."""
    s = pl.program_id(0)
    span = K * ps                         # tokens a chunk
    page_rows = ps * kvh                  # (token, kv head) rows a page
    held = lens_ref[s]                    # keys in the pool: positions < held
    n = (held + span - 1) // span         # live chunks

    def copies(slot, chunk, buf, k):
        """The two copies of page ``k`` of ``slot``'s ``chunk`` into its rows
        of buffer ``buf``."""
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
    H = q.shape[0]
    # row t * kvh + g of a chunk is token t's key for kv head g: a query head
    # keeps the rows of its own kv head
    row = jax.lax.broadcasted_iota(jnp.int32, (H, span * kvh), 1)
    own = (row % kvh) == (jax.lax.broadcasted_iota(
        jnp.int32, (H, span * kvh), 0) // rep)

    def chunk(i, carry):
        cur = (first + i) % 2

        @pl.when(i + 1 < n)
        def _():
            start(s, i + 1, 1 - cur)

        @pl.when((i + 1 == n) & after_live)
        def _():
            start(after, 0, 1 - cur)

        wait(s, i, cur)
        k, v = kbuf[cur], vbuf[cur]                           # [span * kvh, hd]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        # a chunk that holds a key holds its first: no head's row is all
        # masked, so every masked p is exp(-1e30 - m) = 0 exactly
        live = own & (row < (held - i * span) * kvh)
        fold(jnp.where(live, sc, NEG_INF),
             lambda p: jax.lax.dot_general(
                 p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                 preferred_element_type=jnp.float32))
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)

    @pl.when((n == 0) & after_live)
    def _():
        start(after, 0, first)

    first_ref[0] = (first + n) % 2
    # the step's own row, the last key: one row a head, so products and sums
    # on the vector unit; never masked, so l > 0
    f32 = jnp.float32
    kn, vn = kn_ref[0].astype(f32), vn_ref[0].astype(f32)     # [H, hd]
    sc = jnp.sum(q.astype(f32) * kn, axis=-1, keepdims=True) * scale
    fold(sc, lambda p: p.astype(vn_ref.dtype).astype(f32) * vn)
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_gqa_attention(q, k_new, v_new, k_pool, v_pool, page_table, lens, *,
                        scale, chunk_pages=None, interpret=None):
    """``out [S, H, hd]`` in ``q``'s dtype: head ``h`` of slot ``s`` attends
    kv head ``h // (H // kvh)`` at pool positions ``< lens[s]`` through
    ``page_table[s]`` and then the new row ``(k_new[s], v_new[s])``.
    ``chunk_pages`` overrides K (the tests walk several chunks of a tiny
    table with it)."""
    K = (pages_per_chunk(k_pool.shape[1], page_table.shape[1])
         if chunk_pages is None else chunk_pages)
    if interpret is None:
        interpret = interpret_mode()
    return _program(float(scale), int(K), bool(interpret),
                    call_name("paged_gqa_attention"))(
        q, k_new, v_new, k_pool, v_pool, page_table, lens)


@functools.lru_cache(maxsize=None)
def _program(scale: float, K: int, interpret: bool, name: str):
    """The jitted call for one static choice and call name. The SAME
    callable comes back for every layer, so a decode program traces and
    lowers the kernel once and calls it, not once a layer (seconds of every
    start-up otherwise, as for ``decode_engine._view_branches``)."""
    return jax.jit(functools.partial(_attend, scale=scale, K=K,
                                     interpret=interpret, name=name))


def _attend(q, k_new, v_new, k_pool, v_pool, page_table, lens, *, scale, K,
            interpret, name):
    S, H, hd = q.shape
    pages, ps, kvh, _ = k_pool.shape
    rep, P = H // kvh, page_table.shape[1]
    dtype = k_pool.dtype
    rows = K * ps * kvh

    def per_slot(s, pt, lens):
        return (s, 0, 0)

    def per_head(new):
        """``new [S, kvh, hd]`` with each kv head's row once a query head."""
        return jnp.repeat(new.astype(dtype), rep, axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, hd), per_slot),
                  pl.BlockSpec((1, H, hd), per_slot),
                  pl.BlockSpec((1, H, hd), per_slot),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, hd), per_slot),
        scratch_shapes=[pltpu.VMEM((2, rows, hd), dtype),      # K chunks
                        pltpu.VMEM((2, rows, hd), dtype),      # V chunks
                        pltpu.SemaphoreType.DMA((2, 2)),       # [pool, buffer]
                        pltpu.SMEM((1,), jnp.int32),   # chunk 0's buffer
                        pltpu.VMEM((H, 1), jnp.float32),       # running max
                        pltpu.VMEM((H, 1), jnp.float32),       # denominator
                        pltpu.VMEM((H, hd), jnp.float32)])     # accumulator
    kernel = functools.partial(_kernel, K=K, ps=ps, kvh=kvh, rep=rep,
                               scale=scale, slots=S, table_pages=P)
    # Mosaic has no 64-bit types and the package turns x64 on at import:
    # trace the call (index maps and body) with it off. The slots run in
    # order: a slot starts the next one's first copies.
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, H, hd), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lens, jnp.int32),
          q.astype(dtype), per_head(k_new), per_head(v_new),
          k_pool.reshape(pages, ps * kvh, hd),
          v_pool.reshape(pages, ps * kvh, hd))
