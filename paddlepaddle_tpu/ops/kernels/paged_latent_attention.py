"""Absorbed-latent (MLA) decode attention that reads its live pages in place —
a Pallas TPU kernel walking the page table in chunks of pages (+ the Pallas
interpreter off the chip).

The absorbed form (models/longcat_flash.py) lets all ``H`` heads of a slot
attend the ONE row a token has, kept in two pools a block: the latent ``c``
(``[pages, page_size, rank]``) and its rotated key ``r``
(``[pages, page_size, rope]``). Scores are ``q_abs . c + q_rope . r``, values
are ``c``. The gathered formulation (``decode_engine._attend_view_latent``)
materialises ``pool[page_table[:, :n]]`` for one extent ``n`` that holds the
LONGEST of the slots' contexts, writes it, and reads it three times. Here a
slot's pages are read once, where they lie, as far as ITS OWN length goes.

Shape contract (one decode step, one query row a head):

* ``q_abs [S, H, rank]``, ``q_rope [S, H, rope]``;
* ``c_new [S, rank]``, ``r_new [S, rope]``: the step's new row. It is an
  operand and takes part as the LAST key; the pools are read only (the engine
  writes the row to its page outside, so a donated pool never passes through
  a branch or a kernel's output);
* ``page_table [S, P]`` int32, ``lens [S]`` int32: the slot's length BEFORE
  this step, so pool positions ``< lens`` are keys and the new row sits at
  position ``lens`` (the reference's bottom-right rule, ``k_pos <= lens``).

The walk. ``page_table`` and ``lens`` are scalar-prefetch operands and the
pools stay where they are (``pl.ANY``); the grid is the slots, and inside a
slot a loop runs over its LIVE chunks alone, a chunk being ``K`` pages. Each
page is one ``make_async_copy`` straight into its rows of a ``[K * page_size,
width]`` buffer, so a chunk is one matmul operand with nothing stacked in
registers; two buffers alternate, the next chunk's copies (or the next slot's
first chunk's) in flight while this one is attended. Bytes and operations
follow each slot's own length, the bytes rounded up to a page and the
operations to a chunk:

* a page past the slot's length inside its last chunk is not copied: its rows
  of the buffer keep what an earlier chunk left there (zeros at first) and are
  masked before the softmax;
* chunks wholly past the length do not exist: no grid step, no copy, no
  compute (the first design gave every chunk of the table a grid step and
  every page an operand with its own ``index_map``: the chip read 0.9 us a
  grid step for the index maps alone, live or not: PERF.md section 6, PR 33);
* an inactive slot (stale ``lens``, zeroed table row) walks the null page 0
  and returns masked garbage that the engine discards, as the reference does.

Arithmetic is the configuration's: operands in the pools' dtype straight into
the MXU, scores, online softmax and accumulation in float32, ``P`` cast to the
pools' dtype for the values as the reference casts it.

What Mosaic asked of the layout (docs/kernels.md): the new row comes in as
``[S, 1, rank]`` (a ``(1, rank)`` block of a 2-D array cannot be tiled); and
no slice of an array whose minor dimension is not whole 128-lane tiles can be
the source of a copy, so a pool narrower than that (the 64-wide rotated keys)
is widened with zero lanes first, as are the query's and the new row's rope
parts (zeros add nothing to a score). A caller that holds the pool wide
already (the decode program widens it once a call, not once a step) pays for
no widening here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import call_name, interpret_mode

NEG_INF = -1e30
LANES = 128

# tokens a loop step attends: K = CHUNK_TOKENS / page_size pages. Read on a
# TPU v5 lite at 128 slots x 64 heads x (512 + 64), pages of 64 (PERF.md
# section 6, PR 33; ms a call at 256 / 512 / 1,024 tokens a chunk): 128
# contexts of 300 tokens 0.60 / 0.54 / 0.59, a lognormal mix of mean 869
# 0.82 / 0.72 / 0.69, 128 of 1,900 tokens 1.26 / 1.00 / 0.89. Short chunks
# pay for their steps; long ones attend a short context's masked tail.
CHUNK_TOKENS = 512


def pages_per_chunk(page_size: int, table_pages: int) -> int:
    """K, the pages one step of the walk reads."""
    return max(1, min(table_pages, CHUNK_TOKENS // page_size))


def pages_walked(extent, page_size: int, table_pages: int):
    """The pages of a slot's table row that the walk copies when the slot's
    context reaches ``extent`` tokens: those that hold a key, at most the
    table."""
    return jnp.minimum(-(-extent // page_size), table_pages)


def lane_whole(x, width=None):
    """``x`` with its minor dimension widened by zeros to ``width`` (to whole
    lane tiles where none is given); unchanged where it is that wide."""
    pad = (-x.shape[-1] % LANES) if width is None else width - x.shape[-1]
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _kernel(pt_ref, lens_ref, qa_ref, qr_ref, cn_ref, rn_ref, c_hbm, r_hbm,
            o_ref, cbuf, rbuf, sem, first_ref, m_ref, l_ref, acc_ref, *, K,
            ps, scale, slots, table_pages):
    """Grid (slot,): online softmax over the slot's live chunks of K pages,
    then over the new row."""
    s = pl.program_id(0)
    span = K * ps
    held = lens_ref[s]                    # keys in the pool: positions < held
    n = (held + span - 1) // span         # live chunks

    def copies(slot, chunk, buf, k):
        """The two copies of page ``k`` of ``slot``'s ``chunk`` into its rows
        of buffer ``buf``."""
        page = pt_ref[slot, jnp.minimum(chunk * K + k, table_pages - 1)]
        rows = pl.ds(pl.multiple_of(k * ps, ps), ps)
        return (pltpu.make_async_copy(c_hbm.at[page], cbuf.at[buf, rows],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(r_hbm.at[page], rbuf.at[buf, rows],
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
        cbuf[...] = jnp.zeros_like(cbuf)
        rbuf[...] = jnp.zeros_like(rbuf)

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
        ``weigh(p)`` is ``p``'s ``[H, rank]`` sum of value rows."""
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + weigh(p)
        m_ref[...] = m_new

    def chunk(i, carry):
        cur = (first + i) % 2

        @pl.when(i + 1 < n)
        def _():
            start(s, i + 1, 1 - cur)

        @pl.when((i + 1 == n) & after_live)
        def _():
            start(after, 0, 1 - cur)

        wait(s, i, cur)
        c, r = cbuf[cur], rbuf[cur]                           # [span, width]
        nt = (((1,), (1,)), ((), ()))
        sc = (jax.lax.dot_general(qa_ref[0], c, nt,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr_ref[0], r, nt,
                                    preferred_element_type=jnp.float32)
              ) * scale                                       # [H, span]
        k_pos = i * span + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        fold(jnp.where(k_pos < held, sc, NEG_INF),
             lambda p: jax.lax.dot_general(
                 p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                 preferred_element_type=jnp.float32))
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)

    @pl.when((n == 0) & after_live)
    def _():
        start(after, 0, first)

    first_ref[0] = (first + n) % 2
    # the step's own row, the last key: one row, so products and sums on the
    # vector unit (a one-column matmul is no shape for the MXU); never
    # masked, so l > 0
    f32 = jnp.float32
    cn, rn = cn_ref[0].astype(f32), rn_ref[0].astype(f32)     # [1, width]
    sc = (jnp.sum(qa_ref[0].astype(f32) * cn, axis=-1, keepdims=True)
          + jnp.sum(qr_ref[0].astype(f32) * rn, axis=-1, keepdims=True)
          ) * scale                                           # [H, 1]
    fold(sc, lambda p: p.astype(cn_ref.dtype).astype(f32) * cn)
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_latent_attention(q_abs, q_rope, c_new, r_new, c_pool, r_pool,
                           page_table, lens, *, scale, chunk_pages=None,
                           interpret=None):
    """``out [S, H, rank]`` in ``q_abs``'s dtype: every head of slot ``s``
    attends pool positions ``< lens[s]`` through ``page_table[s]`` and then
    the new row ``(c_new[s], r_new[s])``. The pools may come widened by
    :func:`lane_whole`. ``chunk_pages`` overrides K (the tests walk several
    chunks of a tiny table with it)."""
    K = (pages_per_chunk(c_pool.shape[1], page_table.shape[1])
         if chunk_pages is None else chunk_pages)
    if interpret is None:
        interpret = interpret_mode()
    return _program(float(scale), int(K), bool(interpret),
                    call_name("paged_latent_attention"))(
        q_abs, q_rope, c_new, r_new, c_pool, r_pool, page_table, lens)


@functools.lru_cache(maxsize=None)
def _program(scale: float, K: int, interpret: bool, name: str):
    """The jitted call for one static choice and call name. The SAME
    callable comes back for every block of every layer, so a decode program
    traces and lowers the kernel once and calls it, not once a block
    (seconds of every start-up otherwise, as for
    ``decode_engine._view_branches``)."""
    return jax.jit(functools.partial(_attend, scale=scale, K=K,
                                     interpret=interpret, name=name))


def _attend(q_abs, q_rope, c_new, r_new, c_pool, r_pool, page_table, lens, *,
            scale, K, interpret, name):
    S, H, rank = q_abs.shape
    ps, P = c_pool.shape[1], page_table.shape[1]
    span = K * ps
    dtype = c_pool.dtype
    c_pool, r_pool = lane_whole(c_pool), lane_whole(r_pool)
    wide, rope = c_pool.shape[-1], r_pool.shape[-1]
    q_abs, c_new = lane_whole(q_abs, wide), lane_whole(c_new, wide)
    q_rope, r_new = lane_whole(q_rope, rope), lane_whole(r_new, rope)

    def per_slot(s, pt, lens):
        return (s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, wide), per_slot),
                  pl.BlockSpec((1, H, rope), per_slot),
                  pl.BlockSpec((1, 1, wide), per_slot),
                  pl.BlockSpec((1, 1, rope), per_slot),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, wide), per_slot),
        scratch_shapes=[pltpu.VMEM((2, span, wide), dtype),    # c chunks
                        pltpu.VMEM((2, span, rope), dtype),    # key chunks
                        pltpu.SemaphoreType.DMA((2, 2)),       # [pool, buffer]
                        pltpu.SMEM((1,), jnp.int32),   # chunk 0's buffer
                        pltpu.VMEM((H, 1), jnp.float32),       # running max
                        pltpu.VMEM((H, 1), jnp.float32),       # denominator
                        pltpu.VMEM((H, wide), jnp.float32)])   # accumulator
    kernel = functools.partial(_kernel, K=K, ps=ps, scale=scale, slots=S,
                               table_pages=P)
    # Mosaic has no 64-bit types and the package turns x64 on at import:
    # trace the call (index maps and body) with it off. The slots run in
    # order: a slot starts the next one's first copies.
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, H, wide), q_abs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lens, jnp.int32),
          q_abs.astype(dtype), q_rope.astype(dtype),
          c_new.astype(dtype)[:, None], r_new.astype(dtype)[:, None],
          c_pool, r_pool)
    return out[..., :rank]
