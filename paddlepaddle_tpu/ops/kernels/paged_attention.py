"""Paged-attention decode — Pallas TPU kernel walking the page table
in-kernel (+ interpret-mode execution on CPU).

The r7 paged-KV engine (inference/decode_engine.py `_forward_paged`)
reaches each slot's logical KV view by MATERIALIZING ``pool[page_table]``
in HBM every layer of every decode step — a real gather of
``[slots, P*page_size, kvh, hd]`` bytes that exists only to be read once
by attention and thrown away (BASELINE.md r7 budgets <=5% chunk overhead
for it). This kernel removes the round trip the way PagedAttention
(vLLM, arXiv:2309.06180) and the TPU flash kernels (r1-r4 exemplars in
this directory) do: the page table rides in as a SCALAR-PREFETCH operand
and the kernel's BlockSpec ``index_map`` walks it — grid step (slot s,
page j) DMAs physical page ``page_table[s, j]`` straight from the pool
into VMEM, so the gathered view never exists in HBM.

Shape contract (the engine's decode/verify forward):

* ``q``          — ``[S, W, h, hd]``: W new positions per slot (W=1 is
  the chunked decode step; the speculative verify program runs W=k+1
  through the same kernel).
* ``k_pool/v_pool`` — ``[pages, page_size, kvh, hd]`` (page 0 is the
  engine's sacrificial null page).
* ``page_table`` — ``[S, P]`` int32 physical page per logical page.
* ``lens``       — ``[S]`` int32: the slot's length BEFORE this step's
  writes; query w attends keys ``k_pos <= lens + w`` (the same
  bottom-right causal rule as the reference view math).

Masking rules (the fallback-free safety story):

* positions past ``lens + w`` are masked with -1e30 before the softmax —
  garbage in not-yet-written page tails is never read into a result;
* logical pages wholly beyond the slot's visible window have their
  index_map REDIRECTED to physical page 0 (the null page), so a retired
  slot's zeroed table row or an over-long walk costs one cached null-page
  read, not a wild gather — and the mask discards whatever it held;
* inactive slots (lens stale, table zeroed) compute masked garbage the
  engine already discards host-side (`active` gating) — identical to the
  reference formulation's behavior.

One online-softmax pass per slot (f32 running max / denominator /
accumulator in VMEM scratch), pages visited in logical order, K and V
pages each read exactly once per step: HBM traffic drops from
``gather(view) + attention-read`` to ``attention-read`` alone. The
kernel runs compiled on TPU backends (Mosaic accepts it, bf16 and int8
pages, W=1 and W=4, and it matches the reference view math: chip_smoke.py,
PR 21) and in Pallas INTERPRET mode elsewhere (CPU tier-1: same program,
emulated grid), which is how parity is test-pinned without an accelerator
(tests/test_fused_kernels.py). One thing the compiler showed that the
interpreter cannot: XLA's default TPU layout for a ``[pages, 64, 8, 64]``
pool is not row-major, so a standalone call copies the pool into the
kernel's operand layout first — whether the engine's scan hoists that
copy decides ROADMAP 1.1b (PERF.md, open questions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.flags import flag_value
from . import interpret_mode


NEG_INF = -1e30


def paged_attention_supported(*, page_size: int, head_dim: int,
                              num_heads: int, num_kv_heads: int,
                              plan=None, kv_quant=None) -> tuple:
    """(ok, reason) — the fallback matrix for the decode kernel. The
    engine calls this ONCE at construction; a False here is a loud
    fallback to the reference ``pool[page_table]`` formulation, never a
    silent behavior change (docs/kernels.md has the full matrix)."""
    if not flag_value("fused_paged_attention"):
        return False, "FLAGS_fused_paged_attention off"
    if kv_quant not in (None, "off", "int8"):
        # int8 dequant happens inside the VMEM pass (codes * per-page-
        # per-head scale, the standard quant-kernel pattern); any other
        # scheme is a loud fallback to the gather-dequant reference
        return False, f"kv_quant {kv_quant!r} has no in-kernel dequant"
    if plan is not None:
        # sharded pools would need the kernel to see only the local KV
        # shard + a head-offset — a named follow-up seam, not a silent
        # wrong-results path
        return False, "tensor-parallel plan (kernel is single-chip)"
    if page_size < 8 or page_size % 8:
        # sublane alignment: a [page_size, ...] VMEM block needs 8-row
        # tiles on the MXU; enforced under interpret too so a CPU-tested
        # config is exactly a TPU-servable config
        return False, f"page_size {page_size} not a multiple of 8"
    if num_heads % num_kv_heads:
        return False, (f"num_heads {num_heads} not divisible by "
                       f"num_kv_heads {num_kv_heads}")
    if not (head_dim % 128 == 0 or head_dim in (8, 16, 32, 64)):
        return False, f"head_dim {head_dim} not lane-aligned"
    return True, "ok"


def _paged_attn_kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                       page_size, rep, width, scale, num_pages_per_slot,
                       quantized):
    """Grid (slot, logical page): online-softmax accumulate one page.

    Layouts are chosen for Mosaic, not for the caller: the wrapper hands
    ``q`` in as ``[kvh, M, hd]`` (row ``m = w * rep + r`` is query ``w`` of
    head ``g * rep + r``, M padded to a sublane multiple) so every matmul
    is a leading-batch ``[kvh, M, *]`` contraction, and the only relayout
    in the body is one major<->second-minor swap of the f32 page
    (``[ps, kvh, hd] -> [kvh, ps, hd]``).

    ``quantized`` is a static trace-time flag: the int8 variant takes two
    extra scale operands (``[pages, kvh, 1]`` f32, blocked per page) and
    dequantizes the page inside the VMEM pass — codes are cast to f32 and
    multiplied by the per-page-per-head scale, so int8 K/V bytes cross HBM
    and full precision exists only in VMEM."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    s, j = pl.program_id(0), pl.program_id(1)
    ps = page_size

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qg = q_ref[0].astype(jnp.float32) * scale      # [kvh, M, hd]
    kb = k_ref[0].astype(jnp.float32)              # [ps, kvh, hd]
    vb = v_ref[0].astype(jnp.float32)
    if quantized:
        kb = kb * ks_ref[0][None]                  # scale [kvh, 1] broadcast
        vb = vb * vs_ref[0][None]
    kb = kb.transpose(1, 0, 2)                     # [kvh, ps, hd]
    vb = vb.transpose(1, 0, 2)
    M = qg.shape[1]

    # bottom-right causal mask in pool coordinates: query w (at absolute
    # position lens+w) sees keys k_pos <= lens + w — exactly the
    # reference view math, including this step's own freshly written
    # positions (the engine scatters new K/V before calling the kernel).
    # Row m holds query w = m // rep, spelled as compares (padding rows
    # past width*rep clamp to the last query and are sliced off outside)
    row = jax.lax.broadcasted_iota(jnp.int32, (M, ps), 0)
    w_idx = jnp.zeros((M, ps), jnp.int32)
    for w in range(1, width):
        w_idx += (row >= w * rep).astype(jnp.int32)
    k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (M, ps), 1)
    mask = k_pos <= lens_ref[s] + w_idx

    # GQA uncontracted: each kv head's M = W*rep query rows dot the
    # unrepeated page (the r4 serving lesson — never materialize a
    # repeated cache)
    sblk = jax.lax.dot_general(
        qg, kb, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [kvh, M, ps]
    sblk = jnp.where(mask[None], sblk, NEG_INF)

    m_prev, l_prev = m_ref[:], l_ref[:]            # [kvh, M, 1]
    m_new = jnp.maximum(m_prev, jnp.max(sblk, axis=-1, keepdims=True))
    p = jnp.exp(sblk - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, vb, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [kvh, M, hd]
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = m_new

    @pl.when(j == num_pages_per_slot - 1)
    def _():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, page_table, lens, *, rep, scale,
                    k_scale=None, v_scale=None, interpret=None):
    """Attend ``q [S, W, h, hd]`` over each slot's paged KV through the
    page table, in-kernel. Returns ``out [S, W, h, hd]`` in q's dtype.
    New K/V for this step must already be scattered into the pool (the
    engine writes pages first; the causal mask then admits them).

    ``k_scale``/``v_scale`` (``[pages, kvh]`` f32, both or neither) arm
    the int8 path: the pools hold int8 codes and each page is dequantized
    in VMEM as ``codes * scale`` — the page walk, masking and softmax are
    byte-for-byte the same program otherwise."""
    S, W, h, hd = q.shape
    ps, kvh = k_pool.shape[1], k_pool.shape[2]
    P = page_table.shape[1]
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    if interpret is None:
        interpret = interpret_mode()
    M = -(-W * rep // 8) * 8                       # sublane-aligned rows

    def idx_q(s, j, pt, lens):
        return (s, 0, 0, 0)

    def idx_kv(s, j, pt, lens):
        # logical pages wholly past the slot's visible window read the
        # null page: a zeroed table row already points there, and
        # clamping here keeps even a stale nonzero entry from pulling a
        # real page into VMEM for fully-masked keys
        visible = j * ps <= lens[s] + (W - 1)
        return (jnp.where(visible, pt[s, j], 0), 0, 0, 0)

    def idx_scale(s, j, pt, lens):
        # same redirect as the pages: a masked page's scale row is the
        # null page's — finite, and the mask discards the product anyway
        return idx_kv(s, j, pt, lens)[:3]

    # [S, W, h, hd] -> [S, kvh, W*rep (padded to M), hd]: head g*rep+r of
    # query w becomes row w*rep+r of kv head g
    qg = q.reshape(S, W, kvh, rep, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(S, kvh, W * rep, hd)
    if M != W * rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, M - W * rep), (0, 0)))

    in_specs = [
        pl.BlockSpec((1, kvh, M, hd), idx_q),
        pl.BlockSpec((1, ps, kvh, hd), idx_kv),
        pl.BlockSpec((1, ps, kvh, hd), idx_kv),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        # [pages, kvh] -> [pages, kvh, 1]: a (1, kvh, 1) block covers the
        # array's two minor dimensions whole (a (1, kvh) block of the 2-D
        # array has a second-minor extent Mosaic cannot tile), and keeps
        # kvh on sublanes, where the page's kv-head axis already sits
        in_specs += [pl.BlockSpec((1, kvh, 1), idx_scale),
                     pl.BlockSpec((1, kvh, 1), idx_scale)]
        operands += [jnp.asarray(k_scale, jnp.float32)[:, :, None],
                     jnp.asarray(v_scale, jnp.float32)[:, :, None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, M, hd), idx_q),
        scratch_shapes=[
            pltpu.VMEM((kvh, M, 1), jnp.float32),          # running max
            pltpu.VMEM((kvh, M, 1), jnp.float32),          # denominator
            pltpu.VMEM((kvh, M, hd), jnp.float32),         # f32 accum
        ],
    )
    kernel = functools.partial(
        _paged_attn_kernel, page_size=ps, rep=rep, width=W, scale=scale,
        num_pages_per_slot=P, quantized=quantized)
    # Mosaic has no 64-bit types and the package turns x64 on at import:
    # trace the call (index maps and body) with it off
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, kvh, M, hd), q.dtype),
            interpret=interpret,
        )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lens, jnp.int32),
          *operands)
    out = out[:, :, :W * rep].reshape(S, kvh, W, rep, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(S, W, h, hd)
