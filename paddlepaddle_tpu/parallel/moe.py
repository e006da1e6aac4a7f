"""MoE with expert parallelism — GShard-style dense dispatch on TPU.

Reference surface: python/paddle/incubate/distributed/models/moe/moe_layer.py
(MoELayer:99, MoEScatter/MoEGather alltoall PyLayers:149,263) + gate/
(NaiveGate, SwitchGate, GShardGate) + fused kernel
python/paddle/incubate/nn/functional/fused_moe.py and SPMD rules
paddle/phi/infermeta/spmd_rules/{moe_gate_dispatch,moe_combine}.cc.

TPU-native design: the reference's explicit alltoall scatter/gather becomes
EINSUM dispatch over a capacity-bounded one-hot routing tensor (the GShard /
Switch-Transformer formulation) with expert weights stacked [E, ...] and
sharded over the 'ep' mesh axis — XLA turns the token→expert einsum into the
ICI all_to_all the reference codes by hand. Static shapes (capacity bound +
token dropping) keep it MXU-friendly; no per-expert dynamic gather.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..nn.initializer import XavierNormal
from ..nn.layer import Layer
from ..ops.kernels import current_partition
from ..ops.kernels.held_experts import held_experts_decode, picked_experts
from .mpu import mark_placement


def _top1_routing(logits, capacity):
    """Switch routing: (dispatch [T,E,C], combine [T,E,C], aux_loss)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                      # [T]
    expert_mask = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    # position of each token within its expert's capacity buffer
    pos_in_expert = jnp.cumsum(expert_mask, axis=0) * expert_mask  # 1-based
    keep = (pos_in_expert <= capacity) * expert_mask
    pos = (pos_in_expert - 1.0) * keep
    dispatch = keep[..., None] * jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), capacity, dtype=jnp.float32)[:, None, :]
    dispatch = dispatch * expert_mask[..., None]
    gate_val = (probs * expert_mask).sum(-1, keepdims=True)       # [T,1]
    combine = dispatch * gate_val[..., None]
    # Switch aux loss: E * sum_e fraction_tokens_e * mean_prob_e
    frac = expert_mask.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def _topk_routing(logits, capacity, k):
    """GShard-style top-k: route each token to its top-k experts, renormalized."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    remaining = probs
    # fill counters shared across the k rounds so capacity is respected
    fill = jnp.zeros((E,), jnp.float32)
    topk_val, _ = jax.lax.top_k(probs, k)
    denom = topk_val.sum(-1, keepdims=True) + 1e-9
    aux = jnp.zeros((), jnp.float32)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                     # [T]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        pos_in_expert = (jnp.cumsum(mask, axis=0) - 1.0) + fill[None, :]
        keep = ((pos_in_expert < capacity) * mask)
        pos = pos_in_expert * keep
        d = keep[..., None] * jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), capacity, dtype=jnp.float32)[:, None, :]
        d = d * mask[..., None]
        gate_val = ((probs * mask).sum(-1, keepdims=True) / denom)
        dispatch = dispatch + d
        combine = combine + d * gate_val[..., None]
        fill = fill + mask.sum(axis=0)
        aux = aux + E * jnp.sum(mask.mean(0) * probs.mean(0))
        remaining = remaining * (1.0 - mask)
    return jnp.minimum(dispatch, 1.0), combine, aux / k


class NaiveGate(Layer):
    """Linear router (reference: incubate moe gate/naive_gate.py)."""

    def __init__(self, d_model, num_experts, topk=2):
        super().__init__()
        self.num_experts = num_experts
        # a token cannot route to more experts than exist (E=1 degrades to dense)
        self.topk = min(topk, num_experts)
        self.weight = self.create_parameter([d_model, num_experts],
                                            default_initializer=XavierNormal())

    def routing(self, x_flat, capacity):
        def f(x, w):
            logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
            if self.topk == 1:
                return _top1_routing(logits, capacity)
            return _topk_routing(logits, capacity, self.topk)

        return apply_op(f, x_flat, self.weight, op_name="moe_gate")


class SwitchGate(NaiveGate):
    def __init__(self, d_model, num_experts):
        super().__init__(d_model, num_experts, topk=1)


class GShardGate(NaiveGate):
    def __init__(self, d_model, num_experts):
        super().__init__(d_model, num_experts, topk=2)


def _sorted_moe_ffn(x, logits, wg, wu, wd, topk, capacity):
    """LEGACY sorted (ragged) dispatch — superseded by
    _gathered_capacity_moe_ffn (same capacity semantics, ~40% faster
    full-model; tools/moe_dispatch_bench.py keeps this for comparison).

    The fused-MoE formulation
    (reference python/paddle/incubate/nn/functional/fused_moe.py — their
    CUDA kernel sorts tokens by expert; same idea, expressed as XLA sort +
    scatter/gather so dispatch costs O(T·k·d) memory ops instead of the
    O(T·E·C·d) MACs of the one-hot einsum).

    x: [T, d]; logits: [T, E]; weights: [E, d, h]/[E, h, d].
    Returns (y [T, d], aux_loss).
    """
    T, d = x.shape
    E = logits.shape[1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, topk)         # [T, k]
    if topk > 1:  # GShard renormalizes over the k choices; Switch (k=1)
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
        # uses the raw router probability so the router learns through it

    flat_e = expert_idx.reshape(-1)                            # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    token_of = order // topk                                   # token per entry
    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * topk) - offsets[sorted_e]             # rank in expert
    keep = pos < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos, E * capacity)

    # scatter kept tokens into the expert buffers (+1 trash row for drops)
    buf = jnp.zeros((E * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(x[token_of])
    xin = buf[:-1].reshape(E, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xin, wg))
    h = h * jnp.einsum("ecd,edh->ech", xin, wu)
    out = jnp.einsum("ech,ehd->ecd", h, wd).reshape(E * capacity, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])  # trash row

    gate_sorted = gate_vals.reshape(-1)[order].astype(x.dtype)
    contrib = out[slot] * (gate_sorted * keep.astype(x.dtype))[:, None]
    y = jnp.zeros((T, d), x.dtype).at[token_of].add(contrib)

    # load-balance loss averaged over the k routing rounds — same
    # normalization as the einsum path's _topk_routing (aux / k)
    mean_prob = probs.mean(0)
    aux = jnp.zeros((), jnp.float32)
    for r in range(topk):
        mask_r = jax.nn.one_hot(expert_idx[:, r], E, dtype=jnp.float32)
        aux = aux + E * jnp.sum(mask_r.mean(0) * mean_prob)
    return y, aux / topk


def _route_topk_iter(logits, k, num_experts):
    """Iterative-argmax top-k routing: (gate_vals [T,k], expert_idx [T,k],
    aux_loss). For the small E of expert banks, k argmax rounds over [T, E]
    are ~free, while XLA's top_k VALUE path alone measured ~5 ms at
    [8k·1024, 16] on a v5e (tools/moe_dispatch_bench.py) — top_k was the
    single biggest cost of the sorted dispatch. Gate values and the
    load-balance loss match _topk_routing/_top1_routing exactly."""
    E = num_experts
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    rem = probs
    gvs, eis = [], []
    aux = jnp.zeros((), jnp.float32)
    mean_prob = probs.mean(0)
    for _ in range(k):
        idx = jnp.argmax(rem, axis=-1)
        oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        gvs.append((rem * oh).sum(-1))
        eis.append(idx)
        aux = aux + E * jnp.sum(oh.mean(0) * mean_prob)
        rem = rem * (1.0 - oh)
    gate_vals = jnp.stack(gvs, -1)
    if k > 1:  # GShard renormalizes; Switch (k=1) keeps the raw probability
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    return gate_vals, jnp.stack(eis, -1).astype(jnp.int32), aux / k


def _counting_sort(fe, num_experts, block=256):
    """Stable counting sort of expert assignments WITHOUT lax.sort.

    Returns (dest [N], sidx [N], counts [E], offs [E]): entry i lands at
    sorted slot dest[i]; sorted slot s holds entry sidx[s] (a permutation —
    both directions are gathers); offs is the exclusive cumsum of counts.
    The rank-within-expert prefix sum runs as a blockwise lower-triangular
    MATMUL (MXU work, exact in bf16 for block counts <= 256) + a tiny
    cross-block cumsum: measured 2.6x faster than argsort and 1.25x faster
    than jnp.cumsum over [32k, 16] on a v5e (tools/moe_dispatch_bench.py)."""
    N = fe.shape[0]
    oh = jax.nn.one_hot(fe, num_experts, dtype=jnp.float32)
    if N % block == 0 and N > block:
        nb = N // block
        ohb = oh.reshape(nb, block, num_experts).astype(jnp.bfloat16)
        tri = jnp.tril(jnp.ones((block, block), jnp.bfloat16))
        within = jnp.einsum("qp,npe->nqe", tri, ohb,
                            preferred_element_type=jnp.float32)
        bsum = within[:, -1, :]
        boffs = jnp.cumsum(bsum, axis=0) - bsum
        csum = (within + boffs[:, None, :]).reshape(N, num_experts)
    else:
        csum = jnp.cumsum(oh, axis=0)
    pos = (csum * oh).sum(-1) - 1.0
    counts = csum[-1]
    offs = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                            jnp.cumsum(counts)[:-1]])
    dest = (offs[fe] + pos).astype(jnp.int32)
    sidx = jnp.zeros((N,), jnp.int32).at[dest].set(
        jnp.arange(N, dtype=jnp.int32))
    return dest, sidx, counts.astype(jnp.int32), offs.astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_gather(x, sidx, dest, k):
    """xin[s] = x[token of sorted entry s]. Entries are ROUND-MAJOR
    (entry j = r·T + t — all first choices before any second choice, the
    same fill priority as the einsum path's shared capacity counter), so
    the token of entry j is j % T. The vjp is a GATHER by the inverse
    permutation (dx[t] = sum_r dxin[dest[r·T+t]]) instead of the
    scatter-add XLA would emit for the gather's transpose — scatter was the
    second-largest cost of the sorted path (tools/moe_dispatch_bench.py)."""
    return x[sidx % x.shape[0]]


def _dispatch_gather_fwd(x, sidx, dest, k):
    return x[sidx % x.shape[0]], (sidx, dest)


def _dispatch_gather_bwd(k, res, dxin):
    _, dest = res
    dx = dxin[dest].reshape(k, -1, dxin.shape[-1]).sum(0)
    return dx.astype(dxin.dtype), None, None


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


@jax.custom_vjp
def _combine_gather(out, sidx, dest):
    """entry i reads expert output at its sorted slot; vjp gathers by sidx
    (dest is a permutation, so the transpose is exactly out[sidx])."""
    return out[dest]


def _combine_gather_fwd(out, sidx, dest):
    return out[dest], (sidx, dest)


def _combine_gather_bwd(res, dy):
    sidx, _ = res
    return dy[sidx], None, None


_combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _slot_dispatch(x, slot_entry, slot_valid, slots_of_entry, k):
    """xin[slot] = x[token of the entry ranked c in expert e] (zero-padded
    beyond each expert's count; entries round-major, token = entry % T).
    vjp gathers by the entry->slot map instead of scatter-adding."""
    return jnp.where(slot_valid[:, None], x[slot_entry % x.shape[0]], 0)


def _slot_dispatch_fwd(x, slot_entry, slot_valid, slots_of_entry, k):
    return _slot_dispatch(x, slot_entry, slot_valid, slots_of_entry, k), \
        slots_of_entry


def _slot_dispatch_bwd(k, res, dxin):
    slots_of_entry = res              # [k, T] slot id, or -1 if dropped
    dpad = jnp.concatenate([dxin, jnp.zeros((1, dxin.shape[1]), dxin.dtype)])
    idx = jnp.where(slots_of_entry >= 0, slots_of_entry, dxin.shape[0])
    return dpad[idx].sum(0).astype(dxin.dtype), None, None, None


_slot_dispatch.defvjp(_slot_dispatch_fwd, _slot_dispatch_bwd)


@jax.custom_vjp
def _slot_combine(out, slots_of_entry, slot_entry, slot_valid):
    """entry (r, t) reads its expert-buffer slot (zeros if dropped); vjp
    gathers entry cotangents back to slots."""
    opad = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
    idx = jnp.where(slots_of_entry >= 0, slots_of_entry, out.shape[0])
    return opad[idx]                  # [k, T, d]


def _slot_combine_fwd(out, slots_of_entry, slot_entry, slot_valid):
    return _slot_combine(out, slots_of_entry, slot_entry, slot_valid), \
        (slot_entry, slot_valid)


def _slot_combine_bwd(res, dy):
    slot_entry, slot_valid = res
    dyf = dy.reshape(-1, dy.shape[-1])
    dout = jnp.where(slot_valid[:, None], dyf[slot_entry], 0)
    return dout.astype(dy.dtype), None, None, None


_slot_combine.defvjp(_slot_combine_fwd, _slot_combine_bwd)


def _capacity_slot_maps(logits, topk, E, C, T):
    """The capacity dispatch's routing + slot index maps, shared by the
    sorted (einsum) and fused (gather-GEMM kernel) paths so their drop
    semantics CANNOT drift: round-major entries (j = r*T + t — all first
    choices fill capacity before any second choice, the einsum path's
    shared-counter priority), counting-sorted, capacity-clipped. Returns
    (gate_vals [T,k], aux, slots_of_entry [k,T], slot_valid [E*C],
    slot_entry [E*C])."""
    N = T * topk
    gate_vals, expert_idx, aux = _route_topk_iter(logits, topk, E)
    fe = expert_idx.T.reshape(-1)
    dest, sidx, counts, offs = _counting_sort(fe, E)
    pos = dest - offs[fe]                               # rank within expert
    slots_of_entry = jnp.where(pos < C, fe * C + pos, -1).reshape(topk, T)
    e_of_slot = jnp.repeat(jnp.arange(E, dtype=jnp.int32), C)
    c_of_slot = jnp.tile(jnp.arange(C, dtype=jnp.int32), E)
    slot_valid = c_of_slot < jnp.minimum(counts[e_of_slot], C)
    slot_entry = sidx[jnp.clip(offs[e_of_slot] + c_of_slot, 0, N - 1)]
    return gate_vals, aux, slots_of_entry, slot_valid, slot_entry


def _slot_combine_weighted(x, out, gate_vals, slots_of_entry, slot_entry,
                           slot_valid):
    """Shared combine epilogue: gather each entry's expert output and
    gate-weight the k contributions back onto tokens."""
    contrib = _slot_combine(out, slots_of_entry, slot_entry, slot_valid)
    return (contrib
            * jnp.swapaxes(gate_vals, 0, 1).astype(x.dtype)[..., None]
            ).sum(0)


def _gathered_capacity_moe_ffn(x, logits, wg, wu, wd, topk, capacity):
    """Capacity-bounded fast dispatch — counting-sort routing + STATIC
    [E, C, d] expert buffers run as batched einsums (XLA batches them on the
    MXU with no ragged-size overhead), gather-only vjps. The gate+up
    projections are fused into ONE batched matmul inside
    :func:`_reference_expert_ffn` (the concat is a cheap weight-side copy
    XLA folds into the operand read).

    This is the rewritten "sorted" mode: same capacity/drop semantics as the
    reference fused-MoE path (fused_moe.py sorts tokens by expert into
    capacity buffers), but with no lax.sort/top_k and no scatter anywhere.
    Static shapes trade ~(capacity_factor-1) extra matmul rows for
    ragged_dot's per-group overhead (tools/moe_dispatch_bench.py).
    Returns (y [T, d], aux_loss).
    """
    T = x.shape[0]
    E = wg.shape[0]
    gate_vals, aux, slots_of_entry, slot_valid, slot_entry = \
        _capacity_slot_maps(logits, topk, E, capacity, T)
    out = _reference_expert_ffn(x, slot_entry, slot_valid, slots_of_entry,
                                wg, wu, wd, topk)
    y = _slot_combine_weighted(x, out, gate_vals, slots_of_entry,
                               slot_entry, slot_valid)
    return y, aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _fused_expert_ffn(x, slot_token, slot_entry, slot_valid, slots_of_entry,
                      wg, wu, wd, topk):
    """Expert FFN over the capacity slots through the FUSED gather-GEMM
    Pallas kernel (ops/kernels/gather_gemm.py): the dispatch gather, both
    FFN GEMMs and the activation run per (expert, token-block) entirely
    in VMEM — the gathered ``[E*C, d]`` activations and the two FFN
    intermediates never exist in HBM (the r5 dispatch-movement floor).

    ``slot_token [E*C]`` carries the token row each slot reads (sentinel
    T = unfilled slot -> zero row), precomputed from the same counting
    sort the reference path uses, so drop/capacity semantics are
    IDENTICAL to ``_gathered_capacity_moe_ffn``. Backward is the
    reference gather formulation recomputed (gather-only vjps; fusing
    the backward GEMMs is a named follow-up seam in docs/kernels.md)."""
    from ..ops.kernels.gather_gemm import gather_gemm_ffn

    E, d, h = wg.shape
    C = slot_token.shape[0] // E
    return gather_gemm_ffn(x, slot_token, jnp.concatenate([wg, wu], axis=-1),
                           wd, capacity=C)


def _reference_expert_ffn(x, slot_entry, slot_valid, slots_of_entry,
                          wg, wu, wd, topk):
    """The capacity path's FFN body (dispatch gather + batched einsums) —
    the recompute target of the fused kernel's backward pass and the
    numeric reference its parity tests pin against."""
    E, d, h = wg.shape
    C = slot_entry.shape[0] // E
    xin = _slot_dispatch(x, slot_entry, slot_valid, slots_of_entry,
                         topk).reshape(E, C, d)
    gu = jnp.einsum("ecd,edh->ech", xin, jnp.concatenate([wg, wu], axis=-1))
    hmid = jax.nn.silu(gu[..., :h]) * gu[..., h:]
    return jnp.einsum("ech,ehd->ecd", hmid, wd).reshape(E * C, d)


def _fused_expert_ffn_fwd(x, slot_token, slot_entry, slot_valid,
                          slots_of_entry, wg, wu, wd, topk):
    out = _fused_expert_ffn(x, slot_token, slot_entry, slot_valid,
                            slots_of_entry, wg, wu, wd, topk)
    return out, (x, slot_entry, slot_valid, slots_of_entry, wg, wu, wd)


def _fused_expert_ffn_bwd(topk, res, g):
    x, slot_entry, slot_valid, slots_of_entry, wg, wu, wd = res
    _, vjp = jax.vjp(
        lambda x_, wg_, wu_, wd_: _reference_expert_ffn(
            x_, slot_entry, slot_valid, slots_of_entry, wg_, wu_, wd_,
            topk),
        x, wg, wu, wd)
    dx, dwg, dwu, dwd = vjp(g)
    return dx, None, None, None, None, dwg, dwu, dwd


_fused_expert_ffn.defvjp(_fused_expert_ffn_fwd, _fused_expert_ffn_bwd)


def _fused_gather_gemm_moe_ffn(x, logits, wg, wu, wd, topk, capacity):
    """Capacity dispatch with the FUSED gather-GEMM kernel — identical
    routing/drop semantics to :func:`_gathered_capacity_moe_ffn` (same
    counting sort, same slot maps, same combine), only the
    dispatch-gather + expert-FFN block runs in-kernel.
    Returns (y [T, d], aux_loss)."""
    T = x.shape[0]
    E = wg.shape[0]
    gate_vals, aux, slots_of_entry, slot_valid, slot_entry = \
        _capacity_slot_maps(logits, topk, E, capacity, T)
    # the kernel gathers by TOKEN row (entry j reads x[j % T]); sentinel T
    # marks unfilled slots so the kernel zeroes them without a branch
    slot_token = jnp.where(slot_valid, slot_entry % T, T).astype(jnp.int32)
    out = _fused_expert_ffn(x, slot_token, slot_entry, slot_valid,
                            slots_of_entry, wg, wu, wd, topk)
    y = _slot_combine_weighted(x, out, gate_vals, slots_of_entry,
                               slot_entry, slot_valid)
    return y, aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_gather_pad(x, sidx_pad, dest_pad, k):
    """Padded-slot dispatch: slot s holds x[token of entry sidx_pad[s]],
    zeros in alignment-padding slots (sidx_pad == N sentinel). Both
    directions are gathers, like _dispatch_gather."""
    T = x.shape[0]
    N = T * k
    valid = sidx_pad < N
    return jnp.where(valid[:, None], x[sidx_pad % T], 0)


def _dispatch_gather_pad_fwd(x, sidx_pad, dest_pad, k):
    return _dispatch_gather_pad(x, sidx_pad, dest_pad, k), dest_pad


def _dispatch_gather_pad_bwd(k, dest_pad, dxin):
    dx = dxin[dest_pad].reshape(k, -1, dxin.shape[-1]).sum(0)
    return dx.astype(dxin.dtype), None, None


_dispatch_gather_pad.defvjp(_dispatch_gather_pad_fwd, _dispatch_gather_pad_bwd)


@jax.custom_vjp
def _combine_gather_pad(out, sidx_pad, dest_pad):
    """entry i reads its padded slot; vjp scatters entry cotangents back to
    slots as a gather by sidx_pad (zero into padding slots)."""
    return out[dest_pad]


def _combine_gather_pad_fwd(out, sidx_pad, dest_pad):
    return out[dest_pad], sidx_pad


def _combine_gather_pad_bwd(sidx_pad, dy):
    dpad = jnp.concatenate([dy, jnp.zeros((1, dy.shape[1]), dy.dtype)])
    idx = jnp.minimum(sidx_pad, dy.shape[0])       # sentinel -> zero row
    return dpad[idx].astype(dy.dtype), None, None


_combine_gather_pad.defvjp(_combine_gather_pad_fwd, _combine_gather_pad_bwd)


def _dropless_moe_ffn(x, logits, wg, wu, wd, topk, align=1):
    """Dropless grouped-matmul dispatch (no capacity bound, no token drops).

    Megablox/dropless-MoE formulation (arXiv:2211.15841): tokens sorted by
    expert via counting sort, expert FFNs as ``lax.ragged_dot`` grouped
    matmuls over the contiguous groups, combine by inverse-permutation
    gather. Every index op is a gather in BOTH directions (custom vjps
    above), and routing avoids lax.sort/top_k entirely.

    ``align`` > 1 pads group boundaries to multiples of ``align`` (zero
    rows) so each ragged group starts on an MXU tile boundary — megablox
    pads its block-diagonal groups the same way. Measured NEUTRAL at 128
    on the full model (the 12.5% extra rows offset the tile win), so the
    default is 1; the knob stays because the trade-off is shape-dependent
    (parity across aligns is tested in tests/test_moe.py).

    Returns (y [T, d], aux_loss).
    """
    T, d = x.shape
    E = wg.shape[0]
    N = T * topk
    gate_vals, expert_idx, aux = _route_topk_iter(logits, topk, E)
    fe = expert_idx.T.reshape(-1)          # round-major (j = r*T + t)
    dest, sidx, counts, offs = _counting_sort(fe, E)
    if align > 1:
        n_pad = N + E * align              # static upper bound
        counts_p = ((counts + align - 1) // align) * align
        counts_p = counts_p.at[-1].add(
            jnp.int32(n_pad) - counts_p.sum().astype(jnp.int32))  # absorb slack
        offs_p = jnp.concatenate([jnp.zeros((1,), counts_p.dtype),
                                  jnp.cumsum(counts_p)[:-1]]).astype(jnp.int32)
        dest = (offs_p[fe] + (dest - offs[fe])).astype(jnp.int32)
        sidx = jnp.full((n_pad,), N, jnp.int32).at[dest].set(
            jnp.arange(N, dtype=jnp.int32))
        counts = counts_p
        xin = _dispatch_gather_pad(x, sidx, dest, topk)
    else:
        xin = _dispatch_gather(x, sidx, dest, topk)
    # NOT fused gate|up here: a concatenated [E, d, 2h] ragged_dot measured
    # SLOWER than two separate calls (97.8 vs 90.9 ms/step full-model),
    # unlike the capacity path's batched einsum where the fusion wins
    hmid = jax.nn.silu(jax.lax.ragged_dot(xin, wg, counts)) \
        * jax.lax.ragged_dot(xin, wu, counts)
    out = jax.lax.ragged_dot(hmid, wd, counts)
    if align > 1:
        contrib = _combine_gather_pad(out, sidx, dest).reshape(topk, T, d)
    else:
        contrib = _combine_gather(out, sidx, dest).reshape(topk, T, d)
    y = (contrib * jnp.swapaxes(gate_vals, 0, 1).astype(x.dtype)[..., None]
         ).sum(0)
    return y, aux


# -- one chip's share of an expert layer ----------------------------------------
# The grouped kernel XLA emits for ``lax.ragged_dot`` on the TPU walks row tiles
# of 512: with no more tokens than one tile, every touched expert costs a whole
# tile there, and the plain batched matmul over the held experts is no more work
# and needs no sort. Above it the sorted, grouped form does the work of the
# pairs that exist.
GROUPED_ABOVE_TOKENS = 512
# Up to this many tokens the held experts run through a Pallas kernel that
# reads only the experts the tokens picked (ops/kernels/held_experts.py): the
# dense form does T operations a weight byte, and below the v5e's ridge (197
# TFLOP/s over 819 GB/s, 240) the weight bytes set its time, so the bytes of
# experts nobody picked are time lost (a decode step: 32 or 128 rows). Above
# it only admissions remain, whose rows touch nearly every held expert.
HELD_KERNEL_MAX_TOKENS = 128
# Up to this many tokens the grouped form sorts and runs EVERY pair in one call
# of each matmul, held or not; beyond it the held pairs alone run, a block of
# sorted rows at a time (:func:`_held_experts_row_blocks`): the one-call form
# gathers a row and writes a float32 output row for every pair (1.9 GB and 3.8
# GB at a 16,640-token prefill with 8 picks), and ``lax.ragged_dot`` on the TPU
# costs by the rows it is given, not by the rows that lie in a group.
GROUPED_ALL_PAIRS_TOKENS = 2048
# Sorted pairs a block of the row-block form: a held expert sees some hundreds
# of pairs of a long prefill, so a block spans a few experts' weights (read on
# a TPU v5 lite at 12 held of 384 experts, 8 picks, 7,168 x 2,048, ms a layer
# at 512 / 1,024 / 2,048 rows: 8,320 tokens 8.1 / 8.5 / 19.8, 16,640 tokens
# 11.9 / 12.3 / 13.1; every pair in token chunks of 2,048: 39.3 and 133.7).
GROUPED_BLOCK_ROWS = 512


def route_scores_topk(x, router, bias, topk, scale):
    """Softmax routing without renormalisation: ``p = softmax(f32(x) @ router)``
    over every output of the router, the ``topk`` largest of ``p + bias`` are
    picked, and a pick weighs ``scale * p`` (the bias chooses, it does not
    weigh). Returns (weights [T, topk] f32, expert ids [T, topk] int32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(p + bias.astype(jnp.float32), topk)
    return scale * jnp.take_along_axis(p, ids, axis=-1), ids.astype(jnp.int32)


def route_sigmoid_topk(x, router, bias, topk, scale):
    """Sigmoid routing with renormalisation (DeepSeek-V3's, Kimi-K2's):
    ``s = sigmoid(f32(x) @ router)`` over every output of the router, the
    ``topk`` largest of ``s + bias`` are picked, and a pick weighs
    ``scale * s_i / (sum of the picked s + 1e-20)``: the bias chooses, it does
    not weigh. The published group limit (``n_group``, ``topk_group``) is the
    identity at one group, which is all that is computed here. Returns as
    :func:`route_scores_topk`."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), topk)
    picked = jnp.take_along_axis(s, ids, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    return weights, ids.astype(jnp.int32)


ROUTINGS = {"softmax": route_scores_topk, "sigmoid": route_sigmoid_topk}


def _held_experts_dense(x, w_local, wg, wu, wd):
    """Every held expert on every token, weighted by ``w_local [T, count]``
    (zero where the token did not pick the expert): three batched matmuls,
    the down projection contracting expert and width at once."""
    g = jnp.einsum("td,edf->tef", x, wg, preferred_element_type=jnp.float32)
    u = jnp.einsum("td,edf->tef", x, wu, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u * w_local[:, :, None]).astype(x.dtype)
    return jnp.einsum("tef,efd->td", a, wd, preferred_element_type=jnp.float32)


def _held_experts_grouped(x, weights, local, count, wg, wu, wd):
    """The pairs of (token, held expert) sorted by expert and run as grouped
    matmuls (``lax.ragged_dot``), no capacity and no drop: pairs of experts
    that are not held sort behind the last group, which no tile visits."""
    T, k = local.shape
    N = T * k
    fe = local.T.reshape(-1)                       # round-major, as _counting_sort's users
    dest, sidx, counts, _ = _counting_sort(fe, count + 1)
    held = counts[:count]
    xin = x[sidx % T]                              # sorted slot -> its token's row
    mid = (jax.nn.silu(jax.lax.ragged_dot(xin, wg, held,
                                          preferred_element_type=jnp.float32))
           * jax.lax.ragged_dot(xin, wu, held, preferred_element_type=jnp.float32))
    out = jax.lax.ragged_dot(mid.astype(x.dtype), wd, held,
                             preferred_element_type=jnp.float32)
    # rows past the last held pair were never computed: they hold anything
    out = jnp.where((jnp.arange(N, dtype=jnp.int32) < held.sum())[:, None], out, 0.0)
    w_pair = jnp.where(fe < count, weights.T.reshape(-1), 0.0)
    return (out[dest] * w_pair[:, None]).reshape(k, T, -1).sum(0)


def _held_experts_row_blocks(x, weights, local, count, wg, wu, wd):
    """The pairs of (token, held expert) sorted by expert as in
    :func:`_held_experts_grouped`, but run ``GROUPED_BLOCK_ROWS`` sorted rows
    at a time and only as many blocks as hold a held pair (they sort first):
    the work and the memory follow the pairs this chip holds, not all that
    were routed, and no pair is dropped however many there are. A block's
    results are weighed and added to their tokens' rows."""
    T, k = local.shape
    R = GROUPED_BLOCK_ROWS
    fe = local.T.reshape(-1)                       # round-major, as _counting_sort's users
    _, sidx, counts, _ = _counting_sort(fe, count + 1)
    ends = jnp.cumsum(counts[:count])              # where each held expert's run ends
    starts = ends - counts[:count]
    total = ends[-1]
    sidx = jnp.pad(sidx, (0, -sidx.shape[0] % R))  # a padded slot lies past ``total``
    w_pair = weights.T.reshape(-1)

    def block(i, acc):
        lo = i * R
        pairs = jax.lax.dynamic_slice(sidx, (lo,), (R,))
        tok = pairs % T
        xin = x[tok]
        sizes = jnp.clip(ends, lo, lo + R) - jnp.clip(starts, lo, lo + R)
        mid = (jax.nn.silu(jax.lax.ragged_dot(xin, wg, sizes,
                                              preferred_element_type=jnp.float32))
               * jax.lax.ragged_dot(xin, wu, sizes, preferred_element_type=jnp.float32))
        out = jax.lax.ragged_dot(mid.astype(x.dtype), wd, sizes,
                                 preferred_element_type=jnp.float32)
        live = lo + jnp.arange(R, dtype=jnp.int32) < total
        out = jnp.where(live[:, None], out * w_pair[pairs][:, None], 0.0)
        return acc.at[tok].add(out)

    return jax.lax.fori_loop(0, (total + R - 1) // R, block,
                             jnp.zeros((T, x.shape[1]), jnp.float32))


def held_form(rows: int) -> str:
    """The form of the held experts for a call of ``rows`` tokens, from the
    count alone: ``"kernel"`` (:data:`HELD_KERNEL_MAX_TOKENS`; the dense form
    where a sharded step declared a kernel partition, since GSPMD cannot
    partition a Mosaic call), ``"dense"`` (:data:`GROUPED_ABOVE_TOKENS`),
    ``"grouped"`` (:data:`GROUPED_ALL_PAIRS_TOKENS`) or ``"row_blocks"``."""
    if rows <= HELD_KERNEL_MAX_TOKENS and current_partition() is None:
        return "kernel"
    if rows <= GROUPED_ABOVE_TOKENS:
        return "dense"
    return "grouped" if rows <= GROUPED_ALL_PAIRS_TOKENS else "row_blocks"


def experts_read(picks, count):
    """The held experts whose weights a call of :func:`expert_share_ffn` with
    these ``picks [T, topk]`` reads (int32): those the rows touched where the
    kernel runs, every one of the ``count`` in any other form."""
    if held_form(picks.shape[0]) == "kernel":
        return picked_experts(picks, count)[1][0]
    return jnp.int32(count)


def expert_share_ffn(x, router, bias, wg, wu, wd, *, topk, scale, num_routed,
                     first, routing="softmax"):
    """What ONE chip adds to an expert layer's result for tokens ``x [T, d]``:
    it holds routed experts ``first .. first + count`` (``count`` is the
    leading size of the stacked weights) of ``num_routed``; router outputs
    past ``num_routed`` are zero-compute experts, the identity. The router
    keeps its whole width and its ``topk`` picks, scored as ``routing`` names
    (:data:`ROUTINGS`). Returns the partial sum
    ``sum over held picks of w * E(x) + x * sum of the picked identity
    weights`` in float32, and ``picks [T, topk]``: the held expert's local
    index, ``count`` for an identity pick, ``count + 1`` for an expert that
    lives on another chip (its part is left out). The held experts' form
    follows from ``T`` alone (:func:`held_form`)."""
    count = wg.shape[0]
    weights, ids = ROUTINGS[routing](x, router, bias, topk, scale)
    local = ids - first
    is_held = (local >= 0) & (local < count)
    is_zero = ids >= num_routed
    picks = jnp.where(is_held, local, jnp.where(is_zero, count, count + 1))
    form = held_form(x.shape[0])
    if form in ("kernel", "dense"):
        w_local = jnp.sum(jnp.where(is_held[..., None], weights[..., None], 0.0)
                          * jax.nn.one_hot(picks, count, dtype=jnp.float32), axis=1)
        if form == "kernel":
            ids_, n = picked_experts(picks, count)
            y = held_experts_decode(x, w_local, ids_, n, wg, wu, wd)
        else:
            y = _held_experts_dense(x, w_local, wg, wu, wd)
    else:
        grouped = (_held_experts_grouped if form == "grouped"
                   else _held_experts_row_blocks)
        y = grouped(x, weights, jnp.where(is_held, local, count), count, wg,
                    wu, wd)
    if router.shape[1] == num_routed:                # no identity experts
        return y, picks
    w_zero = jnp.sum(jnp.where(is_zero, weights, 0.0), axis=-1, keepdims=True)
    return y + x.astype(jnp.float32) * w_zero, picks


def pick_counts(picks, count, mask=None):
    """The counters of one call of an expert share, int32 ``[count + 3]``:
    pairs on each held expert, identity picks, picks of absent experts, and
    the held experts with at least one pair. ``mask [T]`` leaves tokens out
    (a retired slot's lane still computes)."""
    oh = jax.nn.one_hot(picks, count + 2, dtype=jnp.int32)
    if mask is not None:
        oh = oh * mask.astype(jnp.int32)[:, None, None]
    hist = oh.sum((0, 1))
    return jnp.concatenate([hist, jnp.sum(hist[:count] > 0, keepdims=True)])


class PickTap:
    """Collects, while a program is traced, the ``picks`` of every expert share
    it runs (``with PickTap() as tap: ...`` around the model call; the layers
    call :func:`record_picks`). The owner turns them into counters inside the
    same trace: nothing crosses a program's edge."""

    _active = None

    def __init__(self):
        # (picks [T, topk], count, experts read) per expert share run
        self.picks = []

    def __enter__(self):
        self._outer, PickTap._active = PickTap._active, self
        return self

    def __exit__(self, *exc):
        PickTap._active = self._outer

    def counts(self, mask=None):
        """Sum over the calls collected (all shares hold the same ``count``)
        of :func:`pick_counts` followed by the held experts whose weights the
        call read (:func:`experts_read`, every row's picks: a masked row is
        computed all the same; 0 where ``mask`` leaves every row out), int32
        ``[count + 4]``; None where there was no call."""
        if not self.picks:
            return None
        live = 1 if mask is None else jnp.any(mask).astype(jnp.int32)
        return sum(jnp.concatenate([pick_counts(p, c, mask), (read * live)[None]])
                   for p, c, read in self.picks)


def record_picks(picks, count):
    if PickTap._active is not None:
        PickTap._active.picks.append((picks, count,
                                      experts_read(picks, count)))


class ExpertShareLayer(Layer):
    """One chip's share of a sparse expert layer.

    The layer is TOLD which experts it holds: ``held = (first, count)`` of
    ``num_routed`` routed experts (SwiGLU, stacked ``[count, ...]``), beside
    ``num_zero`` identity experts (zero-compute: they cost nothing and belong
    to the token's own chip; 0 where the model has none). Routing is at the
    full width ``num_routed + num_zero``, ``topk`` picks of the scores plus a
    correction bias that chooses and does not weigh, and the model states
    which scores: ``routing="softmax"`` weighs a pick ``scaling * p`` with no
    renormalisation (LongCat-Flash), ``routing="sigmoid"`` weighs it
    ``scaling * s_i / sum of the picked s`` (Kimi-K2, DeepSeek-V3;
    :func:`route_sigmoid_topk`). ``shared_hidden`` > 0 adds a SHARED expert,
    a SwiGLU of that width that every token passes on every chip
    (``shared_gate_proj``, ``shared_up_proj``, ``shared_down_proj``).
    ``forward`` returns the partial sum this chip adds: its held experts' part,
    the identity part and the shared expert's (what absent experts would add
    is left out: there is no exchange here, and nothing stands in for it), and
    the picks (:func:`expert_share_ffn`). Held experts run droplessly at static
    shapes; the formulation follows from the token count alone."""

    def __init__(self, d_model, d_hidden, num_routed, num_zero, topk,
                 held=None, scaling=1.0, dtype="float32", init_std=0.02,
                 routing="softmax", shared_hidden=0):
        super().__init__(dtype=dtype)
        from ..nn.initializer import Constant, Normal

        first, count = (0, num_routed) if held is None else held
        if not (0 <= first and count >= 1 and first + count <= num_routed):
            raise ValueError(f"held={held!r} does not lie inside the "
                             f"{num_routed} routed experts")
        if routing not in ROUTINGS:
            raise ValueError(f"routing={routing!r}: one of {sorted(ROUTINGS)}")
        self.num_routed, self.num_zero, self.topk = num_routed, num_zero, topk
        self.first, self.count, self.scaling = first, count, float(scaling)
        self.routing, self.shared_hidden = routing, int(shared_hidden)
        normal = Normal(0.0, init_std)
        self.router = self.create_parameter(
            [d_model, num_routed + num_zero], default_initializer=normal)
        self.e_score_correction_bias = self.create_parameter(
            [num_routed + num_zero], dtype="float32",
            default_initializer=Constant(0.0))
        self.gate_proj = self.create_parameter(
            [count, d_model, d_hidden], default_initializer=normal)
        self.up_proj = self.create_parameter(
            [count, d_model, d_hidden], default_initializer=normal)
        self.down_proj = self.create_parameter(
            [count, d_hidden, d_model], default_initializer=normal)
        if self.shared_hidden:
            self.shared_gate_proj = self.create_parameter(
                [d_model, self.shared_hidden], default_initializer=normal)
            self.shared_up_proj = self.create_parameter(
                [d_model, self.shared_hidden], default_initializer=normal)
            self.shared_down_proj = self.create_parameter(
                [self.shared_hidden, d_model], default_initializer=normal)

    def forward(self, x):
        def f(a, r, b, wg, wu, wd, *shared):
            flat = a.reshape(-1, a.shape[-1])
            y, picks = expert_share_ffn(
                flat, r, b, wg, wu, wd, topk=self.topk, scale=self.scaling,
                num_routed=self.num_routed, first=self.first,
                routing=self.routing)
            if shared:
                sg, su, sd = shared
                mid = jax.nn.silu(jnp.matmul(flat, sg)) * jnp.matmul(flat, su)
                y = y + jnp.matmul(mid, sd, preferred_element_type=jnp.float32)
            record_picks(picks, self.count)
            return y.astype(a.dtype).reshape(a.shape), picks

        shared = ((self.shared_gate_proj, self.shared_up_proj,
                   self.shared_down_proj) if self.shared_hidden else ())
        return apply_op(f, x, self.router, self.e_score_correction_bias,
                        self.gate_proj, self.up_proj, self.down_proj, *shared,
                        op_name="expert_share_ffn")


class MoELayer(Layer):
    """Token-routed expert FFN bank (reference MoELayer:99).

    Expert weights are stacked Parameters [E, ...] with dist_spec ('ep', ...)
    so ShardedTrainStep places one expert group per ep shard.

    ``dispatch_mode`` (full-model 16e/top-2 train-step numbers, TPU v5e
    bf16, round-4 slope-timed harness — see BASELINE.md):
      * "sorted" (default) — counting-sort routing into STATIC capacity
        buffers run as batched einsums with a fused gate|up projection,
        gather-only vjps (the reference fused-MoE capacity semantics,
        85.2 ms/step): the single-chip perf path. Tokens beyond
        ``capacity_factor`` per expert are dropped.
      * "dropless" — same routing, ``lax.ragged_dot`` grouped matmuls, no
        capacity bound / no drops (~6% slower full-model, r5) — trade
        step time for exact routing. Attacked in rounds 4-5 and kept
        non-default on the numbers: 128-aligned group boundaries measured
        neutral, a fused gate|up parameter measured SLOWER (XLA already
        folds the in-graph concat), and an r5 fixed-assignment A/B shows
        routing+dispatch INDEX MATH costs ~0 ms (r4's "11.5 ms" was
        cross-session variance) — the real MoE premium over a
        dense-equivalent model is capacity padding + dispatch data
        movement + expert-granularity (decomposition in BASELINE.md and
        tools/moe_ab.py).
      * "einsum" — GShard one-hot dispatch/combine einsums (~2x sorted);
        XLA's SPMD partitioner turns the token-expert contraction into the
        ICI all_to_all, the cleanest multi-chip ep-sharded lowering — use
        this when sharding the expert bank over an ep mesh axis.
      * "fused" — the sorted path's routing/drop semantics with the
        dispatch gather + expert FFN run by the Pallas gather-GEMM
        kernel (ops/kernels/gather_gemm.py): indices read in-kernel, no
        HBM-resident gathered activations (the r5 data-movement floor).
        Forward-fused; backward recomputes the reference formulation.
        Unsupported configs fall back LOUDLY to "sorted"
        (docs/kernels.md).
    Only stock gates take the fast paths (a custom ``routing()`` override
    falls back to einsum, the extension point that honors it).
    """

    def __init__(self, d_model, d_hidden, num_experts, gate: Optional[Layer] = None,
                 capacity_factor: float = 1.25, ep_axis: str = "ep",
                 activation=None, dispatch_mode: str = "sorted"):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        if dispatch_mode not in ("einsum", "sorted", "dropless", "fused"):
            raise ValueError(
                f"dispatch_mode must be 'einsum', 'sorted', 'dropless' or "
                f"'fused', got {dispatch_mode!r}")
        if dispatch_mode == "fused":
            # resolve the fallback ONCE, loudly: an unsupported config
            # serves the reference formulation with one stderr line, never
            # a silent behavior change (docs/kernels.md fallback matrix)
            from ..ops.kernels.gather_gemm import gather_gemm_supported

            ok, reason = gather_gemm_supported(d_model=d_model,
                                               d_hidden=d_hidden)
            if not ok:
                import sys

                sys.stderr.write(
                    f"[moe] fused gather-GEMM dispatch unavailable "
                    f"({reason}); falling back to 'sorted'\n")
                try:
                    from ..inference.robustness import safe_inc

                    safe_inc("paddle_fused_kernel_fallbacks_total",
                             "fused-kernel requests that fell back to the "
                             "reference formulation", kernel="gather_gemm",
                             reason=reason.split(" ")[0])
                except Exception:
                    pass
                dispatch_mode = "sorted"
        self.dispatch_mode = dispatch_mode
        self.gate = gate or GShardGate(d_model, num_experts)
        self.w_gate_proj = mark_placement(self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=XavierNormal()),
            (ep_axis, None, None))
        self.w_up_proj = mark_placement(self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=XavierNormal()),
            (ep_axis, None, None))
        self.w_down_proj = mark_placement(self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=XavierNormal()),
            (ep_axis, None, None))
        self.l_aux = None  # set per forward (load-balance loss)

    def capacity(self, num_tokens: int) -> int:
        per = num_tokens * max(self.gate.topk, 1) / self.num_experts
        return max(4, int(math.ceil(per * self.capacity_factor)))

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        d = self.d_model
        x_flat = x.reshape([b * s, d])
        cap = self.capacity(b * s)

        # the fast paths inline softmax+top-k routing; a custom routing()
        # override must keep its behavior, so it routes via einsum
        stock_gate = type(self.gate).routing is NaiveGate.routing
        if self.dispatch_mode == "dropless" and stock_gate:
            topk = max(self.gate.topk, 1)

            def dropless_ffn(xf, gw, wg, wu, wd):
                logits = xf.astype(jnp.float32) @ gw.astype(jnp.float32)
                return _dropless_moe_ffn(xf, logits, wg, wu, wd, topk)

            y, aux = apply_op(dropless_ffn, x_flat, self.gate.weight,
                              self.w_gate_proj, self.w_up_proj,
                              self.w_down_proj, op_name="moe_ffn_dropless")
            self.l_aux = aux
            return y.reshape([b, s, d])
        if self.dispatch_mode == "fused" and stock_gate:
            topk = max(self.gate.topk, 1)

            def fused_ffn(xf, gw, wg, wu, wd):
                logits = xf.astype(jnp.float32) @ gw.astype(jnp.float32)
                return _fused_gather_gemm_moe_ffn(xf, logits, wg, wu, wd,
                                                  topk, cap)

            y, aux = apply_op(fused_ffn, x_flat, self.gate.weight,
                              self.w_gate_proj, self.w_up_proj,
                              self.w_down_proj, op_name="moe_ffn_fused")
            self.l_aux = aux
            return y.reshape([b, s, d])
        if self.dispatch_mode == "sorted" and stock_gate:
            topk = max(self.gate.topk, 1)

            def sorted_ffn(xf, gw, wg, wu, wd):
                logits = xf.astype(jnp.float32) @ gw.astype(jnp.float32)
                return _gathered_capacity_moe_ffn(xf, logits, wg, wu, wd,
                                                  topk, cap)

            y, aux = apply_op(sorted_ffn, x_flat, self.gate.weight,
                              self.w_gate_proj, self.w_up_proj,
                              self.w_down_proj, op_name="moe_ffn_sorted")
            self.l_aux = aux
            return y.reshape([b, s, d])

        dispatch, combine, aux = self.gate.routing(x_flat, cap)
        self.l_aux = aux

        def expert_ffn(xf, disp, comb, wg, wu, wd):
            xin = jnp.einsum("tec,td->ecd", disp.astype(xf.dtype), xf)
            h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xin, wg))
            h = h * jnp.einsum("ecd,edh->ech", xin, wu)
            out = jnp.einsum("ech,ehd->ecd", h, wd)
            return jnp.einsum("tec,ecd->td", comb.astype(xf.dtype), out)

        y = apply_op(expert_ffn, x_flat, dispatch, combine,
                     self.w_gate_proj, self.w_up_proj, self.w_down_proj,
                     op_name="moe_ffn")
        return y.reshape([b, s, d])


def moe_sharding_rules(ep_axis="ep", tp_axis="tp", fsdp_axis="fsdp"):
    """Rules for MoE LMs: expert banks on ep (via dist_spec, these are a
    fallback), dense weights as llama."""
    from ..models.llama import llama_sharding_rules

    return [
        (r".*w_(gate|up|down)_proj$", (ep_axis,)),
        (r".*gate\.weight$", ()),
    ] + llama_sharding_rules(tp_axis=tp_axis, fsdp_axis=fsdp_axis)
