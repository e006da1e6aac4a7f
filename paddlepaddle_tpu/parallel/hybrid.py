"""4D hybrid parallelism — dp × fsdp × tp × pp composed in ONE mesh.

Reference surface: fleet/base/topology.py:189 ``HybridCommunicateGroup``
(data × pipe × sharding × sep × model — the reference's whole fleet stack
exists to run these axes TOGETHER) and the end-to-end recipe
test/auto_parallel/hybrid_strategy/semi_auto_llama.py. The TPU-native
composition is one ``shard_map`` over a single 4-axis ``Mesh``:

* **pp** — pipeline stages via the instruction-table executor
  (``parallel.pipeline_spmd.spmd_pipeline_train``), ring ``ppermute`` over ICI;
* **tp** — Megatron tensor parallel INSIDE each stage as explicit collectives:
  column-parallel qkv/gate/up (no comm), row-parallel o/down followed by one
  ``psum`` over 'tp' per sub-block (fleet/layers/mpu/mp_layers.py:336,543
  semantics), plus a vocab-parallel cross-entropy head
  (ParallelCrossEntropy, mp_layers.py) that never materializes full logits;
* **fsdp** — ZeRO-3 parameter sharding as all-gather-at-use: weights live
  sharded on the 'fsdp' axis and are gathered just-in-time inside the block.
  The transpose of ``lax.all_gather`` is ``psum_scatter``, so the stage vjp
  returns gradients already reduce-scattered into the same sharded layout
  (group_sharded_stage3.py semantics, compiler-scheduled);
* **dp** — batch over 'dp' (and 'fsdp': both are data axes for activations).

Everything here is a pure function of jax arrays — it runs inside the
pipeline executor's ``shard_map``/``lax.scan``, with per-layer remat
(``jax.checkpoint``) inside the stage vjp and flash attention on the local
TP head group.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..models.llama import rope_tables, rotate_half
from ..ops.kernels.flash_attention import _flash_core, _use_pallas
from ..ops.kernels.ring_attention import _block_attn_update


class HybridStageConfig(NamedTuple):
    """Shape card for one homogeneous pipeline stage of a Llama-style LM."""

    hidden_size: int
    intermediate_size: int
    num_heads: int
    num_kv_heads: int
    layers_per_stage: int
    vocab_size: int
    max_seq_len: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (n * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, cos, sin):
    return x * cos + rotate_half(x) * sin


def _fg_pair(tp_axis):
    """Megatron's conjugate f/g operators (mp_layers.py c_identity /
    mp_allreduce semantics) for manual-collective TP under shard_map with
    replication checking off:

    * ``f`` — identity forward, psum backward: placed where a REPLICATED
      activation enters the tp-sharded region, so the cotangent sums each
      member's partial contribution;
    * ``g`` — psum forward, identity backward: the row-parallel output
      reduction, whose incoming cotangent is already replicated/full.

    A raw ``lax.psum`` would transpose to another psum (check_vma=False
    cannot assume replication), over-counting by the tp size.
    """
    if tp_axis is None:
        return (lambda x: x), (lambda x: x)

    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None), lambda _, ct: (jax.lax.psum(ct, tp_axis),))

    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, tp_axis)

    g.defvjp(lambda x: (jax.lax.psum(x, tp_axis), None), lambda _, ct: (ct,))
    return f, g


def init_llama_stage(cfg: HybridStageConfig, key, dtype=jnp.float32) -> dict:
    """Full (unsharded) parameters for ONE pipeline stage: ``layers_per_stage``
    decoder layers, leaves with a leading layer dim. Stack stages with
    ``pipeline_spmd.stack_stage_params`` and shard with
    ``llama_stage_specs()``."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    hd = cfg.head_dim
    L = cfg.layers_per_stage
    ks = jax.random.split(key, 7)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, (L,) + shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    return {
        "ln1": jnp.ones((L, h), dtype),
        "ln2": jnp.ones((L, h), dtype),
        "wq": w(ks[0], (h, cfg.num_heads * hd), h),
        "wk": w(ks[1], (h, cfg.num_kv_heads * hd), h),
        "wv": w(ks[2], (h, cfg.num_kv_heads * hd), h),
        "wo": w(ks[3], (cfg.num_heads * hd, h), cfg.num_heads * hd),
        "wg": w(ks[4], (h, f), h),
        "wu": w(ks[5], (h, f), h),
        "wd": w(ks[6], (f, h), f),
    }


def init_llama_head(cfg: HybridStageConfig, key, dtype=jnp.float32) -> dict:
    """Final-norm + vocab projection (the vocab-parallel loss head)."""
    return {
        "ln": jnp.ones((cfg.hidden_size,), dtype),
        "w": (jax.random.normal(key, (cfg.hidden_size, cfg.vocab_size),
                                jnp.float32)
              / math.sqrt(cfg.hidden_size)).astype(dtype),
    }


def llama_stage_specs(tp_axis="tp", fsdp_axis="fsdp") -> dict:
    """PartitionSpecs for one stage's leaves (per-stage dims only — the
    pipeline executor prepends the V/S dims). Column-parallel weights shard
    the output dim over tp, row-parallel the input dim; fsdp takes the other
    matmul dim (ZeRO-3)."""
    col = P(None, fsdp_axis, tp_axis)   # [L, h, f]: gather h, keep f local
    row = P(None, tp_axis, fsdp_axis)   # [L, f, h]: keep f local, gather h
    return {
        "ln1": P(), "ln2": P(),
        "wq": col, "wk": col, "wv": col, "wo": row,
        "wg": col, "wu": col, "wd": row,
    }


def llama_head_specs(tp_axis="tp") -> dict:
    """Head: vocab dim over tp (ParallelCrossEntropy layout); norm replicated."""
    return {"ln": P(), "w": P(None, tp_axis)}


def make_llama_block(cfg: HybridStageConfig, tp_axis="tp", fsdp_axis="fsdp",
                     sp_axis=None, sp_size=1, remat=True, use_flash=True):
    """(stage_params_local, acts) -> acts: one pipeline stage =
    ``layers_per_stage`` decoder layers with explicit tp/fsdp collectives.

    Runs inside shard_map: ``stage_params_local`` leaves are the local tp/fsdp
    shards (see ``llama_stage_specs``); activations are replicated over tp and
    batch-sharded over the data axes by the caller. With ``sp_axis`` the
    SEQUENCE dim of the activations is additionally sharded over a context-
    parallel axis and attention runs blockwise over the gathered K/V
    (``_sp_blockwise_attention`` — allgather-KV context parallelism; the
    standalone ring lives in ops/kernels/ring_attention.py but ppermute is
    not branch-safe inside the schedule executor): the full 5-D
    dp x fsdp x tp x pp x sp composition. ``sp_size`` must be the static
    mesh size of ``sp_axis``."""
    cos_t, sin_t = rope_tables(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    eps = cfg.rms_norm_eps
    f_in, g_out = _fg_pair(tp_axis)

    def gather(wloc, axis):
        if fsdp_axis is None:
            return wloc
        return jax.lax.all_gather(wloc, fsdp_axis, axis=axis, tiled=True)

    def layer(x, lp):
        x = _attention_residual(
            x, lp, cfg=cfg, cos_t=cos_t, sin_t=sin_t, f_in=f_in,
            g_out=g_out, gather=gather, sp_axis=sp_axis, sp_size=sp_size,
            use_flash=use_flash)
        # --- MLP (column gate/up, row down + psum) ---
        hm = f_in(_rms(x, lp["ln2"], eps))
        wg, wu = gather(lp["wg"], 0), gather(lp["wu"], 0)
        wd = gather(lp["wd"], 1)
        y = g_out((jax.nn.silu(hm @ wg) * (hm @ wu)) @ wd)
        return x + y

    if remat:
        layer = jax.checkpoint(layer)

    def block(params, x):
        def body(xc, lp):
            return layer(xc, lp), None
        x, _ = jax.lax.scan(body, x, params)
        return x

    return block


def _sp_blockwise_attention(q, k, v, sp_axis, n_shards, scale, rep=1):
    """Context-parallel causal attention INSIDE the pipeline executor:
    all-gather the K/V shards over sp, then blockwise online-softmax against
    the local Q shard (global position offsets), O(s_local x s_global)
    scores never materialized at once.

    Why not the true ring (ops/kernels/ring_attention.py): XLA lowers
    ``collective-permute`` on ONE global channel, so a ppermute inside a
    ``lax.switch`` branch deadlocks when pipeline stages execute different
    opcodes in the same slot (observed as an 8-way rendezvous stuck at 4).
    All-reduce-family collectives (psum / all_gather / psum_scatter) lower
    per replica-group and are branch-safe — the same reason the Megatron
    'allgather-KV' context-parallel variant exists. Memory: O(s_global) K/V
    per chip vs the ring's O(s_local); the scores stay blocked."""
    my = jax.lax.axis_index(sp_axis)
    b, s_loc, h, d = q.shape
    kg = jax.lax.all_gather(k, sp_axis)          # [n, b, s_loc, kvh, d]
    vg = jax.lax.all_gather(v, sp_axis)
    m = jnp.full((b, h, s_loc, 1), -1e30, jnp.float32)
    l = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    acc = jnp.zeros((b, h, s_loc, d), jnp.float32)
    q_off = my * s_loc
    for j in range(n_shards):
        kj, vj = kg[j], vg[j]
        if rep > 1:                              # GQA repeat AFTER the gather
            kj = jnp.repeat(kj, rep, axis=2)
            vj = jnp.repeat(vj, rep, axis=2)
        m2, l2, a2 = _block_attn_update(q, kj, vj, m, l, acc,
                                        q_off, j * s_loc, True, scale)
        skip = j > my                            # block fully in the future
        m = jnp.where(skip, m, m2)
        l = jnp.where(skip, l, l2)
        acc = jnp.where(skip, acc, a2)
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def make_vocab_parallel_head(cfg: HybridStageConfig, tp_axis="tp",
                             sp_axis=None):
    """(head_params_local, acts, labels) -> scalar mean next-token CE.

    ParallelCrossEntropy semantics (fleet/layers/mpu/mp_layers.py — the
    reference's c_softmax_with_cross_entropy): logits stay vocab-sharded over
    tp; the softmax normalizer and the label logit are assembled with psum /
    pmax so the full [b, s, V] tensor never exists. Same shift/mask
    formulation as models.llama.LlamaForCausalLM.loss_from_logits. With
    ``sp_axis`` the sequence dim is context-sharded: the next-token label
    shift crosses shard boundaries via ppermute, positions/valid masks use
    GLOBAL indices, and the mean reduces numerator and denominator with
    psum over sp."""
    eps = cfg.rms_norm_eps
    f_in, g_out = _fg_pair(tp_axis)
    _, g_sp = _fg_pair(sp_axis)

    def _shift_labels(labels):
        """labels for position t = token t+1, across sp shard boundaries."""
        if sp_axis is None:
            return jnp.roll(labels, -1, axis=1)
        # branch-safe shift (no ppermute, see _sp_blockwise_attention): every
        # shard gathers the first columns and takes its RIGHT neighbor's
        n = jax.lax.psum(1, sp_axis)
        firsts = jax.lax.all_gather(labels[:, :1], sp_axis)  # [n, b, 1]
        my = jax.lax.axis_index(sp_axis)
        incoming = jnp.take(firsts, (my + 1) % n, axis=0)
        return jnp.concatenate([labels[:, 1:], incoming], axis=1)

    def head_loss(hp, x, labels):
        xn = f_in(_rms(x, hp["ln"], eps))
        logits = (xn @ hp["w"]).astype(jnp.float32)       # [b, s, V_local]
        v_loc = logits.shape[-1]
        s = logits.shape[1]
        off = (jax.lax.axis_index(tp_axis) * v_loc) if tp_axis else 0
        lbl = _shift_labels(labels)
        # the max shift is numerical-stability only — keep the (non-
        # differentiable) pmax out of the vjp graph
        m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        m = jax.lax.pmax(m_loc, tp_axis) if tp_axis else m_loc
        m = jax.lax.stop_gradient(m)
        se = g_out(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        lse = m + jnp.log(se)
        mine = (lbl >= off) & (lbl < off + v_loc)
        safe = jnp.clip(lbl - off, 0, v_loc - 1)
        lab = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        lab = g_out(jnp.where(mine, lab, 0.0))
        nll = lse - lab
        pos = jax.lax.broadcasted_iota(jnp.int32, nll.shape, 1)
        if sp_axis is not None:
            n = jax.lax.psum(1, sp_axis)
            pos = pos + jax.lax.axis_index(sp_axis) * s
            s_total = s * n
        else:
            s_total = s
        valid = ((lbl >= 0) & (pos < s_total - 1)).astype(jnp.float32)
        # g-style psum (identity backward): a raw psum would transpose to
        # another psum and overcount each shard's cotangent by sp_size
        num = g_sp(jnp.sum(nll * valid))
        den = g_sp(jnp.sum(valid))
        return num / jnp.maximum(den, 1.0)

    return head_loss


def reference_forward(cfg: HybridStageConfig, per_stage_params, head_params,
                      acts, labels):
    """Unsharded single-device forward — the parity oracle for tests: same
    math as make_llama_block(tp=None, fsdp=None) chained over stages + the
    head loss with the full vocab."""
    block = make_llama_block(cfg, tp_axis=None, fsdp_axis=None, remat=False,
                             use_flash=False)
    head = make_vocab_parallel_head(cfg, tp_axis=None)
    x = acts
    for sp in per_stage_params:
        x = block(sp, x)
    return head(head_params, x, labels)


# ---------------------------------------------------------------------------
# MoE stage: expert parallelism composed with the pipeline (ep × tp × pp —
# the ERNIE/DeepSeek hybrid layout, fleet/base/topology.py + moe_layer.py)
# ---------------------------------------------------------------------------


def init_moe_stage(cfg: HybridStageConfig, key, num_experts: int,
                   expert_hidden: int, dtype=jnp.float32) -> dict:
    """One pipeline stage whose MLP is an expert bank: llama attention
    params + gate [h, E] + stacked expert FFNs [L, E, ...]."""
    h = cfg.hidden_size
    L = cfg.layers_per_stage
    base = init_llama_stage(cfg, key, dtype)
    for k_ in ("wg", "wu", "wd"):
        del base[k_]
    ks = jax.random.split(jax.random.fold_in(key, 17), 4)

    def w(k_, shape, fan_in):
        return (jax.random.normal(k_, (L,) + shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    base["gate"] = w(ks[0], (h, num_experts), h)
    base["eg"] = w(ks[1], (num_experts, h, expert_hidden), h)
    base["eu"] = w(ks[2], (num_experts, h, expert_hidden), h)
    base["ed"] = w(ks[3], (num_experts, expert_hidden, h), expert_hidden)
    return base


def moe_stage_specs(tp_axis="tp", fsdp_axis="fsdp", ep_axis="ep") -> dict:
    """Attention sharded like the dense stage; expert banks over ep; the
    router replicated (every ep member routes identically)."""
    specs = llama_stage_specs(tp_axis=tp_axis, fsdp_axis=fsdp_axis)
    for k_ in ("wg", "wu", "wd"):
        del specs[k_]
    specs["gate"] = P()
    specs["eg"] = P(None, ep_axis)
    specs["eu"] = P(None, ep_axis)
    specs["ed"] = P(None, ep_axis)
    return specs


def _inject_aux_grad(y, aux, weight):
    """Identity on ``y`` whose backward ALSO seeds ``aux``'s cotangent with
    ``weight`` — how a scalar auxiliary objective rides through a block
    whose contract only returns activations."""

    @jax.custom_vjp
    def f(y_, aux_):
        return y_

    f.defvjp(lambda y_, aux_: (y_, aux_),
             lambda aux_res, dy: (dy, jnp.full_like(aux_res, weight)))
    return f(y, aux)


def make_moe_block(cfg: HybridStageConfig, num_experts: int, topk: int = 2,
                   capacity_factor: float = 2.0, tp_axis="tp",
                   fsdp_axis="fsdp", ep_axis="ep", ep_size: int = 1,
                   aux_loss_weight: float = 0.0, remat=True, use_flash=True):
    """(stage_params_local, acts) -> acts: llama attention + an
    EXPERT-PARALLEL MoE MLP, branch-safe for the pipeline executor.

    GShard semantics with explicit collectives: tokens stay replicated over
    ep, every member routes identically (replicated gate), each member
    einsum-dispatches only to its LOCAL expert slice, and the combined
    outputs meet in one g-style psum over ep (the role of the reference's
    MoEScatter/MoEGather alltoall pair, moe_layer.py:149,263 — a psum is
    branch-safe inside lax.switch, an alltoall channel may not be). The
    token cotangent sums each member's partial path via the f-operator.
    """
    from .moe import _top1_routing, _topk_routing

    if ep_axis is not None and ep_size <= 1:
        raise ValueError(
            "ep_axis set but ep_size<=1 — pass the mesh's STATIC ep axis "
            "size (a wrong ep_size makes dynamic_slice silently clamp and "
            "double-count experts in the psum)")
    if num_experts % max(ep_size, 1):
        raise ValueError(
            f"num_experts={num_experts} not divisible by ep_size={ep_size}")
    eps = cfg.rms_norm_eps
    cos_t, sin_t = rope_tables(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    f_tp, g_tp = _fg_pair(tp_axis)
    f_ep, g_ep = _fg_pair(ep_axis)

    def gather(wloc, axis):
        if fsdp_axis is None:
            return wloc
        return jax.lax.all_gather(wloc, fsdp_axis, axis=axis, tiled=True)

    def layer(x, lp):
        b, s, h = x.shape
        dt = x.dtype
        # --- attention: the shared residual sub-block ---
        x = _attention_residual(
            x, lp, cfg=cfg, cos_t=cos_t, sin_t=sin_t, f_in=f_tp, g_out=g_tp,
            gather=gather, use_flash=use_flash)
        # --- MoE MLP (ep-parallel GShard einsum) ---
        hm = f_ep(_rms(x, lp["ln2"], eps))
        E = num_experts
        el = E // max(ep_size, 1)
        T = b * s
        cap = max(4, int(math.ceil(T * topk / E * capacity_factor)))
        xf = hm.reshape(T, h)
        # the gate's cotangent arrives as a per-member PARTIAL (each ep
        # member backprops only through its local expert slice) — the
        # f-operator's psum-backward assembles the full router gradient
        gate_w = f_ep(lp["gate"].astype(jnp.float32))
        logits = xf.astype(jnp.float32) @ gate_w
        if topk == 1:
            disp, comb, aux = _top1_routing(logits, cap)
        else:
            disp, comb, aux = _topk_routing(logits, cap, topk)
        # routing is replicated over ep; each member dispatches only to its
        # LOCAL expert slice and the partial outputs meet in ONE psum
        my = jax.lax.axis_index(ep_axis) if ep_axis else 0
        d_loc = jax.lax.dynamic_slice_in_dim(disp, my * el, el, axis=1)
        c_loc = jax.lax.dynamic_slice_in_dim(comb, my * el, el, axis=1)
        xin = jnp.einsum("tec,td->ecd", d_loc.astype(dt), xf)
        hmid = jax.nn.silu(jnp.einsum("ecd,edh->ech", xin, lp["eg"]))
        hmid = hmid * jnp.einsum("ecd,edh->ech", xin, lp["eu"])
        outp = jnp.einsum("ech,ehd->ecd", hmid, lp["ed"])
        y = jnp.einsum("tec,ecd->td", c_loc.astype(dt), outp)
        y = g_ep(y).reshape(b, s, h)
        # router load-balance loss: the executor's block contract returns
        # only activations, so the aux term enters through its GRADIENT —
        # identity-forward, constant-cotangent backward. NOTE the weight is
        # PER MICROBATCH: the CE loss is seeded 1/M per microbatch, so pass
        # aux_loss_weight = desired_total_weight / n_microbatches
        if aux_loss_weight:
            y = _inject_aux_grad(y, aux, aux_loss_weight)
        return x + y

    if remat:
        layer = jax.checkpoint(layer)

    def block(params, x):
        def body(xc, lp):
            return layer(xc, lp), None
        x, _ = jax.lax.scan(body, x, params)
        return x

    return block


def _attention_residual(x, lp, *, cfg, cos_t, sin_t, f_in, g_out, gather,
                        sp_axis=None, sp_size=1, use_flash=True):
    """x + attention(x): the residual attention sub-block SHARED by the
    dense (make_llama_block) and MoE (make_moe_block) stages — column qkv,
    rope at global positions, flash / plain-softmax / context-parallel
    allgather-KV attention, row o-proj + tp psum."""
    b, s, h = x.shape
    dt = x.dtype
    hd = cfg.head_dim
    scale = 1.0 / math.sqrt(hd)
    eps = cfg.rms_norm_eps
    hn = f_in(_rms(x, lp["ln1"], eps))
    wq, wk, wv = gather(lp["wq"], 0), gather(lp["wk"], 0), gather(lp["wv"], 0)
    wo = gather(lp["wo"], 1)
    q = (hn @ wq).reshape(b, s, -1, hd)
    k = (hn @ wk).reshape(b, s, -1, hd)
    v = (hn @ wv).reshape(b, s, -1, hd)
    if sp_axis is not None:
        # rope needs GLOBAL positions: this shard holds rows
        # [rank*s, rank*s + s) of the full sequence. Fail loudly — a
        # dynamic_slice would silently CLAMP an out-of-range offset to 0
        if sp_size * s > cfg.max_seq_len:
            raise ValueError(
                f"global sequence {sp_size * s} exceeds max_seq_len "
                f"{cfg.max_seq_len} (s_local={s} x sp_size={sp_size})")
        off = jax.lax.axis_index(sp_axis) * s
        cos = jax.lax.dynamic_slice_in_dim(cos_t, off, s, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(sin_t, off, s, axis=0)
    else:
        cos, sin = cos_t[:s], sin_t[:s]
    cos = cos[None, :, None, :].astype(dt)
    sin = sin[None, :, None, :].astype(dt)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    rep = q.shape[2] // k.shape[2]
    if sp_axis is not None:
        # gather the UN-repeated KV heads (1/rep the collective volume);
        # the blockwise attention repeats after the gather
        out = _sp_blockwise_attention(q, k, v, sp_axis, sp_size, scale, rep)
    else:
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if use_flash:
            out = _flash_core(q, k, v, True, scale, _use_pallas(
                q.shape[1], k.shape[1], q.shape[-1], True))
        else:
            qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
            kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
            lg = jnp.einsum("bhqd,bhkd->bhqk", qt, kt)
            lg = jnp.where(jnp.tril(jnp.ones((s, s), bool)), lg, -1e30)
            pr = jax.nn.softmax(lg, axis=-1).astype(v.dtype)
            out = jnp.swapaxes(
                jnp.einsum("bhqk,bhkd->bhqd", pr,
                           jnp.swapaxes(v, 1, 2)), 1, 2)
    return x + g_out(out.astype(dt).reshape(b, s, -1) @ wo)
