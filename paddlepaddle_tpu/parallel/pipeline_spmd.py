"""SPMD pipeline — the multi-chip execution path for pipeline parallelism.

This is the TPU-native replacement for the reference's NCCL p2p pipeline
runtime (pipeline_parallel.py send/recv_forward + 1F1B scheduling). Two
execution styles:

* ``spmd_pipeline`` — forward-only GPipe streaming loop; ``jax.grad``
  through it gives an F-then-B training step (all M microbatch residuals
  live at once, like the reference FThenB pass).
* ``spmd_pipeline_train`` — schedule-driven forward+backward in ONE
  ``lax.scan``: a static instruction table (parallel/schedules.py — 1F1B /
  interleaved VPP / GPipe) tells each stage, slot by slot, whether to run a
  forward, an inner backward (cotangent from the right neighbor), or the
  last-virtual-stage backward (loss gradient computed in-op). Activations
  are stashed O(schedule.stash_cap) per stage — O(S) for 1F1B vs O(M) for
  GPipe — and backward recomputes the block under ``jax.vjp`` from the
  stashed input (remat-style, like the reference's recompute+1F1B pairing).
  This reproduces the *memory and bubble behavior* of the reference's
  schedule zoo (pipeline_parallel.py:575 1F1B, :1179 interleaved;
  passes/pipeline_scheduler_pass), not just its result.

All styles run every stage as the SAME block program over a 'pp' mesh axis
inside ``shard_map``, with ``lax.ppermute`` ring transfers over ICI.
Requires homogeneous middle stages (identical block structure), which is how
transformer LMs are pipelined in practice; embed runs outside the loop
(its cotangent is returned), the head/loss runs inside the last stage's
backward op so 1F1B can start draining before all forwards finish.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .schedules import (OP_B, OP_B_LAST, OP_BW, OP_BW_LAST, OP_BX,
                        OP_BX_LAST, OP_F, OP_IDLE, PipelineSchedule,
                        _arrival_tables, build_schedule)


def stack_stage_params(per_stage_params: Sequence[dict]) -> dict:
    """[S trees with same structure] -> one tree with leading stage dim
    (shard it on the 'pp' axis)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def stack_virtual_stage_params(per_stage_params: Sequence[dict], n_devices: int) -> dict:
    """[V*S trees] -> tree with leading dims [V, S, ...] for the interleaved
    (VPP) schedule: global stage g = v*S + s lives on device s as chunk v —
    the reference's virtual-pipeline layer assignment (pp_layers.py VPP)."""
    total = len(per_stage_params)
    if total % n_devices:
        raise ValueError(f"{total} stages not divisible by {n_devices} devices")
    v = total // n_devices
    stacked = stack_stage_params(per_stage_params)      # [V*S, ...]
    return jax.tree_util.tree_map(
        lambda a: a.reshape((v, n_devices) + a.shape[1:]), stacked)


def spmd_pipeline_interleaved(stacked_params, acts, block_fn, mesh: Mesh,
                              n_microbatches: int, pp_axis: str = "pp",
                              data_axis=None):
    """Forward-only virtual-stage placement: VPP *stage assignment* semantics
    (global stage g = v*S + s on device s as chunk v) with a GPipe-per-lap
    schedule — the V laps run sequentially, so this does NOT reproduce VPP's
    bubble reduction. It exists for inference/forward parity; the real
    interleaved schedule (overlapping chunks in one scan, bubble ~(S-1)/V)
    is ``spmd_pipeline_train(..., schedule="interleaved")``.

    stacked_params leaves: [V, S, ...] (see stack_virtual_stage_params).
    """
    leaves = jax.tree_util.tree_leaves(stacked_params)
    v = leaves[0].shape[0]
    for lap in range(v):
        params_lap = jax.tree_util.tree_map(lambda a: a[lap], stacked_params)
        acts = spmd_pipeline(params_lap, acts, block_fn, mesh, n_microbatches,
                             pp_axis=pp_axis, data_axis=data_axis)
    return acts


def _spec_axes(spec) -> set:
    """Mesh-axis names mentioned by a PartitionSpec."""
    names = set()
    for e in spec or ():
        if e is None:
            continue
        if isinstance(e, (tuple, list)):
            names.update(e)
        else:
            names.add(e)
    return names


def _merge_specs(tree, specs, prefix):
    """Per-leaf specs for shard_map: ``prefix + spec`` (spec gives the
    per-stage dims; ``prefix`` covers the leading V/S dims the executor
    added)."""
    return jax.tree_util.tree_map(
        lambda _, s: P(*prefix, *(s or ())), tree, specs)


def spmd_pipeline_train(stacked_params, head_params, acts, labels,
                        block_fn: Callable, head_loss_fn: Callable, mesh: Mesh,
                        schedule="1f1b", n_microbatches: Optional[int] = None,
                        num_virtual: int = 1, pp_axis: str = "pp",
                        data_axis=None, param_specs=None, head_specs=None,
                        seq_axis=None):
    """Schedule-driven pipeline training step: forward AND backward of all
    microbatches in ONE ``lax.scan`` over schedule slots.

    Per slot each device executes its instruction from the static schedule
    table (parallel/schedules.py): F runs the block on an activation from
    the left-neighbor ring (stashing its input), B recomputes the block
    under ``jax.vjp`` from the stash and sends the input-cotangent down the
    ring, B_LAST additionally runs ``head_loss_fn`` so the loss gradient is
    produced as soon as the last virtual stage finishes that microbatch —
    which is what lets 1F1B/VPP start draining early. Peak live activations
    per device = schedule.stash_cap (S for 1F1B, M for GPipe, ~2S per chunk
    for VPP), reproducing the reference schedules' memory/bubble behavior
    (pipeline_parallel.py:575,1179; passes/pipeline_scheduler_pass).

    Args:
        stacked_params: pytree, leaves [S, ...] (num_virtual=1) or [V, S, ...]
            stage-stacked (shard the S dim over ``pp_axis``).
        head_params: pytree for the head/loss (replicated); may be empty.
        acts: [B, ...] activations entering virtual stage 0 (post-embedding).
        labels: [B, ...] targets, consumed by ``head_loss_fn`` per microbatch.
        block_fn: (params_one_stage, acts_mb) -> acts_mb.
        head_loss_fn: (head_params, acts_mb, labels_mb) -> scalar mean loss.
        schedule: PipelineSchedule, or name ('1f1b'|'gpipe'|'interleaved');
            names require ``n_microbatches`` (and ``num_virtual`` for VPP).
        data_axis: mesh axis name (or tuple of names) the batch dim is
            sharded over — dp, or (dp, fsdp) when ZeRO shards the batch too.
        seq_axis: mesh axis the SEQUENCE dim (acts/labels dim 1) is sharded
            over — context parallelism inside the stages (the block must
            run a branch-safe context-parallel attention over this axis,
            e.g. parallel.hybrid's allgather-KV blockwise attention, and
            the head must reduce its token sums over it). Parameter
            gradients are psum'd over it (each shard's tokens contribute
            additively to the same weights).
        param_specs / head_specs: optional pytrees (matching the stage /
            head param structure) of PartitionSpecs for the PER-STAGE leaf
            dims — how each weight is sharded over tp/fsdp INSIDE a stage
            (see parallel.hybrid.llama_stage_specs). The block/head fns are
            then responsible for the matching collectives (all_gather at
            use, psum after row-parallel matmuls). Gradients of a leaf whose
            spec mentions a data axis (fsdp-sharded weights) arrive already
            reduce-scattered by the vjp of the block's all_gather, so the
            executor mean-reduces them only over the remaining data axes.
    Returns:
        (loss, grads_stacked, grads_head, dacts): loss is the mean over the
        batch; grads_* match their params' structure; dacts is [B, ...], the
        cotangent for ``acts`` (backpropagate the embedding outside).
    """
    S = mesh.shape[pp_axis]
    if isinstance(schedule, str):
        if n_microbatches is None:
            raise ValueError("n_microbatches required with a schedule name")
        schedule = build_schedule(schedule, S, int(n_microbatches), V=num_virtual)
    sched: PipelineSchedule = schedule
    if sched.S != S:
        raise ValueError(f"schedule built for S={sched.S}, mesh has {S}")
    M, V = sched.M, sched.V
    B = acts.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    data_axes = () if data_axis is None else (
        (data_axis,) if isinstance(data_axis, str) else tuple(data_axis))
    stage_specs_tree = param_specs
    head_specs_tree = head_specs
    if stage_specs_tree is None:
        stage_specs_tree = jax.tree_util.tree_map(lambda _: P(), stacked_params)
    if head_specs_tree is None:
        head_specs_tree = jax.tree_util.tree_map(lambda _: P(), head_params)

    # normalize param leaves to [V, S, ...]
    added_v = V == 1
    if added_v:
        stacked_params = jax.tree_util.tree_map(lambda a: a[None], stacked_params)

    x_mb = acts.reshape(M, mb, *acts.shape[1:])
    y_mb = labels.reshape(M, mb, *labels.shape[1:])

    ops_t = jnp.asarray(sched.ops)
    mbs_t = jnp.asarray(sched.mbs)
    chs_t = jnp.asarray(sched.chunks)
    arr = tuple(jnp.asarray(a) for a in _arrival_tables(sched))
    Cs, Cf, Cb = sched.stash_cap, sched.inbox_f_cap, sched.inbox_b_cap
    # schedules without split BX/BW ops never touch the gstash — zero-size
    # buffer (gstash_entries is the shared executor/estimate predicate)
    Cg = sched.gstash_entries
    up_perm = [(i, (i + 1) % S) for i in range(S)]
    down_perm = [(i, (i - 1) % S) for i in range(S)]

    def per_stage(params, hp, x_l, y_l):
        p_local = jax.tree_util.tree_map(lambda a: a[:, 0], params)  # [V, ...]
        s_idx = jax.lax.axis_index(pp_axis)
        a_shape = x_l.shape[1:]
        dtype = x_l.dtype
        zero_act = jnp.zeros(a_shape, dtype)

        def slot(carry, row):
            (stash, gstash, inf, inb, gacc, hg, dacts, loss,
             left_in, right_in) = carry
            op_r, m_r, c_r, fv, fm, fc, bv, bm, bc = row
            # deposit last slot's ring arrivals into the chunk inboxes
            inf = inf.at[fc[s_idx], fm[s_idx] % Cf].set(
                jnp.where(fv[s_idx] == 1, left_in, inf[fc[s_idx], fm[s_idx] % Cf]))
            inb = inb.at[bc[s_idx], bm[s_idx] % Cb].set(
                jnp.where(bv[s_idx] == 1, right_in, inb[bc[s_idx], bm[s_idx] % Cb]))

            op = op_r[s_idx]
            m = m_r[s_idx]
            c = c_r[s_idx]
            g = c * S + s_idx
            p_c = jax.tree_util.tree_map(lambda a: a[c], p_local)

            def idle_fn(_):
                return stash, gstash, gacc, hg, dacts, loss, zero_act, zero_act

            def f_fn(_):
                a_in = jnp.where(g == 0, x_l[m], inf[c, m % Cf])
                stash2 = stash.at[c, m % Cs].set(a_in)
                a_out = block_fn(p_c, a_in).astype(dtype)
                return stash2, gstash, gacc, hg, dacts, loss, a_out, zero_act

            def b_fn(_):
                a_in = stash[c, m % Cs]
                g_in = inb[c, m % Cb]
                _, vjp = jax.vjp(block_fn, p_c, a_in)
                dp, da = vjp(g_in.astype(dtype))
                gacc2 = jax.tree_util.tree_map(
                    lambda acc, d: acc.at[c].add(d), gacc, dp)
                dacts2 = dacts.at[m].add(jnp.where(g == 0, da, jnp.zeros_like(da)))
                return (stash, gstash, gacc2, hg, dacts2, loss, zero_act,
                        da.astype(dtype))

            def blast_fn(_):
                a_in = stash[c, m % Cs]

                def fwd_loss(p_, hp_, a_):
                    return head_loss_fn(hp_, block_fn(p_, a_), y_l[m])

                loss_m, vjp = jax.vjp(fwd_loss, p_c, hp, a_in)
                # seed 1/M: the step's loss is the mean over microbatches
                dp, dhp, da = vjp(jnp.full_like(loss_m, 1.0 / M))
                gacc2 = jax.tree_util.tree_map(
                    lambda acc, d: acc.at[c].add(d), gacc, dp)
                hg2 = jax.tree_util.tree_map(jnp.add, hg, dhp)
                dacts2 = dacts.at[m].add(jnp.where(g == 0, da, jnp.zeros_like(da)))
                return (stash, gacc2, hg2, dacts2,
                        loss + loss_m.astype(jnp.float32), zero_act,
                        da.astype(dtype))

            def blast_wrap(_):
                st, gacc2, hg2, dacts2, loss2, up, down = blast_fn(_)
                return st, gstash, gacc2, hg2, dacts2, loss2, up, down

            # --- zero-bubble split ops (ZBH1): BX = input grad only (the
            # critical path; parks the cotangent for BW), BW = weight grad
            # only (fills bubbles). Each re-linearizes the block (remat).
            def bx_fn(_):
                a_in = stash[c, m % Cs]
                g_in = inb[c, m % Cb]
                _, vjp = jax.vjp(lambda a_: block_fn(p_c, a_), a_in)
                (da,) = vjp(g_in.astype(dtype))
                gst2 = gstash.at[c, m % Cg].set(g_in)
                dacts2 = dacts.at[m].add(jnp.where(g == 0, da, jnp.zeros_like(da)))
                return (stash, gst2, gacc, hg, dacts2, loss, zero_act,
                        da.astype(dtype))

            def bw_fn(_):
                a_in = stash[c, m % Cs]
                g_in = gstash[c, m % Cg]
                _, vjp = jax.vjp(lambda p_: block_fn(p_, a_in), p_c)
                (dp,) = vjp(g_in.astype(dtype))
                gacc2 = jax.tree_util.tree_map(
                    lambda acc, d: acc.at[c].add(d), gacc, dp)
                return stash, gstash, gacc2, hg, dacts, loss, zero_act, zero_act

            def bxlast_fn(_):
                a_in = stash[c, m % Cs]

                def fwd_loss(a_):
                    return head_loss_fn(hp, block_fn(p_c, a_), y_l[m])

                loss_m, vjp = jax.vjp(fwd_loss, a_in)
                (da,) = vjp(jnp.full_like(loss_m, 1.0 / M))
                dacts2 = dacts.at[m].add(jnp.where(g == 0, da, jnp.zeros_like(da)))
                return (stash, gstash, gacc, hg, dacts2,
                        loss + loss_m.astype(jnp.float32), zero_act,
                        da.astype(dtype))

            def bwlast_fn(_):
                a_in = stash[c, m % Cs]

                def fwd_loss(p_, hp_):
                    return head_loss_fn(hp_, block_fn(p_, a_in), y_l[m])

                loss_m, vjp = jax.vjp(fwd_loss, p_c, hp)
                dp, dhp = vjp(jnp.full_like(loss_m, 1.0 / M))
                gacc2 = jax.tree_util.tree_map(
                    lambda acc, d: acc.at[c].add(d), gacc, dp)
                hg2 = jax.tree_util.tree_map(jnp.add, hg, dhp)
                return stash, gstash, gacc2, hg2, dacts, loss, zero_act, zero_act

            branches = {OP_IDLE: idle_fn, OP_F: f_fn, OP_B: b_fn,
                        OP_B_LAST: blast_wrap, OP_BX: bx_fn, OP_BW: bw_fn,
                        OP_BX_LAST: bxlast_fn, OP_BW_LAST: bwlast_fn}
            # lax.switch traces every branch it is given: substitute idle
            # for opcodes this schedule never emits (a zbh1 table carries no
            # fused B, a 1f1b table no split ops — each saves compiling two
            # full block linearizations per chunk)
            present = set(int(o) for o in np.unique(sched.ops))
            branch_list = [branches[i] if i in present or i == OP_IDLE
                           else idle_fn
                           for i in range(max(present) + 1)]
            (stash, gstash, gacc, hg, dacts, loss, up_out,
             down_out) = jax.lax.switch(op, branch_list, None)
            left_next = jax.lax.ppermute(up_out, pp_axis, up_perm)
            right_next = jax.lax.ppermute(down_out, pp_axis, down_perm)
            return (stash, gstash, inf, inb, gacc, hg, dacts, loss,
                    left_next, right_next), None

        carry0 = (
            jnp.zeros((V, Cs) + a_shape, dtype),
            jnp.zeros((V, Cg) + a_shape, dtype),
            jnp.zeros((V, Cf) + a_shape, dtype),
            jnp.zeros((V, Cb) + a_shape, dtype),
            jax.tree_util.tree_map(jnp.zeros_like, p_local),
            jax.tree_util.tree_map(jnp.zeros_like, hp),
            jnp.zeros((M,) + a_shape, dtype),
            jnp.zeros((), jnp.float32),
            zero_act, zero_act,
        )
        xs = (ops_t, mbs_t, chs_t) + arr
        carry, _ = jax.lax.scan(slot, carry0, xs)
        _, _, _, _, gacc, hg, dacts, loss, _, _ = carry

        loss = jax.lax.psum(loss, pp_axis) / M
        hg = jax.tree_util.tree_map(lambda a: jax.lax.psum(a, pp_axis), hg)
        dacts = jax.lax.psum(dacts, pp_axis)
        if seq_axis is not None:
            # sp shards hold disjoint tokens of the SAME batch rows: weight
            # grads are partial sums over local tokens
            gacc = jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, seq_axis), gacc)
            hg = jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, seq_axis), hg)
        if data_axes:
            loss = jax.lax.pmean(loss, data_axes)

            def reduce_grad(g, spec):
                # a leaf sharded over a data axis (fsdp) arrives already
                # SUMMED over that axis by the vjp of the block's all_gather
                # (psum_scatter); mean-reduce only over the others and
                # rescale the already-summed ones to a mean
                inside = tuple(a for a in data_axes if a in _spec_axes(spec))
                outside = tuple(a for a in data_axes if a not in _spec_axes(spec))
                if outside:
                    g = jax.lax.pmean(g, outside)
                for a in inside:
                    g = g / mesh.shape[a]
                return g

            gacc = jax.tree_util.tree_map(reduce_grad, gacc, stage_specs_tree)
            hg = jax.tree_util.tree_map(reduce_grad, hg, head_specs_tree)
            # dacts is per-example: local-loss cotangent / D == global-mean
            # cotangent, so a plain jax.vjp(embed)(dacts) outside needs no
            # further reduction
            for a in data_axes:
                dacts = dacts / mesh.shape[a]
        # re-insert the stage dim for the [V, S, ...] out spec
        gacc = jax.tree_util.tree_map(lambda a: a[:, None], gacc)
        return loss, gacc, hg, dacts

    ndim_rest = acts.ndim - 1
    p_specs = _merge_specs(stacked_params, stage_specs_tree, (None, pp_axis))
    h_specs = _merge_specs(head_params, head_specs_tree, ())
    batch_dim = data_axes if data_axes else None
    if seq_axis is not None and ndim_rest < 2:
        raise ValueError(
            f"seq_axis={seq_axis!r} needs activations [B, seq, ...]; got "
            f"rank {acts.ndim}")
    seq_rest = [seq_axis] + [None] * (ndim_rest - 2) if ndim_rest >= 2 else []
    x_spec = P(None, batch_dim, *(seq_rest if seq_axis is not None
                                  else [None] * (ndim_rest - 1)))
    y_spec = P(None, batch_dim, *([seq_axis] + [None] * (labels.ndim - 2)
                                  if seq_axis is not None and labels.ndim >= 2
                                  else [None] * (labels.ndim - 1)))

    loss, gacc, hg, dacts = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(p_specs, h_specs, x_spec, y_spec),
        out_specs=(P(), p_specs, h_specs, x_spec),
        check_vma=False,
    )(stacked_params, head_params, x_mb, y_mb)

    if added_v:
        gacc = jax.tree_util.tree_map(lambda a: a[0], gacc)
    return loss, gacc, hg, dacts.reshape(B, *acts.shape[1:])


def spmd_pipeline(stacked_params, acts, block_fn: Callable, mesh: Mesh,
                  n_microbatches: int, pp_axis: str = "pp",
                  data_axis=None):
    """Run ``block_fn(stage_params, activations)`` through S pipeline stages.

    Args:
        stacked_params: pytree, each leaf [S, ...] (stage-major; shard dim 0
            over ``pp_axis``). Inside the loop each stage sees its own slice.
        acts: [B, ...] activations entering stage 0 (post-embedding).
        block_fn: (params_one_stage, acts_mb) -> acts_mb; the per-stage program.
        n_microbatches: M; B must divide by M.
        data_axis: optional mesh axis name the batch dim is sharded over (DP
            composed with PP).
    Returns [B, ...] activations leaving the last stage (replicated over pp).
    """
    S = mesh.shape[pp_axis]
    M = int(n_microbatches)
    B = acts.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    x_mb = acts.reshape(M, mb, *acts.shape[1:])
    pad = jnp.zeros((S - 1, mb) + tuple(acts.shape[1:]), acts.dtype)
    xs = jnp.concatenate([x_mb, pad], axis=0)  # [M+S-1, mb, ...]

    perm = [(i, (i + 1) % S) for i in range(S)]

    def per_stage(params, xs_local):
        stage = jax.lax.axis_index(pp_axis)
        p_local = jax.tree_util.tree_map(lambda a: a[0], params)

        out_aval = jax.eval_shape(block_fn, p_local, xs_local[0])
        if out_aval.shape != xs_local[0].shape:
            raise ValueError(
                f"pipeline block must preserve activation shape, got "
                f"{xs_local[0].shape} -> {out_aval.shape}")

        def step(state, xt):
            inj = jnp.where(stage == 0, xt.astype(out_aval.dtype), state)
            out = block_fn(p_local, inj).astype(out_aval.dtype)
            nxt = jax.lax.ppermute(out, pp_axis, perm)
            return nxt, out

        state0 = jnp.zeros(out_aval.shape, out_aval.dtype)
        _, ys = jax.lax.scan(step, state0, xs_local)
        # stage S-1 finishes microbatch m at loop step m+S-1
        outs = ys[S - 1:]
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, pp_axis)  # replicate result over pp

    ndim_rest = acts.ndim - 1
    p_specs = jax.tree_util.tree_map(lambda _: P(pp_axis), stacked_params)
    x_spec = P(None, data_axis, *([None] * (ndim_rest - 1)))

    out = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stacked_params, xs)
    return out.reshape(B, *acts.shape[1:])
