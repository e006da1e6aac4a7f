"""Speculative decoding — fixed-k draft+verify programs that multiply
tokens per target weight-read.

Decode on the serving card is weight-bandwidth-bound (BASELINE.md pins
~254 MB of bf16 weight reads per token-step against a 650-700 GB/s
effective HBM roofline). Weight-only int8 (docs/quantization.md) halves
that traffic; speculative decoding (Leviathan et al. 2023; Chen et al.
2023) attacks the same roofline from the other side: a small DRAFT model
proposes ``k`` greedy tokens per slot, the TARGET model verifies all
``k+1`` positions in ONE batched forward, and every accepted token
amortizes the target's weight read. At acceptance rate ``a`` a target
step yields ``1 + a*k`` tokens instead of 1.

Static-shape JAX form, three fixed-shape programs per engine config — all
first-class :mod:`~.compile_plan` entries, so they ride warmup, the
persistent compile cache, AOT bundles, and the recompile watchdog's
planned-region exemptions exactly like the decode program:

* ``draft_admit_p<bucket>`` — prefill the prompt through the draft model
  into its slot-contiguous KV cache at admission (the draft always
  prefills the FULL prompt, even on a target prefix-cache hit — the
  draft keeps no prefix cache of its own).
* ``draft_k<K>`` — K greedy draft steps over all slots. The FIRST step
  feeds a fixed 2-token window ``[prev, tokens]`` at positions
  ``lens-1, lens``: after a fully-accepted round the draft cache is
  exactly one position behind the committed stream, and re-writing an
  already-written position produces identical K/V — so one static shape
  repairs every possible deficit.
* ``verify_k<K>`` — ONE target forward over the ``k+1`` tokens
  ``[tokens, d_1..d_k]`` at positions ``lens..lens+k`` (the model's
  ragged cached-attention path handles multi-token steps at per-slot
  positions natively), then accept/reject as masked ops in-graph:
  greedy acceptance ``d_{j+1} == argmax(logits_j)`` on the longest
  matching prefix, plus the target's own token at the first mismatch
  (the "bonus"/correction token) — token-EXACT vs the non-speculative
  engine by construction, for ANY draft model. Sampling-correctness
  (rejection resampling at temperature > 0) is a follow-up seam; the
  engine rejects non-greedy requests at admission.

KV ROLLBACK IS AN INDEX EDIT: the verify forward writes K/V for all
``k+1`` positions, but ``lens`` only advances by the tokens actually
emitted — rejected positions sit beyond the new length, masked out of
every later gather by the ragged causal mask, and are overwritten in
place when decode reaches them. Page-table indirection makes this free:
positions past the slot's reservation land in the null page, positions
past ``max_len`` are explicitly redirected there, and no page is copied
or moved to roll back. The draft cache rolls back the same way (its
writes are position-indexed by the shared ``lens``).

The draft model is itself servable weight-only int8 (``draft_quant``) —
the draft's weight reads are the speculation overhead, so halving them
compounds with the amortization. Draft facts (arch, quant, k) join the
compile-plan fingerprint: a bundle built with one draft can never be
silently served with another.
"""

from __future__ import annotations

import weakref
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd as _ag
from ..core.dispatch import unwrap
from .decode_engine import _head

__all__ = ["SpeculativeDecoder", "resolve_draft"]


def _model_forward(model, params, toks, caches, pos):
    """One forward of ``model`` (target or draft): toks [b, s] ->
    (logits [b, s, V], caches') — the draft-parameterized twin of
    ``BatchDecodeEngine._forward``."""
    with _ag.no_grad(), model.bind_state(params):
        hidden, new_caches = model.model(toks, caches=caches, pos=pos)
        logits = _head(model, hidden)
    return logits, [(unwrap(k), unwrap(v)) for k, v in new_caches]


def resolve_draft(draft, target_cfg, max_len: int, spec_k: int):
    """Normalize the ``draft=`` argument into a live model.

    Accepts a ``LlamaConfig``-shaped config (a draft model is built from
    it, with ``max_position_embeddings`` widened to cover the engine's
    ``max_len + k`` rope positions) or a ready model instance (anything
    exposing ``.config``, ``.model(...)`` and ``.functional_state()``).
    Validates the two facts speculation cannot survive without: a shared
    vocabulary (proposals are target token ids) and rope tables long
    enough for every verify position."""
    import dataclasses

    if hasattr(draft, "functional_state") and hasattr(draft, "config"):
        model = draft
    elif hasattr(draft, "vocab_size"):
        from ..models import LlamaForCausalLM

        cfg = draft
        need = max_len + spec_k
        if cfg.max_position_embeddings < need:
            cfg = dataclasses.replace(cfg, max_position_embeddings=need)
        model = LlamaForCausalLM(cfg)
    else:
        raise ValueError(
            f"draft must be a model config or a LlamaForCausalLM-shaped "
            f"model, got {type(draft).__name__}")
    dcfg = model.config
    if dcfg.vocab_size != target_cfg.vocab_size:
        raise ValueError(
            f"draft vocab_size {dcfg.vocab_size} != target "
            f"{target_cfg.vocab_size} — speculative proposals are target "
            "token ids, the vocabularies must be identical")
    if dcfg.max_position_embeddings < max_len:
        raise ValueError(
            f"draft max_position_embeddings {dcfg.max_position_embeddings} "
            f"< engine max_len {max_len} — the draft must cover every "
            "position it proposes at")
    return model


class SpeculativeDecoder:
    """Draft-model state + the three program implementations, owned by a
    :class:`~.decode_engine.BatchDecodeEngine` with ``spec_k > 0``.

    Host-side accounting (``stats``/``runlen``) is engine-thread-only,
    updated once per spec chunk (never per token); ``info()`` is the
    ``health()["spec"]`` block and is safe to read from probe threads."""

    def __init__(self, engine, draft, spec_k: int,
                 draft_quant: Optional[str] = None):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if engine.kv_layout != "paged":
            raise ValueError(
                "speculative decoding requires kv_layout='paged' — the "
                "page-table indirection IS the KV rollback mechanism")
        self.engine_ref = weakref.ref(engine)
        self.k = int(spec_k)
        self.draft_model = resolve_draft(draft, engine.cfg, engine.L,
                                         self.k)
        dcfg = self.draft_model.config
        self.draft_cfg = dcfg
        self.draft_quant = draft_quant
        self.draft_params = self.draft_model.functional_state()
        self.draft_quant_meta: Dict[str, object] = {}
        if draft_quant is not None:
            from ..nn.quant import quantize_param_tree

            self.draft_params, self.draft_quant_meta = quantize_param_tree(
                self.draft_params, algo=draft_quant)
        if engine.plan is not None:
            # the draft is small by construction: replicate it (params and
            # KV) rather than teaching the sharding plan a second head
            # count — the target's ICI collectives are untouched
            self.draft_params = jax.tree_util.tree_map(
                engine.plan.replicate, self.draft_params)
        dtype = (jnp.bfloat16 if dcfg.dtype == "bfloat16" else jnp.float32)
        S, L = engine.S, engine.L
        kvh, hd = dcfg.num_key_value_heads, dcfg.head_dim
        # slot-contiguous draft KV: the draft is small, so the paged
        # layout's byte savings don't pay for a second page table
        self.draft_caches = [
            (engine._repl(jnp.zeros((S, L, kvh, hd), dtype)),
             engine._repl(jnp.zeros((S, L, kvh, hd), dtype)))
            for _ in range(dcfg.num_hidden_layers)]
        # token at position lens-1 of the committed stream (the draft
        # catch-up window's first element); engine.tokens is the second
        self.prev_tokens = engine._repl(jnp.zeros((S,), jnp.int32))
        self.stats = {"target_steps": 0, "proposed": 0, "accepted": 0,
                      "rollbacks": 0, "emitted": 0}
        self.runlen = [0] * (self.k + 1)   # accepted-run-length histogram
        try:
            from ..observability import flight

            ref = weakref.ref(self)

            def _spec_annotation():
                s = ref()
                return s.info() if s is not None else {"enabled": "released"}

            flight.annotate("serving_spec", _spec_annotation)
        except Exception:
            pass

    # -- facts ---------------------------------------------------------------
    def facts(self) -> Dict[str, object]:
        """The compile-plan fingerprint's spec block: everything that makes
        draft/verify programs exchangeable. A draft-model swap (arch OR
        quant) changes the fingerprint, so a stale bundle falls back
        loudly instead of serving another draft's executables."""
        dcfg = self.draft_cfg
        arch = {k: v for k, v in sorted(vars(dcfg).items())
                if isinstance(v, (int, float, str, bool, type(None)))}
        return {"k": self.k, "draft_model": arch,
                "draft_quant": self.draft_quant or "off"}

    def describe_draft(self) -> Dict[str, object]:
        dcfg = self.draft_cfg
        return {
            "hidden_size": dcfg.hidden_size,
            "num_hidden_layers": dcfg.num_hidden_layers,
            "num_attention_heads": dcfg.num_attention_heads,
            "vocab_size": dcfg.vocab_size,
            "params_m": round(dcfg.num_params() / 1e6, 2),
            "quant": self.draft_quant or "off",
        }

    def info(self) -> Dict[str, object]:
        """``health()["spec"]``: config + live acceptance."""
        st = self.stats
        steps = st["target_steps"]
        return {
            "enabled": True,
            "k": self.k,
            "draft": self.describe_draft(),
            "target_steps": steps,
            "proposed": st["proposed"],
            "accepted": st["accepted"],
            "rollbacks": st["rollbacks"],
            "acceptance_rate": (round(st["accepted"] / st["proposed"], 4)
                                if st["proposed"] else None),
            "tokens_per_target_step": (round(st["emitted"] / steps, 3)
                                       if steps else None),
            "accept_run_p50": self.runlen_pct(0.50),
            "accept_run_p99": self.runlen_pct(0.99),
        }

    def runlen_pct(self, q: float) -> Optional[int]:
        """Percentile of the accepted-run-length histogram (0..k)."""
        total = sum(self.runlen)
        if not total:
            return None
        target = q * (total - 1) + 1
        seen = 0
        for length, n in enumerate(self.runlen):
            seen += n
            if seen >= target:
                return length
        return self.k

    # -- program implementations --------------------------------------------
    def draft_admit_impl(self, dparams, dcaches, prev, ids, plen, slot):
        """Prefill ``ids[1, bucket]`` through the draft model and scatter
        the K/V prefix into draft-cache slot ``slot``; record the last
        prompt token as the slot's catch-up ``prev``. The logits are
        discarded — the target's admission already sampled the first
        token, and speculation must propose from the SAME stream."""
        dcfg = self.draft_cfg
        bucket = ids.shape[1]
        kvh, hd = dcfg.num_key_value_heads, dcfg.head_dim
        dtype = dcaches[0][0].dtype
        scratch = [(jnp.zeros((1, bucket, kvh, hd), dtype),
                    jnp.zeros((1, bucket, kvh, hd), dtype))
                   for _ in range(dcfg.num_hidden_layers)]
        _, scratch = _model_forward(self.draft_model, dparams, ids, scratch,
                                    jnp.int32(0))
        zero = jnp.int32(0)
        out = []
        for (kc, vc), (ks, vs) in zip(dcaches, scratch):
            kc = jax.lax.dynamic_update_slice(kc, ks, (slot, zero, zero,
                                                       zero))
            vc = jax.lax.dynamic_update_slice(vc, vs, (slot, zero, zero,
                                                       zero))
            out.append((kc, vc))
        prev = prev.at[slot].set(ids[0, plen - 1])
        return out, prev

    def draft_program(self, k: int):
        """K greedy draft proposals per slot: one 2-token catch-up step
        (``[prev, tokens]`` at ``lens-1, lens``) then ``k-1`` single-token
        steps via ``lax.scan``. Inactive slots' writes land inside their
        own retired cache rows (re-prefilled at the next admission) and
        their proposals are discarded by the verify emit mask."""
        model = self.draft_model

        def run(dparams, dcaches, prev, tokens, lens, active):
            toks0 = jnp.stack([prev, tokens], axis=1)          # [S, 2]
            logits, dcaches = _model_forward(
                model, dparams, toks0, dcaches,
                jnp.maximum(lens - 1, 0))
            cur = jnp.argmax(logits[:, 1].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)

            def body(carry, i):
                caches, tok = carry
                lg, caches = _model_forward(model, dparams, tok[:, None],
                                            caches, lens + i)
                nxt = jnp.argmax(lg[:, 0].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
                return (caches, nxt), nxt

            (dcaches, _), rest = jax.lax.scan(
                body, (dcaches, cur),
                jnp.arange(1, k, dtype=jnp.int32))
            props = jnp.concatenate([cur[:, None], rest.T], axis=1)
            return dcaches, props                              # [S, k]

        return run

    def verify_program(self, k: int):
        """ONE batched target forward over the ``k+1`` positions plus the
        greedy accept/reject as masked in-graph ops.

        Emission semantics are EXACTLY the sequential engine's: a token is
        emitted iff it extends the longest draft/target-greedy matching
        prefix (the bonus token always does), the per-slot budget has room,
        and no earlier token in this run was the slot's eos. ``lens``
        advances by the emitted count — that IS the KV rollback. Returns
        one packed [S, k+4] payload per step (k+1 emitted-token columns,
        -1 padded; the raw accepted-run length, -1 when the slot is
        inactive; the end-of-step active flag; the pages of the K/V view
        the forward gathered) so a chunk of steps syncs to the host as a
        single transfer."""

        def run(params, caches, page_table, lens, tokens, prev, active,
                budgets, eos_ids, proposals):
            eng = self.engine_ref()
            S = eng.S
            rows = jnp.arange(S, dtype=jnp.int32)
            # the k+1-position target forward IS the engine's paged decode
            # forward at W=k+1 — one implementation, so the verify path
            # can never diverge from single-token decode. This includes
            # kv_quant="int8": verify scatters quantized pages and
            # dequantizes in the same kernel (or reference) pass as W=1
            # decode, while the draft keeps its own full-precision
            # contiguous caches above — acceptance compares target
            # greedy tokens, so quantization error shows up as a lower
            # acceptance rate, never as a divergent committed stream
            toks = jnp.concatenate([tokens[:, None], proposals], axis=1)
            rung = eng._view_rung(lens, active, k + 1)
            logits, caches = eng._forward_paged(
                params, toks, caches, page_table, lens, rung)
            g = jnp.argmax(logits.astype(jnp.float32),
                           axis=-1).astype(jnp.int32)           # [S, k+1]
            match = (proposals == g[:, :k]).astype(jnp.int32)
            acc = jnp.cumprod(match, axis=1).astype(jnp.int32)
            # dtype pinned: under x64 an int32 sum promotes to int64 and
            # the carry would stop matching the compiled avals
            a = jnp.sum(acc, axis=1, dtype=jnp.int32)     # accepted 0..k
            bonus = g[rows, a]
            idx = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            prop_ext = jnp.concatenate(
                [proposals, jnp.zeros((S, 1), jnp.int32)], axis=1)
            cand = jnp.where(idx < a[:, None], prop_ext, bonus[:, None])
            eos_hit = ((eos_ids[:, None] >= 0)
                       & (cand == eos_ids[:, None])).astype(jnp.int32)
            prior_eos = jnp.cumsum(eos_hit, axis=1, dtype=jnp.int32) \
                - eos_hit
            emit = (active[:, None] & (idx <= a[:, None])
                    & (idx < budgets[:, None]) & (prior_eos == 0))
            m = jnp.sum(emit, axis=1, dtype=jnp.int32)    # [S] emitted
            emitted = jnp.where(emit, cand, -1)
            # committed stream tail: full[0] = the pre-step last token,
            # full[i+1] = cand_i — so the new last/second-to-last tokens
            # are plain gathers at m and m-1
            full = jnp.concatenate([tokens[:, None], cand], axis=1)
            m_pos = jnp.minimum(m, k + 1)
            tokens_new = jnp.where(m > 0, full[rows, m_pos], tokens)
            prev_new = jnp.where(m > 0,
                                 full[rows, jnp.maximum(m_pos - 1, 0)],
                                 prev)
            lens_new = lens + m
            budgets_new = budgets - m
            active_new = (active & (budgets_new > 0)
                          & ~((eos_ids >= 0) & (tokens_new == eos_ids)))
            a_report = jnp.where(active, a, -1)
            payload = jnp.concatenate(
                [emitted, a_report[:, None],
                 active_new[:, None].astype(jnp.int32),
                 eng._view_pages_column(rung)], axis=1)
            return (caches, lens_new, tokens_new, prev_new, active_new,
                    budgets_new, payload)

        return run

    # -- host-side accounting -------------------------------------------------
    def round_summary(self, acc_row: np.ndarray) -> Dict[str, int]:
        """One slot's spec-chunk attrs for its request journey
        (observability.reqtrace ``spec.round`` span): verify steps run
        this chunk and draft tokens proposed/accepted at this k — defined
        here, next to the payload format that produces ``acc_row``, so
        the trace schema can never drift from the verify program."""
        live = acc_row[acc_row >= 0]
        return {"k": self.k, "steps": int(live.size),
                "proposed": int(live.size) * self.k,
                "accepted": int(live.sum())}

    def record_chunk(self, acc_matrix: np.ndarray, emitted_count: int
                     ) -> None:
        """Fold one spec chunk's accepted-run lengths (``[S, steps]``, -1
        for inactive slot-steps) into stats + metrics — once per chunk,
        the same cold cadence as the engine's KV gauges."""
        from .robustness import safe_inc as _safe_inc

        live = acc_matrix[acc_matrix >= 0]
        if live.size == 0:
            return
        steps = int(live.size)
        accepted = int(live.sum())
        rollbacks = int((live < self.k).sum())
        st = self.stats
        st["target_steps"] += steps
        st["proposed"] += steps * self.k
        st["accepted"] += accepted
        st["rollbacks"] += rollbacks
        st["emitted"] += int(emitted_count)
        counts = np.bincount(live, minlength=self.k + 1)
        for length, n in enumerate(counts[: self.k + 1]):
            if n:
                self.runlen[length] += int(n)
                _safe_inc("paddle_serving_spec_accept_run_length_total",
                          "accepted-run-length histogram of speculative "
                          "verify steps, by run length", int(n),
                          len=str(length))
        _safe_inc("paddle_serving_spec_proposed_total",
                  "draft tokens proposed to the target verifier",
                  steps * self.k)
        _safe_inc("paddle_serving_spec_accepted_total",
                  "draft tokens accepted by the target verifier", accepted)
        if rollbacks:
            _safe_inc("paddle_serving_spec_rollbacks_total",
                      "verify steps that rejected at least one draft "
                      "token (KV rolled back by index rewind)", rollbacks)
