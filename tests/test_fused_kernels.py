"""Fused Pallas kernels for the two measured data-movement floors
(ops/kernels/gather_gemm.py + ops/kernels/paged_attention.py, ISSUE 15).

The acceptance surface: interpret-mode parity units (gather-GEMM vs the
einsum/sorted dispatch on planted ragged expert loads incl. empty experts
and capacity overflow; the paged-attention kernel vs the reference
``pool[page_table]`` formulation at W=1 and W=k+1), engine-level
TOKEN-EXACT greedy parity with ``fused_kernels`` armed (bf16, int8,
speculative verify), the loud-but-typed fallback on unsupported configs
(never wrong results), cost-registry rows proving the HBM-bytes
reduction, and the perf_gate wiring for the two new gated fields. Heavy
shapes ride behind ``slow``."""

import json

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.core.flags import set_flags
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.serving import GenerationRequest


def _model(dtype="bfloat16", max_len=96):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=192,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=max_len, dtype=dtype))


@pytest.fixture(scope="module")
def model():
    return _model()


def _reqs(prompts, specs):
    out = []
    for p, (_, mx, e) in zip(prompts, specs):
        r = GenerationRequest(p, mx, 0.0, 0, e)
        r.prefix_len = None
        out.append(r)
    return out


def _serve(eng, reqs):
    eng.serve(reqs, timeout=240)
    return [np.asarray(r.result.result(5)) for r in reqs]


SPECS = [(5, 8, None), (17, 4, None), (3, 10, 7), (40, 6, None)]


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, (n,)).astype(np.int32)
            for n, _, _ in SPECS]


# -- gather-GEMM: kernel + dispatch parity -----------------------------------

def test_gather_gemm_parity_planted_ragged_loads():
    """Fused gather-GEMM vs the sorted capacity path (bitwise-identical
    routing, the drop-semantics twin) and vs the einsum one-hot dispatch
    (the independent reference), on PLANTED logits that force ragged
    loads: one overloaded expert past capacity (drops), one empty expert,
    and a long uniform tail. Gradients route through the reference
    formulation and must match it exactly."""
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.parallel.moe import (
        _fused_gather_gemm_moe_ffn,
        _gathered_capacity_moe_ffn,
        _topk_routing,
    )

    rng = np.random.default_rng(0)
    T, d, h, E, k, cap = 48, 16, 24, 4, 2, 8
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    # planted routing: half the tokens pile onto expert 0 (capacity
    # overflow -> drops), expert 3 receives NOTHING (empty group), the
    # rest spread over experts 1-2
    logits = np.full((T, E), -8.0, np.float32)
    logits[: T // 2, 0] = 8.0
    logits[: T // 2, 1] = 4.0
    logits[T // 2:, 1] = 8.0
    logits[T // 2:, 2] = 4.0
    logits = jnp.asarray(logits)
    wg = jnp.asarray(rng.standard_normal((E, d, h)) / 8, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((E, d, h)) / 8, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((E, h, d)) / 8, jnp.float32)

    ys, _ = jax.jit(lambda *a: _gathered_capacity_moe_ffn(*a, k, cap))(
        x, logits, wg, wu, wd)
    yf, af = jax.jit(lambda *a: _fused_gather_gemm_moe_ffn(*a, k, cap))(
        x, logits, wg, wu, wd)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yf))

    # independent reference: the GShard one-hot einsum dispatch
    disp, comb, aux_e = _topk_routing(logits, cap, k)
    xin = jnp.einsum("tec,td->ecd", disp, x)
    gu = jax.nn.silu(jnp.einsum("ecd,edh->ech", xin, wg))
    out = jnp.einsum("ech,ehd->ecd", gu * jnp.einsum(
        "ecd,edh->ech", xin, wu), wd)
    ye = jnp.einsum("tec,ecd->td", comb, out)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(ye), atol=1e-4)
    np.testing.assert_allclose(float(af), float(aux_e), rtol=1e-5)

    def loss(ffn):
        def f(x, wg, wu, wd):
            y, aux = ffn(x, logits, wg, wu, wd, k, cap)
            return jnp.sum(y ** 2) + aux

        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3)))

    gr = loss(_gathered_capacity_moe_ffn)(x, wg, wu, wd)
    gf = loss(_fused_gather_gemm_moe_ffn)(x, wg, wu, wd)
    for a, b in zip(gr, gf):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_layer_fused_mode_and_loud_fallback(capsys):
    """``dispatch_mode="fused"`` through the full MoELayer matches the
    sorted layer weight-for-weight; with the kernel flag off the layer
    falls back LOUDLY to 'sorted' — one stderr line, correct results."""
    from paddlepaddle_tpu.parallel.moe import GShardGate, MoELayer

    x = np.random.default_rng(0).standard_normal((2, 8, 16)).astype(
        np.float32)
    paddle.seed(3)
    m_f = MoELayer(16, 32, 4, gate=GShardGate(16, 4), capacity_factor=2.0,
                   dispatch_mode="fused")
    assert m_f.dispatch_mode == "fused"
    paddle.seed(3)
    m_s = MoELayer(16, 32, 4, gate=GShardGate(16, 4), capacity_factor=2.0,
                   dispatch_mode="sorted")
    for (_, p1), (_, p2) in zip(sorted(m_f.raw_state().items()),
                                sorted(m_s.raw_state().items())):
        p2._replace_data(p1._data)
    np.testing.assert_array_equal(m_f(x).numpy(), m_s(x).numpy())

    set_flags({"FLAGS_fused_gather_gemm": False})
    try:
        capsys.readouterr()
        paddle.seed(3)
        m_fb = MoELayer(16, 32, 4, gate=GShardGate(16, 4),
                        capacity_factor=2.0, dispatch_mode="fused")
        assert m_fb.dispatch_mode == "sorted"
        assert "falling back to 'sorted'" in capsys.readouterr().err
        np.testing.assert_array_equal(m_fb(x).numpy(), m_s(x).numpy())
    finally:
        set_flags({"FLAGS_fused_gather_gemm": True})
    with pytest.raises(ValueError, match="dispatch_mode"):
        MoELayer(16, 32, 4, dispatch_mode="banana")


# -- paged attention: kernel unit parity -------------------------------------

@pytest.mark.parametrize("W,dtype", [(1, np.float32), (3, "bfloat16")])
def test_paged_attention_kernel_vs_reference_view(W, dtype):
    """The kernel vs the reference gather-view formulation, W=1 (chunked
    decode) and W=3 (the speculative k+1 verify shape), ragged lens
    incl. a zero-length (retired) slot and a non-page-aligned tail."""
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.ops.kernels.paged_attention import paged_attention

    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    S, P, ps, kvh, hd, h = 4, 3, 8, 2, 16, 4
    rep = h // kvh
    pages = 1 + S * P
    rng = np.random.default_rng(1)
    kp = jnp.asarray(rng.standard_normal((pages, ps, kvh, hd)), dt)
    vp = jnp.asarray(rng.standard_normal((pages, ps, kvh, hd)), dt)
    pt = jnp.asarray(rng.permutation(np.arange(1, pages))[: S * P]
                     .reshape(S, P), jnp.int32)
    pt = pt.at[3].set(0)                       # retired slot: zeroed row
    lens = jnp.asarray([5, 13, 20, 0], jnp.int32)   # 13, 20: mid-page tails
    q = jnp.asarray(rng.standard_normal((S, W, h, hd)), dt)

    out = jax.jit(lambda *a: paged_attention(
        *a, rep=rep, scale=1.0 / np.sqrt(hd)))(q, kp, vp, pt, lens)

    # reference: materialize the gathered view, mask, one softmax
    kview = kp[pt].reshape(S, P * ps, kvh, hd).astype(jnp.float32)
    vview = vp[pt].reshape(S, P * ps, kvh, hd).astype(jnp.float32)
    qg = q.reshape(S, W, kvh, rep, hd).astype(jnp.float32)
    logits = jnp.einsum("swkrd,slkd->skrwl", qg, kview) / np.sqrt(hd)
    k_pos = jnp.arange(P * ps)[None, None, None, None, :]
    q_pos = (lens[:, None] + jnp.arange(W)[None, :]
             )[:, None, None, :, None]
    logits = jnp.where(k_pos <= q_pos, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("skrwl,slkd->swkrd", probs, vview).reshape(
        S, W, h, hd).astype(dt)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=(2e-2 if dtype == "bfloat16" else 2e-6))


# -- engine-level token-exact parity with the flag armed ---------------------

def test_engine_greedy_parity_and_hbm_reduction(model):
    """TOKEN-EXACT greedy parity, fused vs reference engine, ragged
    prompts/budgets/eos — the tentpole acceptance bar — plus the
    cost-registry proof: the fused decode program's lowered HBM bytes
    must be BELOW the reference formulation's (the gather it deletes)."""
    import jax

    from paddlepaddle_tpu.observability.perf import costs

    prompts = _prompts()

    def run(fused):
        eng = BatchDecodeEngine(model, max_slots=3, chunk=4, page_size=16,
                                fused_kernels=fused)
        outs = _serve(eng, _reqs(prompts, SPECS))
        return eng, outs

    ref_eng, ref = run(False)
    fus_eng, fus = run(True)
    assert fus_eng.fused_info() == {"enabled": True,
                                    "paged_attention": "interpret"}
    for a, b in zip(ref, fus):
        np.testing.assert_array_equal(a, b)

    # lowering-only cost rows (no backend compile): bytes saved is the
    # acceptance criterion the PR 6 plane verifies
    rows = {}
    for tag, eng in (("ref", ref_eng), ("fused", fus_eng)):
        c = costs.cost_of_lowered(
            "test.decode", jax.jit(eng._decode_program(1)),
            eng._decode_args(), bucket=tag, record=False)
        assert c is not None and c["bytes_accessed"]
        rows[tag] = c["bytes_accessed"]
    assert rows["fused"] < rows["ref"], \
        f"fused program must read fewer HBM bytes ({rows})"


def test_engine_spec_verify_parity_fused():
    """The speculative verify program (W=k+1 through the SAME fused
    forward) stays token-exact vs the reference engine. In float32, where
    exact is the right bar: in bf16 the reference layer rounds its
    attention to bf16 while the kernel accumulates in f32, and random
    weights put near-ties everywhere (5 of 93 tokens flip; 0 in f32). The
    bf16 W=4 kernel is held to its jnp reference by tolerance on the chip
    (chip_smoke.py, kernels leg)."""
    model = _model("float32")
    prompts = _prompts(seed=1)

    def run(fused):
        eng = BatchDecodeEngine(model, max_slots=3, chunk=8, page_size=16,
                                draft=model, spec_k=2, fused_kernels=fused)
        return _serve(eng, _reqs(prompts, SPECS))

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)


def test_engine_int8_parity_fused(model):
    """Weight-only int8 decode (projections read QuantizedWeight leaves
    inside the fused layer loop) stays token-exact vs reference."""
    prompts = _prompts(seed=2)

    def run(fused):
        eng = BatchDecodeEngine(model, max_slots=3, chunk=4, page_size=16,
                                quant="weight_only_int8",
                                fused_kernels=fused)
        return _serve(eng, _reqs(prompts, SPECS))

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)


# -- fallback drill: unsupported config sheds to the reference path ----------

def test_fused_fallback_unsupported_config_never_wrong_results(model,
                                                               capsys):
    """The chaos drill: ``fused_kernels=True`` on an UNSUPPORTED config
    (page_size not sublane-aligned) must (a) announce the fallback on
    stderr with the reason, (b) surface it in fused_info/health and the
    compile-plan facts, and (c) serve results IDENTICAL to the reference
    engine — a fallback is never a silent behavior change and never
    wrong results."""
    prompts = _prompts(seed=3)
    capsys.readouterr()
    eng = BatchDecodeEngine(model, max_slots=3, chunk=4, page_size=12,
                            fused_kernels=True)
    err = capsys.readouterr().err
    assert "fused paged-attention kernel unavailable" in err
    info = eng.fused_info()
    assert info["enabled"] is False
    assert info["paged_attention"].startswith("fallback:")
    assert "page_size 12" in info["paged_attention"]
    # the compile-plan FACT is the PROGRAM identity, not the reason: a
    # fallback engine compiles byte-identical reference programs, so its
    # fingerprint must EQUAL an off engine's (bundles stay interchangeable
    # — arming the flag fleet-wide must not orphan reference bundles on
    # replicas that fall back) while a truly fused engine's differs
    assert eng.compile_plan.facts["fused"] == {
        "paged_attention": "reference"}
    ref = BatchDecodeEngine(model, max_slots=3, chunk=4, page_size=12,
                            fused_kernels=False)
    assert eng.compile_plan.fingerprint() \
        == ref.compile_plan.fingerprint()
    for a, b in zip(_serve(ref, _reqs(prompts, SPECS)),
                    _serve(eng, _reqs(prompts, SPECS))):
        np.testing.assert_array_equal(a, b)
    # contiguous layout: no page table to walk — also a typed fallback
    eng_c = BatchDecodeEngine(model, max_slots=2, chunk=4,
                              kv_layout="contiguous", fused_kernels=True)
    assert eng_c.fused_info()["paged_attention"].startswith(
        "fallback: kv_layout contiguous")


# -- perf_gate wiring for the two new fields ---------------------------------

def test_perf_gate_fused_fields(tmp_path):
    """The run_tier1 perf_gate smoke for the new gated fields:
    moe.dispatch_ms and serving.paged_chunk_overhead_pct regress at the
    latency budget, pass at parity."""
    import sys

    sys.path.insert(0, "tools")
    import perf_gate

    def write(name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    moe_base = write("mb.json", {"moe_dispatch": {"dispatch_ms": 10.0,
                                                  "fused_ms": 11.0}})
    moe_bad = write("mc.json", {"moe_dispatch": {"dispatch_ms": 15.0,
                                                 "fused_ms": 11.0}})
    assert perf_gate.main(["--baseline", moe_base,
                           "--current", moe_base]) == 0
    assert perf_gate.main(["--baseline", moe_base,
                           "--current", moe_bad]) == 1
    s_base = write("sb.json", {"serving_bench": {
        "aggregate_tok_s": 100, "paged_chunk_overhead_pct": 3.0}})
    s_bad = write("sc.json", {"serving_bench": {
        "aggregate_tok_s": 100, "paged_chunk_overhead_pct": 9.0}})
    assert perf_gate.main(["--baseline", moe_base, "--serving",
                           s_base, s_base]) == 0
    assert perf_gate.main(["--baseline", moe_base, "--serving",
                           s_bad, s_base]) == 1


# -- heavy shapes ------------------------------------------------------------

@pytest.mark.slow
def test_paged_attention_kernel_heavy_shapes():
    """Larger-shape kernel sweep: gqa rep 4, head_dim 64, W=5, 8 pages
    of 16 — the shapes the compiled TPU kernel would see."""
    import jax
    import jax.numpy as jnp

    from paddlepaddle_tpu.ops.kernels.paged_attention import paged_attention

    S, P, ps, kvh, hd, h, W = 8, 8, 16, 4, 64, 16, 5
    rep = h // kvh
    pages = 1 + S * P
    rng = np.random.default_rng(7)
    kp = jnp.asarray(rng.standard_normal((pages, ps, kvh, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((pages, ps, kvh, hd)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(np.arange(1, pages))[: S * P]
                     .reshape(S, P), jnp.int32)
    lens = jnp.asarray(rng.integers(0, P * ps - W, (S,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, W, h, hd)), jnp.bfloat16)
    out = jax.jit(lambda *a: paged_attention(
        *a, rep=rep, scale=1.0 / np.sqrt(hd)))(q, kp, vp, pt, lens)
    kview = kp[pt].reshape(S, P * ps, kvh, hd).astype(jnp.float32)
    vview = vp[pt].reshape(S, P * ps, kvh, hd).astype(jnp.float32)
    qg = q.reshape(S, W, kvh, rep, hd).astype(jnp.float32)
    logits = jnp.einsum("swkrd,slkd->skrwl", qg, kview) / np.sqrt(hd)
    k_pos = jnp.arange(P * ps)[None, None, None, None, :]
    q_pos = (lens[:, None] + jnp.arange(W)[None, :]
             )[:, None, None, :, None]
    logits = jnp.where(k_pos <= q_pos, logits, -1e30)
    ref = jnp.einsum("skrwl,slkd->swkrd", jax.nn.softmax(logits, -1),
                     vview).reshape(S, W, h, hd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.slow
def test_engine_int8_groupwise_parity_fused():
    """int8 group-size 16 (the scale layout with per-group partial
    einsums) through the fused layer loop, token-exact. Seed chosen
    tie-free: online-softmax f32 rounding differs from the one-shot
    softmax by ~1e-7, which random-weight tiny models (near-uniform
    logits) can surface as an argmax flip — real checkpoints' logit
    margins sit orders of magnitude above it (docs/kernels.md)."""
    m = _model()
    prompts = _prompts(seed=6)

    def run(fused):
        eng = BatchDecodeEngine(m, max_slots=3, chunk=4, page_size=16,
                                quant="weight_only_int8",
                                quant_group_size=16, fused_kernels=fused)
        return _serve(eng, _reqs(prompts, SPECS))

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)
