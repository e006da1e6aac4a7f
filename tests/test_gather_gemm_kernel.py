"""The fused gather-GEMM MoE dispatch (ops/kernels/gather_gemm.py), what
``MoELayer(dispatch_mode="fused")`` runs.

The acceptance surface: interpret-mode parity (gather-GEMM vs the
einsum/sorted dispatch on planted ragged expert loads incl. empty experts
and capacity overflow), the layer's loud-but-typed fallback to 'sorted'
where the kernel is switched off (never wrong results), and the perf_gate
wiring of the dispatch shoot-out's gated fields."""

import json

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.core.flags import set_flags


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


# -- perf_gate wiring of the dispatch shoot-out -----------------------------------

def test_perf_gate_moe_dispatch_fields(tmp_path):
    """moe.dispatch_ms (tools/moe_dispatch_bench.py's line) regresses at
    the latency budget and passes at parity."""
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
