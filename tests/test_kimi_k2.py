"""Kimi-K2 through the program (models/kimi_k2.py, the shared latent block of
models/latent_attention.py, the stated routing and the shared expert of
parallel/moe.py, decode_engine.py) at tiny widths in float32, against the plain
reference of benchmark/families/kimi_k2_reference.py on seeded weights and against
formulas written here.

(a) the whole forward equals the reference's; (b) prefill then paged decode through
ServingEngine (plain, a prefix miss, a prefix hit) serves tokens whose reference
logits are the reference's best; (c) the routing alone against a hand-written
sigmoid / bias / renormalise / scale; (d) YaRN's tables against the formula beyond
the original length; (e) the shares add up with the shared expert counted once, in
every formulation of the held experts; (f) the blocked prefill attention equals the
unblocked one at a length no multiple of the block, alone and through an admission;
(g) what a latent row cannot do yet refuses for this model too; (h) the cache spec
and the new counters.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddlepaddle_tpu as paddle
from benchmark.families import kimi_k2_reference as reference
from paddlepaddle_tpu.core import autograd as _ag
from paddlepaddle_tpu.core.dispatch import unwrap
from paddlepaddle_tpu.inference.decode_engine import ADMIT_COUNTERS, BatchDecodeEngine
from paddlepaddle_tpu.inference.kv_pool import PoolSpec, cache_spec_of, spec_bytes_per_token
from paddlepaddle_tpu.inference.serving import ServingEngine
from paddlepaddle_tpu.models import KimiK2Config, KimiK2ForCausalLM, latent_attention
from paddlepaddle_tpu.ops.kernels.latent_prefill_attention import latent_prefill_attention
from paddlepaddle_tpu.parallel import moe


def _model(held=None, seed=0, **kw):
    paddle.seed(seed)
    return KimiK2ForCausalLM(KimiK2Config.tiny(held=held, **kw))


def _weights(model):
    return {n: jnp.asarray(np.asarray(p._data, np.float32)) for n, p in model.named_parameters()}


def _ref_cfg(config):
    """The reference's configuration dict: ``n_routed_experts`` counts what is held, the router's width is published."""
    d = dataclasses.asdict(config)
    d["published"] = {"n_routed_experts": config.n_routed_experts}
    d["n_routed_experts"] = config.held[1]
    return d


def _ids(n, seed=1, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, (n,)).astype(np.int32)


def _forward(m, ids):
    def f(state, ids):
        with _ag.no_grad(), m.bind_state(state):
            return unwrap(m(paddle.Tensor._from_data(ids)))

    return np.asarray(jax.jit(f)(m.functional_state(), jnp.asarray(ids[None])))[0]


# -- (a) ----------------------------------------------------------------------------

@pytest.mark.parametrize("held", [None, (2, 4)])
def test_logits_equal_the_reference(held):
    """Every expert held, and a share of four: 100 positions, most of them beyond YaRN's original 32."""
    m = _model(held=held)
    ids = _ids(100)
    want = np.asarray(reference.forward_logits(_ref_cfg(m.config), _weights(m), jnp.asarray(ids)))
    # float32 on both sides; the sums run in another order (grouped heads, stacked experts)
    np.testing.assert_allclose(_forward(m, ids), want, atol=2e-5 * np.abs(want).max() + 1e-6)


def test_layer_zero_is_dense_and_every_later_layer_an_expert_share():
    m = _model()
    kinds = [type(layer.mlp).__name__ for layer in m.model.layers]
    assert kinds == ["SwiGLU", "ExpertShareLayer", "ExpertShareLayer"]
    share = m.model.layers[1].mlp
    assert (share.routing, share.num_zero, share.shared_hidden) == ("sigmoid", 0, m.config.moe_intermediate_size)
    assert not m.config.mla_scale_q_lora and m.model.layers[0].self_attn.q_scale is None


# -- (b) ----------------------------------------------------------------------------

def test_prefill_then_paged_decode_through_the_serving_engine_follows_the_reference():
    """Every served token's logit in the reference's full forward over the finished sequence is the reference's best
    to within 1e-4 of max|logit| (float32 on both sides: the absorbed decode form and the paged admission sum in
    another order than the reference's expanded form, so an exact tie aside the tokens are the reference's own)."""
    m = _model(held=(0, 4), seed=7)
    cfg, w = _ref_cfg(m.config), _weights(m)
    srv = ServingEngine(m, max_batch_size=4, max_len=128, decode_chunk=4, kv_page_size=16, kv_num_pages=40)
    srv.start()
    try:
        doc = _ids(48, seed=11)
        prompts = [_ids(21, seed=12), np.concatenate([doc, _ids(9, seed=13)]), np.concatenate([doc, _ids(14, seed=14)])]
        first = [srv.submit(prompts[0], max_new_tokens=8, temperature=0.0),
                 srv.submit(prompts[1], max_new_tokens=8, temperature=0.0, prefix_len=48)]
        outs = [np.asarray(f.result(timeout=240)) for f in first]
        outs.append(np.asarray(srv.submit(prompts[2], max_new_tokens=8, temperature=0.0, prefix_len=48).result(timeout=240)))
        stats, kv = dict(srv.stats), srv._engine.kv_stats()
    finally:
        srv.stop()
    assert kv["prefix"]["hits"] == 1 and kv["prefix"]["misses"] == 1
    for out, prompt in zip(outs, prompts):
        assert len(out) == len(prompt) + 8 and np.array_equal(out[:len(prompt)], prompt)
        logits = np.asarray(reference.forward_logits(cfg, w, jnp.asarray(out)))[len(prompt) - 1:-1]
        served = logits[np.arange(8), out[len(prompt):]]
        assert np.all(logits.max(-1) - served <= 1e-4 * np.abs(logits).max(-1))
    # (h) what the admissions computed and what they took from the cache, by kind
    assert (stats["admit_n.whole"], stats["admit_n.prefix_hit"]) == (2, 1)
    assert stats["admit_tokens_computed.whole"] == 21 + 57 and stats["admit_tokens_computed.prefix_hit"] == 14
    assert stats["admit_tokens_cached"] == 48
    assert stats["moe_picks_total"] == stats["moe_picks_held"] + stats["moe_picks_absent"] > 0 == stats["moe_picks_zero"]
    assert stats["moe_layer_steps"] % 2 == 0        # two expert layers a live step: the dense layer counts none
    # the kernel reads what any row touched, idle lanes' picks included: at least what live rows touched
    assert stats["moe_experts_touched"] <= stats["moe_experts_read"] <= 4 * stats["moe_layer_steps"]


# -- (c) ----------------------------------------------------------------------------

def test_sigmoid_routing_against_the_formula_where_the_bias_changes_the_pick_but_not_the_weight():
    x = jnp.asarray(np.eye(3, 4), jnp.float32)                    # token t reads row t of the router
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0],
                          [0.0, 0.5, 1.0, 1.5, 2.0],
                          [1.0, 1.0, 1.0, 1.0, 1.0],
                          [0.0, 0.0, 0.0, 0.0, 0.0]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.9], jnp.float32)
    sig = 1.0 / (1.0 + np.exp(-np.asarray(router[:3])))
    w0, ids0 = moe.route_sigmoid_topk(x, router, jnp.zeros(5), 2, 2.827)
    w1, ids1 = moe.route_sigmoid_topk(x, router, bias, 2, 2.827)
    # token 0 picks experts 0, 1 without the bias; with it expert 4 (sigmoid(-2) + 0.9) displaces expert 1
    assert np.asarray(ids0)[0].tolist() == [0, 1] and sorted(np.asarray(ids1)[0].tolist()) == [0, 4]
    for weights, ids in ((w0, ids0), (w1, ids1)):
        picked = np.take_along_axis(sig, np.asarray(ids), -1)       # the weight is of the sigmoid alone, never of the bias
        np.testing.assert_allclose(np.asarray(weights), 2.827 * picked / (picked.sum(-1, keepdims=True) + 1e-20), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.827, rtol=1e-6)
    # softmax routing is the other stated choice, and stays what it was: scale x p, not renormalised
    ws, _ = moe.route_scores_topk(x, router, jnp.zeros(5), 2, 6.0)
    assert np.all(np.asarray(ws).sum(-1) < 6.0)
    with pytest.raises(ValueError, match="routing="):
        moe.ExpertShareLayer(8, 8, 8, 0, 2, routing="tanh")


# -- (d) ----------------------------------------------------------------------------

def _yarn_by_hand(dim, theta, factor, orig, beta_fast, beta_slow):
    corr = lambda rot: dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    out = []
    for i in range(dim // 2):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        base = theta ** (-2.0 * i / dim)
        out.append(base / factor * ramp + base * (1.0 - ramp))
    return np.asarray(out), lo, hi


def test_yarn_tables_against_the_formula_beyond_the_original_length():
    sc = KimiK2Config().rope_scaling
    inv, lo, hi = _yarn_by_hand(64, 50000.0, 32.0, 4096, 1.0, 1.0)
    assert (lo, hi) == (19, 20)                                     # one and the same correction dimension: 19.16
    assert np.allclose(inv[:20], 50000.0 ** (-np.arange(20) / 32.0)) and np.allclose(inv[20:], 50000.0 ** (-np.arange(20, 32) / 32.0) / 32)
    np.testing.assert_allclose(np.asarray(latent_attention.yarn_inv_freq(64, 50000.0, sc)), inv, rtol=1e-6)
    np.testing.assert_allclose(reference.yarn_inv_freq(64, 50000.0, sc), inv, rtol=1e-12)
    cos, sin = latent_attention.rope_tables(64, 16896, 50000.0, sc)
    at = np.asarray([4095, 4096, 8192, 16383, 16895])
    ang = at[:, None].astype(np.float64) * inv[None, :]
    # float32 angles of up to 16,895 radians: 1e-3 absolute is their rounding
    np.testing.assert_allclose(np.asarray(cos)[at], np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin)[at], np.sin(ang), atol=2e-3)
    plain = latent_attention.rope_tables(64, 16896, 50000.0)[0]
    assert np.abs(np.asarray(plain)[at] - np.asarray(cos)[at]).max() > 0.5          # the scaling does work out there
    assert latent_attention.softmax_scale(192, sc) == pytest.approx(0.07217 * 1.8133, rel=1e-3)
    assert latent_attention.softmax_scale(192) == pytest.approx(192 ** -0.5)
    with pytest.raises(ValueError, match="only type 'yarn'"):
        latent_attention.rope_tables(8, 16, 1e4, {"type": "linear", "factor": 2.0})


# -- (e) ----------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["kernel", "dense", "grouped", "row_blocks"])
def test_the_shares_add_up_with_the_shared_expert_counted_once(form, monkeypatch):
    """All four shares of eight experts: their routed parts plus the shared expert's, counted once, are the uncut layer."""
    assert moe.held_form(42) == "kernel"
    if form != "kernel":
        monkeypatch.setattr(moe, "HELD_KERNEL_MAX_TOKENS", 8)
    if form not in ("kernel", "dense"):
        monkeypatch.setattr(moe, "GROUPED_ABOVE_TOKENS", 8)
    if form == "row_blocks":                                       # 126 pairs in blocks of 16: several blocks an expert
        monkeypatch.setattr(moe, "GROUPED_ALL_PAIRS_TOKENS", 16)
        monkeypatch.setattr(moe, "GROUPED_BLOCK_ROWS", 16)
    paddle.seed(3)
    whole = moe.ExpertShareLayer(32, 16, 8, 0, 3, scaling=2.827, routing="sigmoid", shared_hidden=16)
    x = paddle.Tensor._from_data(jnp.asarray(np.random.default_rng(0).standard_normal((2, 21, 32)), jnp.float32))

    def run(layer):
        return np.asarray(unwrap(layer(x)[0]))

    def share(first, count, shared):
        layer = moe.ExpertShareLayer(32, 16, 8, 0, 3, held=(first, count), scaling=2.827, routing="sigmoid",
                                     shared_hidden=16)
        for name in ("router", "e_score_correction_bias", "shared_gate_proj", "shared_up_proj", "shared_down_proj"):
            getattr(layer, name)._replace_data(getattr(whole, name)._data * (1.0 if shared or "shared" not in name else 0.0))
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer, name)._replace_data(getattr(whole, name)._data[first:first + count])
        return layer

    routed = sum(run(share(first, 2, shared=False)) for first in (0, 2, 4, 6))
    alone = moe.ExpertShareLayer(32, 16, 8, 0, 3, held=(0, 1), scaling=2.827, routing="sigmoid", shared_hidden=16)
    for name in ("shared_gate_proj", "shared_up_proj", "shared_down_proj"):
        getattr(alone, name)._replace_data(getattr(whole, name)._data)
    alone.gate_proj._replace_data(alone.gate_proj._data * 0.0)
    shared = run(alone)
    assert np.abs(shared).max() > 1e-3 and np.abs(routed).max() > 1e-3
    np.testing.assert_allclose(routed + shared, run(whole), atol=2e-5)
    picks = np.asarray(unwrap(share(2, 2, True)(x)[1]))
    assert set(np.unique(picks)) <= {0, 1, 3}                       # held, held, absent: never 2, the identity's index


# -- (f) ----------------------------------------------------------------------------

def test_the_blocked_prefill_attention_equals_the_unblocked_one():
    """The kernel (under the interpreter here) at 37 queries behind per-row starts and 50 keys, no multiple of 8 or 16."""
    rng = np.random.default_rng(0)
    b, s, L, H, nope, rope, vd, rank = 2, 37, 50, 4, 8, 8, 8, 16
    f = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)
    qn, qr, c, kr, w = f(b, s, H, nope), f(b, s, H, rope), f(b, L, rank), f(b, L, rope), f(rank, H * (nope + vd))
    start = jnp.asarray([13, 5], jnp.int32)
    pos = start[:, None] + jnp.arange(s)[None]
    whole = latent_attention._expanded_attention(qn, qr, c, kr, w, pos, nope, 0.3)
    kv = jnp.einsum("blr,rhd->blhd", c, w.reshape(rank, H, -1))
    for block in (8, 16, 64):
        got = latent_prefill_attention(qn, qr, kv[..., :nope], kr, kv[..., nope:], start, scale=0.3, block=block)
        np.testing.assert_allclose(np.asarray(got).reshape(b, s, -1), np.asarray(whole), atol=1e-5)
    # the path a long prompt takes: the same numbers through the model's own call of it
    np.testing.assert_allclose(np.asarray(latent_attention._long_attention(qn, qr, c, kr, w, pos, nope, 0.3)),
                               np.asarray(whole), atol=1e-5)


def test_no_prompt_makes_the_prefill_hold_more_scores_than_the_bound(monkeypatch):
    """With the bound pulled down to 4 heads x 40 x 40 scores, a 72-token admission goes to the blocked kernel and
    serves the tokens it served in one piece; a prompt under the bound keeps the one-piece form."""
    m = _model(held=(0, 4), seed=5)
    prompt, seen = _ids(72, seed=21), []

    def serve():
        eng = BatchDecodeEngine(m, max_slots=2, max_len=128, chunk=4, page_size=16, num_pages=24)
        req = paddle.inference.serving.GenerationRequest(prompt, 6, 0.0, 0, None)
        eng.serve([req], timeout=240)
        return np.asarray(req.result.result(5))

    real = latent_attention._long_attention

    def spy(*a, **kw):
        seen.append(a[0].shape)
        return real(*a, **kw)

    monkeypatch.setattr(latent_attention, "_long_attention", spy)
    want = serve()
    assert not seen                                                 # 4 heads x 128 x 128 scores are under 1 << 26
    monkeypatch.setattr(latent_attention, "_SCORE_VALUES", 4 * 40 * 40)
    np.testing.assert_array_equal(serve(), want)
    assert seen and all(shape[1] == 128 for shape in seen)          # the admission's bucket went through the blocks


# -- (g), (h) -------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs, reason", [
    ({"kv_quant": "int8"}, "one scale a kv head"),
    ({"mesh": object()}, "shards a pool on its kv heads"),
    ({"draft": object(), "spec_k": 2}, "draft decoder builds K/V pair caches"),
    ({"kv_host_bytes": 1 << 20}, "spilled slab is laid out"),
])
def test_what_the_latent_row_cannot_do_yet_refuses_for_this_model_too(kwargs, reason):
    with pytest.raises(ValueError, match="latent.*" + reason):
        BatchDecodeEngine(_model(held=(0, 4)), max_slots=2, max_len=64, chunk=2, page_size=16, num_pages=12, **kwargs)


@pytest.mark.parametrize("form", ["kernel", "dense"])
def test_the_experts_read_counter_follows_the_form_of_the_held_experts(form, monkeypatch):
    """One slot, so every row is live: on the kernel's rung the experts read a layer-step are those it touched, on
    the dense rung all four held."""
    if form == "dense":
        monkeypatch.setattr(moe, "HELD_KERNEL_MAX_TOKENS", 0)
    eng = BatchDecodeEngine(_model(held=(0, 4), seed=7), max_slots=1, max_len=64, chunk=4, page_size=16, num_pages=12)
    req = paddle.inference.serving.GenerationRequest(_ids(11, seed=3), 9, 0.0, 0, None)
    eng.serve([req], timeout=240)
    st = eng.stats
    assert st["moe_layer_steps"] == 2 * 8 and 0 < st["moe_experts_touched"] <= 4 * 16
    assert st["moe_experts_read"] == (st["moe_experts_touched"] if form == "kernel" else 4 * 16)


def test_the_cache_spec_is_one_latent_block_a_layer():
    m = _model()
    assert cache_spec_of(m) == [(PoolSpec("latent", (16,)), PoolSpec("latent", (8,)))] * 3
    published = KimiK2Config(num_hidden_layers=6)
    spec = KimiK2ForCausalLM.cache_spec(type("M", (), {"config": published})())
    assert spec_bytes_per_token(spec, 2) == 6912 and published.latent_row == 576
    assert set(ADMIT_COUNTERS) <= set(BatchDecodeEngine(m, max_slots=2, max_len=64, chunk=2, page_size=16,
                                                        num_pages=12).stats)
