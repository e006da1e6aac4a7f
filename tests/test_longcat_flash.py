"""LongCat-Flash through the program (models/longcat_flash.py, the expert share
of parallel/moe.py, the cache spec of inference/kv_pool.py and decode_engine.py)
at tiny widths in float32, against a plain numpy/jnp reference written here.

(a) logits with every expert held equal the reference's; (b) the shares add
up: four chips' routed parts plus the identity part counted once are the uncut
layer; (c) prefill then decode through the engine's latent pool (plain,
prefix-hit and W = k + 1) give the full forward's logits, and the absorbed form
equals the expanded one; (d) the counters equal those of the reference's
routing; (e) each loud refusal names its reason; (f) a Mistral-shaped engine
built from the cache spec serves the tokens it served before.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.core import autograd as _ag
from paddlepaddle_tpu.core.dispatch import unwrap
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.kv_pool import (PoolSpec, cache_spec_of,
                                                spec_bytes_per_token)
from paddlepaddle_tpu.inference.serving import GenerationRequest, ServingEngine
from paddlepaddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                     LongcatFlashConfig, LongcatFlashForCausalLM)
from paddlepaddle_tpu.parallel import moe


# -- the plain reference (numpy-shaped jnp, float32, every expert looped) -------

def _rms(x, w, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(t)[:, None] * inv[None, :]
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), d // 2)
    a, b = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2] = a * np.cos(ang) - b * np.sin(ang)
    out[..., 1::2] = a * np.sin(ang) + b * np.cos(ang)
    return out


def _silu(x):
    return x / (1.0 + np.exp(-x))


def ref_route(cfg, w, p, h):
    logits = h @ w[p + "mlp.router"]
    e = np.exp(logits - logits.max(-1, keepdims=True))
    prob = e / e.sum(-1, keepdims=True)
    ids = np.argsort(-(prob + w[p + "mlp.e_score_correction_bias"]), -1,
                     kind="stable")[:, : cfg.moe_topk]
    return cfg.routed_scaling_factor * np.take_along_axis(prob, ids, -1), ids


def ref_experts(cfg, w, p, h, held=None, identity=True):
    """The expert layer's result; ``held=(first, count)`` keeps those routed
    experts' part alone (``w`` then holds the stacked share)."""
    first, count = (0, cfg.n_routed_experts) if held is None else held
    weights, ids = ref_route(cfg, w, p, h)
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for wt, e in zip(weights[t], ids[t]):
            if e >= cfg.n_routed_experts:
                out[t] += wt * h[t] * identity
            elif first <= e < first + count:
                j = e - first
                a = _silu(h[t] @ w[p + "mlp.gate_proj"][j]) * (h[t] @ w[p + "mlp.up_proj"][j])
                out[t] += wt * (a @ w[p + "mlp.down_proj"][j])
    return out, ids


def ref_mla(cfg, w, p, x):
    t = x.shape[0]
    H, r, nope, rope, vd = (cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.v_head_dim)
    cq = _rms(x @ w[p + "q_a_proj.weight"], w[p + "q_a_layernorm.weight"]) * np.sqrt(cfg.hidden_size / cfg.q_lora_rank)
    q = (cq @ w[p + "q_b_proj.weight"]).reshape(t, H, nope + rope)
    ckv = x @ w[p + "kv_a_proj_with_mqa.weight"]
    c = _rms(ckv[:, :r], w[p + "kv_a_layernorm.weight"]) * np.sqrt(cfg.hidden_size / r)
    kv = (c @ w[p + "kv_b_proj.weight"]).reshape(t, H, nope + vd)
    q_rope, k_rope = _rope(q[..., nope:], cfg.rope_theta), _rope(ckv[:, r:], cfg.rope_theta)
    s = (np.einsum("shd,thd->hst", q[..., :nope], kv[..., :nope])
         + np.einsum("shd,td->hst", q_rope, k_rope)) / np.sqrt(nope + rope)
    s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("hst,thd->shd", e / e.sum(-1, keepdims=True), kv[..., nope:])
    return o.reshape(t, H * vd) @ w[p + "o_proj.weight"]


def ref_logits(cfg, w, ids, held=None, routing=None):
    """Full forward of one row of token ids in float64-free numpy float32."""
    x = w["model.embed_tokens.weight"][ids]
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        mlp = lambda j, h: (_silu(h @ w[p + f"mlps.{j}.gate_proj.weight"]) * (h @ w[p + f"mlps.{j}.up_proj.weight"])) \
            @ w[p + f"mlps.{j}.down_proj.weight"]
        x = x + ref_mla(cfg, w, p + "self_attn.0.", _rms(x, w[p + "input_layernorm.0.weight"]))
        h = _rms(x, w[p + "post_attention_layernorm.0.weight"])
        s, picked = ref_experts(cfg, w, p, h, held)
        if routing is not None:
            routing.append(picked)
        x = x + mlp(0, h)
        x = x + ref_mla(cfg, w, p + "self_attn.1.", _rms(x, w[p + "input_layernorm.1.weight"]))
        x = x + mlp(1, _rms(x, w[p + "post_attention_layernorm.1.weight"])) + s
    return _rms(x, w["model.norm.weight"]) @ w["lm_head.weight"]


def _weights(model):
    return {n: np.asarray(p._data, np.float32) for n, p in model.named_parameters()}


def _model(held=None, seed=0, **kw):
    paddle.seed(seed)
    return LongcatFlashForCausalLM(LongcatFlashConfig.tiny(held=held, **kw))


def _ids(n, seed=1, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, (n,)).astype(np.int32)


def _forward(m, ids):
    """The model's whole forward (no cache: expanded attention) as one program."""
    def f(state, ids):
        with _ag.no_grad(), m.bind_state(state):
            return unwrap(m(paddle.Tensor._from_data(ids)))

    return np.asarray(jax.jit(f)(m.functional_state(), jnp.asarray(ids[None])))[0]


# -- (a) --------------------------------------------------------------------------

def test_logits_equal_the_reference_with_every_expert_held():
    m = _model()
    ids = _ids(24)
    got = _forward(m, ids)
    want = ref_logits(m.config, _weights(m), ids)
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_a_held_share_of_the_model_equals_the_reference_given_the_same_share():
    m = _model(held=(2, 4))
    ids = _ids(24, seed=3)
    got = _forward(m, ids)
    want = ref_logits(m.config, _weights(m), ids, held=(2, 4))
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


# -- (b) --------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["kernel", "dense", "grouped"])
def test_the_shares_add_up(form, monkeypatch):
    """8 routed + 4 zero-compute experts, top 3, cut 4 ways: the four chips'
    routed parts plus the identity part counted once are the uncut layer, in
    each form of the held experts that 48 tokens can be given."""
    if form != "kernel":
        monkeypatch.setattr(moe, "HELD_KERNEL_MAX_TOKENS", 8)
    if form == "grouped":
        monkeypatch.setattr(moe, "GROUPED_ABOVE_TOKENS", 8)
    cfg = LongcatFlashConfig.tiny()
    paddle.seed(5)
    whole = moe.ExpertShareLayer(32, 32, 8, 4, 3, scaling=6.0)
    w = {"mlp." + n: np.asarray(p._data, np.float32) for n, p in whole.named_parameters()}
    h = np.random.default_rng(2).normal(size=(48, 32)).astype(np.float32)
    want, ids = ref_experts(cfg, w, "", h)
    assert (ids >= 8).any() and (ids < 8).any()
    identity = ref_experts(cfg, w, "", h, held=(0, 0))[0]
    total = np.zeros_like(want)
    for chip in range(4):
        share = moe.ExpertShareLayer(32, 32, 8, 4, 3, held=(2 * chip, 2), scaling=6.0)
        share.router._replace_data(whole.router._data)
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(share, name)._replace_data(getattr(whole, name)._data[2 * chip: 2 * chip + 2])
        y, picks = share(paddle.to_tensor(h))
        total += np.asarray(unwrap(y)) - identity          # every chip computes the identity part alike
        assert int((np.asarray(unwrap(picks)) < 2).sum()) == int(((ids >= 2 * chip) & (ids < 2 * chip + 2)).sum())
    np.testing.assert_allclose(total + identity, want, atol=1e-4)
    y, _ = whole(paddle.to_tensor(h))
    np.testing.assert_allclose(np.asarray(unwrap(y)), want, atol=1e-4)


def test_held_must_lie_inside_the_routed_experts():
    with pytest.raises(ValueError, match="does not lie inside"):
        moe.ExpertShareLayer(8, 8, 8, 4, 3, held=(6, 4))


# -- (c) --------------------------------------------------------------------------

def _engine(m, **kw):
    kw = {"max_slots": 4, "max_len": 128, "chunk": 4, "page_size": 16, "num_pages": 40, **kw}
    return BatchDecodeEngine(m, **kw)


def _req(ids, n, prefix_len=None):
    r = GenerationRequest(ids, n, 0.0, 0, None)
    r.prefix_len = prefix_len
    return r


def _greedy_reference(cfg, w, prompt, n, held):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(ref_logits(cfg, w, np.asarray(seq), held=held)[-1])))
    return np.asarray(seq, np.int32)


def test_prefill_then_decode_through_the_latent_pool_follows_the_full_forward():
    """Plain admission, a prefix miss and a prefix hit: every served token is
    the argmax of the reference's full forward (expanded attention, float32)."""
    m = _model(held=(0, 4), seed=7)
    cfg, w = m.config, _weights(m)
    eng = _engine(m)
    shared = _ids(32, seed=11)
    prompts = [_ids(21, seed=12), np.concatenate([shared, _ids(9, seed=13)]),
               np.concatenate([shared, _ids(14, seed=14)])]
    reqs = [_req(prompts[0], 6), _req(prompts[1], 6, prefix_len=32)]
    eng.serve(reqs, timeout=240)
    hit = _req(prompts[2], 6, prefix_len=32)
    eng.serve([hit], timeout=240)
    kv = eng.kv_stats()
    assert kv["prefix"]["hits"] == 1 and kv["prefix"]["misses"] == 1
    assert kv["bytes_per_token"] == 2 * cfg.num_layers * cfg.latent_row * 4
    assert kv["row_shapes"] == [[cfg.kv_lora_rank], [cfg.qk_rope_head_dim]] * 2 and kv["row_roles"] == ["latent"] * 4
    for r, prompt in zip(reqs + [hit], prompts):
        got = np.asarray(r.result.result(5))
        np.testing.assert_array_equal(got, _greedy_reference(cfg, w, prompt, 6, (0, 4)))


def test_the_absorbed_form_and_the_w_wide_form_equal_the_expanded_one():
    """One W = 3 call of ``_forward_paged`` (the speculative verify's shape)
    after a prefill: its logits are the whole forward's at those positions."""
    m = _model(held=(0, 8), seed=9)
    cfg = m.config
    eng = _engine(m, chunk=1)
    ids = _ids(27, seed=21)
    req = _req(ids[:24], 2)
    assert eng._admit(req)
    rung = eng._view_rung(eng.lens, eng.active, 3)
    toks = jnp.zeros((eng.S, 3), jnp.int32).at[0].set(jnp.asarray(ids[24:27]))
    logits, _ = jax.jit(eng._forward_paged)(eng.params, toks, eng.caches, eng.page_table, eng.lens, rung)
    want = _forward(m, ids)[24:27]                                              # expanded, no cache
    np.testing.assert_allclose(np.asarray(logits)[0], want, atol=2e-4 * np.abs(want).max())
    np.testing.assert_allclose(want, ref_logits(cfg, _weights(m), ids)[24:27], atol=2e-4 * np.abs(want).max())


def test_the_contiguous_layout_serves_the_same_tokens():
    m = _model(held=(0, 4), seed=7)
    prompt = _ids(19, seed=31)
    outs = []
    for layout in ("paged", "contiguous"):
        r = _req(prompt, 5)
        _engine(m, kv_layout=layout).serve([r], timeout=240)
        outs.append(np.asarray(r.result.result(5)))
    np.testing.assert_array_equal(outs[0], outs[1])


# -- (d) --------------------------------------------------------------------------

def test_pick_counters_equal_the_reference_routing():
    cfg = LongcatFlashConfig.tiny()
    paddle.seed(3)
    layer = moe.ExpertShareLayer(32, 32, 8, 4, 3, held=(2, 4), scaling=6.0)
    w = {"mlp." + n: np.asarray(p._data, np.float32) for n, p in layer.named_parameters()}
    h = np.random.default_rng(4).normal(size=(20, 32)).astype(np.float32)
    _, ids = ref_route(cfg, w, "", h)
    mask = np.arange(20) % 3 != 0
    with moe.PickTap() as tap:
        layer(paddle.to_tensor(h))
    got = np.asarray(tap.counts(jnp.asarray(mask)))
    live = ids[mask]
    hist = [int((live == e).sum()) for e in range(2, 6)]
    assert list(got[:4]) == hist
    assert got[4] == (live >= 8).sum()                                   # identity picks
    assert got[5] == ((live < 2) | ((live >= 6) & (live < 8))).sum()     # experts of other chips
    assert got[6] == sum(1 for c in hist if c)                           # held experts touched
    assert got[:6].sum() == mask.sum() * 3


def test_the_decode_counters_reach_engine_stats_and_follow_the_reference():
    m = _model(held=(2, 4), seed=7)
    cfg, w = m.config, _weights(m)
    eng = _engine(m, chunk=4)
    prompt = _ids(18, seed=41)
    r = _req(prompt, 9)                        # first token at admission, then two chunks of 4
    eng.serve([r], timeout=240)
    out = np.asarray(r.result.result(5))
    routing = []
    ref_logits(cfg, w, out[:-1], held=(2, 4), routing=routing)
    picked = np.stack(routing)[:, len(prompt):]         # [layers, decoded positions, topk]
    st = eng.stats
    assert st["moe_experts_held"] == 4 and st["moe_layer_steps"] == 8 * cfg.num_layers
    assert st["moe_picks_zero"] == (picked >= 8).sum()
    assert st["moe_expert_pairs"] == tuple(int((picked == e).sum()) for e in range(2, 6))
    assert st["moe_picks_held"] == sum(st["moe_expert_pairs"])
    assert st["moe_picks_zero"] + st["moe_picks_held"] + st["moe_picks_absent"] == picked.size
    assert st["moe_experts_touched"] == sum(
        len({int(e) for e in picked[l, t] if 2 <= e < 6}) for l in range(cfg.num_layers) for t in range(8))


@pytest.mark.parametrize("form", ["kernel", "dense"])
def test_the_experts_read_counter_follows_the_form_of_the_held_experts(form, monkeypatch):
    """``moe_experts_read`` through decode calls of one slot (so the reference's own picks are every row's): on the
    kernel's rung the held experts a layer-step touched, on the dense rung every held expert of every layer-step."""
    if form == "dense":
        monkeypatch.setattr(moe, "HELD_KERNEL_MAX_TOKENS", 0)
    m = _model(held=(2, 4), seed=7)
    cfg, w = m.config, _weights(m)
    eng = _engine(m, chunk=4, max_slots=1)
    prompt = _ids(18, seed=41)
    r = _req(prompt, 9)
    eng.serve([r], timeout=240)
    out = np.asarray(r.result.result(5))
    routing = []
    ref_logits(cfg, w, out[:-1], held=(2, 4), routing=routing)
    picked = np.stack(routing)[:, len(prompt):]
    touched = [len({int(e) for e in picked[l, t] if 2 <= e < 6}) for l in range(cfg.num_layers) for t in range(8)]
    st = eng.stats
    assert st["moe_layer_steps"] == len(touched) and st["moe_experts_touched"] == sum(touched)
    assert st["moe_experts_read"] == (sum(touched) if form == "kernel" else 4 * len(touched))


def test_serving_engine_copies_the_counters_and_a_dense_model_has_none():
    srv = ServingEngine(_model(held=(0, 4)), max_batch_size=2, max_len=64, decode_chunk=2, kv_page_size=16,
                        kv_num_pages=12)
    srv.start()
    try:
        srv.submit(_ids(10), max_new_tokens=5, temperature=0.0).result(timeout=240)
        assert srv.stats["moe_layer_steps"] > 0 and len(srv.stats["moe_expert_pairs"]) == 4
    finally:
        srv.stop()
    dense = _engine(_llama())
    assert dense.pick_stat_keys == () and "moe_picks_zero" not in dense.stats


# -- (e) --------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs, reason", [
    ({"kv_quant": "int8"}, "one scale a kv head"),
    ({"mesh": object()}, "shards a pool on its kv heads"),
    ({"draft": object(), "spec_k": 2}, "draft decoder builds K/V pair caches"),
    ({"kv_host_bytes": 1 << 20}, "spilled slab is laid out"),
])
def test_what_the_latent_row_cannot_do_yet_refuses_with_its_reason(kwargs, reason):
    with pytest.raises(ValueError, match="latent.*" + reason):
        _engine(_model(held=(0, 4)), **kwargs)


# -- (f) --------------------------------------------------------------------------

def _llama():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=192, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=96))


def test_the_cache_spec_of_both_families():
    llama, longcat = _llama(), _model()
    assert cache_spec_of(llama) == [(PoolSpec("k", (2, 16)), PoolSpec("v", (2, 16)))] * 2
    assert llama.cache_spec() == cache_spec_of(llama)
    assert cache_spec_of(longcat) == [(PoolSpec("latent", (16,)), PoolSpec("latent", (8,))) * 2] * 2
    assert spec_bytes_per_token(cache_spec_of(llama), 2) == 2 * 2 * 2 * 16 * 2
    published = LongcatFlashConfig(num_layers=4)
    assert (published.latent_row, 2 * 4 * published.latent_row * 2) == (576, 9216)


def test_a_mistral_shaped_engine_from_the_spec_serves_the_tokens_of_the_dense_forward():
    """Token-exact on a fixed seed: the paged engine built from the declared
    K/V spec against ``generate_cached`` (the dense-cache forward it served
    before) and against the contiguous layout."""
    m = _llama()
    prompts = [_ids(13, seed=51), _ids(30, seed=52)]
    want = [np.asarray(m.generate_cached(p[None], max_new_tokens=7, temperature=0.0).numpy())[0] for p in prompts]
    for layout in ("paged", "contiguous"):
        reqs = [_req(p, 7) for p in prompts]
        eng = BatchDecodeEngine(m, max_slots=2, max_len=96, chunk=4, page_size=16, kv_layout=layout)
        eng.serve(reqs, timeout=240)
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.result.result(5)), w)
    assert eng.kv_stats()["bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert eng.kv_stats()["row_roles"] == ["k", "v"]
