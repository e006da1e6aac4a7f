"""MiMo-V2 through the program (models/mimo_v2.py, the window class of KV pages of
inference/kv_pool.py and decode_engine.py, the windowed and sinked page walk of
ops/kernels/paged_gqa_attention.py) at tiny widths in float32, against the plain
reference of benchmark/families/mimo_v2_reference.py and against formulas written here.

(a) the whole forward equals the reference's; (b) prefill then paged decode through
ServingEngine, past three windows, serves tokens whose reference logits are the
reference's best, and the same tokens read far from the best under the reference with
the window, the sink, the value scale or the partial rotary left out; (c) the window
class holds at most its ring a slot and hands on the pages that fell behind; (d) the
walk with a window, a sink and rows of one a token against a plain formulation; (e) the
32 shares of an expert layer add up to the uncut layer; (f) a model that declares no
window gets one page table, the pools and the programs it had; (g) what a window class
cannot do yet refuses.
"""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddlepaddle_tpu as paddle
from benchmark.families import mimo_v2_reference as reference
from paddlepaddle_tpu.core import autograd as _ag
from paddlepaddle_tpu.core.dispatch import unwrap
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.kv_pool import PoolSpec, cache_spec_of, page_classes, ring_pages
from paddlepaddle_tpu.inference.serving import ServingEngine
from paddlepaddle_tpu.models import MimoV2Config, MimoV2ForCausalLM
from paddlepaddle_tpu.ops.kernels import paged_gqa_attention as pga
from paddlepaddle_tpu.parallel import moe

WINDOW, PAGE, CHUNK = 8, 4, 2


def _model(held=(0, 4), seed=3, **kw):
    paddle.seed(seed)
    return MimoV2ForCausalLM(MimoV2Config.tiny(held=held, window=WINDOW, **kw))


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


def _gap(cfg, w, out, plen, mode="f32"):
    """Per served token: how far its logit lies below the best of the reference (in ``mode``), over max|logit|."""
    logits = np.asarray(reference.forward_logits(cfg, w, jnp.asarray(out), mode))[plen - 1:-1]
    served = logits[np.arange(len(out) - plen), out[plen:]]
    return (logits.max(-1) - served) / np.abs(logits).max(-1)


# -- (a) ----------------------------------------------------------------------------

@pytest.mark.parametrize("held", [None, (2, 4)])
def test_logits_equal_the_reference(held):
    """Every expert held, and a share of four: 60 positions, over seven windows."""
    m = _model(held=held)
    ids = _ids(60)
    want = np.asarray(reference.forward_logits(_ref_cfg(m.config), _weights(m), jnp.asarray(ids)))
    np.testing.assert_allclose(_forward(m, ids), want, atol=2e-5 * np.abs(want).max())


def test_the_layers_follow_the_pattern():
    m = _model()
    attn = [layer.self_attn for layer in m.model.layers]
    assert [a.window for a in attn] == [0, WINDOW, WINDOW, 0, WINDOW]
    assert [a.kv_heads for a in attn] == [4, 8, 8, 4, 8] and {(a.qk_width, a.v_width, a.rot) for a in attn} == {(64, 32, 16)}
    assert [a.attention_sink_bias is not None for a in attn] == [False, True, True, False, True]
    assert [type(layer.mlp).__name__ for layer in m.model.layers] == ["SwiGLU"] + ["ExpertShareLayer"] * 4
    share = m.model.layers[1].mlp
    assert (share.routing, share.num_zero, share.shared_hidden, share.scaling) == ("sigmoid", 0, 0, 1.0)


# -- (b) ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Three requests through ServingEngine: prompts of 5, 13 (longer than the window: the admission stores its tail)
    and 30 tokens, 26 new tokens each, pages of 4, chunks of 2."""
    m = _model()
    srv = ServingEngine(m, max_batch_size=3, max_len=128, decode_chunk=CHUNK, kv_page_size=PAGE, prefix_cache=False)
    srv.start()
    try:
        prompts = [_ids(n, seed=n) for n in (5, 13, 30)]
        futs = [srv.submit(p, max_new_tokens=26, temperature=0.0) for p in prompts]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        stats, kv = dict(srv._engine.stats), srv._engine.kv_stats()
    finally:
        srv.stop()
    return m, prompts, outs, stats, kv


def test_prefill_then_decode_past_three_windows_follows_the_reference(served):
    m, prompts, outs, _, _ = served
    cfg, w = _ref_cfg(m.config), _weights(m)
    for out, prompt in zip(outs, prompts):
        assert len(out) == len(prompt) + 26 > 3 * WINDOW and np.array_equal(out[:len(prompt)], prompt)
        assert _gap(cfg, w, out, len(prompt)).max() <= 1e-4


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_mechanism_left_out_breaks_the_comparison(served, fault):
    """The window, the sink, the 0.707 value scale and the partial rotary each move the tokens the program served far
    from the best of a reference that leaves it out."""
    m, prompts, outs, _, _ = served
    cfg, w = _ref_cfg(m.config), _weights(m)
    assert max(_gap(cfg, w, out, len(p), fault).max() for out, p in zip(outs, prompts)) > 0.05


# -- (c) ----------------------------------------------------------------------------

def test_the_window_class_holds_its_ring_and_hands_on_what_fell_behind(served):
    m, prompts, outs, stats, kv = served
    ring = ring_pages(WINDOW, PAGE)
    assert ring == 3
    (cls,) = kv["window_classes"]
    assert cls["window"] == WINDOW and cls["layers"] == [1, 2, 4] and cls["ring_pages"] == ring
    assert cls["pages_total"] == 3 * ring and cls["pages_peak"] <= 3 * ring and cls["pages_used"] == 0
    assert cls["bytes_per_token"] == 3 * 8 * (64 + 32) * 4 and kv["bytes_per_token"] == 2 * 4 * (64 + 32) * 4
    # a walk reads at most the ring, and a slot's live pages never pass it
    assert stats["window_pages_walked_max"] == ring and stats["window_pages_live_peak"] <= 3 * ring
    # the pages that fell wholly behind the window, summed from each slot's admission to its last key
    first = lambda held: max(held - WINDOW + 1, 0) // PAGE
    want = sum(first(len(p) + 25) - first(len(p)) for p in prompts)
    assert stats["window_pages_released"] == want > 0


def test_the_decode_counts_the_expert_picks_it_holds(served):
    """The counters ``moe.held_pick_share`` reads in the cell: every live slot's top-k picks of every expert layer, and
    those that landed on a held expert."""
    _, _, _, stats, _ = served
    assert 0 < stats["moe_picks_held"] <= stats["moe_picks_total"]


def test_the_full_class_and_its_table_keep_every_position():
    m = _model()
    eng = BatchDecodeEngine(m, max_slots=2, max_len=64, chunk=CHUNK, page_size=PAGE, prefix_cache=False)
    assert eng.page_table.shape == (2, 16) and [wc.table.shape for wc in eng.window_classes] == [(2, 3)]
    assert [c[0].shape for c in eng.caches] == [(33, PAGE, 256), (7, PAGE, 512), (7, PAGE, 512), (33, PAGE, 256),
                                                (7, PAGE, 512)]
    assert page_classes(eng.cache_spec) == {0: [0, 3], WINDOW: [1, 2, 4]}


# -- (d) ----------------------------------------------------------------------------

def _plain_walk(q, kn, vn, kp, vp, table, lens, scale, window, sink):
    """Each slot's keys at ``max(0, lens - window + 1) .. lens - 1`` (the ring's entry ``page % ring`` where there is a
    window) and then its new row, a softmax a head with the sink one more term in its denominator."""
    S, H, hd = q.shape
    kvh, ps = kn.shape[1], kp.shape[1]
    kp, vp = kp.reshape(*kp.shape[:2], kvh, -1), vp.reshape(*vp.shape[:2], kvh, -1)
    out = np.zeros((S, H, vn.shape[-1]), np.float32)
    for s in range(S):
        h = int(lens[s])
        pos = np.arange(max(0, h - window + 1) if window else 0, h)
        pages = table[s, (pos // ps) % table.shape[1] if window else pos // ps]
        K = np.concatenate([kp[pages, pos % ps], kn[s][None]])
        V = np.concatenate([vp[pages, pos % ps], vn[s][None]])
        for hh in range(H):
            g = hh // (H // kvh)
            sc = K[:, g] @ q[s, hh] * scale
            m = sc.max() if sink is None else max(sc.max(), sink[hh])
            e = np.exp(sc - m)
            out[s, hh] = e @ V[:, g] / (e.sum() + (0.0 if sink is None else np.exp(sink[hh] - m)))
    return out


@pytest.mark.parametrize("window, sink", [(0, False), (0, True), (8, True), (8, False), (6, True)])
@pytest.mark.parametrize("chunk_pages", [None, 1, 2])
def test_the_walk_with_a_window_a_sink_and_token_rows_equals_the_plain_form(window, sink, chunk_pages):
    rng = np.random.default_rng(window + 10 * sink)
    S, H, kvh, hd, hv, ps = 5, 8, 4, 64, 32, 4
    entries = ring_pages(window, ps) if window else 8
    pages = 1 + S * entries
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)
    kp, vp = f(pages, ps, kvh * hd), f(pages, ps, kvh * hv)
    table = (1 + rng.permutation(S * entries)).reshape(S, entries).astype(np.int32)
    lens = np.array([0, 3, 9, 17, 29 if window else 31], np.int32)     # an idle slot; windows across two and three pages
    q, kn, vn = f(S, H, hd), f(S, kvh, hd), f(S, kvh, hv)
    b = f(H) + 1.0 if sink else None
    got = pga.paged_gqa_attention(*map(jnp.asarray, (q, kn, vn, kp, vp, table, lens)), scale=0.2, window=window,
                                  sink=None if b is None else jnp.asarray(b), chunk_pages=chunk_pages)
    np.testing.assert_allclose(np.asarray(got), _plain_walk(q, kn, vn, kp, vp, table, lens, 0.2, window, b),
                               atol=2e-6)


def test_a_window_walk_is_named_apart_and_reads_only_its_window():
    """The call is ``paged_window_attention``; what lies in the ring behind the window is never a key."""
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    args = (f(1, 8, 64), f(1, 4, 64), f(1, 4, 32), f(4, 4, 256), f(4, 4, 128), table, jnp.asarray([15], jnp.int32))
    base = pga.paged_gqa_attention(*args, scale=0.2, window=8)
    # 15 keys held: the window is 8..14 (and the new row), logical pages 2 (entry 2: physical 3) and 3 (entry 0:
    # physical 1); entry 1 (physical 2) holds logical page 1, behind it, and position 15 is no key yet
    loud = list(args)
    loud[3] = args[3].at[2].set(50.0).at[1, 3:].set(50.0)
    np.testing.assert_array_equal(np.asarray(pga.paged_gqa_attention(*loud, scale=0.2, window=8)), np.asarray(base))
    text = str(jax.make_jaxpr(lambda *a: pga.paged_gqa_attention(*a, scale=0.2, window=8))(*args))
    assert "paged_window_attention" in text and "paged_gqa_attention" not in text
    assert pga.reads_in_place(args[3]) and pga.token_rows(args[3]) and not pga.token_rows(jnp.zeros((4, 4, 2, 128)))


# -- (e) ----------------------------------------------------------------------------

def test_the_32_shares_add_up_to_the_uncut_layer():
    """64 routed experts, top 8, no shared expert, scaling 1: the 32 shares of two experts each add up to the reference's
    uncut layer (every expert held)."""
    paddle.seed(5)
    whole = moe.ExpertShareLayer(32, 16, 64, 0, 8, routing="sigmoid", init_std=0.3)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((21, 32)), jnp.float32)
    parts = 0.0
    for first in range(0, 64, 2):
        layer = moe.ExpertShareLayer(32, 16, 64, 0, 8, held=(first, 2), routing="sigmoid")
        layer.router._replace_data(whole.router._data)
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer, name)._replace_data(getattr(whole, name)._data[first:first + 2])
        parts = parts + np.asarray(unwrap(layer(paddle.Tensor._from_data(x))[0]))
    cfg = {"experts_first": 0, "num_experts_per_tok": 8, "routed_scaling_factor": None}
    w = {"l.mlp.router": whole.router._data, "l.mlp.e_score_correction_bias": jnp.zeros((64,), jnp.float32),
         **{"l.mlp." + n: getattr(whole, n)._data for n in ("gate_proj", "up_proj", "down_proj")}}
    want = np.asarray(reference.expert_layer(cfg, w, "l.", x, "f32"))
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(parts, want, atol=2e-5 * np.abs(want).max())


# -- (f) ----------------------------------------------------------------------------

# sha1 of each program's lowered text at the parent of the window class (CPU, x64 on, as tests/conftest.py sets up)
LOWERED = {
    ("llama", "decode"): "0ee9aadbeeda6ee331d48c1f93fd37c5aa328269",
    ("llama", "admit_p64"): "52b5e95d2c848bb7e78bc351ab969e16640a9d8a",
    ("llama", "admit_pfx1t56"): "3870251b807aba4cb6240098c394c8c0bdbb6d34",
    ("kimi", "decode"): "2b3e8f4db57a723d54bb1012f35804eff67f0f7b",
    ("kimi", "admit_p64"): "cc17e0adeb85f064d84b83be60af2aba344ffafc",
    ("longcat", "decode"): "2fa703c69e7419f91b0f98600a2329bebbb5ff16",
    ("longcat", "admit_p64"): "cc144b4c2803f61ddea98eecffe5a1a02ed8dffb",
}


def _family(name):
    from paddlepaddle_tpu.models import (KimiK2Config, KimiK2ForCausalLM, LlamaConfig, LlamaForCausalLM,
                                         LongcatFlashConfig, LongcatFlashForCausalLM)

    paddle.seed(0)
    if name == "llama":        # heads of 128: the decode step walks its pages in place
        return LlamaForCausalLM(LlamaConfig(vocab_size=128, hidden_size=512, intermediate_size=256,
                                            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                                            max_position_embeddings=64))
    if name == "kimi":
        return KimiK2ForCausalLM(KimiK2Config.tiny(held=(0, 4), max_len=64))
    return LongcatFlashForCausalLM(LongcatFlashConfig.tiny(held=(0, 4), max_len=64))


@pytest.mark.parametrize("name", ["llama", "kimi", "longcat"])
def test_a_model_without_a_window_keeps_its_table_pools_and_programs(name):
    m = _family(name)
    eng = BatchDecodeEngine(m, max_slots=4, chunk=4, page_size=8)
    assert eng.window_classes == [] and eng._tables() is eng.page_table and eng.page_table.shape == (4, 8)
    assert all(p.window == 0 for layer in cache_spec_of(m) for p in layer)
    assert "window_classes" not in eng.kv_stats() and "window_pages_released" not in eng.stats
    for (fam, key), want in LOWERED.items():
        if fam == name:
            text = eng._build_program(key).lower(*eng._example_args(key)).as_text()
            assert hashlib.sha1(text.encode()).hexdigest() == want, key


# -- (g) ----------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs, reason", [
    ({"prefix_cache": True}, "prefix sharing"),
    ({"kv_quant": "int8"}, "kv_quant"),
    ({"mesh": "mp2"}, "tensor-parallel"),
    ({"draft": "self", "spec_k": 2}, "speculative"),
])
def test_what_a_window_class_cannot_do_yet_refuses(kwargs, reason):
    kw = dict({"prefix_cache": False}, **kwargs)
    with pytest.raises(ValueError, match=reason):
        BatchDecodeEngine(_model(), max_slots=2, max_len=64, chunk=CHUNK, page_size=PAGE, **kw)


def test_the_cache_spec_declares_two_classes():
    spec = _model().cache_spec()
    assert spec[0] == (PoolSpec("k", (256,), 0), PoolSpec("v", (128,), 0))
    assert spec[1] == (PoolSpec("k", (512,), WINDOW), PoolSpec("v", (256,), WINDOW))
    assert PoolSpec("k", (4, 128)).window == 0          # what every other model declares: the full class
