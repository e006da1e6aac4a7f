"""The GQA decode kernel (ops/kernels/paged_gqa_attention.py) on the CPU, under the
Pallas interpreter, at tiny widths: (a) it equals the plain gathered formulation
(``decode_engine._attend_view``) on ragged lengths with idle slots, whatever the
chunk, in float32 to rounding and in bfloat16 inside the reference's own error;
(b) the step's new row is the last key and the pools are read only; (c) a slot
handed the walk length 0 copies no page; (d) the engine through the kernel serves
the tokens the engine through the gathered view serves, and int8 pairs, the
speculative verify's width, narrow heads and a sharding plan keep the view;
(e) the counter ``decode_view_pages`` is the pages the walk copies.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.inference import decode_engine as de
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.serving import GenerationRequest
from paddlepaddle_tpu.ops.kernels import paged_gqa_attention as pga

S, H, KVH, HD, PS, P, PAGES = 6, 4, 2, 16, 8, 8, 64
SCALE = 0.3
# a context of one token; one that ends on a page edge with the new row and one
# whose new row opens a page; lengths in different chunks; one that fills the
# table; a slot whose stale length (29) lies over a zeroed table row
LENS = (0, 2 * PS - 1, 2 * PS, 37, P * PS - 1, 29)
STALE = 5


def _case(dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    table = rng.permutation(np.arange(1, PAGES))[:S * P].reshape(S, P)
    table[STALE] = 0
    return dict(q=f(S, 1, H, HD), k_new=f(S, 1, KVH, HD), v_new=f(S, 1, KVH, HD), k_pool=f(PAGES, PS, KVH, HD),
                v_pool=f(PAGES, PS, KVH, HD), page_table=jnp.asarray(table, jnp.int32),
                lens=jnp.asarray(LENS, jnp.int32))


def _view(c):
    """The plain formulation over the whole table."""
    return de._attend_view(P, PS, H // KVH, SCALE, c["q"], c["k_new"], c["v_new"], c["k_pool"], c["v_pool"],
                           c["page_table"], c["lens"])[:, 0]


def _kernel(c, chunk_pages, lens=None):
    return pga.paged_gqa_attention(c["q"][:, 0], c["k_new"][:, 0], c["v_new"][:, 0], c["k_pool"], c["v_pool"],
                                   c["page_table"], c["lens"] if lens is None else lens, scale=SCALE,
                                   chunk_pages=chunk_pages)


# -- (a) --------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_pages", [1, 2, 3, P])     # 3 does not divide the table: the last chunk is short
def test_the_kernel_equals_the_gathered_view_on_ragged_lengths(chunk_pages):
    c = _case()
    got, want = np.asarray(_kernel(c, chunk_pages)), np.asarray(_view(c))
    # the stale slot walks the null page as the view gathers it: the same numbers, discarded by the engine
    np.testing.assert_allclose(got, want, atol=2e-6 * np.abs(want).max())


@pytest.mark.parametrize("chunk_pages", [2, P])
def test_bfloat16_stays_inside_the_references_own_error(chunk_pages):
    c = {k: (v if v.dtype == jnp.int32 else v.astype(jnp.bfloat16)) for k, v in _case().items()}
    c32 = {k: (v if v.dtype == jnp.int32 else v.astype(jnp.float32)) for k, v in c.items()}   # the rounded inputs
    exact = np.asarray(_view(c32))
    ref_err = np.abs(np.asarray(_view(c), np.float32) - exact).max()
    got = _kernel(c, chunk_pages)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - exact).max()
    assert err <= 2 * ref_err + 1e-3, (err, ref_err)


def test_the_default_chunk_follows_the_page_size(monkeypatch):
    assert pga.pages_per_chunk(64, 64) == pga.CHUNK_TOKENS // 64
    assert pga.pages_per_chunk(16, 8) == 8               # no more than the table
    monkeypatch.setattr(pga, "CHUNK_TOKENS", 16)
    c = _case()
    np.testing.assert_array_equal(np.asarray(_kernel(c, None)), np.asarray(_kernel(c, 2)))


@pytest.mark.parametrize("pool, ok", [
    (jnp.zeros((4, 8, 2, 128)), True), (jnp.zeros((4, 8, 2, 256), jnp.bfloat16), True),
    (jnp.zeros((4, 8, 2, 64)), False),                                   # half a lane tile: no copy's source
    ((jnp.zeros((4, 8, 2, 128), jnp.int8), jnp.zeros((4, 2))), False),   # an int8 (codes, scales) pair
])
def test_a_pool_is_read_in_place_where_its_rows_are_whole_lane_tiles(pool, ok):
    assert pga.reads_in_place(pool) is ok


# -- (b) --------------------------------------------------------------------------

def test_the_new_row_is_the_last_key_and_the_pools_are_read_only():
    c = _case()
    before = np.asarray(c["k_pool"]).copy(), np.asarray(c["v_pool"]).copy()
    base = np.asarray(_kernel(c, 2))
    # what lies in the pool AT and past the new row's position is never a key ...
    s, n = 3, LENS[3]
    page, off = int(c["page_table"][s, n // PS]), n % PS
    loud = dict(c, k_pool=c["k_pool"].at[page, off:].set(50.0), v_pool=c["v_pool"].at[page, off:].set(50.0))
    np.testing.assert_array_equal(np.asarray(_kernel(loud, 2))[s], base[s])
    # ... the operand row is: the slot of one token returns its own values, each kv head's to its query heads,
    # and another row moves the result
    np.testing.assert_allclose(base[0], np.repeat(np.asarray(c["v_new"][0, 0]), H // KVH, axis=0), rtol=1e-6)
    moved = np.asarray(_kernel(dict(c, v_new=c["v_new"].at[s].add(1.0)), 2))
    assert np.abs(moved[s] - base[s]).max() > 1e-3
    np.testing.assert_array_equal(moved[[0, 1, 2, 4]], base[[0, 1, 2, 4]])
    # a key of kv head 0 moves the query heads of kv head 0 and no other
    k_pos = dict(c, k_pool=c["k_pool"].at[int(c["page_table"][s, 0]), 1, 0].add(3.0))
    heads = np.abs(np.asarray(_kernel(k_pos, 2))[s] - base[s]).max(axis=-1)
    assert (heads[:H // KVH] > 1e-4).all() and (heads[H // KVH:] == 0).all()
    # the kernel has one result and no pool among its outputs
    np.testing.assert_array_equal(np.asarray(c["k_pool"]), before[0])
    np.testing.assert_array_equal(np.asarray(c["v_pool"]), before[1])

    def calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    call = list(calls(jax.make_jaxpr(lambda c: _kernel(c, 2))(c).jaxpr))
    assert len(call) == 1 and [v.aval.shape for v in call[0].outvars] == [(S, H, HD)]


# -- (c) --------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_pages", [1, 3])
def test_an_idle_slot_handed_the_walk_length_zero_copies_no_page(chunk_pages):
    """The engine hands ``where(active, lens, 0)``: the retired slot's stale 29 tokens over a zeroed table row
    would copy the null page four times. With nothing finite in the null page, a single copy would show."""
    c = _case()
    c = dict(c, k_pool=c["k_pool"].at[0].set(jnp.nan), v_pool=c["v_pool"].at[0].set(jnp.nan))
    walk = c["lens"].at[STALE].set(0)
    got = np.asarray(_kernel(c, chunk_pages, lens=walk))
    assert np.isfinite(got).all()
    # ... its only key is its new row, and the slots around it (one starts the next one's copies) read as before
    np.testing.assert_allclose(got[STALE], np.repeat(np.asarray(c["v_new"][STALE, 0]), H // KVH, axis=0), rtol=1e-6)
    live = [s for s in range(S) if s != STALE]
    np.testing.assert_allclose(got[live], np.asarray(_view(_case()))[live], atol=2e-6 * 4)
    assert not np.isfinite(np.asarray(_kernel(c, chunk_pages))[STALE]).all()     # the stale length does copy it


# -- (d), (e): through the engine ---------------------------------------------------

E_PS, E_CHUNK, E_MAX_LEN = 8, 4, 64        # 8 pages a slot; the view's ladder is (2, 3, 7, 8)


def _model(head_dim=128, seed=7):
    """Two layers, 4 query over 2 kv heads; heads of 128 are whole lane tiles, heads of 64 are not."""
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=4 * head_dim, intermediate_size=256, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=E_MAX_LEN, dtype="float32"))


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(m, view=False, **kw):
    eng = BatchDecodeEngine(m, **{"max_slots": 4, "chunk": E_CHUNK, "page_size": E_PS, **kw})
    if view:                   # the gathered view, the program of every engine until PR 35
        assert eng._walks_pairs
        eng._walks_pairs = False
    return eng


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(0, 128, (n,)).astype(np.int32)


def _serve(eng, prompts, n):
    reqs = [GenerationRequest(p, n, 0.0, 0, None) for p in prompts]
    eng.serve(reqs, timeout=240)
    return [np.asarray(r.result.result(5)) for r in reqs]


def _count_kernel_calls(monkeypatch):
    traced, kernel = [], de.paged_gqa_attention
    monkeypatch.setattr(de, "paged_gqa_attention", lambda *a, **kw: traced.append(1) or kernel(*a, **kw))
    return traced


def test_the_engine_through_the_kernel_serves_the_gathered_views_tokens(monkeypatch, model):
    """Float32, three slots of different lengths and an idle one, three chunks of 4 steps: the 30-token context
    crosses the page edge at 32 (with chunks of one page, a chunk edge of the walk), the 10-token one crosses the
    view's rung at 16 inside a chunk. Token for token."""
    monkeypatch.setattr(pga, "CHUNK_TOKENS", E_PS)          # K = 1
    prompts = [_ids(30, seed=3), _ids(10, seed=4), _ids(5, seed=5)]
    traced = _count_kernel_calls(monkeypatch)

    def run(view):
        eng = _engine(model, view=view)
        return _serve(eng, prompts, 11), eng.stats

    toks_k, stats_k = run(view=False)
    assert traced                                           # the decode step's attention IS the kernel's call
    del traced[:]
    toks_v, stats_v = run(view=True)
    assert not traced
    for a, b in zip(toks_k, toks_v):
        np.testing.assert_array_equal(a, b)
    # the view read the rung of the longest context in every slot, the walk each live slot's own pages
    assert stats_v["decode_view_pages"] == 7 + 7 + 7        # 34, 38 and 42 positions: the 7-page rung
    assert stats_k["decode_view_pages"] == 3 + 3 + 3        # ceil((5+2+2) / 4), ceil((5+3+2) / 4), ceil((6+3+3) / 4)
    assert stats_k["decode_table_pages"] == stats_v["decode_table_pages"] == 3 * 8


def test_a_steps_logits_agree_with_the_gathered_views_to_rounding(model):
    eng = _engine(model)
    for i, n in enumerate((30, 10, 5)):
        assert eng._admit(GenerationRequest(_ids(n, seed=3 + i), 8, 0.0, 0, None))
    eng._collect_firsts()
    assert int(np.asarray(eng.active).sum()) == 3          # and one idle slot
    forward = jax.jit(lambda walk: eng._forward_paged(
        eng.params, eng.tokens[:, None], eng.caches, eng.page_table, eng.lens, jnp.int32(len(eng._ladder) - 1),
        walk=walk)[0])
    live = np.asarray(eng.active)
    kernel = np.asarray(forward(jnp.where(eng.active, eng.lens, 0)))[live]
    view = np.asarray(forward(None))[live]
    np.testing.assert_allclose(kernel, view, atol=2e-5 * np.abs(view).max())


def test_idle_slots_and_dead_pages_are_never_read(monkeypatch, model):
    """Nothing finite in the null page, which a retired slot's zeroed table row and every table entry past a
    slot's reservation point to: the tokens do not change, so the walk copied none of it. (The gathered view
    reads it for every idle slot, and discards what it computes.)"""
    prompts = [_ids(50, seed=5), _ids(5, seed=6)]

    def run(poison):
        eng = _engine(model, max_slots=3)
        if poison:
            eng.caches = [tuple(p.at[0].set(jnp.nan) for p in layer) for layer in eng.caches]
        reqs = [GenerationRequest(prompts[0], 2, 0.0, 0, None), GenerationRequest(prompts[1], 13, 0.0, 0, None)]
        eng.serve(reqs, timeout=240)        # the long request retires in the first chunk; its lens stays stale
        assert int(np.asarray(eng.lens).max()) == 51 and not np.asarray(eng.active).any()
        return [np.asarray(r.result.result(5)) for r in reqs]

    for a, b in zip(run(poison=True), run(poison=False)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("what", ["int8_pairs", "narrow_heads", "a_sharding_plan", "the_verifys_width"])
def test_what_the_kernel_does_not_take_keeps_the_gathered_view(monkeypatch, model, what):
    """The choice is read from the pools, the static width of the call and the engine's plan; no option."""
    traced = _count_kernel_calls(monkeypatch)
    if what == "the_verifys_width":                       # W = 3 through the same forward, walk or no walk
        eng = _engine(model)
        assert eng._admit(GenerationRequest(_ids(9), 8, 0.0, 0, None))
        toks = jnp.zeros((eng.S, 3), jnp.int32)
        for walk in (None, jnp.where(eng.active, eng.lens, 0)):
            logits, _ = jax.jit(eng._forward_paged)(eng.params, toks, eng.caches, eng.page_table, eng.lens,
                                                    eng._view_rung(eng.lens, eng.active, 3), walk=walk)
            assert logits.shape[:2] == (eng.S, 3)
        assert not traced
        return
    eng = {"int8_pairs": lambda: _engine(model, kv_quant="int8"),
           "narrow_heads": lambda: _engine(_model(head_dim=64)),
           "a_sharding_plan": lambda: _engine(model, mesh="mp2")}[what]()
    assert not eng._walks_pairs
    _serve(eng, [_ids(12, seed=2)], 6)
    assert not traced
    assert eng.stats["decode_view_pages"] == 2 + 3         # the rung's pages in every row: 12 + 4, then 16 + 4


@pytest.mark.parametrize("prompt, slots, new, want", [
    # pages of 8; a call of 4 steps from lens = prompt reports ceil((prompt + 4) / 8) pages for the one live
    # slot and 0 for the others; the engine counts their mean, rounded up
    (10, 1, 5, [2]),                    # 14 tokens
    (14, 1, 5, [3]),                    # 18 tokens: the call crosses a page edge
    (14, 4, 5, [1]),                    # 3 pages over four slots
    (43, 2, 9, [3, 4]),                 # 6 pages, then 51 tokens in 7: over two slots 3 and 4
])
def test_decode_view_pages_counts_the_pages_the_walk_copies(monkeypatch, model, prompt, slots, new, want):
    monkeypatch.setattr(pga, "CHUNK_TOKENS", 2 * E_PS)    # chunks of two pages: the count is by page all the same
    eng = _engine(model, max_slots=slots)
    _serve(eng, [_ids(prompt, seed=5)], new)
    assert eng.stats["decode_calls"] == len(want)
    assert eng.stats["decode_view_pages"] == sum(want)
    assert eng.stats["decode_table_pages"] == len(want) * eng.P
