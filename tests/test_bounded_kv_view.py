"""The length-bounded K/V view of the paged decode step (decode_engine
``_view_ladder`` / ``_view_rung`` / ``_view_branches``): a decode call gathers
and attends over ``page_table[:, :n]``, ``n`` the smallest rung of a static
ladder that holds the longest ACTIVE context, not the whole table.

What is pinned here, all on the CPU at tiny widths: tokens and logits equal
the whole-table program's at every rung, a context that crosses a rung
inside a chunk loses no position, a retired slot's stale ``lens`` neither
widens the view nor touches a neighbour's pages, the speculative verify and
the int8-KV reference keep their parity partners, the two counters say what
the program used and reach ``ServingEngine.stats``, and a warmed engine
serves at every rung without compiling anything."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.core import compile_cache
from paddlepaddle_tpu.inference import ServingEngine
from paddlepaddle_tpu.inference import decode_engine as de
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.serving import GenerationRequest
from paddlepaddle_tpu.observability import watchdog

PS, CHUNK, MAX_LEN = 8, 4, 64          # 8 pages a slot
LADDER = (2, 3, 7, 8)


def _llama(hidden=64, seed=0, dtype="float32"):
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=hidden, intermediate_size=2 * hidden,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=MAX_LEN, dtype=dtype))


@pytest.fixture(scope="module")
def model():
    return _llama()


def _engine(model, whole=False, **kw):
    kw.setdefault("max_slots", 4)
    eng = BatchDecodeEngine(model, chunk=CHUNK, page_size=PS, **kw)
    assert eng._ladder == LADDER
    if whole:                  # today's program shape: one rung, the table
        eng._ladder = (eng.P,)
    return eng


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 128, (n,)).astype(np.int32)


def _req(ids, n, eos=None):
    return GenerationRequest(ids, n, 0.0, 0, eos)


def _serve(eng, reqs):
    eng.serve(reqs, timeout=240)
    return [np.asarray(r.result.result(5)) for r in reqs]


def _mixed(longest):
    """Three prompts, the longest of ``longest`` tokens."""
    return [_prompt(n, 10 + i) for i, n in
            enumerate((max(1, longest // 3), max(1, longest // 2), longest))]


# -- the ladder and the rung --------------------------------------------------

@pytest.mark.parametrize("pages, ladder", [
    (64, (16, 24, 56, 64)), (8, (2, 3, 7, 8)), (12, (3, 5, 11, 12)),
    (6, (2, 3, 6)), (2, (1, 2)), (1, (1,)), (100, (25, 38, 88, 100))])
def test_ladder_sits_on_eighths_of_the_table(pages, ladder):
    assert de._view_ladder(pages) == ladder


@pytest.mark.parametrize("lens, active, span, pages", [
    ([11, 0, 0, 0], [1, 0, 0, 0], 4, 2),      # 11 + 4 = 15 positions: 2 pages
    ([12, 0, 0, 0], [1, 0, 0, 0], 4, 2),      # exactly 16: still two pages
    ([13, 0, 0, 0], [1, 0, 0, 0], 4, 3),      # 17: the next rung
    ([13, 60, 0, 0], [1, 0, 0, 0], 4, 3),     # a stale lens does not count
    ([13, 60, 0, 0], [0, 0, 0, 0], 4, 2),     # nobody live: the first rung
    ([5, 22, 0, 0], [1, 1, 0, 0], 3, 7),      # the verify's span k + 1 = 3
    ([5, 21, 0, 0], [1, 1, 0, 0], 3, 3),
    ([5, 53, 0, 0], [1, 1, 0, 0], 4, 8),      # 57 positions: the whole table
    ([5, 62, 0, 0], [1, 1, 0, 0], 4, 8),      # past the table: the top rung
])
def test_rung_follows_the_longest_active_context(model, lens, active, span,
                                                 pages):
    eng = _engine(model)
    rung = eng._view_rung(jnp.asarray(lens, jnp.int32),
                          jnp.asarray(active, bool), span)
    assert rung.dtype == jnp.int32
    assert eng._ladder[int(rung)] == pages
    assert int(eng._view_pages_column(rung)[0, 0]) == pages


# -- (a) bounded against whole-table, at every rung --------------------------

@pytest.mark.parametrize("rung_pages", LADDER)
def test_tokens_equal_whole_table_at_every_rung(model, rung_pages):
    """One admission token plus one chunk of 4: the longest context ends
    at ``rung_pages * 8 - 2``, so the call runs exactly that rung."""
    prompts = _mixed(rung_pages * PS - 2 - CHUNK)
    bounded, whole = _engine(model), _engine(model, whole=True)
    got = _serve(bounded, [_req(p, 1 + CHUNK) for p in prompts])
    want = _serve(whole, [_req(p, 1 + CHUNK) for p in prompts])
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert bounded.stats["decode_calls"] == 1
    assert bounded.stats["decode_view_pages"] == rung_pages
    assert bounded.stats["decode_table_pages"] == bounded.P
    assert whole.stats["decode_view_pages"] == whole.P


def test_logits_agree_to_float32_rounding_on_every_rung_that_holds(model):
    eng = _engine(model)
    for p in _mixed(19):        # lens 6, 9, 19: the 3-page rung holds them
        assert eng._admit(_req(p, 8))
    eng._collect_firsts()
    lens = np.asarray(eng.lens)
    forward = jax.jit(lambda rung: eng._forward_paged(
        eng.params, eng.tokens[:, None], eng.caches, eng.page_table,
        eng.lens, rung)[0])
    logits = [np.asarray(forward(jnp.int32(i)), np.float32)
              for i in range(len(eng._ladder))]
    live = np.asarray(eng.active)
    assert live.sum() == 3
    for i, n in enumerate(eng._ladder):
        if n * PS >= lens.max() + 1:
            np.testing.assert_allclose(logits[i][live], logits[-1][live],
                                       rtol=2e-6, atol=2e-6)
        else:                              # a view too short must show
            assert not np.allclose(logits[i][live], logits[-1][live],
                                   rtol=1e-3, atol=1e-3)
    # a slot the view does hold reads the same on a rung too short for
    # its neighbour: rows are independent
    short = int(np.argmin(np.where(live, lens, 10 ** 6)))
    np.testing.assert_allclose(logits[0][short], logits[-1][short],
                               rtol=2e-6, atol=2e-6)


# -- (b) a context that crosses a rung inside a chunk ------------------------

def test_crossing_a_rung_mid_chunk_loses_no_position(model):
    """10 prompt tokens, 9 new: the first chunk ends at 14 (2 pages), the
    second writes 14..17 across the boundary at 16 and so runs 3 pages
    from its first step."""
    ids = _prompt(10, 3)
    bounded, whole = _engine(model), _engine(model, whole=True)
    got = _serve(bounded, [_req(ids, 9)])[0]
    want = _serve(whole, [_req(ids, 9)])[0]
    np.testing.assert_array_equal(got, want)
    assert bounded.stats["decode_calls"] == 2
    assert bounded.stats["decode_view_pages"] == 2 + 3
    contiguous = BatchDecodeEngine(model, max_slots=4, chunk=CHUNK,
                                   kv_layout="contiguous")
    np.testing.assert_array_equal(got, _serve(contiguous, [_req(ids, 9)])[0])
    # both programs stored the same rows, null page aside (3 pages: 10 + 8
    # positions and the prefill's padding), each to float32 rounding: the
    # sums run over other widths
    for pool_a, pool_b in zip(bounded.caches, whole.caches):
        for mine, theirs in zip(pool_a, pool_b):
            mine, theirs = np.asarray(mine)[1:], np.asarray(theirs)[1:]
            written = np.abs(mine).sum(axis=(2, 3)) > 0
            assert written.sum() == 3 * PS
            np.testing.assert_array_equal(
                written, np.abs(theirs).sum(axis=(2, 3)) > 0)
            np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-5)


# -- (c) a retired slot's stale lens -----------------------------------------

def test_stale_lens_neither_widens_the_view_nor_touches_a_neighbour(model):
    long_ids, short_ids = _prompt(50, 5), _prompt(5, 6)
    eng = _engine(model, max_slots=2)
    a, b = _req(long_ids, 2), _req(short_ids, 13)
    assert eng._admit(a) and eng._admit(b)
    slot_b = next(i for i, s in enumerate(eng._host_slots) if s.req is b)
    pages_b = list(eng._slot_pages[slot_b])
    views = []
    for _ in range(3):
        before = eng.stats["decode_view_pages"]
        eng._decode_chunk()
        views.append(eng.stats["decode_view_pages"] - before)
    # chunk 1 holds the long context (50 + 4 positions: 7 pages);
    # then it is retired, its lens stays at 51 on the device, and the view
    # follows the short one alone: 9 + 4 -> 2 pages, 13 + 4 -> 3 pages
    assert views == [7, 2, 3]
    assert a.result.done() and b.result.done()
    lens, active = np.asarray(eng.lens), np.asarray(eng.active)
    assert lens[1 - slot_b] == 51 and not active.any()
    assert not np.asarray(eng.page_table)[1 - slot_b].any()

    solo = _engine(model, whole=True, max_slots=2)
    c = _req(short_ids, 13)
    assert solo._admit(c)
    pages_c = list(solo._slot_pages[0])
    for _ in range(3):
        solo._decode_chunk()
    np.testing.assert_array_equal(np.asarray(b.result.result(5)),
                                  np.asarray(c.result.result(5)))
    # the neighbour's pages hold what they hold when it is served alone
    for (ka, va), (kb, vb) in zip(eng.caches, solo.caches):
        for mine, alone in ((ka, kb), (va, vb)):
            got = np.asarray(mine)[pages_b].reshape(-1, *mine.shape[2:])
            want = np.asarray(alone)[pages_c].reshape(-1, *alone.shape[2:])
            np.testing.assert_allclose(got[:17], want[:17], rtol=2e-6,
                                       atol=2e-6)


# -- (d) the verify's width and the int8 reference ---------------------------

def _workload():
    """Contexts on every rung: the 45-token one holds 7 pages for its
    chunk and goes, one crosses 16 and then 24 positions while it decodes,
    the short ones stay on the first rung."""
    return [(_prompt(n, 20 + i), budget)
            for i, (n, budget) in enumerate([(3, 9), (12, 24), (45, 4),
                                             (21, 7), (5, 12)])]


def test_speculative_verify_stays_token_exact_across_rungs(model):
    draft = _llama(hidden=32, seed=7)
    plain = ServingEngine(model, max_batch_size=3, decode_chunk=6,
                          kv_page_size=PS)
    spec = ServingEngine(model, max_batch_size=3, decode_chunk=6,
                         kv_page_size=PS, draft=draft, spec_k=2)
    try:
        want = [np.asarray(plain.submit(p, max_new_tokens=n).result(120))
                for p, n in _workload()]
        futs = [spec.submit(p, max_new_tokens=n) for p, n in _workload()]
        for f, w in zip(futs, want):
            np.testing.assert_array_equal(np.asarray(f.result(120)), w)
        st = spec._engine.stats
        assert 0 < st["decode_view_pages"] < st["decode_table_pages"]
        assert st["decode_table_pages"] == st["decode_calls"] * 8
    finally:
        plain.stop()
        spec.stop()


def test_int8_reference_stays_token_exact(model):
    reqs = lambda: [_req(p, n) for p, n in _workload()]
    bounded = _engine(model, kv_quant="int8")
    got = _serve(bounded, reqs())
    other = _engine(model, whole=True, kv_quant="int8")
    for a, b in zip(got, _serve(other, reqs())):
        np.testing.assert_array_equal(a, b)
    st = bounded.stats
    assert 0 < st["decode_view_pages"] < st["decode_table_pages"]
    assert other.stats["decode_view_pages"] \
        == other.stats["decode_table_pages"]


def test_int8_speculative_verify_stays_token_exact_across_rungs(model):
    """The verify's ``W = k + 1`` rows quantised into their pages behind
    the view: the bounded ladder against the whole table, both int8."""
    draft = _llama(hidden=32, seed=7)

    def spec_engine(whole):
        eng = ServingEngine(model, max_batch_size=3, decode_chunk=6,
                            kv_page_size=PS, draft=draft, spec_k=2,
                            kv_quant="int8")
        assert eng._engine._ladder == LADDER
        if whole:
            eng._engine._ladder = (eng._engine.P,)
        return eng

    bounded, whole = spec_engine(False), spec_engine(True)
    try:
        want = [np.asarray(whole.submit(p, max_new_tokens=n).result(120))
                for p, n in _workload()]
        futs = [bounded.submit(p, max_new_tokens=n) for p, n in _workload()]
        for f, w in zip(futs, want):
            np.testing.assert_array_equal(np.asarray(f.result(120)), w)
        st = bounded._engine.stats
        assert 0 < st["decode_view_pages"] < st["decode_table_pages"]
        assert whole._engine.stats["decode_view_pages"] \
            == whole._engine.stats["decode_table_pages"]
    finally:
        bounded.stop()
        whole.stop()


# -- (e) the counters --------------------------------------------------------

def test_counters_reach_the_serving_engine(model):
    eng = ServingEngine(model, max_batch_size=2, decode_chunk=CHUNK,
                        kv_page_size=PS)
    try:
        assert eng.stats["decode_view_pages"] == 0
        assert eng.stats["decode_table_pages"] == 0
        out = eng.submit(_prompt(10, 1), max_new_tokens=1 + 2 * CHUNK)
        assert len(out.result(120)) == 10 + 1 + 2 * CHUNK
        inner = eng._engine.stats
        # 10 + 4 = 14 positions: 2 pages; 14 + 4 = 18: 3 pages
        assert inner["decode_view_pages"] == 2 + 3
        assert inner["decode_table_pages"] == 2 * 8
        # the loop copies the engine's counters once the chunk that
        # delivered the result is booked
        deadline = time.perf_counter() + 10
        while eng.stats["decode_table_pages"] != 16 \
                and time.perf_counter() < deadline:
            time.sleep(0.01)
        for key in ("decode_view_pages", "decode_table_pages"):
            assert eng.stats[key] == inner[key]
            assert eng.health()["stats"][key] == inner[key]
    finally:
        eng.stop()


def test_contiguous_layout_counts_no_view(model):
    eng = BatchDecodeEngine(model, max_slots=2, chunk=CHUNK,
                            kv_layout="contiguous")
    _serve(eng, [_req(_prompt(5, 2), 6)])
    assert eng.stats["decode_calls"] >= 1
    assert eng.stats["decode_view_pages"] == 0
    assert eng.stats["decode_table_pages"] == 0


# -- (f) nothing compiles after warm-up --------------------------------------

def test_warm_engine_serves_every_rung_without_compiling(model, tmp_path):
    watchdog.install(threshold=3)
    assert compile_cache.install(str(tmp_path / "ccache")) is True
    try:
        eng = _engine(model)
        eng.warmup()
        _serve(eng, [_req(_prompt(2, 0), 2)])      # host-side odds and ends
        misses = compile_cache.stats()["misses"]
        compiles = sum(watchdog.compile_counts().values())
        seen = set()
        for rung_pages in LADDER:
            before = eng.stats["decode_view_pages"]
            _serve(eng, [_req(_prompt(rung_pages * PS - 2 - CHUNK, 4),
                              1 + CHUNK)])
            seen.add(eng.stats["decode_view_pages"] - before)
        assert seen == set(LADDER)
        assert compile_cache.stats()["misses"] == misses
        assert sum(watchdog.compile_counts().values()) == compiles
    finally:
        compile_cache.uninstall()
