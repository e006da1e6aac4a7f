"""The absorbed-latent decode kernel (ops/kernels/paged_latent_attention.py) on
the CPU, under the Pallas interpreter, at tiny widths: (a) it equals the plain
gathered formulation (``decode_engine._attend_view_latent``) on ragged lengths,
whatever the chunk, in float32 to rounding and in bfloat16 inside the
reference's own error; (b) the step's new row is the last key and the pools
are read only; (c) the engine through the kernel serves the tokens the engine
through the gathered view serves, across a page edge; (d) the counter
``decode_view_pages`` is the pages the walk copies.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.inference import decode_engine as de
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.serving import GenerationRequest
from paddlepaddle_tpu.models import LongcatFlashConfig, LongcatFlashForCausalLM
from paddlepaddle_tpu.ops.kernels import paged_latent_attention as pla

S, H, RANK, ROPE, PS, P, PAGES = 6, 4, 16, 8, 8, 8, 64
SCALE = 0.3
# a context of one token; one that ends on a page edge with the new row and one
# whose new row opens a page; lengths in different chunks; one that fills the
# table; an inactive slot (stale length, zeroed table row)
LENS = (0, 2 * PS - 1, 2 * PS, 37, P * PS - 1, 29)
INACTIVE = 5


def _case(dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    table = rng.permutation(np.arange(1, PAGES))[:S * P].reshape(S, P)
    table[INACTIVE] = 0
    return dict(q_abs=f(S, 1, H, RANK), q_rope=f(S, 1, H, ROPE), c_new=f(S, 1, RANK), r_new=f(S, 1, ROPE),
                c_pool=f(PAGES, PS, RANK), r_pool=f(PAGES, PS, ROPE), page_table=jnp.asarray(table, jnp.int32),
                lens=jnp.asarray(LENS, jnp.int32))


def _view(c):
    """The plain formulation over the whole table."""
    return de._attend_view_latent(P, PS, SCALE, c["q_abs"], c["q_rope"], c["c_new"], c["r_new"], c["c_pool"],
                                  c["r_pool"], c["page_table"], c["lens"])[:, 0]


def _kernel(c, chunk_pages):
    return pla.paged_latent_attention(c["q_abs"][:, 0], c["q_rope"][:, 0], c["c_new"][:, 0], c["r_new"][:, 0],
                                      c["c_pool"], c["r_pool"], c["page_table"], c["lens"], scale=SCALE,
                                      chunk_pages=chunk_pages)


# -- (a) --------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_pages", [1, 2, 3, P])     # 3 does not divide the table: the last chunk is short
def test_the_kernel_equals_the_gathered_view_on_ragged_lengths(chunk_pages):
    c = _case()
    got, want = np.asarray(_kernel(c, chunk_pages)), np.asarray(_view(c))
    live = [s for s in range(S) if s != INACTIVE]
    np.testing.assert_allclose(got[live], want[live], atol=2e-6 * np.abs(want).max())
    assert np.isfinite(got[INACTIVE]).all()              # masked garbage, discarded by the engine


@pytest.mark.parametrize("chunk_pages", [2, P])
def test_bfloat16_stays_inside_the_references_own_error(chunk_pages):
    c = {k: (v if v.dtype == jnp.int32 else v.astype(jnp.bfloat16)) for k, v in _case().items()}
    c32 = {k: (v if v.dtype == jnp.int32 else v.astype(jnp.float32)) for k, v in c.items()}   # the rounded inputs
    exact = np.asarray(_view(c32))
    live = [s for s in range(S) if s != INACTIVE]
    ref_err = np.abs(np.asarray(_view(c), np.float32) - exact)[live].max()
    got = _kernel(c, chunk_pages)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - exact)[live].max()
    assert err <= 2 * ref_err + 1e-3, (err, ref_err)


def test_the_default_chunk_follows_the_page_size(monkeypatch):
    assert pla.pages_per_chunk(64, 64) == pla.CHUNK_TOKENS // 64
    assert pla.pages_per_chunk(16, 8) == 8               # no more than the table
    monkeypatch.setattr(pla, "CHUNK_TOKENS", 16)
    c = _case()
    np.testing.assert_array_equal(np.asarray(_kernel(c, None)), np.asarray(_kernel(c, 2)))


# -- (b) --------------------------------------------------------------------------

def test_the_new_row_is_the_last_key_and_the_pools_are_read_only():
    c = _case()
    before = np.asarray(c["c_pool"]).copy(), np.asarray(c["r_pool"]).copy()
    base = np.asarray(_kernel(c, 2))
    # what lies in the pool AT and past the new row's position is never a key ...
    s, n = 3, LENS[3]
    page, off = int(c["page_table"][s, n // PS]), n % PS
    loud = dict(c, c_pool=c["c_pool"].at[page, off:].set(50.0), r_pool=c["r_pool"].at[page, off:].set(50.0))
    np.testing.assert_array_equal(np.asarray(_kernel(loud, 2))[s], base[s])
    # ... the operand row is: the slot of one token returns its own row, and another row moves the result
    np.testing.assert_allclose(base[0], np.broadcast_to(np.asarray(c["c_new"][0, 0]), (H, RANK)), rtol=1e-6)
    moved = np.asarray(_kernel(dict(c, c_new=c["c_new"].at[s].add(1.0)), 2))
    assert np.abs(moved[s] - base[s]).max() > 1e-3
    np.testing.assert_array_equal(moved[[0, 1, 2, 4]], base[[0, 1, 2, 4]])
    # the kernel has one result and no pool among its outputs
    np.testing.assert_array_equal(np.asarray(c["c_pool"]), before[0])
    np.testing.assert_array_equal(np.asarray(c["r_pool"]), before[1])
    def calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    call = list(calls(jax.make_jaxpr(lambda c: _kernel(c, 2))(c).jaxpr))
    assert len(call) == 1 and [v.aval.shape[:2] for v in call[0].outvars] == [(S, H)]


# -- (c), (d): through the engine ---------------------------------------------------

def _model(seed=7):
    paddle.seed(seed)
    return LongcatFlashForCausalLM(LongcatFlashConfig.tiny(held=(0, 4)))


def _engine(m, **kw):
    return BatchDecodeEngine(m, **{"max_slots": 4, "max_len": 128, "chunk": 4, "page_size": 16, "num_pages": 40,
                                   **kw})


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(0, 128, (n,)).astype(np.int32)


def _serve(eng, prompts, n):
    reqs = [GenerationRequest(p, n, 0.0, 0, None) for p in prompts]
    eng.serve(reqs, timeout=240)
    return [np.asarray(r.result.result(5)) for r in reqs]


def _gathered(q_abs, q_rope, c_new, r_new, c_pool, r_pool, page_table, lens, *, scale):
    """``paged_latent_attention``'s contract by the plain formulation over the whole table (the decode program
    holds the pools widened with zero lanes: the queries and the new row are widened alike, the result cut)."""
    rank = q_abs.shape[-1]
    q_abs, c_new = de._as_row_of(c_pool, q_abs), de._as_row_of(c_pool, c_new)
    q_rope, r_new = de._as_row_of(r_pool, q_rope), de._as_row_of(r_pool, r_new)
    out = de._attend_view_latent(page_table.shape[1], c_pool.shape[1], scale, q_abs[:, None], q_rope[:, None],
                                 c_new[:, None], r_new[:, None], c_pool, r_pool, page_table, lens)
    return out[:, 0, :, :rank]


def test_the_engine_through_the_kernel_serves_the_gathered_views_tokens(monkeypatch):
    """Float32, two slots of different lengths, a chunk of 4 steps that crosses the page edge at 32 (and with
    chunks of one page, a chunk edge): token for token, and the last step's logits to rounding."""
    monkeypatch.setattr(pla, "CHUNK_TOKENS", 16)          # K = 1: the 30-token context walks two chunks
    prompts = [_ids(30, seed=3), _ids(11, seed=4)]

    def run():
        eng = _engine(_model())
        toks = _serve(eng, prompts, 7)
        step = jnp.zeros((eng.S, 1), jnp.int32)
        logits, _ = jax.jit(eng._forward_paged)(eng.params, step, eng.caches, eng.page_table, eng.lens,
                                                jnp.int32(0))
        return toks, np.asarray(logits)

    traced, kernel = [], de.paged_latent_attention
    monkeypatch.setattr(de, "paged_latent_attention", lambda *a, **kw: traced.append(1) or kernel(*a, **kw))
    toks_k, logits_k = run()
    assert traced                                         # the decode step's attention IS the kernel's call
    monkeypatch.setattr(de, "paged_latent_attention", _gathered)
    toks_v, logits_v = run()
    for a, b in zip(toks_k, toks_v):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(logits_k, logits_v, atol=2e-5 * np.abs(logits_v).max())


@pytest.mark.parametrize("extent, page_size, table, pages", [
    (1, 16, 8, 1),              # one token, one page
    (32, 16, 8, 2),             # ends on a page's edge
    (33, 16, 8, 3),
    (128, 16, 8, 8),            # the table itself
    (200, 16, 8, 8),            # a stale length: never past the table
    (900, 64, 64, 15),          # the agent cell's geometry: 900 tokens read 960, not the longest slot's 3,584
])
def test_pages_walked_are_the_pages_that_hold_a_key(extent, page_size, table, pages):
    assert int(pla.pages_walked(jnp.int32(extent), page_size, table)) == pages


@pytest.mark.parametrize("prompt, slots, new, want", [
    # pages of 16; a call of 4 steps from lens = prompt reports ceil((prompt + 4) / 16) pages for the one live
    # slot and 0 for the others; the engine counts their mean, rounded up
    (20, 1, 5, [2]),                    # 24 tokens
    (30, 1, 5, [3]),                    # 34 tokens: the call crosses a page edge
    (30, 4, 5, [1]),                    # 3 pages over four slots
    (27, 2, 9, [1, 2]),                 # 2 pages, then 35 tokens in 3: over two slots 1 and 2
])
def test_decode_view_pages_counts_the_pages_the_walk_copies(monkeypatch, prompt, slots, new, want):
    monkeypatch.setattr(pla, "CHUNK_TOKENS", 32)          # chunks of two pages: the count is by page all the same
    eng = _engine(_model(), max_slots=slots)
    _serve(eng, [_ids(prompt, seed=5)], new)
    assert eng.stats["decode_calls"] == len(want)
    assert eng.stats["decode_view_pages"] == sum(want)
    assert eng.stats["decode_table_pages"] == len(want) * eng.P
