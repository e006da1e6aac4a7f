"""The sampler runs only what its live slots ask for (``BatchDecodeEngine._sample``):
(a) on every mix of greedy, sampled and filtered slots, in a decode step of 32 slots
and in an admission's single row, a live slot's token equals the straight-line formula
every call ran until PR 37 (kept below as the plain reference) under the same key, and
the branch taken is the cheapest the live slots allow; (b) the decode program of a paged
engine holds ONE conditional around the sampler alone, whose greedy branch has no
``top_k``, no sort and no random bits (tests/test_tpu_compile.py holds the compiled
form for the v5e); (c) through ``ServingEngine`` the tiny Llama,
LongCat and Kimi engines serve the greedy tokens their own tests pin, and a sampled and
a filtered request complete in the same engine; (d) ``sample_calls.*`` count the decode
calls by branch, on the host, and reach ``ServingEngine.stats``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import kimi_k2_reference
from paddlepaddle_tpu.inference.decode_engine import SAMPLE_COUNTERS, BatchDecodeEngine
from paddlepaddle_tpu.inference.serving import GenerationRequest, ServingEngine

import test_kimi_k2 as kimi
import test_longcat_flash as longcat

SLOTS, VOCAB = 32, 300          # a vocabulary over TOP_K_CAP, so the filter's cap is the cap
GREEDY, DRAW, FILTER = range(3)


def straight_line(rows, temps, top_ks, key, kcap=BatchDecodeEngine.TOP_K_CAP):
    """The sampler as it stood: everything for every slot, whatever was asked."""
    kcap = min(kcap, rows.shape[-1])
    topv = jax.lax.top_k(rows, kcap)[0]
    kth = jnp.take_along_axis(topv, jnp.clip(top_ks[:, None] - 1, 0, kcap - 1), axis=1)
    rows = jnp.where((top_ks[:, None] > 0) & (rows < kth), -jnp.inf, rows)
    greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)
    scaled = rows / jnp.maximum(temps[:, None], 1e-6)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def _mix(name):
    """(temps, top_ks, live, the branch a decode step of these slots takes)."""
    rng = np.random.default_rng(5)
    temps, top_ks, live = np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.int32), np.ones(SLOTS, bool)
    live[[3, 17]] = False                                    # two idle slots in every mix, all zeros
    some = lambda lo, hi: rng.integers(lo, hi, SLOTS).astype(np.int32)
    if name == "all_greedy":
        branch = GREEDY
    elif name == "greedy_with_top_k":
        top_ks, branch = some(1, 129), GREEDY
    elif name == "sampled_no_filter":
        temps[:] = rng.uniform(0.3, 1.5, SLOTS)
        branch = DRAW
    elif name == "sampled_with_filter":
        temps[:] = rng.uniform(0.3, 1.5, SLOTS)
        top_ks, branch = some(1, 129), FILTER
    elif name == "mixed":                                   # greedy, greedy with k, sampled, sampled with k
        temps[1::2] = rng.uniform(0.3, 1.5, SLOTS // 2)
        top_ks[2::4], top_ks[3::4] = 7, 40
        branch = FILTER
    elif name == "sampled_beside_greedy_with_top_k":        # nobody who draws filters: no top_k
        temps[1::2] = rng.uniform(0.3, 1.5, SLOTS // 2)
        top_ks[0::2] = 9
        branch = DRAW
    elif name == "retired_slot_with_stale_temps":           # what a finished sampled request leaves behind
        temps[[3, 17]], top_ks[[3, 17]] = (0.9, 1.2), (0, 11)
        top_ks[5] = 4
        branch = GREEDY
    else:
        raise AssertionError(name)
    return temps, top_ks, live, branch


MIXES = ["all_greedy", "greedy_with_top_k", "sampled_no_filter", "sampled_with_filter", "mixed",
         "sampled_beside_greedy_with_top_k", "retired_slot_with_stale_temps"]


@pytest.fixture(scope="module")
def engine():
    return BatchDecodeEngine(longcat._llama(), max_slots=4, max_len=96, chunk=4, page_size=16)


@pytest.fixture
def branches(monkeypatch):
    """The index of every ``lax.switch`` an eager call makes (a traced call's is not known yet)."""
    taken, switch = [], jax.lax.switch

    def spy(index, *a, **kw):
        if not isinstance(index, jax.core.Tracer):
            taken.append(int(index))
        return switch(index, *a, **kw)

    monkeypatch.setattr(jax.lax, "switch", spy)
    return taken


# -- (a) --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ["decode_step", "admission_row"])
@pytest.mark.parametrize("mix", MIXES)
def test_a_live_slots_token_is_the_straight_line_samplers(branches, mix, shape):
    temps, top_ks, live, branch = _mix(mix)
    rows = jax.random.normal(jax.random.PRNGKey(11), (SLOTS, VOCAB), jnp.float32) * 3.0
    rows = rows.at[4, 100].set(rows[4].max()).at[4, 7].set(rows[4].max())      # a tie for the maximum
    key = jax.random.PRNGKey(2037)
    eager = BatchDecodeEngine._sample
    sample, plain = jax.jit(eager), jax.jit(straight_line)
    if shape == "decode_step":
        # as a bf16 head hands them over, many of them tied: the branches widen them, as the plain formula's caller did
        rows = rows.astype(jnp.bfloat16)
        got = np.asarray(sample(rows, temps, top_ks, key, live))
        want = np.asarray(plain(rows.astype(jnp.float32), temps, top_ks, key))
        np.testing.assert_array_equal(got[live], want[live])
        eager(rows, jnp.asarray(temps), jnp.asarray(top_ks), key, jnp.asarray(live))
        assert branches == [branch]
        return
    # an admission samples its one row, live by definition: every slot of the mix in turn
    for s in (0, 1, 2, 3, 5, 17):        # over the mixes: greedy, with k, sampled, sampled with k, stale
        one = (rows[s][None], temps[s][None], top_ks[s][None], key)
        np.testing.assert_array_equal(np.asarray(sample(*one, True)), np.asarray(plain(*one)))
        eager(rows[s][None], jnp.asarray(temps[s])[None], jnp.asarray(top_ks[s])[None], key, True)
        assert branches.pop() == (GREEDY if temps[s] <= 0 else FILTER if top_ks[s] > 0 else DRAW)


def test_a_sampled_slot_draws_what_it_drew_whoever_sits_beside_it():
    """The same key, one slot's row and request: the token does not depend on the branch the batch takes."""
    rows = jax.random.normal(jax.random.PRNGKey(3), (SLOTS, VOCAB), jnp.float32) * 2.0
    temps = jnp.zeros((SLOTS,)).at[6].set(0.8)
    key, live = jax.random.PRNGKey(9), jnp.ones((SLOTS,), bool)
    alone = BatchDecodeEngine._sample(rows, temps, jnp.zeros((SLOTS,), jnp.int32), key, live)             # DRAW
    beside = BatchDecodeEngine._sample(rows, temps.at[20].set(1.1), jnp.zeros((SLOTS,), jnp.int32).at[20].set(3), key, live)
    assert int(alone[6]) == int(beside[6])                                                    # FILTER
    np.testing.assert_array_equal(np.delete(np.asarray(alone), [6, 20]),
                                  np.delete(np.asarray(jnp.argmax(rows, -1)), [6, 20]))


# -- (b) --------------------------------------------------------------------------

def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _names(jaxpr):
    return {e.primitive.name for e in _eqns(jaxpr)}


_FILTERS = {"top_k", "sort"}
_DRAWS = {"random_bits", "threefry2x32", "random_wrap", "random_unwrap", "erf_inv", "log"}


@pytest.mark.parametrize("program", ["decode", "admit"])
def test_the_program_holds_one_conditional_around_the_sampler_alone(engine, program):
    key = "decode" if program == "decode" else next(k for k in engine.compile_plan.keys() if k.startswith("admit_p"))
    fn = engine._decode_program(engine.chunk) if program == "decode" else engine._admit_paged_impl
    jaxpr = jax.make_jaxpr(fn)(*engine._example_args(key)).jaxpr
    top = [e for e in _eqns(jaxpr) if e.primitive.name in _FILTERS]
    conds = [e for e in _eqns(jaxpr) if e.primitive.name == "cond"
             and any(_names(b.jaxpr) & _FILTERS for b in e.params["branches"])]
    assert len(top) == 1 and len(conds) == 1              # no top_k anywhere but in that conditional
    greedy, draw, filtered = (_names(b.jaxpr) for b in conds[0].params["branches"])
    assert "argmax" in greedy and not greedy & (_FILTERS | _DRAWS)
    assert "random_bits" in draw and not draw & _FILTERS
    assert "top_k" in filtered and "random_bits" in filtered
    # the sampler alone: no matmul and no kernel call rides in a branch, so the layers and the head compile once
    assert not (greedy | draw | filtered) & {"dot_general", "pallas_call", "scan", "while"}
    if program == "decode":                               # ... and it sits in the step, under the one scan
        scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1 and conds[0] in list(_eqns(scans[0].params["jaxpr"].jaxpr))
        # the key is split once a step OUTSIDE the branches, so a later sampled request sees today's stream
        assert "random_split" in {e.primitive.name for e in scans[0].params["jaxpr"].jaxpr.eqns}


# -- (c), (d) -----------------------------------------------------------------------

ENGINE = dict(max_batch_size=3, max_len=128, decode_chunk=4, kv_page_size=16, kv_num_pages=40)


def _llama_case():
    """(model, engine options, the check its own tests pin greedy tokens by)."""
    m = longcat._llama()
    pin = lambda prompt, n: np.asarray(m.generate_cached(prompt[None], max_new_tokens=n, temperature=0.0).numpy())[0]
    return m, dict(max_batch_size=3, max_len=96, decode_chunk=4, kv_page_size=16), \
        lambda prompt, out: np.testing.assert_array_equal(out, pin(prompt, len(out) - len(prompt)))


def _longcat_case():
    m = longcat._model(held=(0, 4), seed=7)
    w = longcat._weights(m)
    pin = lambda prompt, n: longcat._greedy_reference(m.config, w, prompt, n, (0, 4))
    return m, ENGINE, lambda prompt, out: np.testing.assert_array_equal(out, pin(prompt, len(out) - len(prompt)))


def _kimi_case():
    """As tests/test_kimi_k2.py pins it: every served token the reference's best to 1e-4 of max|logit|."""
    m = kimi._model(held=(0, 4), seed=7)
    cfg, w = kimi._ref_cfg(m.config), kimi._weights(m)

    def check(prompt, out):
        logits = np.asarray(kimi_k2_reference.forward_logits(cfg, w, jnp.asarray(out)))[len(prompt) - 1:-1]
        served = logits[np.arange(len(out) - len(prompt)), out[len(prompt):]]
        assert np.all(logits.max(-1) - served <= 1e-4 * np.abs(logits).max(-1))

    return m, ENGINE, check


@pytest.mark.parametrize("family", ["llama", "longcat", "kimi"])
def test_greedy_tokens_are_the_pinned_ones_and_sampled_requests_complete_in_the_same_engine(family):
    m, kw, check = {"llama": _llama_case, "longcat": _longcat_case, "kimi": _kimi_case}[family]()
    prompts = [longcat._ids(13, seed=51), longcat._ids(30, seed=52)]
    n = 7
    with ServingEngine(m, **kw) as srv:
        # greedy, one of them with a top_k it cannot feel: the argmax branch in every call
        futs = [srv.submit(prompts[0], max_new_tokens=n, temperature=0.0),
                srv.submit(prompts[1], max_new_tokens=n, temperature=0.0, top_k=5)]
        outs = [np.asarray(f.result(timeout=240)) for f in futs]
        greedy = dict(srv.stats)
        sampled = np.asarray(srv.submit(prompts[0], max_new_tokens=n, temperature=0.8).result(timeout=240))
        drew = dict(srv.stats)
        filtered = np.asarray(srv.submit(prompts[1], max_new_tokens=n, temperature=0.8, top_k=3)
                              .result(timeout=240))
        again = np.asarray(srv.submit(prompts[0], max_new_tokens=n, temperature=0.0).result(timeout=240))
        health = srv.health()["stats"]
    stats = dict(srv.stats)                                   # the loop has copied its last chunk's counters
    for prompt, out in zip(prompts, outs):
        assert np.array_equal(out[:len(prompt)], prompt) and len(out) == len(prompt) + n
        check(prompt, out)
    np.testing.assert_array_equal(again, outs[0])             # greedy again after both other branches ran
    for prompt, out in ((prompts[0], sampled), (prompts[1], filtered)):
        assert len(out) == len(prompt) + n and np.array_equal(out[:len(prompt)], prompt)
        assert ((0 <= out) & (out < 128)).all()               # every tiny model's vocabulary
    # (d) by branch, on the host: they sum to the decode calls at every reading
    calls = lambda st: [st[k] for k in SAMPLE_COUNTERS]
    assert calls(greedy) == [greedy["decode_calls"], 0, 0] and greedy["decode_calls"] > 0
    assert calls(drew)[DRAW] > 0 and calls(drew)[FILTER] == 0
    assert calls(stats)[FILTER] > 0 and calls(stats)[GREEDY] > calls(drew)[GREEDY]
    for st in (drew, stats, health):
        assert sum(calls(st)) == st["decode_calls"]
    assert stats["decode_calls"] == srv._engine.stats["decode_calls"]


def test_sample_calls_follow_the_requests_that_hold_a_slot():
    """One engine, calls of one step: a sampled request beside a greedy one holds the batch on its branch until
    it retires, and not a call longer."""
    eng = BatchDecodeEngine(longcat._llama(), max_slots=3, max_len=96, chunk=1, page_size=16)
    req = lambda n, temp, k: GenerationRequest(longcat._ids(9, seed=n), n, temp, k, None)
    calls = lambda: [eng.stats[k] for k in SAMPLE_COUNTERS]
    assert calls() == [0, 0, 0]
    eng.serve([req(6, 0.0, 0), req(3, 0.7, 4), req(4, 0.7, 0)], timeout=240)
    # budgets 6, 3 and 4 with the first token from the admission: the filtering request lives 2 calls, the
    # drawing one 3, the greedy one 5
    assert calls() == [2, 1, 2] and eng.stats["decode_calls"] == 5
    eng.serve([req(4, 0.0, 7)], timeout=240)                  # a greedy request's top_k asks for nothing
    assert calls() == [5, 1, 2] and eng.stats["decode_calls"] == 8
