"""int8 KV pages behind the paged view (decode_engine ``_PagedView``): the
model's OWN layer runs, and the view decides, from the pools it is handed,
that a K/V pair is an int8 ``(codes, scales)`` pair: it quantises the call's
new rows into their pages, attends the same bytes on the rung's branch, and
hands the pairs back in place of rows.

Pinned here on the CPU at tiny widths: a decoder layer that is not llama's
serves with int8 KV (its extra residual branch runs), the view's store IS
``_kv_quant_scatter`` (a row that fits its page's scale leaves the page's
codes alone, one that does not rescales it), its attention is the float32
reference over the dequantised WHOLE table at every rung and width, neither
engine knows ``fused_kernels=`` any more, and the compile plan's facts still
keep an int8 bundle from a bf16 engine."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.inference import ServingEngine
from paddlepaddle_tpu.inference import compile_plan as cp
from paddlepaddle_tpu.inference import decode_engine as de
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.inference.serving import GenerationRequest
from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddlepaddle_tpu.models.llama import LlamaDecoderLayer, LlamaMLP
from paddlepaddle_tpu.nn import LayerList

PS = 8


def _config():
    return LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=192,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=96, dtype="float32")


def _llama():
    paddle.seed(0)
    return LlamaForCausalLM(_config())


class _BranchLayer(LlamaDecoderLayer):
    """Llama's decoder layer with one more residual branch, under the name
    ``models/moe.py`` gives its own: a layer that only its own ``forward``
    runs whole."""

    def __init__(self, config):
        super().__init__(config)
        self.shared_mlp = LlamaMLP(config)

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None):
        out = super().forward(x, cos, sin, attn_mask, cache=cache, pos=pos)
        y, kept = out if cache is not None else (out, None)
        y = y + self.shared_mlp(self.post_attention_layernorm(y))
        return (y, kept) if cache is not None else y


def _branch_model(branch=True):
    cfg = _config()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.model.layers = LayerList(
        [_BranchLayer(cfg) for _ in range(cfg.num_hidden_layers)])
    if not branch:
        for layer in model.model.layers:
            w = layer.shared_mlp.down_proj.weight
            w._replace_data(jnp.zeros_like(w._data))
    return model


def _req(ids, n):
    return GenerationRequest(ids, n, 0.0, 0, None)


def _prompts(seed, lens=(12, 20, 7)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 127, size=(n,)).astype(np.int32) for n in lens]


def _serve(eng, reqs):
    eng.serve(reqs, timeout=240)
    return [np.asarray(r.result.result(5)) for r in reqs]


# -- int8 KV through the model's own layer ------------------------------------

def test_a_layer_that_is_not_llamas_serves_with_int8_kv_and_its_branch_runs():
    prompts = _prompts(seed=1)

    def run(model, **kw):
        eng = BatchDecodeEngine(model, max_slots=4, chunk=4, page_size=PS,
                                **kw)
        return _serve(eng, [_req(p, 8) for p in prompts])

    model = _branch_model()
    full = run(model)
    quant = run(model, kv_quant="int8")
    agree = np.mean([np.mean(a[len(p):] == b[len(p):])
                     for a, b, p in zip(full, quant, prompts)])
    assert agree >= 0.9, f"greedy top-1 agreement {agree} < 0.9"
    # the same weights with the branch's output zeroed serve other tokens:
    # the branch is in the int8 program
    without = run(_branch_model(branch=False), kv_quant="int8")
    assert any((a != b).any() for a, b in zip(quant, without))


# -- the view's store is _kv_quant_scatter -------------------------------------

@pytest.mark.parametrize("W", [1, 3])
def test_the_views_store_is_the_quantising_scatter(monkeypatch, W):
    """One ``_forward_paged`` call of width ``W`` over three live slots:
    every layer's pools afterwards equal ``_kv_quant_scatter`` of the rows
    its attention was handed, at the pages and offsets the table gives.
    Slot 0's pages are given scales the new rows fit (no code of the page
    moves, the scale stays), slot 1's scales they outgrow (the page is
    requantised under a larger scale)."""
    eng = BatchDecodeEngine(_llama(), max_slots=4, chunk=4, page_size=PS,
                            kv_quant="int8")
    for p in _prompts(seed=2, lens=(14, 6, 21)):      # 14 + 3 crosses a page
        assert eng._admit(_req(p, 8))
    eng._collect_firsts()
    lens, table = np.asarray(eng.lens), np.asarray(eng.page_table)
    pos = lens[:, None] + np.arange(W)[None, :]
    phys = table[np.arange(eng.S)[:, None], pos // PS]
    off = pos % PS
    assert len(set(phys[0])) == (2 if W == 3 else 1)
    roomy, tight = phys[0], phys[1]
    older = np.ones((eng.pool.num_pages, PS), bool)
    older[phys, off] = False           # every (page, offset) the call writes

    def rescaled(pair):
        codes, scales = pair
        return codes, scales.at[roomy].set(64.0).at[tight].multiply(1 / 64.0)

    before = [tuple(rescaled(pair) for pair in layer)
              for layer in eng.caches]
    handed = []
    attend = de._PagedView.attend

    def spy(self, q, k_new, v_new, *rest):
        handed.append((k_new, v_new))
        return attend(self, q, k_new, v_new, *rest)

    monkeypatch.setattr(de._PagedView, "attend", spy)
    toks = jnp.tile(eng.tokens[:, None], (1, W))
    rung = eng._view_rung(eng.lens, eng.active, W)
    _, after = eng._forward_paged(eng.params, toks, before, eng.page_table,
                                  eng.lens, rung)
    assert len(handed) == len(after) == 2
    for pools, new_pools, rows in zip(before, after, handed):
        for (codes, scales), (got_c, got_s), new in zip(pools, new_pools,
                                                        rows):
            assert got_c.dtype == jnp.int8 and got_s.dtype == jnp.float32
            want_c, want_s = de._kv_quant_scatter(
                codes, scales, new.astype(eng._kv_dtype), jnp.asarray(phys),
                jnp.asarray(off))
            np.testing.assert_array_equal(np.asarray(got_c),
                                          np.asarray(want_c))
            np.testing.assert_array_equal(np.asarray(got_s),
                                          np.asarray(want_s))
            codes, got_c = np.asarray(codes), np.asarray(got_c)
            scales, got_s = np.asarray(scales), np.asarray(got_s)
            # the row fits: the scale stays and no older code moves
            np.testing.assert_array_equal(got_s[roomy], scales[roomy])
            np.testing.assert_array_equal(got_c[roomy][older[roomy]],
                                          codes[roomy][older[roomy]])
            # the row outgrows its page's scale: a larger one, codes redone
            assert (got_s[tight] > scales[tight]).all()
            assert (got_c[tight] != codes[tight]).any()


# -- the view's int8 attention against the whole dequantised table -------------

LADDER = (2, 3, 7, 8)


@pytest.mark.parametrize("W", [1, 3])
@pytest.mark.parametrize("rung", range(len(LADDER)))
def test_int8_attention_through_the_view_is_the_whole_tables(rung, W):
    """``attend`` over an int8 pair on each rung, contexts as long as the
    rung holds, against ``_ref_gqa_attention`` over the WHOLE table
    dequantised after the same scatter: the pages a rung leaves out hold
    masked positions alone."""
    S, P, kvh, rep, hd = 3, 8, 2, 2, 16
    n_pages = S * P + 1
    rng = np.random.default_rng(10 * rung + W)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                        .reshape(S, P), jnp.int32)
    longest = LADDER[rung] * PS - W               # fills the rung exactly
    lens = jnp.asarray([longest, longest // 2, 1], jnp.int32)

    def pool():
        return (jnp.asarray(rng.integers(-127, 128, (n_pages, PS, kvh, hd)),
                            jnp.int8),
                jnp.asarray(rng.uniform(0.004, 0.02, (n_pages, kvh)),
                            jnp.float32))

    kpair, vpair = pool(), pool()
    q = jnp.asarray(rng.standard_normal((S, W, kvh * rep, hd)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((S, W, kvh, hd)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((S, W, kvh, hd)), jnp.float32)
    pos = lens[:, None] + jnp.arange(W)[None, :]
    phys = table[jnp.arange(S)[:, None], pos // PS]
    off = pos % PS
    eng = SimpleNamespace(_ladder=LADDER, page_size=PS,
                          _kv_dtype=jnp.float32)
    view = de._PagedView(eng, (kpair, vpair), table, jnp.int32(rung), phys,
                         off)
    scale = hd ** -0.5
    out, kept_k, kept_v = jax.jit(
        lambda *a: view.attend(*a, lens, rep, scale))(q, k_new, v_new)

    scatter = jax.jit(de._kv_quant_scatter)
    want_k = scatter(*kpair, k_new, phys, off)
    want_v = scatter(*vpair, v_new, phys, off)
    for got, want in ((kept_k, want_k), (kept_v, want_v)):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    whole = [de._kv_dequant_gather(c, s, table, jnp.float32).reshape(
        S, P * PS, kvh, hd) for c, s in (want_k, want_v)]
    ref = de._ref_gqa_attention(q, *whole, lens, rep=rep, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-6,
                               atol=2e-6)
    # what the layer's owner keeps IS the pair: stored() writes no row
    assert view.stored((kept_k, kept_v)) == (kept_k, kept_v)


# -- the option is gone; the plan still tells the store formats apart ----------

@pytest.mark.parametrize("engine", [BatchDecodeEngine, ServingEngine])
def test_neither_engine_knows_fused_kernels(engine):
    with pytest.raises(TypeError, match="fused_kernels"):
        engine(_llama(), fused_kernels=True)


def test_plan_facts_name_no_kernel_choice_and_keep_int8_bundles_apart(
        tmp_path):
    model = _llama()
    kw = dict(max_slots=2, chunk=4, page_size=PS)
    bf16 = BatchDecodeEngine(model, **kw)
    int8 = BatchDecodeEngine(model, kv_quant="int8", **kw)
    for eng in (bf16, int8):
        assert "fused" not in eng.compile_plan.facts
    assert int8.compile_plan.facts["kv_quant"] == "int8"
    # a manifest alone (no program serialised) is enough to be refused
    path = str(tmp_path / "int8_bundle")
    int8.save_serving_bundle(path, keys=[])
    with pytest.raises(cp.BundleMismatchError, match="kv_quant"):
        bf16.load_serving_bundle(path, strict=True)
    assert int8.load_serving_bundle(path, strict=True) is True
