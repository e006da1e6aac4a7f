"""An admission asks the output head for the one row it samples
(``decode_engine.BatchDecodeEngine._forward`` with ``last``), not for every
row of the prompt's bucket. For a tiny engine of each family (llama with a tied embedding, LongCat
and Kimi-K2 with an ``lm_head``), at float32:

(a) each admission program (paged whole prompt, contiguous whole prompt,
    prefix hit) gives the first token of the full-bucket form (the head over
    every row, then row ``plen - 1``) and writes the same pages, bit for bit,
    at several prompt lengths within one bucket;
(b) no compiled admission program holds a head over more than one row, and
    in a bfloat16 model that row's head sums in float32 (as the matmul over
    the bucket did), while the decode program keeps its head over every slot.
    (``tests/test_tpu_compile.py`` reads the same head compiled for the v5e.)
"""

import dataclasses
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.inference import compile_plan as cp
from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
from paddlepaddle_tpu.models import (KimiK2Config, KimiK2ForCausalLM, LlamaConfig, LlamaForCausalLM,
                                     LongcatFlashConfig, LongcatFlashForCausalLM)

VOCAB = 80          # no other width of the tiny models: a dot whose last dimension is VOCAB is the head
BUCKET, TAIL_BUCKET, PAGE, SLOTS = 32, 16, 16, 2


def _model(family, dtype="float32"):
    paddle.seed(0)
    if family == "llama":
        cfg = dataclasses.replace(LlamaConfig.tiny(vocab_size=VOCAB, max_len=128), tie_word_embeddings=True,
                                  dtype=dtype)
        return LlamaForCausalLM(cfg)
    if family == "longcat":
        return LongcatFlashForCausalLM(LongcatFlashConfig.tiny(vocab_size=VOCAB, dtype=dtype))
    return KimiK2ForCausalLM(KimiK2Config.tiny(vocab_size=VOCAB, dtype=dtype))


def _engine(model, layout):
    return BatchDecodeEngine(model, max_slots=SLOTS, max_len=128, chunk=2, kv_layout=layout, page_size=PAGE,
                             num_pages=20)


def _bucket_wide(self, params, ids, caches, pos, last=None):
    """The admission's forward in its full-bucket form: the head over every row of the bucket, then row ``last``."""
    logits, caches = BatchDecodeEngine._forward(self, params, ids, caches, pos)
    return (logits, caches) if last is None else (logits[0, last][None], caches)


def _program_key(program):
    return cp.prefix_admit_key(1, TAIL_BUCKET) if program == "prefix_hit" else cp.admit_key(BUCKET)


def _seed_state(eng, rng):
    """Pools of random rows (what a prefix hit reads as its cached prefix) and slot 1's pages in the table."""
    eng.caches = [tuple(jnp.asarray(rng.standard_normal(c.shape), c.dtype) for c in layer) for layer in eng.caches]
    if eng.page_table is not None:
        eng.page_table = eng.page_table.at[1, :4].set(jnp.asarray([3, 7, 5, 9], jnp.int32))


@pytest.mark.parametrize("program", ["paged_whole", "contiguous_whole", "prefix_hit"])
@pytest.mark.parametrize("family", ["llama", "longcat", "kimi_k2"])
def test_the_one_row_head_serves_the_full_bucket_forms_first_token_and_pages(family, program):
    model = _model(family)
    layout = "contiguous" if program == "contiguous_whole" else "paged"
    eng, ref = _engine(model, layout), _engine(model, layout)
    ref._forward = types.MethodType(_bucket_wide, ref)
    rng = np.random.default_rng(3)
    _seed_state(eng, rng)
    key = _program_key(program)
    width = TAIL_BUCKET if program == "prefix_hit" else BUCKET
    fast, full = eng._build_program(key), ref._build_program(key)
    firsts = []
    for plen in ((1, 9, 16) if program == "prefix_hit" else (5, 17, 32)):
        ids = np.zeros((1, width), np.int32)
        ids[0, :plen] = rng.integers(0, VOCAB, plen)
        args = eng._admit_args(key, jnp.asarray(ids), plen=plen, slot=1, temp=0.0, eos=-1, budget=4, top_k=0)
        # the caches are donated: each program gets its own copy
        outs = [fn(args[0], jax.tree.map(jnp.copy, args[1]), *args[2:]) for fn in (fast, full)]
        (pools, *_, first), (want_pools, *_, want_first) = outs
        assert int(first) == int(want_first), (plen, int(first), int(want_first))
        for got, want in zip(jax.tree.leaves(pools), jax.tree.leaves(want_pools)):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        firsts.append(int(first))
    assert len(firsts) == 3


def _heads(text):
    """(shape, element type) of the result of every dot or convolution in a lowered program's text whose last
    dimension is the vocabulary: the output head's."""
    found = re.findall(r"stablehlo\.(?:dot_general|convolution)[^\n]*->\s*tensor<([0-9x]+)x([a-z0-9]+)>", text)
    shapes = [(tuple(int(d) for d in s.split("x")), t) for s, t in found]
    return [(s, t) for s, t in shapes if s[-1] == VOCAB]


@pytest.mark.parametrize("family", ["llama", "longcat", "kimi_k2"])
def test_no_compiled_admission_holds_a_head_over_more_than_one_row(family):
    model = _model(family, dtype="bfloat16")
    paged, contiguous = _engine(model, "paged"), _engine(model, "contiguous")
    for eng, key in ((paged, cp.admit_key(BUCKET)), (paged, cp.prefix_admit_key(1, TAIL_BUCKET)),
                     (contiguous, cp.admit_key(BUCKET))):
        heads = _heads(eng._build_program(key).lower(*eng._example_args(key)).as_text())
        assert heads and all(int(np.prod(s[:-1])) == 1 and t == "f32" for s, t in heads), (key, heads)
    for eng in (paged, contiguous):
        key = cp.decode_key()
        heads = _heads(eng._build_program(key).lower(*eng._example_args(key)).as_text())
        assert heads and all(int(np.prod(s[:-1])) == SLOTS and t == "bf16" for s, t in heads), heads
