"""Kernels of the serving path compiled for a TPU v5e WITHOUT one, at the
benchmark's own shapes: what Mosaic refuses (a slice that is not whole tiles,
too much fast memory) shows here and not under the Pallas interpreter. The
topology is described inside a fixture, never at import (one process at a time
may load the TPU's library; every xdist worker imports this file), and every
such compile lives in this one file. Nothing runs: no number here is a time.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here, or another process holds its library
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# serve-agent-saturated's decode attention: 128 slots, 64 heads, a 512 + 64 latent row, 4,096 pages of 64 tokens
S, H, RANK, ROPE, PS, P, PAGES = 128, 64, 512, 64, 64, 64, 4096


def _compiled(one_chip, rope_pool_width):
    from paddlepaddle_tpu.ops.kernels.paged_latent_attention import paged_latent_attention

    bf = jnp.bfloat16
    shape = lambda s, dt=bf: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    args = (shape((S, H, RANK)), shape((S, H, ROPE)), shape((S, RANK)), shape((S, ROPE)),
            shape((PAGES, PS, RANK)), shape((PAGES, PS, rope_pool_width)), shape((S, P), jnp.int32),
            shape((S,), jnp.int32))
    fn = lambda *a: paged_latent_attention(*a, scale=192 ** -0.5, interpret=False)
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rope_pool_width, pool_pads", [
    (ROPE, 1),            # the pool as the cache spec holds it: widened to whole lanes before the kernel copies from it
    (2 * ROPE, 0),        # as the decode program carries it through its steps: nothing left to widen
])
def test_the_latent_decode_kernel_compiles_for_v5e_at_the_agent_cells_shape(one_chip, rope_pool_width, pool_pads):
    text = _compiled(one_chip, rope_pool_width)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    wide_pool = f"bf16[{PAGES},{PS},128]"
    pads = [l for l in text.splitlines() if " pad(" in l and l.split("=")[1].lstrip().startswith(wide_pool)]
    assert len(pads) == pool_pads


def test_the_gqa_decode_kernel_compiles_for_v5e_at_the_docqa_cells_shape(one_chip):
    """serve-docqa-steady's and serve-chat-saturated's decode attention: 32 slots, 32 query over 8 kv heads of 128,
    a 64-page table over 1,152 pages of 64 tokens, bf16. The pools enter as the engine holds them: seeing a page as
    ``[page_size * kvh, hd]`` has to be free (a bitcast), or every call would copy 302 MB."""
    from paddlepaddle_tpu.ops.kernels.paged_gqa_attention import paged_gqa_attention

    slots, heads, kvh, hd, ps, table, pages = 32, 32, 8, 128, 64, 64, 1152
    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    args = (shape((slots, heads, hd)), shape((slots, kvh, hd)), shape((slots, kvh, hd)),
            shape((pages, ps, kvh, hd)), shape((pages, ps, kvh, hd)), shape((slots, table), jnp.int32),
            shape((slots,), jnp.int32))
    fn = lambda *a: paged_gqa_attention(*a, scale=hd ** -0.5, interpret=False)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the pools reach the call as they are: no operation of the program has a result of a pool's size
    moved = re.findall(rf"= bf16\[{pages},[^ ]* (?!bitcast|parameter)\w+\(", text)
    assert not moved, moved


@pytest.mark.parametrize("queries, keys", [(16640, 16640), (8320, 8320)])
def test_the_latent_prefill_kernel_compiles_for_v5e_at_the_longdoc_cells_shapes(one_chip, queries, keys):
    """serve-longdoc-saturated's whole-prompt admissions, the longest and the shortest: 64 heads, 128 + 64 wide queries
    and keys against 128-wide values, bf16, a head's keys and values resident (12.8 MB at 16,640, double-buffered, past
    the default scope of fast memory). One Mosaic call, and its result is the shape ``latent_prefill_roofline`` reads."""
    from paddlepaddle_tpu.ops.kernels.latent_prefill_attention import latent_prefill_attention

    bf = jnp.bfloat16
    shape = lambda s, dt=bf: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    args = (shape((1, queries, 64, 128)), shape((1, queries, 64, 64)), shape((1, keys, 64, 128)), shape((1, keys, 64)),
            shape((1, keys, 64, 128)), shape((1,), jnp.int32))
    fn = lambda *a: latent_prefill_attention(*a, scale=0.1309, interpret=False)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    rows = -(-queries // 512) * 512
    assert re.search(rf'= bf16\[64,{rows},128\]\S* custom-call\(.*custom_call_target="tpu_custom_call"', text)


def _reaches(text, name):
    """The text of the HLO computation ``name`` and of every computation it calls (a conditional's branches are not followed)."""
    body = re.search(rf"^(?:ENTRY )?%{re.escape(name)} .*?^}}", text, re.M | re.S).group(0)
    called = set(re.findall(r"(?:calls|to_apply|body|condition|called_computations)=\{?%([\w.\-]+)", body))
    return body + "".join(_reaches(text, c) for c in sorted(called))


@pytest.mark.parametrize("slots, vocab", [(32, 32768), (128, 16384), (32, 20480)])   # chat and docqa, agent, longdoc
def test_the_sampler_stays_a_conditional_on_v5e_and_its_greedy_branch_is_the_argmax_alone(one_chip, slots, vocab):
    """The compiler for the chip keeps ``_sample``'s three branches as one real conditional (not every branch
    and a select), and the branch an all-greedy step takes neither sorts nor draws. The logits reach the branches as
    the head rounds them (bfloat16 in every cell): a float32 operand would be fused into the head's matmul unrounded."""
    from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine

    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    args = (shape((slots, vocab), jnp.bfloat16), shape((slots,), jnp.float32), shape((slots,), jnp.int32),
            shape((2,), jnp.uint32), shape((slots,), jnp.bool_))
    text = jax.jit(BatchDecodeEngine._sample).lower(*args).compile().as_text()
    conds = re.findall(r" conditional\(.*branch_computations=\{%([\w.\-]+), %([\w.\-]+), %([\w.\-]+)\}", text)
    assert len(conds) == 1, conds
    greedy, draw, filtered = (_reaches(text, name) for name in conds[0])
    sorts, draws = re.compile(r" sort\(|TopK"), re.compile(r"_gumbel|threefry|rng")    # by the operations' own names
    assert not sorts.search(greedy) and not draws.search(greedy) and f"bf16[{slots},{vocab}]" in greedy
    assert draws.search(draw) and not sorts.search(draw)
    assert sorts.search(filtered) and draws.search(filtered)
    # ... and nothing of the kind is left outside the branches
    outside = _reaches(text, re.search(r"^ENTRY %([\w.\-]+) ", text, re.M).group(1))
    assert " conditional(" in outside and not sorts.search(outside) and not draws.search(outside)
