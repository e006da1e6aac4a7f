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


@pytest.mark.parametrize("rows, d, width, held", [(32, 7168, 2048, 12), (128, 6144, 2048, 16)])   # longdoc, agent
def test_the_held_experts_kernel_compiles_for_v5e_at_both_expert_cells_shapes(one_chip, rows, d, width, held):
    """A decode step's held experts through the kernel that reads the touched experts alone: one Mosaic call, and the
    stacked weights reach it as they are (no operation of the program has a result of the weight stack's size: a
    gather of the touched experts' weights would write what the kernel saves)."""
    from paddlepaddle_tpu.ops.kernels.held_experts import held_experts_decode

    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    args = (shape((rows, d)), shape((rows, held), jnp.float32), shape((held,), jnp.int32), shape((1,), jnp.int32),
            shape((held, d, width)), shape((held, d, width)), shape((held, width, d)))
    fn = lambda *a: held_experts_decode(*a, interpret=False)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    moved = re.findall(rf"= bf16\[{held},[^ ]* (?!bitcast|parameter)\w+\(", text)
    assert not moved, moved


# -- the names a device trace shows, and the readers that match them ------------------------------------------------

def _reader_module(name):
    """The benchmark's reader of per-layer metric ``name`` as a module (its ``PATTERN`` with it)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "metrics",
                        name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mosaic_calls(text):
    """Each Mosaic call's HLO text as a device trace names the operation."""
    return [l.strip().removeprefix("ROOT ") for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]


def _kernel_call(kernel, one_chip):
    """(function, abstract arguments) of one call of ``kernel`` at a small shape Mosaic takes."""
    from paddlepaddle_tpu.ops.kernels import (gather_gemm, held_experts, latent_prefill_attention,
                                              paged_gqa_attention, paged_latent_attention)

    bf = jnp.bfloat16
    shape = lambda s, dt=bf: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    i32 = jnp.int32
    if kernel == "paged_gqa_attention":
        return (lambda *a: paged_gqa_attention.paged_gqa_attention(*a, scale=0.088, interpret=False),
                (shape((8, 8, 128)), shape((8, 2, 128)), shape((8, 2, 128)), shape((64, 64, 2, 128)),
                 shape((64, 64, 2, 128)), shape((8, 8), i32), shape((8,), i32)))
    if kernel == "paged_latent_attention":
        return (lambda *a: paged_latent_attention.paged_latent_attention(*a, scale=0.072, interpret=False),
                (shape((8, 16, RANK)), shape((8, 16, ROPE)), shape((8, RANK)), shape((8, ROPE)),
                 shape((64, PS, RANK)), shape((64, PS, ROPE)), shape((8, 8), i32), shape((8,), i32)))
    if kernel == "held_experts":
        return (lambda *a: held_experts.held_experts_decode(*a, interpret=False),
                (shape((32, 512)), shape((32, 4), jnp.float32), shape((4,), i32), shape((1,), i32),
                 shape((4, 512, 512)), shape((4, 512, 512)), shape((4, 512, 512))))
    if kernel == "latent_prefill_attention":
        return (lambda *a: latent_prefill_attention.latent_prefill_attention(*a, scale=0.13, interpret=False),
                (shape((1, 512, 4, 128)), shape((1, 512, 4, 64)), shape((1, 512, 4, 128)), shape((1, 512, 64)),
                 shape((1, 512, 4, 128)), shape((1,), i32)))
    return (lambda x, se, wgu, wd: gather_gemm.gather_gemm_ffn(x, se, wgu, wd, capacity=16, interpret=False),
            (shape((64, 256)), shape((4 * 16,), i32), shape((4, 256, 512)), shape((4, 256, 256))))


# (kernel, the engine program that traces it, the reader of its calls there); every kernel of the package but the flash
# family, which keeps its transformations' names below
NAMED = [("paged_gqa_attention", "decode", "decode.attention_ms_per_step"),
         ("paged_latent_attention", "decode", "decode.attention_ms_per_step"),
         ("held_experts", "decode", "decode.experts_ms_per_step"),
         ("held_experts", "admit", None),
         ("held_experts", None, None),
         ("latent_prefill_attention", "admit", "prefill.attention_ms_per_request.saturated"),
         ("gather_gemm", None, None)]


@pytest.mark.parametrize("kernel, program, reader", NAMED)
def test_each_kernel_names_its_call_after_itself_and_the_program_that_traced_it(one_chip, kernel, program, reader):
    """The call's HLO instruction, the name a device trace and the benchmark's breakdown show, begins with the kernel's
    module name, and carries the engine program that traced it where one declared itself (``kernels.traced_by``):
    the held experts' kernel runs in a decode step and in a short admission at one shape, and only the name tells the
    two apart. The reader of the kernel's part of that program matches the call; no reader matches another program's."""
    from paddlepaddle_tpu.ops import kernels

    fn, args = _kernel_call(kernel, one_chip)
    if program is not None:
        fn = kernels.traced_by(program)(fn)
    calls = _mosaic_calls(jax.jit(fn).lower(*args).compile().as_text())
    want = kernel if program is None else f"{kernel}_{program}"
    assert len(calls) == 1 and re.match(rf"%{want}(\.\d+)? = ", calls[0]), calls
    patterns = {name: _reader_module(name).PATTERN for name in (
        "decode.attention_ms_per_step", "decode.attention_ms_per_step.steady", "decode.experts_ms_per_step",
        "prefill.attention_ms_per_request.saturated")}
    matched = {name for name, pattern in patterns.items() if re.search(pattern, calls[0])}
    assert matched == ({reader} | ({"decode.attention_ms_per_step.steady"} if reader == "decode.attention_ms_per_step"
                                   else set()) if reader else set())


def test_the_flash_kernels_keep_the_names_the_train_readers_match(one_chip, monkeypatch):
    """A train step's flash forward and backward, through the functional entry the models call and under
    ``jax.value_and_grad``: the Mosaic calls are still named after the transformations around them, as
    ``flash_fwd_roofline`` and ``flash_bwd_roofline`` match them (one forward and two backward kernels a layer)."""
    from paddlepaddle_tpu.core.dispatch import unwrap, wrap
    from paddlepaddle_tpu.nn import functional as F

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")      # the route the chip takes
    shape = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out, _ = F.flash_attention(wrap(q), wrap(k), wrap(v), causal=True)
        return jnp.sum(unwrap(out).astype(jnp.float32))

    calls = _mosaic_calls(jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(shape, shape, shape)
                          .compile().as_text())
    fwd = [c for c in calls if re.search(_reader_module("flash_fwd_roofline").PATTERN, c)]
    bwd = [c for c in calls if re.search(_reader_module("flash_bwd_roofline").PATTERN, c)]
    assert (len(fwd), len(bwd), len(calls)) == (1, 2, 3), calls


def test_the_engines_programs_keep_their_module_names_and_name_their_kernels_calls():
    """On the CPU, where the kernels run under the interpreter: a latent model's decode program, whole-prompt
    admission and prefix-hit admission lower to the XLA modules ``jit_run``, ``jit__admit_paged_impl`` and
    ``jit_impl`` (the names the serving readers match), and each names the kernel calls it traces after itself."""
    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.inference import compile_plan as cp
    from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
    from paddlepaddle_tpu.models import LongcatFlashConfig, LongcatFlashForCausalLM

    paddle.seed(0)
    eng = BatchDecodeEngine(LongcatFlashForCausalLM(LongcatFlashConfig.tiny()), max_slots=4, max_len=128, chunk=2,
                            page_size=16, num_pages=40)
    for key, module, named, other in (
            (cp.decode_key(), "jit_run", ("paged_latent_attention_decode", "held_experts_decode"), "_admit"),
            (cp.admit_key(32), "jit__admit_paged_impl", ("held_experts_admit",), "_decode"),
            (cp.prefix_admit_key(1, 16), "jit_impl", ("held_experts_admit",), "_decode")):
        lowered = eng._build_program(key).lower(*eng._example_args(key))
        assert re.search(r"^module @(\S+)", lowered.as_text(), re.M).group(1) == module
        # a named call's operations stand under its name in the locations
        calls = set(re.findall(r'loc\("(\w+)/pallas_call"', lowered.as_text(debug_info=True)))
        assert set(named) <= calls and not any(c.endswith(other) for c in calls), (key, calls)
