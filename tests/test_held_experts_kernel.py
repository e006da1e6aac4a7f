"""The held-experts kernel (ops/kernels/held_experts.py) on the CPU, under the
Pallas interpreter, at tiny widths: (a) it equals the dense form
(``moe._held_experts_dense``) within float32 rounding for every pick pattern
(none touched, all, one, only the last expert, an idle lane picking alone), and
exactly where one expert is touched; (b) the prefetch list: the touched experts
first, in order, the last one repeated, and their number, from the picks and
never from the weights; (c) the rung chosen by the token count; (d) a gradient
raises rather than differentiating the kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlepaddle_tpu.ops.kernels import held_experts as he
from paddlepaddle_tpu.ops.kernels import partition_over
from paddlepaddle_tpu.parallel import moe

D, F, TILE = 32, 256, 128            # two width tiles a touched expert


def _picks(pattern, T, held):
    """``picks [T, k]`` (a held expert's local index; ``held`` an identity
    pick, ``held + 1`` an absent expert) with enough picks a row that the
    rows can touch every held expert."""
    k = max(3, -(-held // T))
    rng = np.random.default_rng(T * 100 + held)
    other = rng.integers(held, held + 2, (T, k))            # identity or absent
    if pattern == "none":
        p = other
    elif pattern == "all":
        p = (np.arange(T * k).reshape(T, k)) % held
    elif pattern == "one":
        p = other.copy()
        p[T // 2, 0] = held // 2
    elif pattern == "last":
        p = np.where(rng.random((T, k)) < 0.5, held - 1, other)
        p[0, 0] = held - 1
    elif pattern == "idle_alone":
        # every row but the last picks among experts 1..held-1 or none; the
        # last row (a lane no request holds) alone picks expert 0
        p = np.where(rng.random((T, k)) < 0.5, rng.integers(1, max(held, 2), (T, k)), other)
        p = np.where(p == 0, held + 1, p)
        p[-1, 0] = 0
    return jnp.asarray(p, jnp.int32)


def _case(pattern, T, held, dtype=jnp.float32, width=F):
    rng = np.random.default_rng(held * 1000 + T)
    f = lambda *s, scale=1.0: jnp.asarray(rng.normal(size=s) * scale, dtype)
    picks = _picks(pattern, T, held)
    w = jnp.asarray(rng.random(picks.shape), jnp.float32)
    # as expert_share_ffn weighs them
    w_local = jnp.sum(jnp.where((picks < held)[..., None], w[..., None], 0.0)
                      * jax.nn.one_hot(picks, held, dtype=jnp.float32), axis=1)
    return (f(T, D), w_local, picks, f(held, D, width, scale=D ** -0.5), f(held, D, width, scale=D ** -0.5),
            f(held, width, D, scale=width ** -0.5))


def _kernel(x, w_local, picks, wg, wu, wd, tile=TILE):
    ids, n = he.picked_experts(picks, wg.shape[0])
    return he.held_experts_decode(x, w_local, ids, n, wg, wu, wd, tile=tile)


PATTERNS = ["none", "all", "one", "last", "idle_alone"]


# -- (a) --------------------------------------------------------------------------

@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("T, held", [(1, 1), (1, 12), (8, 4), (8, 16), (32, 12), (32, 1), (128, 16), (128, 4)])
def test_the_kernel_equals_the_dense_form(pattern, T, held):
    x, w_local, picks, wg, wu, wd = _case(pattern, T, held)
    got, want = np.asarray(_kernel(x, w_local, picks, wg, wu, wd)), \
        np.asarray(moe._held_experts_dense(x, w_local, wg, wu, wd))
    assert got.shape == (T, D) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * max(np.abs(want).max(), 1.0))
    if pattern == "none":
        assert not got.any()
    if pattern == "idle_alone":                      # the lone lane's part is computed, as the dense form computes it
        assert np.abs(got[-1]).max() > 0


@pytest.mark.parametrize("T, held", [(8, 1), (8, 12), (32, 4), (128, 16)])
def test_one_touched_expert_gives_the_dense_form_exactly(T, held):
    """One touched expert and one width tile: the same products, casts and sums as the dense form, whose other
    experts add exact zeros. (At T = 1 XLA's CPU dot takes a vector path that sums in another order.)"""
    x, w_local, picks, wg, wu, wd = _case("one", T, held, width=64)
    np.testing.assert_array_equal(np.asarray(_kernel(x, w_local, picks, wg, wu, wd, tile=None)),
                                  np.asarray(moe._held_experts_dense(x, w_local, wg, wu, wd)))


def test_bf16_operands_stay_inside_the_dense_forms_rounding():
    x, w_local, picks, wg, wu, wd = _case("all", 32, 12, dtype=jnp.bfloat16)
    got = np.asarray(_kernel(x, w_local, picks, wg, wu, wd))
    want = np.asarray(moe._held_experts_dense(x, w_local, wg, wu, wd))
    exact = np.asarray(moe._held_experts_dense(*(a.astype(jnp.float32) for a in (x, w_local, wg, wu, wd))))
    assert np.abs(got - want).max() <= np.abs(want - exact).max() + 1e-6


# -- (b) --------------------------------------------------------------------------

@pytest.mark.parametrize("picks, count, ids, n", [
    ([[5, 2], [5, 6]], 5, [2, 2, 2, 2, 2], 1),             # one touched; identity (5) and absent (6) picks skipped
    ([[3, 1], [1, 6]], 4, [1, 3, 3, 3], 2),                # the touched in order, the last repeated
    ([[0, 1], [2, 3]], 4, [0, 1, 2, 3], 4),                # all touched
    ([[4, 5], [5, 4]], 4, [0, 0, 0, 0], 0),                # none: nothing past the first block is read
    ([[3, 4]], 4, [3, 3, 3, 3], 1),                        # only the last
])
def test_the_prefetch_list(picks, count, ids, n):
    got_ids, got_n = he.picked_experts(jnp.asarray(picks, jnp.int32), count)
    assert np.asarray(got_ids).tolist() == ids and np.asarray(got_n).tolist() == [n]
    assert got_ids.dtype == jnp.int32 and got_n.dtype == jnp.int32


def test_a_pick_that_weighs_nothing_is_still_touched_and_read():
    x, w_local, picks, wg, wu, wd = _case("one", 8, 4)
    ids, n = he.picked_experts(picks, 4)
    assert int(n[0]) == 1 and int(moe.experts_read(picks, 4)) == 1
    got = he.held_experts_decode(x, w_local * 0.0, ids, n, wg, wu, wd)
    assert not np.asarray(got).any()


# -- (c) --------------------------------------------------------------------------

@pytest.mark.parametrize("rows, form", [(1, "kernel"), (128, "kernel"), (129, "dense"), (512, "dense"),
                                        (513, "grouped"), (2048, "grouped"), (2049, "row_blocks")])
def test_the_rung_is_chosen_by_the_token_count(rows, form):
    assert moe.held_form(rows) == form


def test_a_declared_kernel_partition_keeps_the_dense_form():
    with partition_over(object(), "dp"):
        assert moe.held_form(32) == "dense"
        assert int(moe.experts_read(jnp.zeros((32, 2), jnp.int32), 12)) == 12
    assert moe.held_form(32) == "kernel"


@pytest.mark.parametrize("rows, form", [(128, "kernel"), (129, "dense"), (512, "dense"), (513, "grouped")])
def test_expert_share_ffn_runs_the_form_its_rung_names(rows, form, monkeypatch):
    ran = []
    for name, f in (("kernel", "held_experts_decode"), ("dense", "_held_experts_dense"),
                    ("grouped", "_held_experts_grouped")):
        real = getattr(moe, f)
        monkeypatch.setattr(moe, f, lambda *a, _real=real, _name=name, **k: ran.append(_name) or _real(*a, **k))
    rng = np.random.default_rng(rows)
    count = 4
    x = jnp.asarray(rng.normal(size=(rows, D)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32) for s in ((count, D, 64), (count, D, 64), (count, 64, D))]
    y, picks = moe.expert_share_ffn(x, jnp.asarray(rng.normal(size=(D, 12)), jnp.float32), jnp.zeros(12), *ws,
                                    topk=3, scale=1.0, num_routed=12, first=4)
    assert ran == [form] and y.shape == (rows, D)
    touched = len({int(p) for p in np.asarray(picks).ravel() if p < count})
    assert int(moe.experts_read(picks, count)) == (touched if form == "kernel" else count)


# -- (d) --------------------------------------------------------------------------

def test_a_gradient_through_the_kernel_raises():
    x, w_local, picks, wg, wu, wd = _case("all", 8, 4)
    loss = lambda x, wg: _kernel(x, w_local, picks, wg, wu, wd).sum()
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(loss, argnums=(0, 1))(x, wg)
