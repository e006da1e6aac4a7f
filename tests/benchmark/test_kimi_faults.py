"""Faults of the ``kimi_k2`` cell on the CPU at the tiny width of ``test_kimi_family.py``: ``correct`` is false for the
shared expert left out, for softmax scores in sigmoid's place, for picks that are not renormalised, for positions that
are not scaled (plain RoPE where the configuration states YaRN) and for the long prefill's attention losing a block of
keys. Each fault is planted in the program by the test; the reference is left alone. Readings on the CPU at this width
in float32 (two seeds each; the program reads 0.0): shared expert left out 0.50 / 0.97, softmax scores 0.149 / 0.248, no
renormalisation 0.59 / 0.78, plain RoPE 1.12 / 1.60, a lost block of keys 0.52 / 1.05; the limit is 0.03."""

import jax.numpy as jnp
import pytest

from benchmark.families import kimi_k2_reference as reference
from test_kimi_family import INIT_STD, LIMITS, cell, write


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    std, reference.INIT_STD = reference.INIT_STD, INIT_STD
    yield write(str(tmp_path_factory.mktemp("bench-kimi-faults")))
    reference.INIT_STD = std


def _not_correct(root):
    res = cell(root)
    assert not res["correct"] and res["checks"]["token_gap"]["value"] > LIMITS["token_gap"]
    return res["checks"]["token_gap"]["value"]


def test_the_shared_expert_left_out_is_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.parallel import moe

    real = moe.ExpertShareLayer.forward

    def without_shared(self, x):
        width, self.shared_hidden = self.shared_hidden, 0
        try:
            return real(self, x)
        finally:
            self.shared_hidden = width

    monkeypatch.setattr(moe.ExpertShareLayer, "forward", without_shared)
    _not_correct(root)


def test_softmax_in_sigmoids_place_is_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.parallel import moe

    monkeypatch.setitem(moe.ROUTINGS, "sigmoid", moe.route_scores_topk)
    _not_correct(root)


def test_picks_not_renormalised_are_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.parallel import moe

    real = moe.route_sigmoid_topk

    def raw(x, router, bias, topk, scale):
        weights, ids = real(x, router, bias, topk, 1.0)
        s = jnp.take_along_axis(1.0 / (1.0 + jnp.exp(-jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32)))), ids, -1)
        return scale * s, ids

    monkeypatch.setitem(moe.ROUTINGS, "sigmoid", raw)
    _not_correct(root)


def test_unscaled_rope_is_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.models import kimi_k2

    real = kimi_k2.rope_tables
    monkeypatch.setattr(kimi_k2, "rope_tables", lambda dim, n, theta, scaling=None: real(dim, n, theta, None))
    _not_correct(root)


def test_a_long_prefill_that_loses_a_block_of_keys_is_not_correct(root, monkeypatch):
    """The documents' admissions run blocked (the bound pulled down), and the blocked form forgets its first block."""
    from paddlepaddle_tpu.models import latent_attention

    real = latent_attention._long_attention

    def lossy(q_nope, q_rope, c, k_rope, *a, **kw):
        return real(q_nope, q_rope, c.at[:, :16].set(0), k_rope.at[:, :16].set(0), *a, **kw)

    monkeypatch.setattr(latent_attention, "_SCORE_VALUES", 4 * 40 * 40)
    monkeypatch.setattr(latent_attention, "_long_attention", lossy)
    _not_correct(root)


def test_the_blocked_prefill_as_it_is_stays_correct(root, monkeypatch):
    from paddlepaddle_tpu.models import latent_attention

    monkeypatch.setattr(latent_attention, "_SCORE_VALUES", 4 * 40 * 40)
    res = cell(root)
    assert res["correct"] and res["checks"]["token_gap"]["value"] <= LIMITS["token_gap"]
