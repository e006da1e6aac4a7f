"""Faults of the ``longcat_flash`` cell on the CPU at the tiny width of ``test_longcat_family.py``: ``correct`` is
false for one held expert's part left out, for the identity part left out, for the shortcut branch dropped and for a
cache row rounded to float8. Each fault is planted in the program by the test; the reference is left alone."""

import jax.numpy as jnp
import pytest

from benchmark.families import longcat_flash_reference as reference
from test_longcat_family import INIT_STD, LIMITS, cell, write


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    std, reference.INIT_STD = reference.INIT_STD, INIT_STD
    yield write(str(tmp_path_factory.mktemp("bench-longcat-faults")))
    reference.INIT_STD = std


def _not_correct(root):
    res = cell(root)
    assert not res["correct"] and res["checks"]["token_gap"]["value"] > LIMITS["token_gap"]


def test_one_held_experts_part_left_out_is_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.parallel import moe

    real = moe.expert_share_ffn
    monkeypatch.setattr(moe, "expert_share_ffn",
                        lambda x, r, b, wg, wu, wd, **kw: real(x, r, b, wg, wu, wd.at[1].set(0), **kw))
    _not_correct(root)


def test_the_identity_part_left_out_is_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.parallel import moe

    real = moe.expert_share_ffn

    def no_identity(x, router, bias, *w, **kw):
        y, picks = real(x, router, bias, *w, **kw)
        weights, ids = moe.route_scores_topk(x, router, bias, kw["topk"], kw["scale"])
        zero = jnp.sum(jnp.where(ids >= kw["num_routed"], weights, 0.0), -1, keepdims=True)
        return y - x.astype(jnp.float32) * zero, picks

    monkeypatch.setattr(moe, "expert_share_ffn", no_identity)
    _not_correct(root)


def test_the_shortcut_branch_dropped_is_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.parallel import moe

    real = moe.ExpertShareLayer.forward

    def dropped(self, x):
        y, picks = real(self, x)
        return y * 0.0, picks

    monkeypatch.setattr(moe.ExpertShareLayer, "forward", dropped)
    _not_correct(root)


def test_a_cache_row_rounded_to_float8_is_not_correct(root, monkeypatch):
    from paddlepaddle_tpu.inference import decode_engine

    fp8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
    store = decode_engine.BatchDecodeEngine._store_pages
    attend = decode_engine._PagedView.attend_latent

    def rounded_attend(self, *args):
        out, c, r = attend(self, *args)
        return out, fp8(c), fp8(r)

    monkeypatch.setattr(decode_engine.BatchDecodeEngine, "_store_pages",
                        lambda self, pools, rows, *a: store(self, pools, [fp8(r) for r in rows], *a))
    monkeypatch.setattr(decode_engine._PagedView, "attend_latent", rounded_attend)
    _not_correct(root)
