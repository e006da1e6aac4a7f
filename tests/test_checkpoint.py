"""Distributed checkpoint: roundtrip, async save, reshard-on-load."""

import numpy as np
import pytest

import paddlepaddle_tpu as paddle
from paddlepaddle_tpu.distributed import checkpoint as dist_ckpt


def test_save_load_roundtrip(tmp_path):
    m = paddle.nn.Linear(4, 3)
    sd = m.state_dict()
    orig = {k: v.numpy().copy() for k, v in sd.items()}
    dist_ckpt.save_state_dict(sd, str(tmp_path / "ckpt"))

    m2 = paddle.nn.Linear(4, 3)
    sd2 = m2.state_dict()
    dist_ckpt.load_state_dict(sd2, str(tmp_path / "ckpt"))
    for k in orig:
        np.testing.assert_allclose(sd2[k].numpy(), orig[k])


def test_async_save(tmp_path):
    m = paddle.nn.Linear(8, 8)
    sd = m.state_dict()
    dist_ckpt.save_state_dict(sd, str(tmp_path / "ckpt"), async_save=True)
    dist_ckpt.wait_all_saves()
    meta = dist_ckpt.get_checkpoint_metadata(str(tmp_path / "ckpt"))
    assert set(meta["tensors"]) == set(sd.keys())


def test_reshard_on_load_across_meshes(tmp_path):
    """Save params sharded on mesh A; load into params sharded on mesh B."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlepaddle_tpu.distributed.mesh import ProcessMesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")

    mesh_a = ProcessMesh(shape=[2, 4], dim_names=["x", "y"]).to_jax()
    mesh_b = ProcessMesh(shape=[4, 2], dim_names=["x", "y"]).to_jax()
    val = np.arange(64, dtype=np.float32).reshape(8, 8)

    t = paddle.to_tensor(val)
    t._replace_data(jax.device_put(t._data, NamedSharding(mesh_a, P("x", "y"))))
    dist_ckpt.save_state_dict({"w": t}, str(tmp_path / "ckpt"))
    meta = dist_ckpt.get_checkpoint_metadata(str(tmp_path / "ckpt"))
    assert meta["tensors"]["w"]["sharding"]["mesh_shape"] == [2, 4]

    t2 = paddle.to_tensor(np.zeros_like(val))
    t2._replace_data(jax.device_put(t2._data, NamedSharding(mesh_b, P("y", "x"))))
    dist_ckpt.load_state_dict({"w": t2}, str(tmp_path / "ckpt"))
    np.testing.assert_allclose(t2.numpy(), val)
    # sharding of the TARGET is preserved (reshard-on-load)
    assert t2._data.sharding.mesh.shape == {"x": 4, "y": 2}


def test_sharded_save_writes_per_shard_files(tmp_path):
    """v2 format: one file per unique shard, none holding the global value,
    replicated shards deduped (reference save_state_dict.py:63,117)."""
    import os

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlepaddle_tpu.distributed.mesh import ProcessMesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")

    mesh = ProcessMesh(shape=[4, 2], dim_names=["dp", "tp"]).to_jax()
    val = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    t = paddle.to_tensor(val)
    # sharded over tp only -> 2 unique shards, 4-way replicated each
    t._replace_data(jax.device_put(t._data, NamedSharding(mesh, P(None, "tp"))))
    dist_ckpt.save_state_dict({"w": t}, str(tmp_path / "ckpt"))

    meta = dist_ckpt.get_checkpoint_metadata(str(tmp_path / "ckpt"))
    rec = meta["tensors"]["w"]
    assert meta["format"].endswith("v3")
    assert len(rec["shards"]) == 2  # deduped: 8 device shards -> 2 unique
    boxes = sorted(tuple(map(tuple, s["box"])) for s in rec["shards"])
    assert boxes == [((0, 8), (0, 8)), ((0, 8), (8, 16))]
    for s in rec["shards"]:
        shard = np.load(os.path.join(tmp_path / "ckpt", s["file"]))
        assert shard.shape == (8, 8)  # local bytes only, not the global value


def test_reshard_hybrid_to_hybrid(tmp_path):
    """dp4xtp2 -> dp2xfsdp2xtp2 round trip (the VERDICT's target case):
    different axis count, different partition dims, values must survive."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlepaddle_tpu.distributed.mesh import ProcessMesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")

    rng = np.random.default_rng(7)
    vals = {
        "wq": rng.standard_normal((16, 8)).astype(np.float32),
        "wo": rng.standard_normal((8, 16)).astype(np.float32),
        "scale": rng.standard_normal((16,)).astype(np.float32),
    }
    mesh_a = ProcessMesh(shape=[4, 2], dim_names=["dp", "tp"]).to_jax()
    specs_a = {"wq": P(None, "tp"), "wo": P("tp", None), "scale": P()}
    sd = {}
    for k, v in vals.items():
        t = paddle.to_tensor(v.copy())
        t._replace_data(jax.device_put(t._data, NamedSharding(mesh_a, specs_a[k])))
        sd[k] = t
    dist_ckpt.save_state_dict(sd, str(tmp_path / "ckpt"), async_save=True)
    dist_ckpt.wait_all_saves()

    mesh_b = ProcessMesh(shape=[2, 2, 2], dim_names=["dp", "fsdp", "tp"]).to_jax()
    specs_b = {"wq": P(("dp", "fsdp"), "tp"), "wo": P("tp", "fsdp"),
               "scale": P("fsdp")}
    sd2 = {}
    for k, v in vals.items():
        t = paddle.to_tensor(np.zeros_like(v))
        t._replace_data(jax.device_put(t._data, NamedSharding(mesh_b, specs_b[k])))
        sd2[k] = t
    dist_ckpt.load_state_dict(sd2, str(tmp_path / "ckpt"))
    for k, v in vals.items():
        np.testing.assert_allclose(sd2[k].numpy(), v)
        assert sd2[k]._data.sharding.mesh.shape == {"dp": 2, "fsdp": 2, "tp": 2}


def test_multihost_save_merges_rank_metadata(tmp_path):
    """Two simulated hosts (save_state_dict.py:46,63,145 semantics): each
    writes only its local shards + a rank record; the coordinator merges
    them (deduping boxes both hosts replicate) into one metadata.json that
    loads as the full global state."""
    import numpy as np

    from paddlepaddle_tpu.distributed.checkpoint import LocalShards

    w = np.arange(12, dtype=np.float32).reshape(4, 3)
    b = np.arange(3, dtype=np.float32)
    ck = str(tmp_path / "ckpt")
    # non-coordinator host 1 first: rows 2:4 of w + its replica of b
    dist_ckpt.save_state_dict(
        {"w": LocalShards((4, 3), "float32", [([[2, 4], [0, 3]], w[2:4])]),
         "b": LocalShards((3,), "float32", [([[0, 3]], b)])},
        ck, process_index=1, process_count=2)
    # coordinator host 0: rows 0:2 + its replica of b; merges on return
    dist_ckpt.save_state_dict(
        {"w": LocalShards((4, 3), "float32", [([[0, 2], [0, 3]], w[0:2])]),
         "b": LocalShards((3,), "float32", [([[0, 3]], b)])},
        ck, process_index=0, process_count=2)

    meta = dist_ckpt.get_checkpoint_metadata(ck)
    assert meta["world_size"] == 2
    assert len(meta["tensors"]["w"]["shards"]) == 2
    assert len(meta["tensors"]["b"]["shards"]) == 1  # replica deduped
    out = {"w": np.zeros((4, 3), np.float32), "b": np.zeros((3,), np.float32)}
    dist_ckpt.load_state_dict(out, ck)
    np.testing.assert_allclose(out["w"], w)
    np.testing.assert_allclose(out["b"], b)


def test_multihost_merge_times_out_on_missing_rank(tmp_path):
    from paddlepaddle_tpu.distributed.checkpoint import LocalShards

    with pytest.raises(TimeoutError, match="rank"):
        dist_ckpt.save_state_dict(
            {"w": LocalShards((2,), "float32",
                              [([[0, 2]], np.zeros(2, np.float32))])},
            str(tmp_path / "ckpt"), process_index=0, process_count=2,
            merge_timeout=0.3)


def test_async_save_flushed_at_process_exit(tmp_path):
    """A process that async-saves and exits WITHOUT calling wait_all_saves
    must still leave a complete checkpoint (the atexit flush)."""
    import subprocess
    import sys

    ck = str(tmp_path / "ckpt")
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {repr(str(__import__('pathlib').Path(__file__).resolve().parent.parent))})\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np\n"
        "import paddlepaddle_tpu as paddle\n"
        "from paddlepaddle_tpu.distributed import checkpoint as dist_ckpt\n"
        "m = paddle.nn.Linear(64, 64)\n"
        f"dist_ckpt.save_state_dict(m.state_dict(), {ck!r}, async_save=True)\n"
        "sys.exit(0)\n"  # no wait_all_saves: atexit must flush
    )
    env = dict(__import__("os").environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    m2 = paddle.nn.Linear(64, 64)
    sd2 = m2.state_dict()
    dist_ckpt.load_state_dict(sd2, ck)  # raises if torn/missing
