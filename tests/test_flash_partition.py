"""The flash kernels on a mesh: GSPMD cannot partition a Mosaic call, so a
sharded step declares its mesh (``kernels.partition_over``) and the kernels
run under shard_map over batch rows and heads. On the virtual CPU mesh the
kernels run interpreted; the real mesh is chip_smoke.py's four-chip leg."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlepaddle_tpu.ops import kernels
from paddlepaddle_tpu.ops.kernels import flash_attention as fa


def _mesh():
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))


def test_split_prefers_declared_batch_axes_then_heads():
    part = kernels.KernelPartition(_mesh(), ("dp",))
    assert part.split(8, 16) == (("dp",), ("mp",))
    # a batch the data axis does not divide stays whole; heads take both
    assert part.split(3, 16) == (None, ("dp", "mp"))
    assert part.split(8, 3) == (("dp",), None)
    assert kernels.current_partition() is None


def test_flash_kernels_under_a_declared_mesh_match_the_xla_route():
    mesh = _mesh()
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    sharding = NamedSharding(mesh, P("dp", None, "mp", None))
    q, k, v, g = (jax.device_put(
        jax.random.normal(kk, (2, 32, 2, 64), jnp.float32), sharding)
        for kk in keys)

    def route(use_pallas):
        def f(q, k, v, g):
            with kernels.partition_over(mesh, "dp"):
                out, vjp = jax.vjp(
                    lambda *a: fa._flash_core(*a, True, 0.125, use_pallas),
                    q, k, v)
                return (out,) + vjp(g)
        return jax.jit(f)

    with pltpu.force_tpu_interpret_mode():
        got = route(True)(q, k, v, g)
        assert "manual" in route(True).lower(q, k, v, g).as_text()
    want = route(False)(q, k, v, g)
    for a, b in zip(got, want):
        assert a.sharding.spec == P("dp", None, "mp", None)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
