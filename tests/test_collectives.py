"""Correctness of the in-jit functional collectives on an 8-device CPU
mesh. Mirrors the reference's per-op correctness style in
``test/parallel/test_tensorflow.py`` (exhaustive dtype/op coverage) at
the scale that makes sense for unit tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.ops as hops
from horovod_tpu.common.ops_enum import Average, Sum, Min, Max, Product

from jax import shard_map


def _shmap(fn, mesh, in_specs, out_specs):
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("op,npfn", [(Sum, np.sum), (Average, np.mean),
                                     (Min, np.min), (Max, np.max)])
def test_allreduce(mesh8, dtype, op, npfn):
    if dtype == jnp.int32 and op == Average:
        pytest.skip("integer average not defined")
    x = jnp.arange(8 * 4 * 3, dtype=dtype).reshape(8, 4, 3)
    f = _shmap(lambda v: hops.allreduce(v[0], op=op), mesh8,
               in_specs=P("dp"), out_specs=P())
    got = jax.jit(f)(x)
    want = npfn(np.asarray(x, np.float64), axis=0)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_allreduce_prescale_postscale(mesh8):
    x = jnp.ones((8, 16), jnp.float32)
    f = _shmap(lambda v: hops.allreduce(v[0], op=Sum, prescale_factor=0.5,
                                        postscale_factor=0.25),
               mesh8, in_specs=P("dp"), out_specs=P())
    got = jax.jit(f)(x)
    np.testing.assert_allclose(got, np.full((16,), 8 * 0.5 * 0.25), rtol=1e-6)


def test_allreduce_product(mesh8):
    x = jnp.full((8, 4), 2.0, jnp.float32)
    f = _shmap(lambda v: hops.allreduce(v[0], op=Product), mesh8,
               in_specs=P("dp"), out_specs=P())
    np.testing.assert_allclose(jax.jit(f)(x), np.full((4,), 256.0))


def test_grouped_allreduce_pytree(mesh8):
    tree = {"a": jnp.arange(8 * 2, dtype=jnp.float32).reshape(8, 2),
            "b": (jnp.ones((8, 3, 3), jnp.float32),)}
    f = _shmap(lambda t: hops.grouped_allreduce(
                   jax.tree.map(lambda v: v[0], t), op=Sum),
               mesh8, in_specs=(P("dp"),), out_specs=P())
    got = jax.jit(f)(tree)
    np.testing.assert_allclose(got["a"], np.asarray(tree["a"]).sum(0))
    np.testing.assert_allclose(got["b"][0], np.full((3, 3), 8.0))


def test_allgather(mesh8):
    # all_gather output is per-shard identical but VMA-"varying"; return
    # each shard's copy stacked so we can assert they all match.
    x = jnp.arange(8 * 2 * 3, dtype=jnp.float32).reshape(8, 2, 3)
    f = _shmap(lambda v: hops.allgather(v)[None], mesh8,
               in_specs=P("dp"), out_specs=P("dp"))
    got = np.asarray(jax.jit(f)(x))
    for shard in got:  # per-shard gathered copy == the full input
        np.testing.assert_allclose(shard, np.asarray(x))


@pytest.mark.parametrize("root", [0, 3, 7])
def test_broadcast(mesh8, root):
    x = jnp.stack([jnp.full((4,), i, jnp.float32) for i in range(8)])
    f = _shmap(lambda v: hops.broadcast(v[0], root_rank=root), mesh8,
               in_specs=P("dp"), out_specs=P())
    np.testing.assert_allclose(jax.jit(f)(x), np.full((4,), root))


def test_broadcast_bool(mesh8):
    x = jnp.asarray([[i % 2 == 0] for i in range(8)])
    for root, want in [(3, False), (2, True)]:
        f = _shmap(lambda v, r=root: hops.broadcast(v[0], root_rank=r), mesh8,
                   in_specs=P("dp"), out_specs=P())
        assert bool(np.asarray(jax.jit(f)(x))[0]) == want


def test_broadcast_bad_root(mesh8):
    x = jnp.ones((8, 2), jnp.float32)
    f = _shmap(lambda v: hops.broadcast(v[0], root_rank=9), mesh8,
               in_specs=P("dp"), out_specs=P())
    with pytest.raises(ValueError, match="root_rank"):
        jax.jit(f)(x)


def test_integer_average_rejected(mesh8):
    x = jnp.ones((8, 2), jnp.int32)
    f = _shmap(lambda v: hops.allreduce(v[0], op=Average), mesh8,
               in_specs=P("dp"), out_specs=P())
    with pytest.raises(TypeError, match="integer"):
        jax.jit(f)(x)


def test_alltoall(mesh8):
    # Each rank r sends slice j to rank j; classic transpose check.
    x = jnp.arange(8 * 8, dtype=jnp.int32).reshape(8, 8)
    f = _shmap(lambda v: hops.alltoall(v[0], split_axis=0, concat_axis=0)[None],
               mesh8, in_specs=P("dp", None), out_specs=P("dp", None))
    got = jax.jit(f)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x).T.reshape(8, 8))


def test_reducescatter(mesh8):
    x = jnp.ones((8, 16), jnp.float32)
    f = _shmap(lambda v: hops.reducescatter(v[0], op=Sum), mesh8,
               in_specs=P("dp"), out_specs=P("dp"))
    got = jax.jit(f)(x)
    assert got.shape == (16,)
    np.testing.assert_allclose(got, np.full((16,), 8.0))


def test_ring_permute(mesh8):
    x = jnp.arange(8, dtype=jnp.int32).reshape(8, 1)
    f = _shmap(lambda v: hops.ring_permute(v, axis_name="dp", shift=1),
               mesh8, in_specs=P("dp"), out_specs=P("dp"))
    got = np.asarray(jax.jit(f)(x)).ravel()
    np.testing.assert_array_equal(got, np.roll(np.arange(8), 1))


def test_axis_rank_size(mesh2x4):
    f = _shmap(lambda: (hops.axis_rank("tp").reshape(1, 1),
                        jnp.full((1, 1), hops.axis_size("tp"), jnp.int32)),
               mesh2x4, in_specs=(), out_specs=P("dp", "tp"))
    r, s = jax.jit(f)()
    np.testing.assert_array_equal(np.asarray(r)[0].ravel(), [0, 1, 2, 3])
    assert int(np.asarray(s)[0, 0]) == 4


def test_multi_axis_allreduce(mesh2x4):
    x = jnp.ones((2, 4, 5), jnp.float32)
    f = _shmap(lambda v: hops.allreduce(v[0, 0], op=Sum, axis_name=("dp", "tp")),
               mesh2x4, in_specs=P("dp", "tp"), out_specs=P())
    np.testing.assert_allclose(jax.jit(f)(x), np.full((5,), 8.0))


def test_mesh_spec_wildcard(devices):
    from horovod_tpu.parallel import MeshSpec, build_mesh
    m = build_mesh(MeshSpec(dp=-1, tp=2))
    assert m.shape["dp"] == 4 and m.shape["tp"] == 2
    with pytest.raises(ValueError):
        build_mesh(dp=3)
