"""Adasum correctness — NumPy-model comparison, the reference's
test/parallel/test_adasum_mpi.py strategy: run the real reduction and
compare against an independent NumPy implementation of the pairwise
projection rule, plus algebraic properties (identical gradients
average, orthogonal gradients add)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu.ops as hops
import horovod_tpu.ops.adasum as adasum
from horovod_tpu.common.ops_enum import Adasum

from _adasum_model import adasum_fold_model, adasum_tree_model, combine
from test_eager_multiprocess import run_job


# ---------------------------------------------------------------------------
# in-jit SPMD tier (8-device virtual mesh)
# ---------------------------------------------------------------------------

def _rank_vectors(n_ranks, n=24, seed=11, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return rng.randn(n_ranks, n).astype(dtype)


def test_adasum_allreduce_vs_model(mesh8):
    x = _rank_vectors(8)
    f = shard_map(lambda v: adasum.adasum_allreduce(v[0], "dp"),
                  mesh=mesh8, in_specs=P("dp"), out_specs=P())
    got = jax.jit(f)(jnp.asarray(x))
    want = adasum_fold_model(list(x))  # == tree model for power of two
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)


def test_adasum_via_collectives_op(mesh8):
    x = _rank_vectors(8, seed=5)
    f = shard_map(lambda v: hops.allreduce(v[0], op=Adasum, axis_name="dp"),
                  mesh=mesh8, in_specs=P("dp"), out_specs=P())
    got = jax.jit(f)(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), adasum_fold_model(list(x)),
                               rtol=1e-4)


def test_adasum_grouped_per_tensor_weighting(mesh8):
    """Each pytree leaf must get its own dot/norm coefficients."""
    a = _rank_vectors(8, n=10, seed=21)
    b = _rank_vectors(8, n=7, seed=22)

    def step(va, vb):
        return hops.grouped_allreduce((va[0], vb[0]), op=Adasum,
                                      axis_name="dp")

    f = shard_map(step, mesh=mesh8,
                  in_specs=(P("dp"), P("dp")),
                  out_specs=(P(), P()))
    ga, gb = jax.jit(f)(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(ga), adasum_fold_model(list(a)),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), adasum_fold_model(list(b)),
                               rtol=1e-4)


def test_adasum_identical_gradients_average(mesh8):
    """adasum(g, g, ..., g) == g: with identical inputs every combine is
    (1-1/2)·a + (1-1/2)·b = a."""
    x = jnp.tile(jnp.arange(6, dtype=jnp.float32)[None], (8, 1))
    f = shard_map(lambda v: adasum.adasum_allreduce(v[0], "dp"),
                  mesh=mesh8, in_specs=P("dp"), out_specs=P())
    got = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(got), np.arange(6), rtol=1e-6)


def test_adasum_orthogonal_gradients_add():
    """Pairwise property: orthogonal vectors sum (dot == 0)."""
    a = np.array([1.0, 0.0], np.float32)
    b = np.array([0.0, 2.0], np.float32)
    np.testing.assert_allclose(combine(a, b), [1.0, 2.0])


def test_adasum_rejects_non_pow2_axis(devices):
    """The in-jit tier is the power-of-two tree; ragged world sizes are
    the eager tier's job (fold step) — requesting them here must fail
    loudly at trace time, not mis-reduce."""
    from jax.sharding import Mesh
    mesh6 = Mesh(np.asarray(devices[:6]), ("dp",))
    f = shard_map(lambda v: adasum.adasum_allreduce(v[0], "dp"),
                  mesh=mesh6, in_specs=P("dp"), out_specs=P())
    with pytest.raises(ValueError, match="power-of-two"):
        jax.jit(f)(jnp.ones((6, 4), jnp.float32))


def test_adasum_int_dtype_rejected(mesh8):
    with pytest.raises(Exception, match="float"):
        f = shard_map(lambda v: adasum.adasum_allreduce(v[0], "dp"),
                      mesh=mesh8, in_specs=P("dp"), out_specs=P())
        jax.jit(f)(jnp.ones((8, 4), jnp.int32))


# ---------------------------------------------------------------------------
# eager host plane (real multi-process jobs)
# ---------------------------------------------------------------------------

# np=4's pure XOR tree is a sub-case of np=5's run (fold pair + a
# 4-member core executes the same tree) — slow tier (budget). np=5
# itself composes np=3's fold handling with np=4's pow2 core, both
# covered (3 in tier-1, 4 in slow) — slow tier too (ISSUE 15 budget);
# tier-1 keeps the pow2 gate (2) and the ragged fold (3).
@pytest.mark.parametrize(
    "np_", [2, 3, pytest.param(4, marks=pytest.mark.slow),
            pytest.param(5, marks=pytest.mark.slow)])
def test_adasum_eager_host(np_):
    """np=3/5 exercise the non-power-of-two fold (5: a fold pair plus a
    4-member core); 2/4 the pure XOR tree."""
    run_job("adasum", np_)


# The np=3 ragged fold under XLA duplicates what adasum_eager_host[3]
# already pins on the same fold code (the XLA leg differs only in the
# exec plane, which np=2 covers) — slow tier per tier-1 budget.
@pytest.mark.parametrize(
    "np_", [2, pytest.param(3, marks=pytest.mark.slow)])
def test_adasum_eager_xla(np_):
    from test_eager_multiprocess import _xla_env
    run_job("xla_adasum", np_, timeout=240, extra_env=_xla_env(np_))


def test_tree_and_fold_models_agree_pow2():
    vecs = list(_rank_vectors(4, seed=33))
    np.testing.assert_allclose(adasum_fold_model(vecs),
                               adasum_tree_model(vecs), rtol=1e-12)
