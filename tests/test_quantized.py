"""In-jit quantized mesh collectives (``ops/quantized.py``) — the mesh-
plane mirror of test_compression.py / the codec-kernel matrix in
test_host_kernels.py. Pins, on XLA-CPU shard_map meshes:

* the blockwise int8 codec bitwise against a numpy reference and its
  per-block error bound (scale/2);
* run-to-run bitwise determinism of the quantized allreduce at
  np=1/2/4, and jit/no-jit bitwise for the cast codecs (int8 to an f32
  ULP: XLA-CPU fuses its decode multiply into the fold as an FMA);
* the EF telescoping identity (time-average of the quantized mean of a
  FIXED gradient converges to the true mean ~1/T);
* narrow-dtype collective operands in the traced program (the
  "quantized reduce-scatter + all-gather really compiled" assertion);
* one-knob plumbing: collectives/optimizer/train-step surfaces, the
  int8+EF small-LM convergence gate, and bitwise identity of every
  ``compression=none`` path with its pre-existing spelling.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu.ops as hops
from jax import shard_map
from horovod_tpu.common.ops_enum import Average, Max, Sum
from horovod_tpu.compression import Compression
from horovod_tpu.ops.quantized import (
    INT8_BLOCK_ELEMS,
    blockwise_int8_decode,
    blockwise_int8_encode,
    quantized_allgather,
    quantized_allreduce,
    quantized_reduce_scatter,
)

jax.config.update("jax_platform_name", "cpu")


def gathered_shard_map(f, **kw):
    """``shard_map`` for bodies that return a quantized collective's
    result under a replicated ``out_specs``: the value leaves
    ops/quantized.py through ``lax.all_gather``, which jax types as
    varying although every rank holds the same bytes, so the VMA
    checker cannot see the replication (no public varying->invariant
    cast exists)."""
    return shard_map(f, check_vma=False, **kw)


def _mesh(n: int) -> Mesh:
    """A dp-only mesh over the first ``n`` forced host devices (the
    mesh8 fixture must use all 8; the quantized paths only name dp)."""
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _np_int8_encode(x):
    """Numpy reference of the blockwise codec, same f32 arithmetic as
    ops/quantized.py: absmax per 256-block, scale = absmax * (1/127)
    (the multiply spelling — a constant DIVISION is what XLA's
    simplifier rewrites under jit, breaking determinism), RNE round,
    clamp to +-127."""
    x = np.asarray(x, np.float32)
    c = x.shape[-1]
    nb = -(-c // INT8_BLOCK_ELEMS)
    pad = nb * INT8_BLOCK_ELEMS - c
    if pad:
        x = np.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    v = x.reshape(x.shape[:-1] + (nb, INT8_BLOCK_ELEMS))
    absmax = np.max(np.abs(v), axis=-1)
    scales = (absmax * np.float32(1.0 / 127.0)).astype(np.float32)
    inv = np.where(scales > 0, np.float32(1.0) / scales,
                   np.float32(0.0)).astype(np.float32)
    q = np.clip(np.round(v * inv[..., None]), -127, 127).astype(np.int8)
    return q.reshape(x.shape[:-1] + (nb * INT8_BLOCK_ELEMS,)), scales


# ---------------------------------------------------------------------------
# Codec unit tests (no mesh)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", [1, 255, 256, 257, 700, 1024])
def test_int8_codec_matches_numpy_reference(c):
    rng = np.random.RandomState(c)
    x = (rng.randn(3, c) * rng.choice([1e-3, 1.0, 37.0], (3, 1))
         ).astype(np.float32)
    q, s = blockwise_int8_encode(jnp.asarray(x))
    qr, sr = _np_int8_encode(x)
    np.testing.assert_array_equal(np.asarray(q), qr)
    np.testing.assert_array_equal(np.asarray(s), sr)


@pytest.mark.parametrize("c", [256, 515])
def test_int8_roundtrip_error_bound(c):
    """|x - decode(encode(x))| <= scale/2 per element — the RNE
    quantization bound, the same contract test_host_kernels pins on
    the native codec."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(4, c).astype(np.float32) * 3.0)
    q, s = blockwise_int8_encode(x)
    y = blockwise_int8_decode(q, s, c)
    per_elem_scale = np.repeat(np.asarray(s), INT8_BLOCK_ELEMS,
                               axis=-1)[:, :c]
    err = np.abs(np.asarray(y) - np.asarray(x))
    assert (err <= per_elem_scale * 0.5 + 1e-7).all(), err.max()


def test_int8_all_zero_block_and_padding():
    # An all-zero block encodes scale 0 / q 0 and decodes exactly; the
    # block padding tail never leaks into real elements.
    x = jnp.zeros((2, 300), jnp.float32)
    q, s = blockwise_int8_encode(x)
    assert float(jnp.abs(s).max()) == 0.0
    np.testing.assert_array_equal(
        np.asarray(blockwise_int8_decode(q, s, 300)), np.zeros((2, 300)))


# ---------------------------------------------------------------------------
# Quantized allreduce: correctness, determinism
# ---------------------------------------------------------------------------

def _det_params():
    # int8 at np=1: slow-tier (the quantize/requantize math at np=1 is
    # pinned by the codec unit tests above, the collective composition
    # by np=2/4, and the size-1-axis collective edge by the cheap
    # bf16/fp16 np=1 variants) — the eager shard_map pass it pays ~5s
    # for adds no unique coverage.
    for codec in ("bf16", "fp16", "int8"):
        for n in (1, 2, 4):
            marks = ([pytest.mark.slow] if (codec, n) == ("int8", 1)
                     else [])
            yield pytest.param(n, codec, id=f"{codec}-{n}", marks=marks)


def _assert_jit_matches_eager(codec, nojit, jitted):
    if codec == "int8":
        np.testing.assert_allclose(nojit, jitted, rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(nojit, jitted)


@pytest.mark.parametrize("n,codec", _det_params())
def test_allreduce_close_and_bitwise_deterministic(n, codec):
    """Value within codec tolerance of the true mean, and bitwise
    identical run-to-run and jit vs no-jit at every mesh shape (the
    native plane's thread-invariance contract, mesh edition). int8 alone
    holds jit vs no-jit to a few f32 ULPs: under jit XLA-CPU contracts
    its decode multiply into the peer fold as an FMA (single rounding),
    which the op-by-op eager shard_map cannot (the same slack
    test_reduce_scatter_residual_reconstructs_exactly documents). The
    cast codecs have no multiply to contract and stay bitwise."""
    rng = np.random.RandomState(n * 31)
    xs = jnp.asarray(rng.randn(n, 3, 113).astype(np.float32))
    f = gathered_shard_map(
        lambda v: quantized_allreduce(v[0], op=Average, axis_name="dp",
                                      codec=codec),
        mesh=_mesh(n), in_specs=P("dp"), out_specs=P())
    nojit = np.asarray(f(xs))
    jitted = np.asarray(jax.jit(f)(xs))
    _assert_jit_matches_eager(codec, nojit, jitted)
    np.testing.assert_array_equal(jitted, np.asarray(jax.jit(f)(xs)))
    want = np.asarray(xs, np.float64).mean(0)
    amax = np.abs(want).max()
    tol = {"bf16": 2 ** -6, "fp16": 2 ** -8, "int8": 0.04}[codec]
    np.testing.assert_allclose(jitted, want, atol=amax * tol + 1e-6)


def test_allreduce_codec_none_is_bitwise_psum(mesh8):
    x = jnp.asarray(np.random.RandomState(0).randn(8, 64).astype(np.float32))
    quant = jax.jit(shard_map(
        lambda v: quantized_allreduce(v[0], op=Sum, axis_name="dp",
                                      codec="none"),
        mesh=mesh8, in_specs=P("dp"), out_specs=P()))
    plain = jax.jit(shard_map(
        lambda v: lax.psum(v[0], "dp"),
        mesh=mesh8, in_specs=P("dp"), out_specs=P()))
    np.testing.assert_array_equal(np.asarray(quant(x)), np.asarray(plain(x)))


def test_allreduce_rejects_bad_usage():
    with pytest.raises(ValueError, match="codec"):
        quantized_allreduce(jnp.ones(4), codec="int4")
    f = shard_map(
        lambda v: quantized_allreduce(v[0], op=Max, axis_name="dp",
                                      codec="int8"),
        mesh=_mesh(2), in_specs=P("dp"), out_specs=P())
    with pytest.raises(ValueError, match="Sum/Average"):
        f(jnp.ones((2, 4)))
    g = shard_map(
        lambda v: quantized_allreduce(v[0].astype(jnp.int32), op=Sum,
                                      axis_name="dp", codec="int8"),
        mesh=_mesh(2), in_specs=P("dp"), out_specs=P())
    with pytest.raises(TypeError, match="quantize"):
        g(jnp.ones((2, 4)))


def test_allgather_codecs():
    xs = jnp.asarray(np.random.RandomState(3).randn(4, 2, 70)
                     .astype(np.float32))
    want = np.concatenate([np.asarray(xs)[i] for i in range(4)], axis=-1)
    for codec, tol in (("none", 0.0), ("bf16", 2 ** -6), ("int8", 0.03)):
        f = jax.jit(shard_map(
            lambda v: quantized_allgather(v[0], "dp", codec=codec,
                                          axis=-1)[None],
            mesh=_mesh(4), in_specs=P("dp"), out_specs=P("dp")))
        got = np.asarray(f(xs))[0]
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want,
                                   atol=np.abs(want).max() * tol + 1e-7)


# ---------------------------------------------------------------------------
# Quantized reduce-scatter (the explicit fsdp gradient hop)
# ---------------------------------------------------------------------------

def test_reduce_scatter_codec_none_bitwise_psum_slice(mesh8):
    """codec="none" IS reduce-scatter: bitwise the psum-then-slice
    result (same fixed f32 fold order on both spellings)."""
    x = jnp.asarray(np.random.RandomState(1).randn(8, 64, 6)
                    .astype(np.float32))
    quant = jax.jit(shard_map(
        lambda v: quantized_reduce_scatter(v[0], op=Sum, axis_name="dp",
                                           codec="none")[None],
        mesh=mesh8, in_specs=P("dp"), out_specs=P("dp")))
    plain = jax.jit(shard_map(
        lambda v: lax.dynamic_slice_in_dim(
            lax.psum(v[0], "dp"), lax.axis_index("dp") * 8, 8)[None],
        mesh=mesh8, in_specs=P("dp"), out_specs=P("dp")))
    np.testing.assert_array_equal(np.asarray(quant(x)), np.asarray(plain(x)))


@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_reduce_scatter_codecs_close_and_deterministic(codec):
    """Each rank's slice lands within codec tolerance of the true sum,
    bitwise run-to-run and jit vs no-jit (int8: to a few f32 ULPs, the
    FMA slack above), on a non-leading scatter axis."""
    n = 2
    rng = np.random.RandomState(17)
    x = jnp.asarray(rng.randn(n, 3, 8, 70).astype(np.float32))
    f = shard_map(
        lambda v: quantized_reduce_scatter(v[0], op=Sum, axis_name="dp",
                                           codec=codec, axis=1)[None],
        mesh=_mesh(n), in_specs=P("dp"), out_specs=P("dp"))
    nojit = np.asarray(f(x))
    jitted = np.asarray(jax.jit(f)(x))
    _assert_jit_matches_eager(codec, nojit, jitted)
    np.testing.assert_array_equal(jitted, np.asarray(jax.jit(f)(x)))
    want = np.stack(np.split(np.asarray(x, np.float64).sum(0), n, axis=1))
    tol = {"bf16": 2 ** -6, "int8": 0.04}[codec]
    np.testing.assert_allclose(jitted, want,
                               atol=np.abs(want).max() * tol + 1e-6)


def test_reduce_scatter_residual_reconstructs_exactly():
    """EF contract at np=1 (the identity exchange, where the returned
    shard IS the decoded payload): the new residual is the difference
    x - decode(encode(x)) — the single-encode-point telescoping
    invariant the fsdp island's optimizer-state leaves rely on. Pinned
    to one-ULP slack, not bitwise: XLA legally fuses the decode
    multiply into the subtraction as an FMA (single rounding), so the
    two spellings of the difference drift by ~1e-7 while the invariant
    itself (residual carries exactly what the wire dropped) holds."""
    x = jnp.asarray(np.random.RandomState(23).randn(4, 300)
                    .astype(np.float32))

    def body(v, r):
        out, nr = quantized_reduce_scatter(v[0], op=Sum, axis_name="dp",
                                           codec="int8", residual=r[0])
        return out[None], nr[None]

    f = jax.jit(shard_map(body, mesh=_mesh(1),
                          in_specs=(P("dp"), P("dp")),
                          out_specs=(P("dp"), P("dp"))))
    shard, nr = f(x[None], jnp.zeros((1,) + x.shape, jnp.float32))
    assert float(np.abs(np.asarray(nr)).max()) > 0
    np.testing.assert_allclose(
        np.asarray(nr)[0], np.asarray(x) - np.asarray(shard)[0],
        atol=1e-6, rtol=0)


def test_reduce_scatter_rejects_bad_usage():
    x = jnp.ones((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="codec"):
        quantized_reduce_scatter(x, codec="int4")
    with pytest.raises(ValueError, match="Sum/Average"):
        quantized_reduce_scatter(x, op=Max, codec="int8")
    f = shard_map(
        lambda v: quantized_reduce_scatter(v[0], op=Sum, axis_name="dp",
                                           codec="int8")[None],
        mesh=_mesh(2), in_specs=P("dp"), out_specs=P("dp"))
    with pytest.raises(ValueError, match="divide"):
        f(jnp.ones((2, 7, 3)))        # dim 0 (7) % axis size (2) != 0


def test_quantized_ops_reject_tuple_axis_up_front():
    """The satellite fix: a tuple axis_name used to sail into the
    all_to_all and die with an opaque XLA shape error; every quantized
    face now rejects it at the API edge with a ValueError that names
    the supported spelling (sequential single-axis hops)."""
    x = jnp.ones((4, 8), jnp.float32)
    for bad in (("dp", "fsdp"), ["dp"]):
        with pytest.raises(ValueError, match="single named mesh axis"):
            quantized_allreduce(x, codec="int8", axis_name=bad)
        with pytest.raises(ValueError, match="single named mesh axis"):
            quantized_reduce_scatter(x, codec="bf16", axis_name=bad)
        with pytest.raises(ValueError, match="single named mesh axis"):
            quantized_allgather(x, bad, codec="int8")


# ---------------------------------------------------------------------------
# Error feedback: the telescoping identity
# ---------------------------------------------------------------------------

def test_ef_telescoping_time_average_converges():
    """Fixed per-rank gradient, repeated int8 quantized pmean with EF:
    any single shot errs at quantization scale, but the residuals carry
    each step's rounding error into the next, so the time-average's
    error shrinks ~1/T (the exact property _mp_worker pins on the wire
    plane's EF slabs)."""
    n = 4
    rng = np.random.RandomState(11)
    g = jnp.asarray(rng.randn(n, 515).astype(np.float32))
    true = np.asarray(g, np.float64).mean(0)

    def step(v, r):
        out, nr = quantized_allreduce(v[0], op=Average, axis_name="dp",
                                      codec="int8", residual=r[0])
        return out, nr[None]

    f = jax.jit(gathered_shard_map(step, mesh=_mesh(n),
                          in_specs=(P("dp"), P("dp")),
                          out_specs=(P(), P("dp"))))
    r = jnp.zeros((n, 515), jnp.float32)
    outs = []
    for _ in range(48):
        out, r = f(g, r)
        outs.append(np.asarray(out))
    single = np.abs(outs[0] - true).max()
    mean_err = np.abs(np.mean(outs, axis=0) - true).max()
    assert single > 1e-5, "int8 mesh codec produced an exact result?"
    assert mean_err < single / 8, (single, mean_err)


def test_ef_without_residual_does_not_telescope():
    """Control for the identity above: WITHOUT a residual the same
    fixed gradient quantizes to the same biased value every step, so
    time-averaging buys nothing — proving the EF state, not averaging,
    is what telescopes."""
    n = 4
    g = jnp.asarray(np.random.RandomState(11).randn(n, 515)
                    .astype(np.float32))
    true = np.asarray(g, np.float64).mean(0)
    f = jax.jit(gathered_shard_map(
        lambda v: quantized_allreduce(v[0], op=Average, axis_name="dp",
                                      codec="int8"),
        mesh=_mesh(n), in_specs=P("dp"), out_specs=P()))
    outs = [np.asarray(f(g)) for _ in range(8)]
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])
    single = np.abs(outs[0] - true).max()
    mean_err = np.abs(np.mean(outs, axis=0) - true).max()
    assert mean_err > single * 0.99


# ---------------------------------------------------------------------------
# Narrow-dtype collective operands really compiled
# ---------------------------------------------------------------------------

def _collect_collectives(jaxpr, acc):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("all_to_all", "reduce_scatter",
                                  "all_gather"):
            acc.append((eqn.primitive.name,
                        [v.aval.dtype for v in eqn.invars]))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v if hasattr(v, "eqns") else None)
            if inner is not None:
                _collect_collectives(inner, acc)
    return acc


@pytest.mark.parametrize("codec,narrow", [("int8", jnp.int8),
                                          ("bf16", jnp.bfloat16)])
def test_traced_program_ships_narrow_collective_operands(codec, narrow):
    """The acceptance assertion: the traced quantized allreduce
    contains a reduce-scatter hop (all_to_all + fold for int8, one
    native psum_scatter for the cast codecs) AND an all-gather whose
    payload operands are the narrow wire dtype — the compression is in
    the XLA graph, not a python-side cast."""
    f = gathered_shard_map(
        lambda v: quantized_allreduce(v[0], op=Average, axis_name="dp",
                                      codec=codec),
        mesh=_mesh(2), in_specs=P("dp"), out_specs=P())
    colls = _collect_collectives(
        jax.make_jaxpr(f)(jnp.zeros((2, 600), jnp.float32)).jaxpr, [])
    rs = [dts for nm, dts in colls if nm in ("all_to_all", "reduce_scatter")]
    ag = [dts for nm, dts in colls if nm == "all_gather"]
    assert any(narrow in dts for dts in rs), colls
    assert any(narrow in dts for dts in ag), colls


def test_train_step_compiles_quantized_collectives():
    """make_train_step(compression=int8) at np=2: the sharded train
    step's program carries int8 all_to_all + all_gather operands for
    the gradient plane."""
    from horovod_tpu.models import TransformerConfig, make_train_step

    # Smallest legal config — this test only TRACES (no compile/run).
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=1, d_model=32,
                                 n_heads=2, n_kv_heads=1, d_ff=64,
                                 vocab_size=128, max_seq=32)
    mesh = _mesh(2)
    init_state, step, _ = make_train_step(cfg, mesh,
                                          compression=Compression.int8)
    state = init_state(jax.random.PRNGKey(0))  # eager: only tracing below
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                              cfg.vocab_size)
    colls = _collect_collectives(
        jax.make_jaxpr(lambda s, b: step(s, b))(
            state, {"tokens": toks}).jaxpr, [])
    assert any(jnp.int8 in dts for nm, dts in colls
               if nm == "all_to_all"), colls
    assert any(jnp.int8 in dts for nm, dts in colls
               if nm == "all_gather"), colls


# ---------------------------------------------------------------------------
# One-knob plumbing: collectives / optimizer / value_and_grad
# ---------------------------------------------------------------------------

def test_collectives_allreduce_accepts_compression():
    n = 4
    xs = jnp.asarray(np.random.RandomState(5).randn(n, 200)
                     .astype(np.float32))
    want = np.asarray(xs, np.float64).mean(0)
    for comp, tol in ((Compression.bf16, 2 ** -6), (Compression.int8, 0.04)):
        f = jax.jit(gathered_shard_map(
            lambda v: hops.allreduce(v[0], op=Average, axis_name="dp",
                                     compression=comp),
            mesh=_mesh(n), in_specs=P("dp"), out_specs=P()))
        np.testing.assert_allclose(np.asarray(f(xs)), want,
                                   atol=np.abs(want).max() * tol + 1e-6)
    # compression=None is bitwise the pre-existing spelling.
    with_none = jax.jit(shard_map(
        lambda v: hops.allreduce(v[0], op=Average, axis_name="dp",
                                 compression=None),
        mesh=_mesh(n), in_specs=P("dp"), out_specs=P()))
    plain = jax.jit(shard_map(
        lambda v: hops.allreduce(v[0], op=Average, axis_name="dp"),
        mesh=_mesh(n), in_specs=P("dp"), out_specs=P()))
    np.testing.assert_array_equal(np.asarray(with_none(xs)),
                                  np.asarray(plain(xs)))


def test_collectives_grouped_allreduce_accepts_compression():
    n = 2
    tree = {"a": jnp.asarray(np.random.RandomState(6).randn(n, 40)
                             .astype(np.float32)),
            "b": (jnp.ones((n, 3, 5), jnp.float32),)}
    f = jax.jit(gathered_shard_map(
        lambda t: hops.grouped_allreduce(
            jax.tree.map(lambda v: v[0], t), op=Sum, axis_name="dp",
            compression=Compression.int8),
        mesh=_mesh(n), in_specs=(P("dp"),), out_specs=P()))
    got = f(tree)
    np.testing.assert_allclose(np.asarray(got["a"]),
                               np.asarray(tree["a"]).sum(0), atol=0.1)
    np.testing.assert_allclose(np.asarray(got["b"][0]),
                               np.full((3, 5), float(n)), atol=0.1)


def test_distributed_optimizer_int8_threads_ef_state():
    """distributed_optimizer(compression=int8, axis_name=...) grows an
    "ef" optimizer-state pytree of f32 zeros and threads it through
    every reduce — the rank-local residuals ride as explicit state
    leaves, exactly like the host plane's EF slabs live in the codec."""
    import optax

    import horovod_tpu.jax as hvd

    n = 4
    g = jnp.asarray(np.random.RandomState(9).randn(n, 300)
                    .astype(np.float32))
    true = np.asarray(g, np.float64).mean(0)
    opt = hvd.distributed_optimizer(optax.sgd(1.0), axis_name="dp",
                                    compression=hvd.Compression.int8)

    def run(v):
        p = {"w": jnp.zeros((300,), jnp.float32)}
        s = opt.init(p)
        assert set(s.keys()) == {"inner", "ef"}
        acc = jnp.zeros((300,), jnp.float32)
        for _ in range(8):  # same grad each call: EF must telescope
            upd, s = opt.update({"w": v[0]}, s, p)
            acc = acc + upd["w"]
        return acc / 8, s["ef"]["w"][None]

    f = jax.jit(gathered_shard_map(run, mesh=_mesh(n),
                          in_specs=(P("dp"),), out_specs=(P(), P("dp"))))
    avg_upd, ef = f(g)
    # sgd(1.0) updates are -grad: the time-average must sit much closer
    # to -mean than one quantized shot's error scale.
    single = jax.jit(gathered_shard_map(
        lambda v: quantized_allreduce(v[0], op=Average, axis_name="dp",
                                      codec="int8"),
        mesh=_mesh(n), in_specs=P("dp"), out_specs=P()))(g)
    single_err = np.abs(np.asarray(single) - true).max()
    mean_err = np.abs(np.asarray(avg_upd) + true).max()
    assert mean_err < single_err / 3, (single_err, mean_err)
    assert np.abs(np.asarray(ef)).max() > 0  # residuals really carried


def test_distributed_optimizer_accumulation_with_int8():
    """backward_passes_per_step + int8: EF state rides the lax.cond
    boundary (both branches carry it) and non-boundary calls leave it
    untouched."""
    import optax

    import horovod_tpu.jax as hvd

    n = 2
    opt = hvd.distributed_optimizer(optax.sgd(1.0), axis_name="dp",
                                    compression=hvd.Compression.int8,
                                    backward_passes_per_step=2)

    def run(v):
        p = {"w": jnp.zeros((64,), jnp.float32)}
        s = opt.init(p)
        assert "ef" in s
        u1, s = opt.update({"w": v[0]}, s, p)
        ef_after_hold = s["ef"]["w"]
        u2, s = opt.update({"w": v[0]}, s, p)
        return u1["w"], u2["w"], ef_after_hold[None], s["ef"]["w"][None]

    f = jax.jit(gathered_shard_map(run, mesh=_mesh(n), in_specs=(P("dp"),),
                          out_specs=(P(), P(), P("dp"), P("dp"))))
    g = jnp.asarray(np.random.RandomState(2).randn(n, 64)
                    .astype(np.float32))
    u1, u2, ef_hold, ef_done = f(g)
    np.testing.assert_array_equal(np.asarray(u1), 0.0)   # held step
    np.testing.assert_array_equal(np.asarray(ef_hold), 0.0)
    want = -np.asarray(g).sum(0)                         # boundary: sum
    np.testing.assert_allclose(np.asarray(u2), want,
                               atol=np.abs(want).max() * 0.05 + 1e-3)


def test_value_and_grad_applies_compression():
    import horovod_tpu.jax as hvd

    n = 2
    xs = jnp.asarray(np.random.RandomState(4).randn(n, 50)
                     .astype(np.float32))
    w0 = jnp.full((50,), 2.0, jnp.float32)

    def loss_fn(w, x):
        return ((w - x) ** 2).mean()

    dvg = hvd.distributed_value_and_grad(
        loss_fn, axis_name="dp", compression=hvd.Compression.int8)
    loss, g = jax.jit(gathered_shard_map(
        lambda w, x: dvg(w, x[0]), mesh=_mesh(n),
        in_specs=(P(), P("dp")), out_specs=(P(), P())))(w0, xs)
    want_g = 2 * (np.asarray(w0) - np.asarray(xs)).mean(0) / 50
    np.testing.assert_allclose(np.asarray(g), want_g,
                               atol=np.abs(want_g).max() * 0.05 + 1e-5)


def test_eager_ef_kwarg_rejected():
    import horovod_tpu.jax as hvd
    with pytest.raises(ValueError, match="in-jit"):
        hvd.allreduce_gradients({"w": np.ones(4, np.float32)},
                                ef={"w": np.zeros(4, np.float32)})


def test_cast_codecs_still_wrap_nonquantizable_ops():
    """bf16 + op=Max keeps the pre-PR cast-around-collective behavior
    (only Average/Sum ride the quantized path); int8 + Max raises up
    front instead of deep inside a cast."""
    import horovod_tpu.jax as hvd

    n = 2
    xs = jnp.asarray(np.random.RandomState(8).randn(n, 33)
                     .astype(np.float32))
    f = jax.jit(shard_map(
        lambda v: hvd.allreduce_gradients(
            {"w": v[0]}, axis_name="dp", op=Max,
            compression=hvd.Compression.bf16)["w"],
        mesh=_mesh(n), in_specs=(P("dp"),), out_specs=P()))
    want = np.asarray(xs).astype("float32").max(0)
    np.testing.assert_allclose(np.asarray(f(xs)), want, rtol=2 ** -6,
                               atol=1e-2)
    with pytest.raises(ValueError, match="int8"):
        hvd.allreduce_gradients({"w": xs[0]}, axis_name="dp", op=Max,
                                compression=hvd.Compression.int8)


def test_cast_codecs_fall_back_on_tuple_axes(mesh2x4):
    """Tuple axis_name + bf16 keeps the pre-PR cast-around-pmean path
    (the quantized composition is single-axis); int8 + tuple raises up
    front. Same contract on the collectives face, which also cast-wraps
    the non-quantizable ops."""
    import horovod_tpu.jax as hvd

    xs = jnp.asarray(np.random.RandomState(12).randn(2, 4, 60)
                     .astype(np.float32))
    f = jax.jit(shard_map(
        lambda v: hvd.allreduce_gradients(
            {"w": v[0, 0]}, axis_name=("dp", "tp"),
            compression=hvd.Compression.bf16)["w"],
        mesh=mesh2x4, in_specs=(P("dp", "tp"),), out_specs=P()))
    want = np.asarray(xs, np.float64).mean((0, 1))
    np.testing.assert_allclose(np.asarray(f(xs)), want, atol=2 ** -6)
    with pytest.raises(NotImplementedError, match="single"):
        hvd.allreduce_gradients({"w": xs[0, 0]}, axis_name=("dp", "tp"),
                                compression=hvd.Compression.int8)
    # collectives face: Max + bf16 cast-wraps; Max + int8 raises.
    g = jax.jit(shard_map(
        lambda v: hops.allreduce(v[0], op=Max, axis_name="dp",
                                 compression=Compression.bf16),
        mesh=_mesh(2), in_specs=P("dp"), out_specs=P()))
    x2 = xs[:, 0]
    np.testing.assert_allclose(
        np.asarray(g(x2)), np.asarray(x2).max(0), rtol=2 ** -6, atol=1e-2)
    with pytest.raises(ValueError, match="int8"):
        hops.allreduce(x2[0], op=Max, axis_name="dp",
                       compression=Compression.int8)


# ---------------------------------------------------------------------------
# Train-step / serve plumbing
# ---------------------------------------------------------------------------

def _full_axis_mesh(n: int) -> Mesh:
    """All six model axes present (the GSPMD step's param_specs name
    tp/fsdp), dp = n, everything else 1 — lets the default and the
    quantized step run on the SAME devices for comparable losses."""
    devs = np.array(jax.devices()[:n]).reshape(n, 1, 1, 1, 1, 1)
    return Mesh(devs, ("dp", "fsdp", "pp", "sp", "tp", "ep"))


_LM_STEPS = 12


def _fsdp_mesh(n: int) -> Mesh:
    """fsdp = n, everything else 1 (all six axes present) — the ZeRO-3
    plane the fsdp island quantizes, on the same devices as
    :func:`_full_axis_mesh` so losses compare across planes."""
    devs = np.array(jax.devices()[:n]).reshape(1, n, 1, 1, 1, 1)
    return Mesh(devs, ("dp", "fsdp", "pp", "sp", "tp", "ep"))


def _lm_run(compression, mesh_fn=_full_axis_mesh):
    """One tiny-LM training run (fixed cfg/data/optimizer on
    ``mesh_fn(2)``); all arms sharing a mesh_fn compare losses 1:1.
    Returns (first_loss, last_loss, final_params_leaves)."""
    import optax

    from horovod_tpu.models import TransformerConfig, make_train_step

    # n_layers=1: halves the compile each arm pays; a 1-layer LM still
    # exercises embed/attention/FFN/head gradients end to end.
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=1)
    mesh = mesh_fn(2)
    toks = jax.random.randint(jax.random.PRNGKey(5), (8, 17), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    init_state, step, _ = make_train_step(
        cfg, mesh, optax.adam(1e-2), compression=compression)
    st = jax.jit(init_state)(jax.random.PRNGKey(0))
    first = last = None
    for _ in range(_LM_STEPS):
        st, loss = step(st, batch)
        first = float(loss) if first is None else first
        last = float(loss)
    return first, last, jax.tree.leaves(st["params"])


@pytest.fixture(scope="module")
def lm_f32_reference():
    """The f32 (compression=None, pre-PR GSPMD) run — computed ONCE;
    both the bitwise-identity pin and the convergence gates diff
    against it, so the expensive baseline compile isn't repeated per
    arm."""
    return _lm_run(None)


def test_train_step_compression_none_bitwise_pre_pr(lm_f32_reference):
    """make_train_step(compression=none) IS the pre-PR step: same code
    path, bitwise-identical losses and params after real steps."""
    f0, ref, ref_params = lm_f32_reference
    f0b, got, params = _lm_run(Compression.none)
    assert (f0b, got) == (f0, ref)
    for a, b in zip(params, ref_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_small_lm_convergence_int8_ef_matches_f32(lm_f32_reference):
    """The convergence gate: the tiny LM trained with the int8+EF
    gradient plane lands within tolerance of the f32 step at equal
    steps on identical data/devices."""
    f0, ref, _ = lm_f32_reference
    _, got, _ = _lm_run(Compression.int8)
    assert ref < f0 - 0.3, (f0, ref)          # training really moved
    assert abs(got - ref) < 0.1 * (f0 - ref), (got, ref, f0)


@pytest.mark.slow  # redundancy-justified: int8 (the lossier codec +
# EF machinery) gates convergence in tier-1; bf16's tolerance is
# already pinned by the optimizer/collectives tests above.
def test_small_lm_convergence_bf16_matches_f32(lm_f32_reference):
    f0, ref, _ = lm_f32_reference
    _, got, _ = _lm_run(Compression.bf16)
    assert ref < f0 - 0.3, (f0, ref)
    assert abs(got - ref) < 0.1 * (f0 - ref), (got, ref, f0)


def test_train_step_compression_rejects_model_sharded_mesh(mesh2x4):
    from horovod_tpu.models import TransformerConfig, make_train_step
    with pytest.raises(ValueError, match="dp-only|data-parallel"):
        make_train_step(TransformerConfig.tiny(), mesh2x4,
                        compression=Compression.int8)


# ---------------------------------------------------------------------------
# fsdp plane: the partial-manual quantized train-step island (ISSUE 14)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_fsdp_f32_reference():
    """The f32 (compression=None, GSPMD ZeRO-3) run on the fsdp=2 mesh
    — computed ONCE; the bitwise-none pin and the slow int8 convergence
    gate both diff against it."""
    return _lm_run(None, mesh_fn=_fsdp_mesh)


def test_fsdp_train_step_compression_none_bitwise_pre_pr(
        lm_fsdp_f32_reference):
    """make_train_step(compression=none) on an fsdp>1 mesh IS the
    pre-PR GSPMD step (the dispatcher only builds the island for real
    codecs): byte-identical losses and params over 12 real steps."""
    f0, ref, ref_params = lm_fsdp_f32_reference
    f0b, got, params = _lm_run(Compression.none, mesh_fn=_fsdp_mesh)
    assert (f0b, got) == (f0, ref)
    for a, b in zip(params, ref_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # slow from the start (ISSUE 14 budget note): the
# island's composition is already pinned in tier-1 by the bitwise-none
# test, the jaxpr narrow-operand assertion, the EF checkpoint
# round-trip below and the reduce-scatter unit tests; this end-to-end
# convergence arm pays one more 12-step island compile on top of those
# and is the direct fsdp twin of the dp-plane int8 gate, so it rides
# the full tier only.
def test_fsdp_small_lm_convergence_int8_ef_matches_f32(
        lm_fsdp_f32_reference):
    """The fsdp convergence gate: the tiny LM trained with the int8+EF
    fsdp island lands within tolerance of the GSPMD f32 ZeRO-3 step at
    equal steps on identical data/devices."""
    f0, ref, _ = lm_fsdp_f32_reference
    _, got, _ = _lm_run(Compression.int8, mesh_fn=_fsdp_mesh)
    assert ref < f0 - 0.3, (f0, ref)          # training really moved
    assert abs(got - ref) < 0.1 * (f0 - ref), (got, ref, f0)


def test_fsdp_train_step_compiles_quantized_collectives():
    """The acceptance assertion for the fsdp program: the island step's
    jaxpr carries int8 all_to_all operands for the gradient
    reduce-scatter hop AND int8 all_gather operands (hop 2 of the
    fsdp-replicated leaves' allreduce) — compression in the XLA graph,
    not a python-side cast."""
    from horovod_tpu.models import TransformerConfig, make_train_step

    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=1, d_model=32,
                                 n_heads=2, n_kv_heads=1, d_ff=64,
                                 vocab_size=128, max_seq=32)
    mesh = _fsdp_mesh(2)
    init_state, step, _ = make_train_step(cfg, mesh,
                                          compression=Compression.int8)
    state = init_state(jax.random.PRNGKey(0))  # eager: only tracing below
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                              cfg.vocab_size)
    colls = _collect_collectives(
        jax.make_jaxpr(lambda s, b: step(s, b))(
            state, {"tokens": toks}).jaxpr, [])
    assert any(jnp.int8 in dts for nm, dts in colls
               if nm == "all_to_all"), colls
    assert any(jnp.int8 in dts for nm, dts in colls
               if nm == "all_gather"), colls


def test_fsdp_island_ef_leaves_checkpoint_roundtrip(tmp_path):
    """EF residuals are ordinary optimizer-state leaves: after real
    steps they live sharded over the data axes (per-rank slabs, not
    replicated), they ride a plain checkpoint save/load (device_get ->
    disk -> device_put back onto their recorded shardings), and the
    restored job continues BITWISE identically to the uninterrupted
    one — which also pins the island step's run-to-run determinism."""
    import optax

    from horovod_tpu.models import TransformerConfig, make_train_step

    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=1, d_model=32,
                                 n_heads=2, n_kv_heads=1, d_ff=64,
                                 vocab_size=128, max_seq=32)
    mesh = _fsdp_mesh(2)
    init_state, step, _ = make_train_step(cfg, mesh, optax.sgd(0.05),
                                          compression=Compression.int8)
    st = init_state(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 9),
                                          0, cfg.vocab_size)}
    for _ in range(3):
        st, _ = step(st, batch)
    ef_leaves = jax.tree.leaves(st["ef"])
    assert ef_leaves and any(
        float(jnp.abs(l).max()) > 0 for l in ef_leaves)
    for leaf in ef_leaves:
        # Leading [dp, fsdp] slab dims sharded over the mesh's 2
        # devices: each device holds a (1, 1, ...) slab of its own.
        assert len(leaf.sharding.device_set) == 2, leaf.sharding
        assert leaf.addressable_shards[0].data.shape[:2] == (1, 1), (
            leaf.shape, leaf.addressable_shards[0].data.shape)
    # Save: flatten -> host numpy -> disk (the repo's checkpoint idiom
    # is orbax in examples/lm_pretrain.py; npz keeps the test hermetic).
    leaves, treedef = jax.tree.flatten(st)
    np.savez(tmp_path / "ck.npz",
             **{str(i): np.asarray(jax.device_get(l))
                for i, l in enumerate(leaves)})
    ref = st
    for _ in range(3):
        ref, ref_loss = step(ref, batch)
    # Load: device_put each leaf back onto the sharding the live state
    # recorded — the EF slabs land sharded again, not replicated.
    data = np.load(tmp_path / "ck.npz")
    st2 = jax.tree.unflatten(treedef, [
        jax.device_put(jnp.asarray(data[str(i)]), l.sharding)
        for i, l in enumerate(leaves)])
    for _ in range(3):
        st2, loss2 = step(st2, batch)
    assert float(loss2) == float(ref_loss)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_embed_lookup_compression_narrows_table_fallback(mesh2x4):
    """On the table-replication fallback (the path this legacy
    container always takes at tp*fsdp>1), compression ships the table
    narrow: codec-bounded row error, none bitwise identical."""
    from horovod_tpu.models.transformer import embed_lookup

    emb = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
    tk = jax.random.randint(jax.random.PRNGKey(4), (4, 7), 0, 64)
    base = jax.jit(lambda e, t: embed_lookup(e, t, jnp.float32, mesh2x4))(
        emb, tk)
    nn = jax.jit(lambda e, t: embed_lookup(e, t, jnp.float32, mesh2x4,
                                           Compression.none))(emb, tk)
    np.testing.assert_array_equal(np.asarray(nn), np.asarray(base))
    for comp, tol in ((Compression.bf16, 2 ** -6), (Compression.int8, 0.05)):
        got = jax.jit(lambda e, t: embed_lookup(e, t, jnp.float32, mesh2x4,
                                                comp))(emb, tk)
        amax = float(np.abs(np.asarray(base)).max())
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   atol=amax * tol)


def test_serve_fns_memoize_per_compression():
    """ServeConfig.compression keys the jit-closure memo: same knob ->
    same compiled programs, different knob -> distinct closures (and
    the default is the pre-existing None key)."""
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.serve.decode import make_serve_fns

    cfg = TransformerConfig.tiny()
    a = make_serve_fns(cfg, None, block_size=16, table_width=4)
    b = make_serve_fns(cfg, None, block_size=16, table_width=4,
                       compression=None)
    c = make_serve_fns(cfg, None, block_size=16, table_width=4,
                       compression=Compression.int8)
    assert a is b
    assert a is not c
