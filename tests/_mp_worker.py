"""Worker script for multi-process eager tests: runs the full op matrix
and asserts per-rank results (the tests/parallel analog of the
reference, test/parallel/test_torch.py style, over the TCP controller +
host data plane)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
# Under a sanitizer run (HOROVOD_NATIVE_LIB set by
# tests/test_sanitizers.py), force numpy's lazy `numpy.testing` import
# NOW, before hvd.init() spawns the runtime's threads: its module body
# runs check_support_sve(), which forks a subprocess, and under
# LD_PRELOADed libtsan a fork while other threads exist deadlocks in
# the tsan runtime (docs/development.md#sanitizer-caveats). Every
# scenario whose first np.testing touch came after init hung under
# tsan through exactly this path. The import-time flavor of the same
# deadlock — OpenBLAS's own thread pool is already up when this line
# forks — is the harness's job: it sets OPENBLAS_NUM_THREADS=1.
# Conditional because the import costs ~0.13s of lscpu probe per
# worker spawn — real seconds across tier-1's many multiprocess tests.
if os.environ.get("HOROVOD_NATIVE_LIB"):
    import numpy.testing  # noqa: E402, F401

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.common.exceptions import HorovodInternalError  # noqa: E402


def until_all_locked(name, width, fill, want=None, cap=200):
    """Allreduce ``np.full(width, fill(i))`` under ``name`` until every
    rank had the steady lock engaged BEFORE one and the same op: each
    rank's reading rides the op's last element (their Sum is the size),
    so all ranks leave at the same op and none reads the flag after a
    peer may have moved on (its shutdown or next pattern unlocks). One
    tensor of one shape throughout: the pattern that locks. Under load
    the lock engages when it engages, not "by op 6". ``want(i)`` is
    what the other elements must sum to."""
    size = hvd.size()
    for i in range(cap):
        x = np.full(width, fill(i), np.float32)
        x[-1] = float(hvd.steady_lock_engaged())
        out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=name))
        if want is not None:
            np.testing.assert_allclose(out[:-1], want(i), rtol=1e-6)
        if out[-1] == size:
            return
    raise AssertionError(f"lock never engaged on every rank in {cap} ops")


def main():
    scenario = sys.argv[1]
    if scenario == "xla_rank_order":
        # A TPU host numbers its processes itself, differently from run
        # to run, whatever process id hvd.init hands jax.distributed.
        # Stand in for that on the CPU: reverse the ids.
        import jax
        real_init = jax.distributed.initialize

        def reversed_ids(*a, process_id, num_processes, **kw):
            return real_init(*a, process_id=num_processes - 1 - process_id,
                             num_processes=num_processes, **kw)
        jax.distributed.initialize = reversed_ids
    hvd.init()
    r, s = hvd.rank(), hvd.size()

    if scenario == "matrix":
        # --- allreduce sum/avg, several dtypes and shapes
        for dtype in (np.float32, np.float64, np.int32, np.int64, np.float16):
            x = (np.arange(24, dtype=dtype) + r).reshape(2, 3, 4)
            out = hvd.allreduce(x, op=hvd.Sum, name=f"ar.{np.dtype(dtype).name}")
            want = sum((np.arange(24, dtype=np.float64) + k) for k in range(s))
            np.testing.assert_allclose(
                np.asarray(out, np.float64).ravel(), want,
                rtol=1e-2 if dtype == np.float16 else 1e-6)
        avg = hvd.allreduce(np.full(5, float(r), np.float32), name="ar.avg")
        np.testing.assert_allclose(avg, np.full(5, (s - 1) / 2.0), rtol=1e-6)

        # prescale/postscale
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                            prescale_factor=0.5, postscale_factor=2.0,
                            name="ar.scaled")
        np.testing.assert_allclose(out, np.full(4, s), rtol=1e-6)

        # min/max
        mn = hvd.allreduce(np.full(3, float(r), np.float32), op=hvd.Min,
                           name="ar.min")
        mx = hvd.allreduce(np.full(3, float(r), np.float32), op=hvd.Max,
                           name="ar.max")
        np.testing.assert_allclose(mn, 0.0)
        np.testing.assert_allclose(mx, float(s - 1))

        # --- grouped allreduce (atomic, enqueued in different order per rank)
        ts = [np.full(4, float(r), np.float32), np.full(2, 2.0 * r, np.float32)]
        outs = hvd.grouped_allreduce(ts, op=hvd.Sum, name="grp")
        np.testing.assert_allclose(outs[0], np.full(4, s * (s - 1) / 2.0))
        np.testing.assert_allclose(outs[1], np.full(2, s * (s - 1)))

        # --- allgather with ragged first dim
        x = np.full((r + 1, 2), float(r), np.float32)
        g = hvd.allgather(x, name="ag")
        rows = sum(k + 1 for k in range(s))
        assert g.shape == (rows, 2), g.shape
        off = 0
        for k in range(s):
            np.testing.assert_allclose(g[off:off + k + 1], float(k))
            off += k + 1

        # --- broadcast from nonzero root
        val = np.full((3,), float(r) + 7.0, np.float32)
        b = hvd.broadcast(val, root_rank=s - 1, name="bc")
        np.testing.assert_allclose(b, float(s - 1) + 7.0)

        # --- alltoall with uneven splits: rank r sends k+1 rows to rank k
        total = sum(k + 1 for k in range(s))
        x = np.repeat(np.arange(s), [k + 1 for k in range(s)]).astype(np.float32)
        x = (x * 10 + r)[:, None]  # row value = dest*10 + src
        out, rsplits = hvd.alltoall(x, splits=[k + 1 for k in range(s)],
                                    name="a2a")
        assert list(rsplits) == [r + 1] * s, rsplits
        assert out.shape == (s * (r + 1), 1)
        off = 0
        for k in range(s):
            np.testing.assert_allclose(out[off:off + r + 1, 0], r * 10 + k)
            off += r + 1

        # --- reducescatter
        x = np.full((2 * s, 3), 1.0, np.float32)
        rs = hvd.reducescatter(x, op=hvd.Sum, name="rs")
        assert rs.shape == (2, 3), rs.shape
        np.testing.assert_allclose(rs, float(s))

        # --- barrier
        hvd.barrier()

        # --- steady-state loop (response cache path)
        for i in range(50):
            out = hvd.allreduce(np.full(8, float(r + i), np.float32),
                                op=hvd.Sum, name="steady")
            np.testing.assert_allclose(
                out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)

    elif scenario == "join":
        # Rank k does k+1 allreduces then joins; reductions keep working
        # with the joined ranks contributing zeros.
        for i in range(r + 1):
            contributors = [k for k in range(s) if k >= i]
            out = hvd.allreduce(np.full(2, float(r + 1), np.float32),
                                op=hvd.Sum, name=f"j.{i}")
            want = float(sum(k + 1 for k in contributors))
            np.testing.assert_allclose(out, want, rtol=1e-6)
        hvd.join()

    elif scenario == "join_race":
        # A rank that announces a collective and joins in the same cycle
        # must not deadlock: the announced tensor still completes with
        # every announcer's contribution (regression: readiness used to
        # require ALL announcers to be active).
        if r == 0:
            h = hvd.allreduce_async(np.full(2, 1.0, np.float32), op=hvd.Sum,
                                    name="t")
            hvd.join()
            out = hvd.synchronize(h)
        else:
            out = hvd.allreduce(np.full(2, 1.0, np.float32), op=hvd.Sum,
                                name="t")
            hvd.join()
        np.testing.assert_allclose(out, float(s))

    elif scenario == "join_solo_announce":
        # A tensor announced ONLY by ranks that then join must still
        # fire (with just the announcers contributing) when everyone has
        # joined, not hang the announcer's synchronize().
        if r == 0:
            h = hvd.allreduce_async(np.full(3, 5.0, np.float32), op=hvd.Sum,
                                    name="solo")
            hvd.join()
            out = hvd.synchronize(h)
            np.testing.assert_allclose(out, 5.0)
        else:
            hvd.join()

    elif scenario == "alltoall_ndim_mismatch":
        # Rank with FEWER dims than the first announcer must still be
        # rejected (regression: the ndim check was order-dependent).
        x = (np.ones((4, 2), np.float32) if r == 0
             else np.ones((4,), np.float32))
        try:
            hvd.alltoall(x, name="bad.a2a")
            raise SystemExit("expected HorovodInternalError")
        except HorovodInternalError as e:
            assert "rank" in str(e) or "dimension" in str(e), str(e)

    elif scenario == "shape_mismatch":
        # Shape disagreement must produce an agreed-on error on every
        # rank, not a hang (reference controller.cc:471 ERROR response).
        shape = (2, 3) if r == 0 else (2, 4)
        try:
            hvd.allreduce(np.ones(shape, np.float32), name="bad")
            raise SystemExit("expected HorovodInternalError")
        except HorovodInternalError as e:
            assert "mismatched shape" in str(e), str(e)
        # ...and the job is still usable afterwards.
        out = hvd.allreduce(np.ones(3, np.float32), op=hvd.Sum, name="good")
        np.testing.assert_allclose(out, float(s))

    elif scenario == "dtype_mismatch":
        dt = np.float32 if r == 0 else np.float64
        try:
            hvd.allreduce(np.ones(3, dt), name="bad")
            raise SystemExit("expected HorovodInternalError")
        except HorovodInternalError as e:
            assert "mismatched dtype" in str(e), str(e)

    elif scenario == "xla_matrix":
        # Full op matrix on jax device arrays with exec_mode=CALLBACK:
        # requires HOROVOD_XLA_EXEC=1 (hvd.init brought up
        # jax.distributed before this point). Every collective below
        # must run as a cross-process XLA program, NOT host staging —
        # asserted by checking jax.distributed is actually active.
        import jax
        import jax.numpy as jnp

        assert jax.process_count() == s, (
            f"jax.distributed not spanning: {jax.process_count()} != {s}")

        # allreduce f32/bf16, avg + scales
        for dt, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 1e-1)):
            x = (jnp.arange(12, dtype=dt) + r).reshape(3, 4)
            out = hvd.allreduce(x, op=hvd.Sum, name=f"x.ar.{dt.__name__}")
            assert out.shape == (3, 4)
            want = sum((np.arange(12, dtype=np.float64) + k)
                       for k in range(s))
            np.testing.assert_allclose(
                np.asarray(out, np.float64).ravel(), want, rtol=tol)
        avg = hvd.allreduce(jnp.full(5, float(r)), name="x.avg",
                            prescale_factor=2.0)
        np.testing.assert_allclose(np.asarray(avg),
                                   2.0 * (s - 1) / 2.0, rtol=1e-6)

        # grouped allreduce -> one fused XLA program
        ts = [jnp.full(4, float(r)), jnp.full(2, 2.0 * r)]
        outs = hvd.grouped_allreduce(ts, op=hvd.Sum, name="x.grp")
        np.testing.assert_allclose(np.asarray(outs[0]),
                                   np.full(4, s * (s - 1) / 2.0))
        np.testing.assert_allclose(np.asarray(outs[1]),
                                   np.full(2, float(s * (s - 1))))

        # allgather, ragged rows
        g = hvd.allgather(jnp.full((r + 1, 2), float(r)), name="x.ag")
        rows = sum(k + 1 for k in range(s))
        assert g.shape == (rows, 2), g.shape
        off = 0
        for k in range(s):
            np.testing.assert_allclose(np.asarray(g[off:off + k + 1]),
                                       float(k))
            off += k + 1

        # broadcast from nonzero root
        b = hvd.broadcast(jnp.full((2, 2), float(r) + 3.0),
                          root_rank=s - 1, name="x.bc")
        np.testing.assert_allclose(np.asarray(b), float(s - 1) + 3.0)

        # alltoall, uneven splits (rank r sends k+1 rows to rank k)
        x = np.repeat(np.arange(s), [k + 1 for k in range(s)]).astype(
            np.float32)
        x = jnp.asarray((x * 10 + r)[:, None])
        out, rsplits = hvd.alltoall(x, splits=[k + 1 for k in range(s)],
                                    name="x.a2a")
        assert list(rsplits) == [r + 1] * s, rsplits
        assert out.shape == (s * (r + 1), 1), out.shape
        off = 0
        for k in range(s):
            np.testing.assert_allclose(np.asarray(out[off:off + r + 1, 0]),
                                       r * 10 + k)
            off += r + 1

        # reducescatter (uneven dim0: 2s+1 rows)
        x = jnp.full((2 * s + 1, 3), 1.0)
        rs_out = hvd.reducescatter(x, op=hvd.Sum, name="x.rs")
        want_rows = 3 if r == 0 else 2
        assert rs_out.shape == (want_rows, 3), rs_out.shape
        np.testing.assert_allclose(np.asarray(rs_out), float(s))

        # steady-state cache loop with a PER-ITERATION factor change
        # (dynamic loss scaling shape): the factor is a traced argument,
        # so this must hit the compiled-program cache every iteration.
        import time as _time
        t0 = _time.monotonic()
        for i in range(20):
            out = hvd.allreduce(jnp.full(8, float(r)), op=hvd.Sum,
                                prescale_factor=float(i + 1),
                                name="x.steady")
            np.testing.assert_allclose(
                np.asarray(out), (i + 1) * s * (s - 1) / 2.0, rtol=1e-6)
        # Recompiling per factor value would take >>1s/iteration; the
        # traced path completes the whole loop in well under that.
        assert _time.monotonic() - t0 < 15, "factor change likely recompiles"

    elif scenario == "adasum":
        # Host-plane Adasum vs. the NumPy fold model (the reference
        # compares against a NumPy VHDD model the same way,
        # test/parallel/test_adasum_*.py).
        from _adasum_model import adasum_fold_model

        def vec(k, n=33, seed=7):
            rng = np.random.RandomState(seed + k)
            return rng.randn(n).astype(np.float32)

        vecs = [vec(k) for k in range(s)]
        out = hvd.allreduce(vecs[r], op=hvd.Adasum, name="ad.f32")
        np.testing.assert_allclose(out, adasum_fold_model(vecs), rtol=1e-5)

        # f64 and f16 dtypes
        v64 = [v.astype(np.float64) for v in vecs]
        out = hvd.allreduce(v64[r], op=hvd.Adasum, name="ad.f64")
        np.testing.assert_allclose(out, adasum_fold_model(v64), rtol=1e-12)
        v16 = [v.astype(np.float16) for v in vecs]
        out = hvd.allreduce(v16[r], op=hvd.Adasum, name="ad.f16")
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   np.asarray(adasum_fold_model(v16),
                                              np.float64), rtol=5e-2,
                                   atol=5e-2)

        # grouped: per-TENSOR dot/norm weighting inside one fused buffer
        a = [vec(k, 8, seed=100) for k in range(s)]
        b = [vec(k, 5, seed=200) for k in range(s)]
        outs = hvd.grouped_allreduce([a[r], b[r]], op=hvd.Adasum, name="ad.g")
        np.testing.assert_allclose(outs[0], adasum_fold_model(a), rtol=1e-5)
        np.testing.assert_allclose(outs[1], adasum_fold_model(b), rtol=1e-5)

        # identical gradients -> adasum degenerates to the average
        same = hvd.allreduce(np.full(6, 4.0, np.float32), op=hvd.Adasum,
                             name="ad.same")
        np.testing.assert_allclose(same, 4.0, rtol=1e-6)

        # integer input is rejected, not silently summed
        try:
            hvd.allreduce(np.ones(4, np.int32), op=hvd.Adasum, name="ad.bad")
            raise SystemExit("expected HorovodInternalError for int adasum")
        except HorovodInternalError:
            pass

    elif scenario == "fused_allgather":
        # Several async allgathers enqueued together fuse into one
        # response (same dtype) and must all come back correct: ragged
        # per-rank rows, different widths, plus a different-dtype one
        # that cannot fuse and an interleaved allreduce.
        hs = []
        hs.append(hvd.allgather_async(
            np.full((r + 1, 2), float(r), np.float32), name="fg.a"))
        hs.append(hvd.allgather_async(
            np.full((2, 3), 10.0 + r, np.float32), name="fg.b"))
        hs.append(hvd.allgather_async(
            np.full((1,), 100.0 + r, np.float64), name="fg.c"))
        hr = hvd.allreduce_async(np.full(4, float(r), np.float32),
                                 op=hvd.Sum, name="fg.ar")
        a = hvd.synchronize(hs[0])
        b = hvd.synchronize(hs[1])
        c = hvd.synchronize(hs[2])
        ar = hvd.synchronize(hr)

        assert a.shape == (s * (s + 1) // 2, 2), a.shape
        off = 0
        for k in range(s):
            np.testing.assert_allclose(a[off:off + k + 1], float(k))
            off += k + 1
        assert b.shape == (2 * s, 3), b.shape
        for k in range(s):
            np.testing.assert_allclose(b[2 * k:2 * k + 2], 10.0 + k)
        np.testing.assert_allclose(c, 100.0 + np.arange(s))
        np.testing.assert_allclose(ar, s * (s - 1) / 2.0)

        # steady state: same fused set again through the cache path
        for i in range(10):
            g = hvd.allgather(np.full((r + 1, 2), float(i), np.float32),
                              name="fg.a2")
            g2 = hvd.allgather(np.full((2, 3), float(i), np.float32),
                               name="fg.b2")
            np.testing.assert_allclose(g, float(i))
            np.testing.assert_allclose(g2, float(i))

    elif scenario == "xla_fused_allgather":
        import jax
        import jax.numpy as jnp

        assert jax.process_count() == s
        hs = [hvd.allgather_async(jnp.full((r + 1, 2), float(r)),
                                  name="xfg.a"),
              hvd.allgather_async(jnp.full((2, 3), 10.0 + r),
                                  name="xfg.b")]
        a = hvd.synchronize(hs[0])
        b = hvd.synchronize(hs[1])
        assert a.shape == (s * (s + 1) // 2, 2), a.shape
        off = 0
        for k in range(s):
            np.testing.assert_allclose(np.asarray(a[off:off + k + 1]),
                                       float(k))
            off += k + 1
        for k in range(s):
            np.testing.assert_allclose(np.asarray(b[2 * k:2 * k + 2]),
                                       10.0 + k)

    elif scenario == "sync_bn":
        # Distributed SyncBatchNorm over the split batch must equal
        # local BatchNorm over the concatenated batch — forward,
        # running stats, input grads, and param grads (param grads are
        # local sums; their allreduce-average times size equals the
        # full-batch grad).
        import torch
        from horovod_tpu.torch import SyncBatchNorm

        torch.manual_seed(0)
        full = torch.randn(4 * s, 3, 5, 5, dtype=torch.float64)
        mine = full[r * 4:(r + 1) * 4].clone().requires_grad_(True)

        sbn = SyncBatchNorm(3).double()
        out = sbn(mine)
        loss = (out * out).sum()
        loss.backward()

        ref = torch.nn.BatchNorm2d(3).double()
        x = full.clone().requires_grad_(True)
        ref_out = ref(x)
        (ref_out * ref_out).sum().backward()

        np.testing.assert_allclose(out.detach().numpy(),
                                   ref_out[r * 4:(r + 1) * 4].detach().numpy(),
                                   rtol=1e-10)
        np.testing.assert_allclose(sbn.running_mean.numpy(),
                                   ref.running_mean.numpy(), rtol=1e-10)
        np.testing.assert_allclose(sbn.running_var.numpy(),
                                   ref.running_var.numpy(), rtol=1e-10)
        np.testing.assert_allclose(mine.grad.numpy(),
                                   x.grad[r * 4:(r + 1) * 4].numpy(),
                                   rtol=1e-9, atol=1e-12)
        # param grads: avg(local sums) * size == full-batch grad
        gw = hvd.allreduce(sbn.weight.grad.numpy(), name="bn.gw")
        np.testing.assert_allclose(gw * s, ref.weight.grad.numpy(),
                                   rtol=1e-9)

        # eval mode = local BN (no collectives)
        sbn.eval()
        ref.eval()
        np.testing.assert_allclose(
            sbn(mine).detach().numpy(),
            ref(full)[r * 4:(r + 1) * 4].detach().numpy(), rtol=1e-9)

    elif scenario == "torch_grads":
        # Differentiable collectives: each op's backward must match the
        # reference's autograd contract (torch/mpi_ops.py:186,393,578,
        # 663,806) — checked analytically per rank.
        import torch
        import horovod_tpu.torch as thvd

        # allreduce(Sum): dx = allreduce_sum(cotangent)
        x = torch.zeros(4, dtype=torch.float64).requires_grad_(True)
        y = thvd.allreduce(x, op=hvd.Sum, name="g.ar")
        (y * float(r + 1)).sum().backward()
        want = sum(range(1, s + 1))
        np.testing.assert_allclose(x.grad.numpy(), np.full(4, want))

        # allreduce(Average): dx = avg(cotangent)
        x = torch.zeros(4, dtype=torch.float64).requires_grad_(True)
        y = thvd.allreduce(x, op=hvd.Average, name="g.aravg")
        (y * float(r + 1)).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(),
                                   np.full(4, (s + 1) / 2.0))

        # grouped allreduce: per-tensor gradients, one fused backward
        xs = [torch.zeros(3, dtype=torch.float64).requires_grad_(True)
              for _ in range(2)]
        ys = thvd.grouped_allreduce(xs, op=hvd.Sum, name="g.gar")
        (ys[0] * float(r + 1) + ys[1] * 2.0 * float(r + 1)).sum().backward()
        np.testing.assert_allclose(xs[0].grad.numpy(), np.full(3, want))
        np.testing.assert_allclose(xs[1].grad.numpy(), np.full(3, 2 * want))

        # allgather with UNEVEN rows: dx = avg-allreduced cotangent,
        # narrowed to this rank's row span (offset bookkeeping).
        rows = r + 1
        total = s * (s + 1) // 2
        x = torch.zeros(rows, 2, dtype=torch.float64).requires_grad_(True)
        y = thvd.allgather(x, name="g.ag")
        assert y.shape == (total, 2), y.shape
        W = torch.arange(total * 2, dtype=torch.float64).reshape(total, 2)
        (y * W).sum().backward()
        offset = r * (r + 1) // 2
        np.testing.assert_allclose(x.grad.numpy(),
                                   W[offset:offset + rows].numpy())

        # broadcast: cotangents flow to the root only (averaged)
        root = s - 1
        x = torch.full((3,), float(r), dtype=torch.float64,
                       requires_grad=True)
        y = thvd.broadcast(x, root_rank=root, name="g.bc")
        np.testing.assert_allclose(y.detach().numpy(), np.full(3, root))
        (y * float(r + 1)).sum().backward()
        exp = np.full(3, (s + 1) / 2.0) if r == root else np.zeros(3)
        np.testing.assert_allclose(x.grad.numpy(), exp)

        # alltoall: backward routes each block back to its sender
        x = torch.zeros(2 * s, dtype=torch.float64).requires_grad_(True)
        y, rs = thvd.alltoall(x, name="g.a2a")
        assert rs.tolist() == [2] * s
        (y * float(r + 1)).sum().backward()
        np.testing.assert_allclose(
            x.grad.numpy(),
            np.repeat(np.arange(1, s + 1, dtype=np.float64), 2))

        # reducescatter(Sum): dx = allgather of segment cotangents
        x = torch.zeros(2 * s, 3, dtype=torch.float64).requires_grad_(True)
        y = thvd.reducescatter(x, op=hvd.Sum, name="g.rs")
        assert y.shape == (2, 3)
        (y * float(r + 1)).sum().backward()
        np.testing.assert_allclose(
            x.grad.numpy(),
            np.repeat(np.arange(1, s + 1, dtype=np.float64), 2)[:, None]
            * np.ones((1, 3)))

        # reducescatter(Average): forward averages, backward scales
        x = torch.zeros(2 * s, 3, dtype=torch.float64).requires_grad_(True)
        y = thvd.reducescatter(x, op=hvd.Average, name="g.rsa")
        (y * float(r + 1)).sum().backward()
        np.testing.assert_allclose(
            x.grad.numpy(),
            np.repeat(np.arange(1, s + 1, dtype=np.float64), 2)[:, None]
            * np.ones((1, 3)) / s)

        # nonlinear reductions must refuse the grad path, not emit a
        # silently-wrong dense gradient
        x = torch.zeros(3, dtype=torch.float64).requires_grad_(True)
        try:
            thvd.allreduce(x, op=hvd.Max, name="g.max")
            raise SystemExit("Max allreduce of a grad tensor must raise")
        except NotImplementedError:
            pass
        thvd.allreduce(x.detach(), op=hvd.Max, name="g.maxd")  # ok

        # a collective INSIDE a module backprops through to parameters
        lin = torch.nn.Linear(4, 4).double()
        inp = torch.randn(2, 4, dtype=torch.float64)
        out = thvd.allreduce(lin(inp), op=hvd.Average, name="g.mod")
        out.sum().backward()
        assert lin.weight.grad is not None
        assert float(lin.weight.grad.abs().sum()) > 0

    elif scenario == "callbacks":
        from horovod_tpu.callbacks import (MetricAverageCallback,
                                           average_metrics)
        got = average_metrics({"loss": float(r), "acc": 2.0 * r})
        np.testing.assert_allclose(got["loss"], (s - 1) / 2.0)
        np.testing.assert_allclose(got["acc"], float(s - 1))
        m = {"loss": float(r)}
        MetricAverageCallback().on_epoch_end(0, m)
        np.testing.assert_allclose(m["loss"], (s - 1) / 2.0)

    elif scenario == "xla_adasum":
        # CALLBACK-mode Adasum: the zero-padded pair tree, per-segment
        # weighting in the fused program.
        import jax
        import jax.numpy as jnp
        from _adasum_model import adasum_tree_model

        assert jax.process_count() == s

        def vec(k, n=17, seed=3):
            rng = np.random.RandomState(seed + k)
            return rng.randn(n).astype(np.float32)

        vecs = [vec(k) for k in range(s)]
        out = hvd.allreduce(jnp.asarray(vecs[r]), op=hvd.Adasum, name="xad")
        # f32 accumulation in-program vs the f64 NumPy model
        np.testing.assert_allclose(np.asarray(out), adasum_tree_model(vecs),
                                   rtol=1e-4)
        a = [vec(k, 9, seed=50) for k in range(s)]
        b = [vec(k, 4, seed=60) for k in range(s)]
        outs = hvd.grouped_allreduce([jnp.asarray(a[r]), jnp.asarray(b[r])],
                                     op=hvd.Adasum, name="xad.g")
        np.testing.assert_allclose(np.asarray(outs[0]), adasum_tree_model(a),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(outs[1]), adasum_tree_model(b),
                                   rtol=1e-4)

    elif scenario == "xla_rank_order":
        # The eager XLA plane's "rank" axis must follow Horovod ranks,
        # not jax process indices (what a sum or mean cannot see).
        import jax
        import jax.numpy as jnp

        assert jax.process_index() == s - 1 - r, jax.process_index()
        rows = hvd.allgather(jnp.full((1, 2), float(r), jnp.float32),
                             name="order.ag")
        np.testing.assert_array_equal(np.asarray(rows)[:, 0], np.arange(s))
        for root in range(s):
            out = hvd.broadcast(jnp.full((3,), float(r), jnp.float32),
                                root_rank=root, name=f"order.bc.{root}")
            np.testing.assert_array_equal(np.asarray(out), float(root))

    elif scenario == "xla_join":
        # CALLBACK-mode Join: joined rank synthesizes a zeros
        # contribution and still launches the same XLA program.
        import jax
        import jax.numpy as jnp

        assert jax.process_count() == s
        if r == s - 1:
            hvd.join()
        else:
            # Scaled allreduce under join: the joined rank only knows
            # factor 1.0 — program identity must not depend on factor
            # values or the ranks trace different HLO and hang.
            out = hvd.allreduce(jnp.full(4, float(r + 1)), op=hvd.Sum,
                                prescale_factor=3.0, name="xj")
            want = 3.0 * sum(k + 1 for k in range(s - 1))
            np.testing.assert_allclose(np.asarray(out), want)
            hvd.join()

    elif scenario == "traffic":
        # Sustained allreduce traffic over a FIXED iteration count
        # (time-based loops desync ranks: the first finisher's
        # shutdown kills everyone else's in-flight ops). Autotune
        # tests: the tuner needs many measurement windows, and the
        # results must stay correct through every parameter flip.
        iters = int(os.environ.get("TRAFFIC_ITERS", "2000"))
        want = float(s) * 1.0
        for i in range(iters):
            out = hvd.allreduce(np.ones(4096, np.float32), op=hvd.Sum,
                                name=f"tr.{i % 4}")
            assert abs(float(np.asarray(out)[0]) - want) < 1e-5
        print(f"OK rank={r} iters={iters}")

    elif scenario == "fused_bitwise":
        # Fused multi-tensor allreduce must be BITWISE identical to the
        # per-tensor path (same accumulate order per element on both),
        # and the result bytes must not depend on HOROVOD_REDUCE_THREADS
        # or the shm pipeline depth — the test runs this scenario under
        # several knob settings and compares the printed digests.
        # Sizes straddle the threading grain and (with the test's tiny
        # HOROVOD_SHM_SEGMENT_BYTES) the shm segment boundaries.
        import hashlib

        rng = np.random.RandomState(100 + r)
        xs = [rng.randn(n).astype(np.float32)
              for n in (8191, 65536, 3, 100003)]
        fused = hvd.grouped_allreduce([x.copy() for x in xs], op=hvd.Sum,
                                      name="fb")
        single = [hvd.allreduce(x.copy(), op=hvd.Sum, name=f"fb.{i}")
                  for i, x in enumerate(xs)]
        for i, (f, u) in enumerate(zip(fused, single)):
            assert np.asarray(f).tobytes() == np.asarray(u).tobytes(), (
                f"fused tensor {i} differs from per-tensor result")
        digest = hashlib.sha1(
            b"".join(np.asarray(o).tobytes() for o in fused)).hexdigest()
        print(f"DIGEST {digest}")
        print(f"OK rank={r}")

    elif scenario == "wire_parity":
        # Wire-compression parity over the TCP data plane (run with
        # HOROVOD_SHM_DISABLE=1; np=2 exercises the doubling exchange,
        # np>=3 with the payload above HOROVOD_RING_THRESHOLD the ring;
        # node-major 2x2 + HIERARCHICAL the cross-node phase).
        rng = np.random.RandomState(3 + r)
        x = rng.randn(120000).astype(np.float32)
        base = hvd.allreduce(x.copy(), op=hvd.Sum, name="wp.none",
                             compression=hvd.Compression.none)
        want = sum(np.random.RandomState(3 + k).randn(120000)
                   .astype(np.float32) for k in range(s))
        np.testing.assert_allclose(base, want, rtol=1e-4, atol=1e-4)

        # bf16/fp16 wire stays within the wire dtype's tolerance of the
        # uncompressed result (absolute slack covers near-zero sums,
        # whose relative error a 2^-8-mantissa wire can't bound).
        amax = float(np.abs(base).max())
        bf = hvd.allreduce(x.copy(), op=hvd.Sum, name="wp.bf16",
                           compression=hvd.Compression.bf16)
        np.testing.assert_allclose(bf, base, atol=amax * 2**-6)
        fp = hvd.allreduce(x.copy(), op=hvd.Sum, name="wp.fp16",
                           compression=hvd.Compression.fp16)
        np.testing.assert_allclose(fp, base, atol=amax * 2**-8)

        # int8 + error feedback: a repeated allreduce of the SAME
        # tensor must converge — residuals carry each step's rounding
        # error into the next, so the time-average's error shrinks
        # ~1/T while any single shot stays at quantization scale.
        outs = [np.asarray(hvd.allreduce(x, op=hvd.Sum, name="wp.i8",
                                         compression=hvd.Compression.int8))
                for _ in range(48)]
        single = float(np.abs(outs[0] - base).max())
        mean_err = float(np.abs(np.mean(outs, axis=0) - base).max())
        assert single > 1e-4, "int8 wire produced an exact result?"
        assert mean_err < single / 8, (single, mean_err)

        # Grouped allreduce rides the codec too (matching codecs fuse).
        g = hvd.grouped_allreduce([x.copy(), np.ones(513, np.float32)],
                                  op=hvd.Sum, name="wp.grp",
                                  compression=hvd.Compression.bf16)
        np.testing.assert_allclose(g[0], base, atol=amax * 2**-6)
        np.testing.assert_allclose(g[1], float(s), atol=0.1)

        # The `none` codec must be bitwise invariant to the reduction
        # thread count (the PR 2 contract survives the codec layer).
        hvd.set_reduce_threads(1)
        t1 = hvd.allreduce(x.copy(), op=hvd.Sum, name="wp.t",
                           compression=hvd.Compression.none)
        hvd.set_reduce_threads(4)
        t4 = hvd.allreduce(x.copy(), op=hvd.Sum, name="wp.t",
                           compression=hvd.Compression.none)
        hvd.set_reduce_threads(1)
        assert np.asarray(t1).tobytes() == np.asarray(t4).tobytes()

    elif scenario == "wire_env":
        # Job-wide HOROVOD_WIRE_COMPRESSION knob: requests without a
        # per-op compression= follow the coordinator's synced value.
        rng = np.random.RandomState(17 + r)
        x = rng.randn(100000).astype(np.float32)
        exact = hvd.allreduce(x.copy(), op=hvd.Sum, name="we.none",
                              compression=hvd.Compression.none)
        dflt = hvd.allreduce(x.copy(), op=hvd.Sum, name="we.dflt")
        env = os.environ.get("HOROVOD_WIRE_COMPRESSION", "")
        amax = float(np.abs(np.asarray(exact)).max())
        if env == "bf16":
            # The default-codec op must actually have been quantized...
            assert np.asarray(dflt).tobytes() != np.asarray(exact).tobytes()
            # ...but stay within bf16 wire tolerance.
            np.testing.assert_allclose(dflt, exact, atol=amax * 2**-6)
        else:
            # Unset or garbage (sanitized to none): bitwise identical.
            assert np.asarray(dflt).tobytes() == np.asarray(exact).tobytes()

    elif scenario == "wire_ring":
        # np>=3 ring with every codec: all ranks must land on BITWISE
        # identical results even under lossy compression (the allgather
        # phase forwards each chunk's encoded bytes verbatim and the
        # owner self-decodes, so every rank decodes the same bytes).
        import hashlib

        rng = np.random.RandomState(100 + r)
        x = rng.randn(200003).astype(np.float32)
        digests = []
        for cname, comp in (("none", hvd.Compression.none),
                            ("bf16", hvd.Compression.bf16),
                            ("fp16", hvd.Compression.fp16),
                            ("int8", hvd.Compression.int8)):
            out = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum,
                                           name=f"wr.{cname}",
                                           compression=comp))
            digests.append(f"{cname}:{hashlib.sha1(out.tobytes()).hexdigest()}")
        base = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum, name="wr.ref",
                                        compression=hvd.Compression.none))
        amax = float(np.abs(base).max())
        # Looser than the np=2 parity case: ring chunks re-quantize at
        # every relay hop, so the worst case stacks P-1 roundings.
        for cname, tol in (("bf16", 2**-5), ("fp16", 2**-7), ("int8", 0.05)):
            out = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum,
                                           name=f"wr2.{cname}",
                                           compression=getattr(
                                               hvd.Compression, cname)))
            np.testing.assert_allclose(out, base, atol=amax * tol,
                                       err_msg=cname)
        print("DIGEST " + "|".join(digests))

    elif scenario == "algo_parity":
        # Every TCP-plane algorithm (ring / hd / striped / doubling and
        # the coordinator's auto pick) must produce the PR 2 ring
        # path's exact bits on integer-valued data — float sums of
        # small integers are exact, so any ordering of the reduction
        # agrees bitwise and the comparison is an equality, not a
        # tolerance. Then, under every lossy codec, all ranks must land
        # on BITWISE identical results for hd/striped (the interpreter
        # forwards each chunk's encoded bytes verbatim and fresh
        # encodes self-decode, so every chunk is quantized exactly once
        # by its owner). Run with HOROVOD_SHM_DISABLE=1 so the TCP
        # plane — not the arena — executes.
        import hashlib

        rng = np.random.RandomState(100 + r)
        x = rng.randint(-50, 50, 120001).astype(np.float32)
        want = sum(np.random.RandomState(100 + k)
                   .randint(-50, 50, 120001).astype(np.float32)
                   for k in range(s))
        ref = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum, name="ap.ref",
                                       algorithm="ring"))
        assert (ref == want).all(), "ring reference wrong"
        for algo in ("hd", "striped", "doubling", None):
            out = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum,
                                           name=f"ap.{algo}",
                                           algorithm=algo))
            assert out.tobytes() == ref.tobytes(), (
                f"{algo} differs from the ring path on exact data")
        # A payload in the latency band rides the table's hd pick at
        # np>=3 and must still be exact.
        small = np.asarray(hvd.allreduce(
            np.full(8000, float(r + 1), np.float32), op=hvd.Sum,
            name="ap.small"))
        assert (small == sum(range(1, s + 1))).all()
        # MIN/MAX ride the interpreter's HostAccumulate dispatch too.
        mx = np.asarray(hvd.allreduce(x.copy(), op=hvd.Max, name="ap.max",
                                      algorithm="hd"))
        assert (mx == np.maximum.reduce(
            [np.random.RandomState(100 + k).randint(-50, 50, 120001)
             .astype(np.float32) for k in range(s)])).all()
        # Lossy codecs: parity within wire tolerance + cross-rank
        # bitwise agreement (digests compared by the test driver).
        y = rng.randn(90007).astype(np.float32)
        base = np.asarray(hvd.allreduce(y.copy(), op=hvd.Sum, name="ap.b",
                                        algorithm="hd",
                                        compression=hvd.Compression.none))
        amax = float(np.abs(base).max())
        digests = []
        for algo in ("hd", "striped"):
            for cname, tol in (("bf16", 2**-5), ("fp16", 2**-7),
                               ("int8", 0.05)):
                out = np.asarray(hvd.allreduce(
                    y.copy(), op=hvd.Sum, name=f"ap.{algo}.{cname}",
                    algorithm=algo,
                    compression=getattr(hvd.Compression, cname)))
                np.testing.assert_allclose(out, base, atol=amax * tol,
                                           err_msg=f"{algo}/{cname}")
                digests.append(
                    f"{algo}.{cname}:"
                    f"{hashlib.sha1(out.tobytes()).hexdigest()}")
        print("DIGEST " + "|".join(digests))
        print(f"OK rank={r}")

    elif scenario == "algo_ef":
        # int8 error feedback through the schedule interpreter: the
        # residual slab must make a repeated allreduce's time-average
        # converge — including at ragged np (the fold hand-off carries
        # EF too; an uncompensated fold leaves a systematic bias the
        # average can never shake).
        rng = np.random.RandomState(7 + r)
        x = rng.randn(60013).astype(np.float32)
        base = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum, name="ae.b",
                                        algorithm="hd",
                                        compression=hvd.Compression.none))
        outs = [np.asarray(hvd.allreduce(x, op=hvd.Sum, name="ae.i8",
                                         algorithm="hd",
                                         compression=hvd.Compression.int8))
                for _ in range(48)]
        single = float(np.abs(outs[0] - base).max())
        mean_err = float(np.abs(np.mean(outs, axis=0) - base).max())
        assert single > 1e-4, "int8 wire produced an exact result?"
        assert mean_err < single / 8, (single, mean_err)
        print(f"OK rank={r}")

    elif scenario == "algo_env":
        # Cross-rank algorithm agreement under CONFLICTING env knobs:
        # the test launches each rank with a different
        # HOROVOD_COLLECTIVE_ALGO and HOROVOD_RING_THRESHOLD. Rank 0's
        # synced values win (param sync), and the coordinator resolves
        # the concrete algorithm into every Response — so the job must
        # complete with exact results instead of deadlocking two ranks
        # into different exchanges (the failure mode the old post-sync
        # threshold note in ops.cc merely documented).
        for i, n in enumerate((1000, 40000, 300000)):
            x = np.full(n, float(r + 1), np.float32)
            out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=f"ae.{i}"))
            assert (out == sum(range(1, s + 1))).all(), (i, out[:4])
        # The introspected force is rank 0's, on every rank.
        print(f"ALGO {hvd.collective_algo()}")
        print(f"OK rank={r}")

    elif scenario == "shm_segmented":
        # Multi-segment shm allreduce (HOROVOD_SHM_SEGMENT_BYTES forced
        # tiny by the test): odd payload lengths so segment boundaries
        # land mid-entry, plus a fused group spanning segments, plus
        # prescale/postscale riding the per-segment pack/unpack.
        rng = np.random.RandomState(7 + r)
        x = rng.randn(100003).astype(np.float32)
        out = hvd.allreduce(x, op=hvd.Sum, name="seg")
        want = sum(np.random.RandomState(7 + k).randn(100003)
                   .astype(np.float32) for k in range(s))
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
        ys = [np.full(n, float(r + 1), np.float32) for n in (17, 4099, 1)]
        outs = hvd.grouped_allreduce(ys, op=hvd.Average, name="segg",
                                     prescale_factor=2.0)
        expect = 2.0 * sum(range(1, s + 1)) / s
        for o, y in zip(outs, ys):
            np.testing.assert_allclose(np.asarray(o),
                                       np.full_like(y, expect), atol=1e-5)
        print(f"OK rank={r}")

    elif scenario == "shm_die":
        # The last rank dies without warning mid-stream; survivors must
        # surface an error within seconds (TCP link error or shm pid
        # liveness poison), never hang out a long timeout.
        import time as _t

        hvd.allreduce(np.ones(4, np.float32), name="warm")  # arena warm
        if r == s - 1:
            os._exit(17)
        t0 = _t.monotonic()
        try:
            for i in range(1000):
                hvd.allreduce(np.ones(4, np.float32), name=f"d.{i}")
            raise SystemExit("survivor never saw the failure")
        except hvd.HorovodInternalError:
            dt = _t.monotonic() - t0
            assert dt < 30.0, f"death took {dt:.1f}s to surface"
        print(f"OK rank={r}")
        os._exit(0)  # shutdown would hang: the job is already broken

    elif scenario == "metrics":
        # Telemetry acceptance (docs/observability.md): after fused +
        # single allreduces over the shm plane, hvd.metrics() must
        # carry non-trivial counters (fusion fill, cycle histogram,
        # per-phase timings/bytes), the Prometheus exposition must be
        # grammatically valid, and metrics_aggregate() must agree
        # across ranks.
        import re

        hvd.metrics_reset()
        # 8 x 1 MB members: the fused 8 MB response fills ~12% of the
        # default 64 MB threshold, so the fill histogram records a
        # non-zero percentage (integer pct — sub-1% fills floor to 0).
        xs = [np.full(1 << 18, float(r + 1), np.float32) for _ in range(8)]
        for i in range(3):
            outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name=f"m.{i % 2}")
            want = sum(range(1, s + 1))
            for o in outs:
                np.testing.assert_allclose(np.asarray(o)[0], want)
        hvd.allreduce(np.ones(1 << 18, np.float32), op=hvd.Sum, name="m.big")

        m = hvd.metrics()
        assert m["cycles_total"] > 0, m
        assert m["responses_allreduce_total"] >= 4, m
        assert m["fused_batches_total"] >= 3, m
        assert m["fused_tensors_total"] >= 24, m
        assert m["tensors_total"] >= 25, m
        assert m["bytes_allreduce_total"] >= 25 * (1 << 20), m
        assert m["fusion_fill_pct_count"] >= 1, m       # fusion fill
        assert 0 < m["fusion_fill_pct_avg"] <= 200, m
        assert m["cycle_us_count"] > 0, m               # cycle histogram
        assert m["cycle_us_p99"] > 0, m
        if r == 0:
            # Negotiation latency is measured where the pending table
            # lives: the coordinator.
            assert m["negotiate_us_count"] >= 1, m
        # Per-phase data-plane series (shm segment pipeline).
        assert m["shm_ops_total"] >= 1 and m["shm_bytes_total"] > 0, m
        for ph in ("shm_pack_us", "shm_reduce_us", "shm_unpack_us",
                   "shm_barrier_us"):
            assert m[f"{ph}_count"] >= 1, (ph, m)
        # Coordinator-only series live on rank 0's registry.
        if r == 0:
            assert m["cache_hits_total"] + m["cache_misses_total"] > 0, m

        # Prometheus exposition: every line must match the text-format
        # grammar (comments, bare samples, or histogram bucket lines).
        txt = hvd.metrics_prometheus()
        line_re = re.compile(
            r'^(# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* '
            r'(counter|gauge|histogram)|HELP .*)'
            r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{le="(\+Inf|[0-9]+)"\})?'
            r' [-+]?([0-9.eE+-]+|inf|nan))$')
        for line in txt.rstrip("\n").splitlines():
            assert line_re.match(line), f"bad exposition line: {line!r}"
        assert "hvd_cycles_total" in txt and "hvd_cycle_us_bucket" in txt

        # Cross-rank aggregation rides the allreduce plane; every rank
        # gets the same reduction, and sum/min/max must be consistent.
        agg = hvd.metrics_aggregate()
        c = agg["cycles_total"]
        assert 0 < c["min"] <= c["max"] <= c["sum"] + 1e-9, c
        b = agg["shm_bytes_total"]
        assert b["sum"] >= s * b["min"] > 0, b
        spread = agg["shm_barrier_us_p99"]
        assert spread["max"] >= spread["min"] >= 0, spread
        print(f"OK rank={r}")

    elif scenario == "stall":
        # Injected stall (HOROVOD_STALL_CHECK_TIME_SECONDS set tiny by
        # the test): rank 0 announces a tensor rank 1 withholds, so the
        # finding must surface in hvd.stalled_tensors() AND the metrics
        # snapshot — then clear once rank 1 joins in.
        import time as _t

        # The name embeds a tab: names are arbitrary user strings, and
        # the stalled_tensors wire uses \t/\n separators — the report
        # escapes, the accessor unescapes, and a separator in the name
        # must not break the very accessor diagnosing its stall.
        lag_name = "st.lag\tq"
        if r == 0:
            h = hvd.allreduce_async(np.full(8, 1.0, np.float32),
                                    name=lag_name)
            # Rank 1's own half-announced collectives (its early
            # barrier) legitimately stall too; select OUR tensor by
            # name instead of assuming a single finding.
            lag = None
            deadline = _t.monotonic() + 30
            while _t.monotonic() < deadline and lag is None:
                lag = next((f for f in hvd.stalled_tensors()
                            if f["name"] == lag_name), None)
                if lag is None:
                    _t.sleep(0.1)
            assert lag, "stall never surfaced in stalled_tensors()"
            assert lag["missing_ranks"] == [1], lag
            assert lag["age_secs"] > 0, lag
            assert hvd.metrics()["stalled_tensors"] >= 1  # snapshot gauge
            # The periodic coordinator check also counts a stall event.
            deadline = _t.monotonic() + 30
            while (_t.monotonic() < deadline
                   and hvd.metrics()["stall_events_total"] == 0):
                _t.sleep(0.1)
            assert hvd.metrics()["stall_events_total"] >= 1
            hvd.barrier()  # release rank 1 to submit its half
            out = hvd.synchronize(h)
        else:
            # Worker ranks hold no pending table: accessor stays empty.
            assert hvd.stalled_tensors() == []
            hvd.barrier()
            out = hvd.allreduce(np.full(8, 1.0, np.float32), name=lag_name)
        np.testing.assert_allclose(np.asarray(out),
                                   np.full(8, 1.0, np.float32))
        if r == 0:
            # Resolved: the finding must clear from the report.
            assert hvd.stalled_tensors() == []
        print(f"OK rank={r}")

    elif scenario == "metrics_overhead":
        # Registry overhead guard: the identical np=2 shm allreduce
        # microbench with observations on vs off, rounds INTERLEAVED
        # (sequential arms drift under this box's scheduler — the
        # PR 1-4 busbw lesson) and each arm keeping its best round.
        # The test asserts the printed ratio < 1.02 (the <2% budget).
        import time as _t

        from horovod_tpu.metrics import set_metrics_enabled

        x = np.ones(1 << 16, np.float32)  # 256 KB
        for i in range(20):
            hvd.allreduce(x, op=hvd.Sum, name="ov.w")
        # Arm order alternates per round (a systematic second-position
        # cost must not read as registry overhead), and a whole attempt
        # retries when the box was too noisy — the decision is taken
        # COLLECTIVELY (max-allreduced ratio) so ranks never diverge on
        # how many allreduces they run. Real >2% overhead fails every
        # attempt on every rank. Deflaked for the slow box phases
        # (pre-existing ~1/3 failure rate, ISSUE 11): more, shorter
        # rounds (50-iter rounds interleave the arms ~1.6x finer, so a
        # multi-second scheduler phase shift lands on both arms instead
        # of eating one), five attempts instead of three, and the
        # early-exit margin at 1.018 — any attempt the box let through
        # honestly ends the protocol. Real overhead still fails: it
        # shows on every rank in every attempt.
        # Box-speed gating (ISSUE 13 deflake): alongside each attempt's
        # ratio, measure the box's OWN weather — the spread between the
        # median and best metrics-off round. On a quiet box the rounds
        # repeat within a few percent and the strict 2% budget is a
        # meaningful gate; in a slow phase (the ~1/3 failure mode: the
        # scheduler parks a rank for multi-second stretches) the spread
        # blows past 15% and a best-vs-best ratio is weather, not
        # registry cost. The spread is Max-allreduced like the ratio so
        # every rank reports the same verdict, and the TEST widens the
        # budget only when the measured spread says the box was noisy —
        # real registry overhead shows at any spread, in every attempt.
        iters, agreed, agreed_spread = 50, None, None
        for att in range(5):
            best = {}
            off_rounds = []
            for rnd in range(10):
                order = (False, True) if rnd % 2 == 0 else (True, False)
                for on in order:
                    set_metrics_enabled(on)
                    t0 = _t.perf_counter()
                    for _ in range(iters):
                        hvd.allreduce(x, op=hvd.Sum, name="ov.t")
                    dt = _t.perf_counter() - t0
                    best[on] = min(best.get(on, dt), dt)
                    if not on:
                        off_rounds.append(dt)
            set_metrics_enabled(True)
            ratio = best[True] / best[False]
            spread = (float(np.median(off_rounds)) - min(off_rounds)) \
                / min(off_rounds)
            worst, worst_spread = np.asarray(hvd.allreduce(
                np.array([ratio, spread]), op=hvd.Max,
                name=f"ov.agree.{att}")).tolist()
            if agreed is None or worst < agreed:
                agreed, agreed_spread = worst, worst_spread
            if agreed < 1.018:
                break
        if r == 0:
            print(f"OVERHEAD on={best[True]:.6f} off={best[False]:.6f} "
                  f"ratio={agreed:.4f} spread={agreed_spread:.4f}")
        print(f"OK rank={r}")

    elif scenario == "timeline_restart":
        # hvd_start_timeline restart semantics (used to silently no-op
        # on a running timeline) in both orders: restart-while-running
        # and start-after-stop, plus the unopenable-path error.
        d = os.environ["TL_DIR"]
        p1, p2 = os.path.join(d, "t1.json"), os.path.join(d, "t2.json")
        hvd.start_timeline(p1)
        hvd.allreduce(np.ones(8, np.float32), name="tl.first")
        # The registry-fed counter tracks are flushed by the
        # BACKGROUND cycle thread, not the allreduce that returned —
        # restarting immediately races its next flush and flakes the
        # counter assertion below. Wait for the evidence itself: a
        # counter event in the file is the "flushed" signal (bounded —
        # the cycle loop ticks continuously while the timeline runs).
        import time as _t
        deadline = _t.monotonic() + 30.0
        while _t.monotonic() < deadline:
            raw1 = open(p1).read()
            if '"ph": "C"' in raw1 and "queue_depth" in raw1:
                break
            _t.sleep(0.02)
        hvd.start_timeline(p2)  # restart onto a NEW path while running
        hvd.allreduce(np.ones(8, np.float32), name="tl.second")
        hvd.stop_timeline()
        raw1, raw2 = open(p1).read(), open(p2).read()
        # Registry-fed counter tracks ride next to the spans.
        assert '"ph": "C"' in raw1 and "queue_depth" in raw1, raw1[:300]
        assert "fusion_bytes" in raw1 and "busbw_gbps" in raw1
        assert "tl.first" in raw1, raw1[:200]
        assert "tl.second" not in raw1, "old file kept recording"
        assert "tl.second" in raw2, raw2[:200]
        assert "tl.first" not in raw2, "new file replays the old epoch"
        try:
            hvd.start_timeline(os.path.join(d, "no/such/dir/t.json"))
            raise SystemExit("unopenable timeline path must raise")
        except HorovodInternalError:
            pass
        # A failed start must not wedge the timeline: a fresh start
        # (stopped state) still works and truncates the old file.
        hvd.start_timeline(p1)
        hvd.allreduce(np.ones(8, np.float32), name="tl.third")
        hvd.stop_timeline()
        raw1 = open(p1).read()
        assert "tl.third" in raw1 and "tl.first" not in raw1
        # A failed RESTART (bad path while running) raises but must
        # leave the running recording untouched — the new file opens
        # before the old timeline shuts down.
        hvd.start_timeline(p1)
        hvd.allreduce(np.ones(8, np.float32), name="tl.fourth")
        try:
            hvd.start_timeline(os.path.join(d, "no/such/dir/t.json"))
            raise SystemExit("unopenable restart path must raise")
        except HorovodInternalError:
            pass
        hvd.allreduce(np.ones(8, np.float32), name="tl.fifth")
        hvd.stop_timeline()
        raw1 = open(p1).read()
        assert "tl.fourth" in raw1 and "tl.fifth" in raw1, \
            "failed restart killed the running timeline"
        print(f"OK rank={r}")

    elif scenario == "transport_digest":
        # Vectored-transport parity probe (ISSUE 10): a cheap spread of
        # ops across every TCP exchange engine (ring/hd/striped/
        # doubling, fused group, fused allgather, broadcast), digests
        # printed so the driver can compare HOROVOD_TCP_ZEROCOPY=off vs
        # auto byte-for-byte. Integer-valued floats keep every sum
        # exact, so the digests are also cross-rank identical.
        import hashlib

        digests = []
        x = np.random.RandomState(100 + r).randint(
            -50, 50, 700003).astype(np.float32)
        for algo in ("ring", "hd", "striped", "doubling"):
            out = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum,
                                           name=f"td.{algo}",
                                           algorithm=algo))
            digests.append(f"{algo}:{hashlib.sha1(out.tobytes()).hexdigest()}")
        ts = [np.full(4096, float(r + i), np.float32) for i in range(8)]
        outs = hvd.grouped_allreduce(ts, op=hvd.Sum, name="td.grp")
        digests.append("grp:" + hashlib.sha1(
            b"".join(np.asarray(o).tobytes() for o in outs)).hexdigest())
        # Fused allgather with ragged rows (async pair enqueued
        # together so the coordinator fuses them): the vectored ring
        # runs straight over the output spans — the zero-staging path.
        ga = hvd.allgather_async(
            np.full((r + 1, 3), float(r), np.float32), name="td.ag.a")
        gb = hvd.allgather_async(
            np.full((2 * r + 1, 5), float(10 + r), np.float32),
            name="td.ag.b")
        gs = [hvd.synchronize(ga), hvd.synchronize(gb)]
        digests.append("ag:" + hashlib.sha1(
            b"".join(np.asarray(g).tobytes() for g in gs)).hexdigest())
        b = np.asarray(hvd.broadcast(
            np.arange(3001, dtype=np.float32) + r, root_rank=s - 1,
            name="td.bc"))
        digests.append("bc:" + hashlib.sha1(b.tobytes()).hexdigest())
        print("DIGEST " + "|".join(digests))
        # Syscall accounting: the vectored layer must be live (sendv
        # syscalls issued on the data plane) and coalescing must hold —
        # bytes-per-send-syscall stays well above frame-header size.
        m = hvd.metrics()
        assert m["tcp_sendv_calls_total"] > 0, m
        assert m["tcp_recvv_calls_total"] > 0, m
        assert m["tcp_zerocopy_mode"] in (0, 1), m
        if m["tcp_zerocopy_mode"] == 0:
            assert m["tcp_zerocopy_sends_total"] == 0, m
        # Floor well above frame-header size but with headroom for the
        # idle coordination cycles' tiny frames (1 ms cadence): a
        # regression to per-header sends would read ~30 B/syscall.
        bytes_per_call = (m["tcp_send_bytes_total"]
                          / m["tcp_sendv_calls_total"])
        assert bytes_per_call > 512, (
            f"sendv averaging {bytes_per_call:.0f} B/syscall — header-"
            "sized sends are back")
        print(f"BPC {bytes_per_call:.0f}")
        # Transport riders (ISSUE 14): the resolved io_uring verdict is
        # a real gauge, and with batching off (forced, or probed out on
        # this 4.4 kernel) no batch may ever have been submitted. The
        # driver test compares the RIDERS line across knob arms.
        assert m["tcp_iouring_mode"] in (0, 1), m
        if m["tcp_iouring_mode"] == 0:
            assert m["tcp_iouring_batches_total"] == 0, m
        print(f"RIDERS iouring={int(m['tcp_iouring_mode'])} "
              f"affinity={int(m['worker_affinity'])}")

    elif scenario == "topo_probe":
        # Measured-topology plumbing (ISSUE 13), launched with
        # HOROVOD_TOPOLOGY_PROBE=force by the test: the startup probe
        # must install a full alpha-beta model on EVERY rank with
        # byte-identical numbers (the broadcast-blob contract measured
        # selection and synthesis rely on), selection must keep exact
        # results, and the on-demand re-probe must run cleanly against
        # the live background cycle (quiet data plane: no collectives
        # in flight when it is called).
        import hashlib
        import json

        topo = hvd.topology()
        assert topo is not None, "probe forced but no model installed"
        assert topo["np"] == s, topo
        for i in range(s):
            for j in range(s):
                a = topo["alpha_us"][i][j]
                b = topo["beta_us_per_byte"][i][j]
                if i == j:
                    assert a == 0.0 and b == 0.0, (i, j, a, b)
                else:
                    assert a > 0 and b > 0, (i, j, a, b)
        blob = json.dumps(topo, sort_keys=True).encode()
        print("TOPO " + hashlib.sha1(blob).hexdigest())
        # Selection under the measured model stays exact (auto verdicts
        # ride the cost model now — any table it picks must agree
        # bitwise on integer-valued data).
        for i, n in enumerate((1000, 40000, 300000)):
            x = np.full(n, float(r + 1), np.float32)
            out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=f"tp.{i}"))
            assert (out == sum(range(1, s + 1))).all(), (i, out[:4])
        m = hvd.metrics()
        assert m["topology_probes_total"] >= 1, m
        assert m["topology_links_measured"] == s * (s - 1), m
        assert m["topology_probe_ms"] >= 0, m
        assert m["collective_measured_selects_total"] >= (
            1 if r == 0 else 0), m
        # On-demand re-probe: collective call, no collectives in
        # flight. The fresh model must remain full and identical.
        ms = hvd.topology_probe()
        assert ms > 0, ms
        topo2 = hvd.topology()
        assert topo2 is not None and topo2["np"] == s
        print("TOPO2 " + hashlib.sha1(
            json.dumps(topo2, sort_keys=True).encode()).hexdigest())
        out = np.asarray(hvd.allreduce(
            np.full(5000, float(r + 1), np.float32), op=hvd.Sum,
            name="tp.post"))
        assert (out == sum(range(1, s + 1))).all()

    elif scenario == "topo_cached":
        # HOROVOD_TOPOLOGY_PROBE=auto with a warm cache: the model must
        # load from disk (rank 0) and broadcast — NO probe rounds run
        # (topology_probes_total stays 0), which is what makes auto
        # free for every job after the first on a hostset.
        topo = hvd.topology()
        assert topo is not None and topo["np"] == s, topo
        m = hvd.metrics()
        assert m["topology_probes_total"] == 0, m
        assert m["topology_links_measured"] == s * (s - 1), m
        out = np.asarray(hvd.allreduce(
            np.full(3000, float(r + 1), np.float32), op=hvd.Sum,
            name="tc.x"))
        assert (out == sum(range(1, s + 1))).all()

    elif scenario == "topo_off":
        # HOROVOD_TOPOLOGY_PROBE=off: no model anywhere, measured
        # selection unavailable (-1), hand bands serve every verdict,
        # results stay exact.
        import ctypes

        from horovod_tpu.common.basics import get_lib

        assert hvd.topology() is None
        assert get_lib().hvd_algo_select_measured(
            ctypes.c_int64(1 << 20), s, 0,
            ctypes.c_int64(256 * 1024)) == -1
        m = hvd.metrics()
        assert m["topology_probes_total"] == 0, m
        assert m["topology_links_measured"] == 0, m
        out = np.asarray(hvd.allreduce(
            np.full(3000, float(r + 1), np.float32), op=hvd.Sum,
            name="to.x"))
        assert (out == sum(range(1, s + 1))).all()

    elif scenario == "table_parity":
        # Allgather / reducescatter / alltoall through the schedule
        # interpreter (ISSUE 13): digests printed so the test driver
        # can compare HOROVOD_COLLECTIVE_TABLES=on vs off jobs bit for
        # bit (the tables are wire-identical to the legacy engines by
        # construction). Run with HOROVOD_SHM_DISABLE=1 so the TCP
        # plane — not the arena — executes; ragged rows/splits exercise
        # the non-uniform span paths, and MIN rides the RECV_REDUCE
        # fold dispatch.
        import hashlib

        digests = []
        rng = np.random.RandomState(40 + r)
        g = hvd.allgather(rng.randn(3 * r + 1, 5).astype(np.float32),
                          name="tb.ag")
        digests.append("ag:" + hashlib.sha1(
            np.asarray(g).tobytes()).hexdigest())
        # Fused pair (async, coordinator fuses): multi-span chunks.
        ga = hvd.allgather_async(
            rng.randn(r + 1, 3).astype(np.float32), name="tb.agf.a")
        gb = hvd.allgather_async(
            rng.randn(2 * r + 2, 7).astype(np.float32), name="tb.agf.b")
        gs = [hvd.synchronize(ga), hvd.synchronize(gb)]
        digests.append("agf:" + hashlib.sha1(
            b"".join(np.asarray(x).tobytes() for x in gs)).hexdigest())
        x = rng.randn(4 * s, 3).astype(np.float32)
        rs = hvd.reducescatter(x, op=hvd.Sum, name="tb.rs")
        digests.append("rs:" + hashlib.sha1(
            np.asarray(rs).tobytes()).hexdigest())
        rs2 = hvd.reducescatter(x, op=hvd.Min, name="tb.rs.min")
        digests.append("rsmin:" + hashlib.sha1(
            np.asarray(rs2).tobytes()).hexdigest())
        splits = [k + 1 for k in range(s)]
        xa = rng.randn(sum(splits), 2).astype(np.float32)
        a2a, rsplits = hvd.alltoall(xa, splits=splits, name="tb.a2a")
        assert list(rsplits) == [r + 1] * s, rsplits
        digests.append("a2a:" + hashlib.sha1(
            np.asarray(a2a).tobytes()).hexdigest())
        # A large allgather so the >8KB helper-thread wave runs too.
        gbig = hvd.allgather(
            rng.randn(5000 + 100 * r, 4).astype(np.float32), name="tb.agL")
        digests.append("agL:" + hashlib.sha1(
            np.asarray(gbig).tobytes()).hexdigest())
        print("DIGEST " + "|".join(digests))

    elif scenario == "synth_live":
        # Synthesized allreduce tables live (ISSUE 13): the test sets
        # HOROVOD_COLLECTIVE_STRIPES / _GRANULARITY / HOROVOD_HD_ORDER
        # (tools/synth.py's hand-off knobs) and every forced family
        # must reproduce the ring path's exact bits on integer-valued
        # data — the live half of the simulated-executor verification.
        rng = np.random.RandomState(300 + r)
        x = rng.randint(-50, 50, 240007).astype(np.float32)
        ref = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum, name="sl.ref",
                                       algorithm="ring"))
        want = sum(np.random.RandomState(300 + k)
                   .randint(-50, 50, 240007).astype(np.float32)
                   for k in range(s))
        assert (ref == want).all(), "ring reference wrong"
        for algo in ("striped", "hd", None):
            out = np.asarray(hvd.allreduce(x.copy(), op=hvd.Sum,
                                           name=f"sl.{algo}",
                                           algorithm=algo))
            assert out.tobytes() == ref.tobytes(), (
                f"{algo} under synthesized parameters differs from ring")
        # And under a lossy codec the synthesized tables must still
        # land every rank on identical bytes (verbatim forwarding).
        import hashlib
        y = rng.randn(60013).astype(np.float32)
        dg = []
        for algo in ("striped", "hd"):
            out = np.asarray(hvd.allreduce(
                y.copy(), op=hvd.Sum, name=f"sl.{algo}.bf16",
                algorithm=algo, compression=hvd.Compression.bf16))
            dg.append(f"{algo}:{hashlib.sha1(out.tobytes()).hexdigest()}")
        print("DIGEST " + "|".join(dg))
        # Span-interpreter kinds in the same job (allgather over output
        # spans, reduce-scatter fold, ragged alltoall) so one sanitizer
        # scenario race-checks BOTH new engines alongside the
        # synthesized allreduce tables.
        g = np.asarray(hvd.allgather(
            np.full((r + 2, 3), float(r), np.float32), name="sl.ag"))
        assert g.shape[0] == sum(k + 2 for k in range(s)), g.shape
        rs = np.asarray(hvd.reducescatter(
            np.full((2 * s, 2), 1.0, np.float32), op=hvd.Sum, name="sl.rs"))
        assert (rs == s).all(), rs
        a2a, _ = hvd.alltoall(
            np.repeat(np.arange(s, dtype=np.float32), 2)[:, None],
            splits=[2] * s, name="sl.a2a")
        assert (np.asarray(a2a) == r).all(), a2a

    elif scenario == "lock_steady":
        # Steady-state schedule lock (ISSUE 15): a repeating loop must
        # engage the lock within K+2 steps, bypass negotiation for the
        # rest, unlock deterministically on a shape change (a test that
        # would hang or diverge without the unlock path: the changed
        # tensor can never match the locked ring), then re-lock on the
        # new steady pattern — values asserted at every step.
        K = 3  # kSteadyLockK (steady_lock.h)
        # Engagement is deterministic by OP COUNT for a synchronous
        # single-tensor loop: op 1 misses, ops 2..K+2 are pure cycles,
        # the engage broadcast rides op K+2's cycle and is installed
        # before op K+3 completes. A rank-local engaged-poll loop would
        # issue rank-DIVERGENT collective counts (the racy read lands
        # differently per rank) and wedge the job at the next pattern
        # change — fixed counts everywhere in these scenarios.
        for i in range(K + 4):
            out = hvd.allreduce(np.full(8, float(r + i), np.float32),
                                op=hvd.Sum, name="lk")
            np.testing.assert_allclose(
                out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
        assert hvd.steady_lock_engaged(), "lock never engaged"
        for i in range(10):
            out = hvd.allreduce(np.full(8, float(r + i), np.float32),
                                op=hvd.Sum, name="lk")
            np.testing.assert_allclose(
                out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
        m = hvd.metrics()
        assert m["ctrl_locks_total"] >= 1, m
        assert m["ctrl_bypassed_responses_total"] >= 5, m
        assert m["ctrl_locked"] == 1, m
        assert m["lock_fire_us_count"] >= 1, m
        # Shape change: every rank's local match fails -> consensus
        # unlock (reason: mismatch), renegotiation fires the new shape.
        out = hvd.allreduce(np.full(3, 1.0, np.float32), op=hvd.Sum,
                            name="lk")
        np.testing.assert_allclose(out, float(s))
        assert not hvd.steady_lock_engaged()
        m = hvd.metrics()
        assert m["ctrl_unlocks_total"] >= 1, m
        assert m["ctrl_unlocks_mismatch_total"] >= 1, m
        # Re-lock on the new steady pattern, fused-group flavor: one
        # grouped enqueue per step -> a multi-bit ring slot.
        for i in range(2 * (K + 4)):
            xs = [np.full(4, float(r + i), np.float32),
                  np.full(2, 2.0 * r, np.float32)]
            outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="lkg")
            np.testing.assert_allclose(
                outs[0], float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
            np.testing.assert_allclose(outs[1], float(s * (s - 1)),
                                       rtol=1e-6)
            if i == 2 * (K + 4) - 2:
                # Asserted BEFORE the last group: a faster peer's
                # exit-time shutdown unlock (near-instant on the
                # persistent cells plane) races a post-loop flag read,
                # but it cannot exit before this rank fires the final
                # slot.
                assert hvd.steady_lock_engaged(), "no re-lock (fused)"
        print(f"OK rank={r}")

    elif scenario == "lock_off":
        # HOROVOD_STEADY_LOCK=off (set by the test): the identical
        # steady loop must never engage or bypass — results bitwise
        # identical to the negotiated plane.
        for i in range(20):
            out = hvd.allreduce(np.full(8, float(r + i), np.float32),
                                op=hvd.Sum, name="lk")
            np.testing.assert_allclose(
                out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
            assert not hvd.steady_lock_engaged()
        m = hvd.metrics()
        assert m["ctrl_locks_total"] == 0, m
        assert m["ctrl_bypassed_responses_total"] == 0, m
        print(f"OK rank={r}")

    elif scenario == "lock_join":
        # Join mid-lock: rank 1 runs out of data while the lock is
        # engaged. Without the unlock path rank 0's next allreduce
        # would wait forever for rank 1's ring slot — the joiner's
        # UNLOCK token must tear the lock down on every rank and the
        # resumed negotiation completes with the joined rank absent.
        for i in range(7):  # fixed count: engaged by op 6 (see lock_steady)
            hvd.allreduce(np.full(4, float(r + 1), np.float32),
                          op=hvd.Sum, name="lkj")
        # Asserted BEFORE the last pre-join op: rank 1 cannot reach
        # join() (whose unlock races this flag read — near-instantly
        # on the persistent cells plane) until op 8 completes, and op
        # 8 cannot complete before this rank fires it.
        assert hvd.steady_lock_engaged(), "lock never engaged"
        hvd.allreduce(np.full(4, float(r + 1), np.float32),
                      op=hvd.Sum, name="lkj")
        if r == 1:
            hvd.join()
            m = hvd.metrics()
            assert m["ctrl_unlocks_join_total"] >= 1, m
        else:
            # Rank 0 keeps training; completes solo once rank 1 joins.
            for i in range(3):
                out = hvd.allreduce(np.full(4, 1.0, np.float32),
                                    op=hvd.Sum, name="lkj")
                np.testing.assert_allclose(out, 1.0)
            assert not hvd.steady_lock_engaged()
            m = hvd.metrics()
            # The joiner's reason rides the token: join, not peer.
            assert m["ctrl_unlocks_join_total"] >= 1, m
            hvd.join()
        print(f"OK rank={r}")

    elif scenario == "lock_stall":
        # Bypass-path stall coverage (ISSUE 15 satellite): locked
        # tensors never pass RecordUncachedTensor, so the token-wait
        # timeout must feed the StallInspector instead — a peer that
        # stops firing mid-lock surfaces in hvd.stalled_tensors() WITH
        # the silent rank listed, on the waiting rank, and clears once
        # the peer resumes.
        import time as _t

        for i in range(8):  # fixed count: engaged by op 6 (see lock_steady)
            hvd.allreduce(np.full(4, 1.0, np.float32), op=hvd.Sum,
                          name="lks")
        assert hvd.steady_lock_engaged(), "lock never engaged"
        if r == 0:
            h = hvd.allreduce_async(np.full(4, 1.0, np.float32),
                                    op=hvd.Sum, name="lks")
            lag = None
            deadline = _t.monotonic() + 30
            while _t.monotonic() < deadline and lag is None:
                lag = next((f for f in hvd.stalled_tensors()
                            if f["name"] == "lks"), None)
                if lag is None:
                    _t.sleep(0.1)
            assert lag, "locked-path stall never surfaced"
            assert lag["missing_ranks"] == [1], lag
            out = hvd.synchronize(h)
            np.testing.assert_allclose(np.asarray(out), float(s))
            # Resolved: the finding clears.
            deadline = _t.monotonic() + 10
            while _t.monotonic() < deadline and any(
                    f["name"] == "lks" for f in hvd.stalled_tensors()):
                _t.sleep(0.1)
            assert not any(f["name"] == "lks"
                           for f in hvd.stalled_tensors())
        else:
            _t.sleep(3.0)  # withhold the slot: rank 0 waits in-token
            out = hvd.allreduce(np.full(4, 1.0, np.float32), op=hvd.Sum,
                                name="lks")
            np.testing.assert_allclose(np.asarray(out), float(s))
        # A stall is a wait, not a divergence: the op completed on the
        # BYPASS plane and no mismatch/partial unlock fired. (The
        # engaged flag itself races the peer's end-of-scenario
        # shutdown, so assert the monotonic counters instead.)
        m = hvd.metrics()
        assert m["ctrl_bypassed_responses_total"] >= 1, m
        assert m["ctrl_unlocks_mismatch_total"] == 0, m
        assert m["ctrl_unlocks_partial_total"] == 0, m
        print(f"OK rank={r}")

    elif scenario == "lock_shutdown":
        # Shutdown mid-lock: every rank's local shutdown raises an
        # UNLOCK (reason: shutdown), the drained lock falls back to one
        # negotiated cycle that carries the global shutdown bit, and
        # the job exits cleanly — without the unlock path the final
        # handshake would never run and shutdown would hang.
        until_all_locked("lkd", 4, lambda i: 1.0)
        hvd.shutdown()
        # A peer's shutdown unlocks this rank too, before or after its
        # own: with the peer's cause where its token said so, as
        # "peer" where only its closed link did.
        m = hvd.metrics()
        assert (m["ctrl_unlocks_shutdown_total"]
                + m["ctrl_unlocks_peer_total"]) >= 1, m
        print(f"OK rank={r}")
        return  # already shut down

    elif scenario == "duplicate_name":
        # A name still in flight is refused at the enqueue (reference
        # common.h:169-172). Rank 0's first "dup" is held in flight by
        # rank 1, which contributes to it only after "go", and "go"
        # needs rank 0, which joins it after its second "dup" was
        # refused: the two enqueues overlap however the ranks are
        # scheduled.
        if r == 0:
            h1 = hvd.allreduce_async(np.ones(8, np.float32), name="dup",
                                     op=hvd.Sum)
            try:
                hvd.allreduce_async(np.ones(8, np.float32), name="dup",
                                    op=hvd.Sum)
                raise SystemExit("the duplicate enqueue was accepted")
            except HorovodInternalError as e:
                assert "uplicate" in str(e), e
            hvd.allreduce(np.ones(1, np.float32), name="go", op=hvd.Sum)
            out = hvd.synchronize(h1)
        else:
            hvd.allreduce(np.ones(1, np.float32), name="go", op=hvd.Sum)
            out = hvd.allreduce(np.ones(8, np.float32), name="dup",
                                op=hvd.Sum)
        np.testing.assert_allclose(out, float(s))
        print(f"OK rank={r}")

    elif scenario == "lock_autotune":
        # Staged-tunables trigger: with the autotuner live (tiny
        # window, set by the test), rank 0 staging new parameters
        # mid-lock must unlock (reason: tunables) so the stage can ride
        # the next negotiated broadcast — without it the tuned values
        # would never reach the workers and the job would train on
        # frozen, half-applied parameters.
        # The tuned-unlock counter lands on each rank at a racy
        # per-rank moment; branching on the local read would diverge
        # the ranks' collective counts. Reduce the verdict (Min: ALL
        # ranks saw it) on a FIXED-NAME side tensor so every rank runs
        # the identical loop shape, bounded by an iteration cap.
        tuned = 0.0
        for i in range(2000):
            out = hvd.allreduce(np.full(256, float(r + i), np.float32),
                                op=hvd.Sum, name="lka")
            np.testing.assert_allclose(
                np.asarray(out)[0], float(s * i) + s * (s - 1) / 2.0,
                rtol=1e-6)
            mine = float(
                hvd.metrics()["ctrl_unlocks_tunables_total"] >= 1)
            tuned = float(np.asarray(hvd.allreduce(
                np.array([mine], np.float32), op=hvd.Min,
                name="lka.agree"))[0])
            if tuned >= 1.0:
                break
        m = hvd.metrics()
        assert tuned >= 1.0, "autotune staging never unlocked the lock"
        assert m["ctrl_locks_total"] >= 1, m
        print(f"OK rank={r}")

    elif scenario == "lock_die":
        # Chaos smoke (ISSUE 15 satellite, pairs with ROADMAP item 3):
        # SIGKILL a rank mid-lock. Survivors' token waits see the dead
        # link (EOF -> unlock reason: peer), fall back to negotiation,
        # and the coordinator's lost-connection path shuts the job down
        # — an error within the timeout, never a hang.
        import signal
        import time as _t

        for i in range(7):  # fixed count: engaged by op 6 (see lock_steady)
            hvd.allreduce(np.full(4, 1.0, np.float32), op=hvd.Sum,
                          name="lkx")
        # Asserted BEFORE the last op: the victim cannot die (whose
        # EOF/poison unlock races this flag read) until op 8 fires.
        assert hvd.steady_lock_engaged(), "lock never engaged"
        hvd.allreduce(np.full(4, 1.0, np.float32), op=hvd.Sum,
                      name="lkx")
        if r == s - 1:
            os.kill(os.getpid(), signal.SIGKILL)
        t0 = _t.monotonic()
        try:
            for i in range(1000):
                hvd.allreduce(np.full(4, 1.0, np.float32), op=hvd.Sum,
                              name="lkx")
            raise SystemExit("survivor never saw the failure")
        except hvd.HorovodInternalError:
            dt = _t.monotonic() - t0
            assert dt < 60.0, f"death took {dt:.1f}s to surface"
        assert not hvd.steady_lock_engaged()
        print(f"OK rank={r}")
        os._exit(0)  # shutdown would hang: the job is already broken

    elif scenario == "lock_churn":
        # tsan lock-churn (ISSUE 15 satellite): engage, force an
        # unlock via a shape change, re-engage — several rounds, so
        # the detector/matcher/token machinery runs concurrently with
        # enqueuing Python threads under the sanitizer.
        for round_ in range(3):
            for i in range(8):
                out = hvd.allreduce(
                    np.full(4 + round_, float(r + i), np.float32),
                    op=hvd.Sum, name="lkc")
                np.testing.assert_allclose(
                    out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
            # Fixed count: 8 same-shape ops engage by op 6 even under
            # the sanitizer's slowdown (engagement is op-count-, not
            # wall-clock-, deterministic; see lock_steady).
            assert hvd.steady_lock_engaged(), f"round {round_}: no lock"
            for i in range(5):
                hvd.allreduce(np.full(4 + round_, float(i), np.float32),
                              op=hvd.Sum, name="lkc")
        m = hvd.metrics()
        assert m["ctrl_locks_total"] >= 3, m
        assert m["ctrl_unlocks_mismatch_total"] >= 2, m
        print(f"OK rank={r}")

    elif scenario == "lock_persistent":
        # Persistent locked data plane (ISSUE 17): every locked
        # firing's token consensus rides the persistent plane — the
        # shared-memory cells on the single-host default, the inline
        # first-frame piggyback on the TCP plane (HOROVOD_SHM_DISABLE=1
        # + pow2 np + payload <= kInlineMaxBytes). With
        # HOROVOD_STEADY_PERSISTENT=off the identical loop must run
        # the classic per-slot socket token round: zero persistent
        # metrics, same values.
        tcp_plane = os.environ.get("HOROVOD_SHM_DISABLE") == "1"
        knob_off = os.environ.get("HOROVOD_STEADY_PERSISTENT") == "off"
        until_all_locked("lp", 8, lambda i: float(r + i),
                         lambda i: float(s * i) + s * (s - 1) / 2.0)
        for i in range(10):
            if i == 9:
                # The gauges, read BEFORE the last op: a faster peer is
                # past the loop by the time this rank returns from it,
                # and its shape change below unlocks (the same race as
                # the re-lock's flag read further down).
                m = hvd.metrics()
            out = hvd.allreduce(np.full(8, float(r + i), np.float32),
                                op=hvd.Sum, name="lp")
            np.testing.assert_allclose(
                out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
        assert m["ctrl_locked"] == 1, m
        if knob_off:
            assert m["ctrl_persistent_fires_total"] == 0, m
            assert m["ctrl_token_piggybacks_total"] == 0, m
            assert m["tcp_prepost_buffers"] == 0, m
        else:
            assert m["ctrl_persistent_fires_total"] >= 5, m
            if tcp_plane:
                # 8 floats = 32B at pow2 np: every locked firing
                # piggybacks its FIRE token on the first data frame,
                # and the compiled plan pre-posts one recv buffer per
                # peer for the single-slot ring.
                assert m["ctrl_token_piggybacks_total"] >= 5, m
                assert m["tcp_prepost_buffers"] == s - 1, m
            else:
                # Cells plane: no TCP data frames to piggyback on.
                assert m["ctrl_token_piggybacks_total"] == 0, m
        # Deterministic unlock (shape change): the gauge drops with
        # the lock, values stay right, and the loop re-locks on the
        # new shape with the persistent plane following.
        out = hvd.allreduce(np.full(3, 1.0, np.float32), op=hvd.Sum,
                            name="lp")
        np.testing.assert_allclose(out, float(s))
        assert not hvd.steady_lock_engaged()
        assert hvd.metrics()["tcp_prepost_buffers"] == 0
        p0 = hvd.metrics()["ctrl_persistent_fires_total"]
        until_all_locked("lp", 3, lambda i: float(r),
                         lambda i: s * (s - 1) / 2.0)
        # No flag is read from here on (a faster peer's exit-time
        # shutdown unlocks, near-instantly over the cells); the
        # counter only grows. The op the ranks agreed at fired locked.
        if not knob_off:
            assert hvd.metrics()["ctrl_persistent_fires_total"] > p0
        out = hvd.allreduce(np.full(3, float(r), np.float32),
                            op=hvd.Sum, name="lp")
        np.testing.assert_allclose(out, s * (s - 1) / 2.0, rtol=1e-6)
        print(f"OK rank={r}")

    elif scenario == "persistent_mismatch":
        # Inline abort + exactly-once requeue (ISSUE 17, np=2 TCP
        # plane): rank 0 arms the token-piggybacked slot and fires its
        # first frame; rank 1 feeds a different tensor first, so its
        # match fails and its UNLOCK token answers rank 0's posted
        # recv. Rank 0 must abort the armed slot and requeue the
        # fed-but-unfired tensor EXACTLY once — the values below are
        # wrong if it fires twice and the job hangs if it is dropped.
        import time as _t

        for i in range(8):
            out = hvd.allreduce(np.full(4, float(r + i), np.float32),
                                op=hvd.Sum, name="pm")
            np.testing.assert_allclose(
                out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
        assert hvd.steady_lock_engaged(), "lock never engaged"
        if r == 1:
            _t.sleep(0.3)  # let rank 0 arm + fire before the mismatch
            hs = [hvd.allreduce_async(np.full(2, 1.0, np.float32),
                                      op=hvd.Sum, name="pm.other"),
                  hvd.allreduce_async(np.full(4, float(r), np.float32),
                                      op=hvd.Sum, name="pm")]
            other, mine = hvd.synchronize(hs[0]), hvd.synchronize(hs[1])
        else:
            hs = [hvd.allreduce_async(np.full(4, float(r), np.float32),
                                      op=hvd.Sum, name="pm"),
                  hvd.allreduce_async(np.full(2, 1.0, np.float32),
                                      op=hvd.Sum, name="pm.other")]
            mine, other = hvd.synchronize(hs[0]), hvd.synchronize(hs[1])
        np.testing.assert_allclose(mine, s * (s - 1) / 2.0, rtol=1e-6)
        np.testing.assert_allclose(other, float(s))
        assert not hvd.steady_lock_engaged()
        m = hvd.metrics()
        assert m["ctrl_unlocks_total"] >= 1, m
        # Sanity that the mismatch really interrupted a persistent
        # session, not a never-engaged one.
        assert m["ctrl_persistent_fires_total"] >= 1, m
        print(f"OK rank={r}")

    elif scenario == "persistent_lock_churn":
        # Persistent-plane chaos (ISSUE 17 satellite, tsan+asan):
        # lock -> persistent firings -> deterministic unlock (shape
        # change) -> re-lock -> more firings -> a SEEDED victim
        # SIGKILLs itself mid-slot. Survivors' waits (cell tick work
        # on the shm plane, posted recv EOF on the TCP plane) must
        # surface the death as an error within the timeout — never a
        # hang, zero sanitizer reports. Seeding mirrors the ISSUE 16
        # chaos harness: one HOROVOD_CHAOS_SEED env, every rank (and
        # the test) derives the same schedule.
        import signal
        import time as _t

        rng = np.random.RandomState(
            int(os.environ.get("HOROVOD_CHAOS_SEED", "17")))
        victim = int(rng.randint(0, s))
        kill_at = int(rng.randint(2, 6))
        for round_ in range(2):
            for i in range(8):
                out = hvd.allreduce(
                    np.full(4 + round_, float(r + i), np.float32),
                    op=hvd.Sum, name="plc")
                np.testing.assert_allclose(
                    out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
            assert hvd.steady_lock_engaged(), f"round {round_}: no lock"
            for i in range(5):
                hvd.allreduce(np.full(4 + round_, float(i), np.float32),
                              op=hvd.Sum, name="plc")
        m = hvd.metrics()
        assert m["ctrl_locks_total"] >= 2, m
        if os.environ.get("HOROVOD_STEADY_PERSISTENT") != "off":
            assert m["ctrl_persistent_fires_total"] >= 1, m
        if r == victim:
            for i in range(kill_at):
                hvd.allreduce(np.full(5, 1.0, np.float32), op=hvd.Sum,
                              name="plc")
            print(f"VICTIM rank={r}", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        t0 = _t.monotonic()
        try:
            for i in range(1000):
                hvd.allreduce(np.full(5, 1.0, np.float32), op=hvd.Sum,
                              name="plc")
            raise SystemExit("survivor never saw the failure")
        except hvd.HorovodInternalError:
            dt = _t.monotonic() - t0
            assert dt < 120.0, f"death took {dt:.1f}s to surface"
        assert not hvd.steady_lock_engaged()
        # The fatal teardown already stopped the background loop, so
        # shutdown() just joins the finished thread — required, or tsan
        # flags the unjoined thread at exit (it intercepts _exit).
        hvd.shutdown()
        print(f"OK rank={r}", flush=True)
        os._exit(0)  # skip atexit: the controller plane is torn down

    elif scenario == "lock_digest":
        # Bitwise parity pin (ISSUE 17): one seeded op stream printed
        # as a single digest; the test runs it under persistent=auto /
        # persistent=off / steady_lock=off arms and requires IDENTICAL
        # bytes — locked firings (cells, inline piggyback, classic
        # token round) may never change a single bit, including across
        # a codec slot (not inline eligible), a grouped Average slot,
        # and a deterministic mid-stream unlock with queued-but-unfired
        # async work that must complete exactly once.
        import hashlib

        h = hashlib.sha256()
        rng = np.random.RandomState(7 + r)
        xs = [rng.randn(16).astype(np.float32) for _ in range(14)]
        for x in xs:
            out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="ld"))
            h.update(out.tobytes())
        for y in [rng.randn(64).astype(np.float32) for _ in range(10)]:
            out = np.asarray(hvd.allreduce(
                y, op=hvd.Sum, name="ldc",
                compression=hvd.Compression.bf16))
            h.update(out.tobytes())
        for i in range(10):
            outs = hvd.grouped_allreduce(
                [np.full(4, float(r + i), np.float32),
                 rng.randn(8).astype(np.float32)],
                op=hvd.Average, name="ldg")
            for o in outs:
                h.update(np.asarray(o).tobytes())
        # Re-lock on the plain loop, then pipeline async feeds ending
        # in a changed shape: on the auto arms the mismatch unlocks
        # with fed-but-unfired requests still queued.
        for x in xs[:8]:
            h.update(np.asarray(
                hvd.allreduce(x, op=hvd.Sum, name="ld")).tobytes())
        hs = [hvd.allreduce_async(xs[i], op=hvd.Sum, name=f"ld.q{i}")
              for i in range(3)]
        hs.append(hvd.allreduce_async(rng.randn(5).astype(np.float32),
                                      op=hvd.Sum, name="ld.q3"))
        for hh in hs:
            h.update(np.asarray(hvd.synchronize(hh)).tobytes())
        print(f"DIGEST rank={r} {h.hexdigest()}")

    elif scenario == "membership_churn":
        # tsan membership churn (ISSUE 16 satellite): the membership
        # plane's advance/fence path racing (a) the background
        # coordination loop mid-steady-lock and (b) a Python thread
        # hammering every reader surface — membership(), the metrics
        # snapshot (which fills the membership gauges), and the decay
        # blacklist. Join (the broadcast-ordered flush advance) and
        # dead-peer advances both fire while the ring is locked. Must
        # be ZERO-report under tsan, like lock_churn; every rank exits
        # 0.
        import threading as _th
        import time as _t

        from horovod_tpu.common import basics as _basics

        lib = _basics.get_lib()
        stop = _th.Event()
        seen: list = []

        def _hammer():
            while not stop.is_set():
                seen.append(hvd.membership().epoch)
                hvd.metrics()
                now = _t.monotonic()
                lib.hvd_blacklist_record(b"churn-host", now)
                lib.hvd_blacklist_check(b"churn-host", now)
                lib.hvd_blacklist_count(now)
                _t.sleep(0.001)  # keep the GIL breathing; still ~1kHz

        th = _th.Thread(target=_hammer, daemon=True)
        th.start()
        e0 = hvd.membership().epoch
        for round_ in range(2):
            for i in range(8):  # fixed count: engaged by op 6
                out = hvd.allreduce(
                    np.full(4 + round_, float(r + i), np.float32),
                    op=hvd.Sum, name="mbc")
                np.testing.assert_allclose(
                    out, float(s * i) + s * (s - 1) / 2.0, rtol=1e-6)
            assert hvd.steady_lock_engaged(), f"round {round_}: no lock"
            # A dead-peer advance (rank -1: epoch-only, no rank-set
            # mutation) fired from a Python thread mid-lock: the
            # topology fence acts inline, the background-owned fences
            # defer — racing the locked loop's bypass cycles. Fixed
            # count per rank, so epochs stay aligned across ranks.
            lib.hvd_membership_advance(_basics.MEMBER_DEAD_PEER, -1)
            for i in range(5):
                hvd.allreduce(np.full(4 + round_, float(i), np.float32),
                              op=hvd.Sum, name="mbc")
        # Everyone joins: the flush advance rides the broadcast
        # response list, i.e. fires on the BACKGROUND thread on every
        # rank while the hammer thread reads.
        hvd.join()
        deadline = _t.monotonic() + 20
        while (_t.monotonic() < deadline
               and hvd.metrics()["membership_changes_total"] < 3):
            _t.sleep(0.05)
        stop.set()
        th.join()
        assert hvd.membership().epoch > e0
        assert seen == sorted(seen), "membership epoch went backwards"
        m = hvd.metrics()
        # 2 dead-peer advances + >=1 join-flush advance.
        assert m["membership_changes_total"] >= 3, m
        assert m["membership_epoch"] == hvd.membership().epoch, m
        print(f"OK rank={r}")

    elif scenario == "algo_stale":
        # Staleness pin (ISSUE 16 satellite): a measured-topology
        # verdict must not outlive the world it was probed under.
        # Inject a np-matching model whose stored job-shape key says
        # np4/ls4 (the world BEFORE a membership change):
        # ResolveAlgoAuto must refuse the measured path — no
        # measured-select tick, hand bands serve. Re-inject with the
        # live key: measured verdicts resume. Results stay exact under
        # both.
        from horovod_tpu.common.basics import get_lib

        lib = get_lib()
        n = s * s

        def _blob(key):
            alpha = " ".join("0" if i % (s + 1) == 0 else "5"
                             for i in range(n))
            beta = " ".join("0" if i % (s + 1) == 0 else "0.001"
                            for i in range(n))
            return (f"hvdtopo 1\nkey {key}\nnp {s}\n"
                    f"alpha {alpha}\nbeta {beta}\n").encode()

        assert lib.hvd_topology_inject(_blob("deadworld|np4|ls4")) == s
        m0 = hvd.metrics()["collective_measured_selects_total"]
        assert lib.hvd_algo_resolve_auto(1 << 20, s, 0) >= 0
        assert (hvd.metrics()["collective_measured_selects_total"]
                == m0), "stale job-shape key served a measured verdict"
        live_key = f"deadworld|np{s}|ls{hvd.local_size()}"
        assert lib.hvd_topology_inject(_blob(live_key)) == s
        assert lib.hvd_algo_resolve_auto(1 << 20, s, 0) >= 0
        assert (hvd.metrics()["collective_measured_selects_total"]
                == m0 + 1), "live key did not serve a measured verdict"
        out = np.asarray(hvd.allreduce(
            np.full(3000, float(r + 1), np.float32), op=hvd.Sum,
            name="as.x"))
        assert (out == sum(range(1, s + 1))).all()
        print(f"OK rank={r}")

    elif scenario == "a2a_algo":
        # Alltoall schedule families (ISSUE 18): whatever family the
        # coordinator resolves (HOROVOD_ALLTOALL_ALGO force or the
        # measured verdict), ragged + uniform + fused alltoalls must
        # produce the exact legacy bytes — the driver compares a
        # bruck-forced job against a pairwise one digest-for-digest.
        import hashlib

        from horovod_tpu.common.basics import get_lib

        digests = []
        rng = np.random.RandomState(50 + r)
        splits = [k + 1 for k in range(s)]
        xa = rng.randn(sum(splits), 3).astype(np.float32)
        a2a, rsplits = hvd.alltoall(xa, splits=splits, name="aa.ragged")
        assert list(rsplits) == [r + 1] * s, rsplits
        digests.append("rag:" + hashlib.sha1(
            np.asarray(a2a).tobytes()).hexdigest())
        # Uniform splits, wide enough rows that the >8KB helper-thread
        # wave runs through the relay scratch when bruck serves.
        xu = rng.randn(4 * s, 2048).astype(np.float32)
        u, _ = hvd.alltoall(xu, name="aa.uniform")
        digests.append("uni:" + hashlib.sha1(
            np.asarray(u).tobytes()).hexdigest())
        ha = hvd.alltoall_async(
            rng.randn(s, 5).astype(np.float32), name="aa.f.a")
        hb = hvd.alltoall_async(
            rng.randn(2 * s, 7).astype(np.float32), name="aa.f.b")
        outs = [hvd.synchronize(ha), hvd.synchronize(hb)]
        digests.append("fus:" + hashlib.sha1(
            b"".join(np.asarray(x).tobytes() for x in outs)).hexdigest())
        print("DIGEST " + "|".join(digests))
        # Introspection: every rank reports the coordinator-synced
        # family force (rank 0's env wins through param field 17).
        print(f"A2AALGO {get_lib().hvd_alltoall_algo()}")
        print(f"OK rank={r}")

    elif scenario == "a2a_measured":
        # Measured alltoall selection (ISSUE 18): inject a synthetic
        # alpha-beta model and pin the verdict bands — bruck's
        # log-round tables win the latency regime, pairwise's
        # every-byte-once exchange wins the bandwidth regime — plus
        # the coordinator's live auto path (metric tick + staleness
        # refusal), all with exact alltoall results throughout.
        import ctypes

        from horovod_tpu.common.basics import get_lib

        lib = get_lib()
        lib.hvd_alltoall_cost_us.restype = ctypes.c_double
        n = s * s

        def _blob(key, alpha, beta):
            al = " ".join("0" if i % (s + 1) == 0 else str(alpha)
                          for i in range(n))
            be = " ".join("0" if i % (s + 1) == 0 else str(beta)
                          for i in range(n))
            return (f"hvdtopo 1\nkey {key}\nnp {s}\n"
                    f"alpha {al}\nbeta {be}\n").encode()

        live_key = f"w|np{s}|ls{hvd.local_size()}"
        assert lib.hvd_topology_inject(
            _blob(live_key, 500, 0.001)) == s
        A2A_PAIRWISE, A2A_BRUCK = 1, 2
        small, huge = ctypes.c_int64(1 << 12), ctypes.c_int64(1 << 27)
        assert lib.hvd_alltoall_select_measured(small, s) == A2A_BRUCK
        assert lib.hvd_alltoall_select_measured(huge, s) == A2A_PAIRWISE
        # The verdict is the argmin of the priced tables, by
        # construction — pin the cost ordering behind each band.
        assert (lib.hvd_alltoall_cost_us(A2A_BRUCK, small)
                < lib.hvd_alltoall_cost_us(A2A_PAIRWISE, small))
        assert (lib.hvd_alltoall_cost_us(A2A_PAIRWISE, huge)
                < lib.hvd_alltoall_cost_us(A2A_BRUCK, huge))
        # Live auto path: the coordinator (rank 0) resolves through the
        # measured model — the select counter ticks there, and the
        # exchange stays exact whichever family served.
        m0 = hvd.metrics()["alltoall_measured_selects_total"]
        x = np.arange(s * 4, dtype=np.float32) + 100 * r
        out, _ = hvd.alltoall(x.reshape(s, 4), name="am.x")
        want = np.stack([np.arange(4, dtype=np.float32) + 4 * r + 100 * k
                         for k in range(s)])
        assert (np.asarray(out) == want).all(), out
        m1 = hvd.metrics()["alltoall_measured_selects_total"]
        if r == 0:
            assert m1 == m0 + 1, (m0, m1)
        # Staleness: a model keyed to a DIFFERENT world shape must be
        # refused — no tick, pairwise fallback serves, still exact.
        assert lib.hvd_topology_inject(
            _blob("w|np64|ls64", 500, 0.001)) == s
        out2, _ = hvd.alltoall(x.reshape(s, 4), name="am.y")
        assert (np.asarray(out2) == want).all()
        if r == 0:
            assert (hvd.metrics()["alltoall_measured_selects_total"]
                    == m1), "stale alltoall model served a verdict"
        print(f"OK rank={r}")

    elif scenario == "idle_cycles":
        # Event-driven loop telemetry (ISSUE 15 satellite): while the
        # process idles the background thread parks on the enqueue CV —
        # a 0.5s pause must cost a handful of heartbeat cycles (counted
        # under cycles_idle_total), not ~500 1ms-polling wakeups, and
        # must not grow the cycle_us histogram at all.
        import time as _t

        hvd.allreduce(np.ones(4, np.float32), name="idle.warm")
        _t.sleep(0.3)  # let the completing cycle's own observes land
        m0 = hvd.metrics()
        _t.sleep(0.5)
        m1 = hvd.metrics()
        spins = (m1["cycles_total"] + m1["cycles_idle_total"]
                 - m0["cycles_total"] - m0["cycles_idle_total"])
        assert spins <= 30, f"idle loop spun {spins} cycles in 0.5s"
        assert m1["cycle_us_count"] == m0["cycle_us_count"], (m0, m1)
        # ...and an op enqueued after the idle gap still completes
        # immediately (the wake path).
        out = hvd.allreduce(np.ones(4, np.float32), name="idle.after")
        np.testing.assert_allclose(np.asarray(out), float(s))
        print(f"OK rank={r}")

    elif scenario == "migration_plane":
        # Direct KV-page migration plane (ISSUE 19): (a) the native
        # alpha-beta cost twin agrees term-for-term with the Python
        # planner over an injected model; (b) an in-thread serving
        # fleet runs TWO migrating drains plus one injected worker
        # death concurrently — peer bulk streams (native sendv/recvv +
        # bf16 wire codec) race the surviving workers' step RPCs and
        # the dead conn's teardown, the scheduling hazards this tier
        # exists to prove clean. Rank 0 runs the fleet; the other rank
        # holds the world open so the injected topology model stays
        # live.
        import ctypes

        from horovod_tpu.common.basics import get_lib

        lib = get_lib()
        hvd.allreduce(np.ones(4, np.float32), name="mig.enter")
        if r == 0:
            from horovod_tpu.serve import migrate

            lib.hvd_link_cost_us.restype = ctypes.c_double
            lib.hvd_link_cost_us.argtypes = [
                ctypes.c_int, ctypes.c_int, ctypes.c_int64]
            lib.hvd_migration_cost_us.restype = ctypes.c_double
            lib.hvd_migration_cost_us.argtypes = [
                ctypes.c_int, ctypes.c_int, ctypes.c_int64,
                ctypes.c_int64]
            n = s * s
            alpha, beta = 500.0, 0.001
            al = " ".join("0" if i % (s + 1) == 0 else str(alpha)
                          for i in range(n))
            be = " ".join("0" if i % (s + 1) == 0 else str(beta)
                          for i in range(n))
            blob = (f"hvdtopo 1\nkey mig|np{s}|ls{hvd.local_size()}\n"
                    f"np {s}\nalpha {al}\nbeta {be}\n").encode()
            assert lib.hvd_topology_inject(blob) == s
            model = {
                "np": s,
                "alpha_us": [[0.0 if i == j else alpha
                              for j in range(s)] for i in range(s)],
                "beta_us_per_byte": [[0.0 if i == j else beta
                                      for j in range(s)]
                                     for i in range(s)],
            }
            # The twins, term for term: link (single span) and the
            # chunked migration form, across payload regimes.
            for nb in (1, 4096, 1 << 20, 1 << 27):
                py = migrate.link_cost_us(model, 0, 1, nb)
                nat = lib.hvd_link_cost_us(0, 1, nb)
                assert abs(py - nat) <= 1e-9 * max(abs(py), 1.0), (
                    nb, py, nat)
                for nc in (1, 2, 8, 64):
                    py = migrate.migration_cost_us(model, 0, 1, nb, nc)
                    nat = lib.hvd_migration_cost_us(0, 1, nb, nc)
                    assert abs(py - nat) <= 1e-9 * max(abs(py), 1.0), (
                        nb, nc, py, nat)
            assert lib.hvd_link_cost_us(0, 0, 4096) == 0.0
            assert lib.hvd_migration_cost_us(1, 1, 4096, 2) == 0.0
            assert lib.hvd_link_cost_us(0, s + 7, 4096) == -1.0
            assert lib.hvd_migration_cost_us(0, 1, 4096, 0) == -1.0

            # -- concurrent migrations: two drains + one injected
            # death through the direct plane --------------------------
            import socket as socket_mod
            import threading as _th

            import jax
            import jax.numpy as jnp

            from horovod_tpu.models import TransformerConfig
            from horovod_tpu.serve import (
                RouterConfig, ServeConfig, ServeRouter,
            )
            from horovod_tpu.serve.rpc import RpcConn, WorkerHandle
            from horovod_tpu.serve.worker import ReplicaWorker

            def _thread_worker():
                a, b = socket_mod.socketpair()
                w = ReplicaWorker(RpcConn(b))
                _th.Thread(target=w.serve, daemon=True).start()
                return WorkerHandle(conn=RpcConn(a))

            cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
            sc = ServeConfig(max_batch=4, block_size=4, max_prompt=24,
                             max_new_tokens=6, batch_buckets=(4,),
                             prefill_buckets=(4, 8, 16, 24))
            rc = RouterConfig(n_replicas=4, direct_migration="auto",
                              handoff_compression="bf16")
            workers = [_thread_worker() for _ in range(4)]
            router = ServeRouter(cfg, None, rc, sc, workers=workers,
                                 worker_seed=0)
            rng = np.random.RandomState(7)
            prompts = [rng.randint(1, 256,
                                   size=int(rng.randint(8, 20))).tolist()
                       for _ in range(12)]
            rids = [router.submit(p, 6) for p in prompts]
            router.step()
            router.step()
            reps = list(router._replicas)
            # Two overlapping migrating drains: the second starts while
            # the first's sequences are still streaming out.
            router.remove_replica(reps[0].instance, migrate_running=True)
            router.step()
            router.remove_replica(reps[1].instance, migrate_running=True)
            router.step()
            # Injected death: a survivor's control conn drops cold; its
            # uncollected work requeues on the remaining replica.
            workers[2].conn.close()
            router.run_until_idle()
            res = [router.result(x) for x in rids]
            assert all(x is not None and x.status == "ok" for x in res)
            assert len({x.rid for x in res}) == len(rids)
            snap = router.metrics.snapshot()
            assert snap["direct_migrations_total"] >= 1, snap
            assert snap["worker_deaths"] >= 1, snap
            router.close()
        hvd.allreduce(np.ones(4, np.float32), name="mig.exit")

    elif scenario == "flight_churn":
        # Flight recorder concurrency (ISSUE 20): Python threads hammer
        # Record() into the seqlock-lite ring while another thread
        # loops flight_events() snapshots and a third dumps SnapshotText
        # to disk, all over live allreduce traffic feeding the ring its
        # native cycle summaries. The ring's claim-then-publish slot
        # protocol (readers skip mid-overwrite slots) is exactly the
        # pattern tsan must prove is synchronization, not luck.
        import tempfile
        import threading

        from horovod_tpu.common import basics
        from horovod_tpu.metrics import (flight_clear, flight_dump,
                                         flight_events, flight_record)

        flight_clear()
        stop = threading.Event()

        def _writer(tag):
            i = 0
            while not stop.is_set():
                flight_record(basics.FLIGHT_REQUEUE, i, tag)
                i += 1

        def _reader():
            while not stop.is_set():
                evs = flight_events()
                for e in evs:
                    assert e["event"], e  # every survivor slot coherent

        def _dumper(path):
            while not stop.is_set():
                assert flight_dump(path)

        dump_path = os.path.join(tempfile.mkdtemp(), f"flight-{r}.txt")
        threads = ([threading.Thread(target=_writer, args=(t,))
                    for t in range(2)]
                   + [threading.Thread(target=_reader),
                      threading.Thread(target=_dumper, args=(dump_path,))])
        for t in threads:
            t.start()
        for i in range(20):
            hvd.allreduce(np.ones(1 << 14, np.float32), name=f"fl.{i % 4}")
        stop.set()
        for t in threads:
            t.join()
        # More events recorded than slots: the ring wrapped under load.
        evs = flight_events()
        assert 0 < len(evs) <= 4096, len(evs)
        assert any(e["event"] == "requeue" for e in evs)
        with open(dump_path) as f:
            head = f.readline()
        assert head.startswith("# flight v1 pid="), head

    else:
        raise SystemExit(f"unknown scenario {scenario}")

    hvd.shutdown()
    print(f"OK rank={r}")


if __name__ == "__main__":
    main()
