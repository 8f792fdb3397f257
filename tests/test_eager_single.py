"""Single-process eager API tests: host (numpy/torch) and device (jax)
paths through the native core, plus handle semantics, duplicate-name
rejection (two ranks: a name has to stay in flight), and timeline
output."""

import json
import os

import numpy as np
import pytest

import horovod_tpu as hvd


@pytest.fixture(scope="module", autouse=True)
def init_hvd():
    hvd.init()
    yield
    hvd.shutdown()


def test_rank_size():
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.is_initialized()


def test_allreduce_numpy():
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Sum), x)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Average), x)
    out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=2.0,
                        postscale_factor=0.5)
    np.testing.assert_allclose(out, x)


def test_allreduce_jax_callback_path():
    import jax.numpy as jnp
    x = jnp.arange(8, dtype=jnp.float32)
    out = hvd.allreduce(x, op=hvd.Sum)
    assert hasattr(out, "devices"), "jax in should give jax out"
    np.testing.assert_allclose(np.asarray(out), np.arange(8, dtype=np.float32))
    out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=0.5)
    np.testing.assert_allclose(np.asarray(out), 0.5 * np.arange(8))


def test_allreduce_torch():
    import torch
    t = torch.arange(6, dtype=torch.float32)
    out = hvd.allreduce(t, op=hvd.Sum)
    assert isinstance(out, torch.Tensor)
    assert torch.allclose(out, t)


def test_allreduce_torch_bfloat16():
    import torch
    t = torch.arange(6, dtype=torch.bfloat16)
    out = hvd.allreduce(t, op=hvd.Sum)
    assert out.dtype == torch.bfloat16
    assert torch.allclose(out.float(), t.float())


def test_grouped_allreduce():
    xs = [np.ones(3, np.float32), np.full(2, 2.0, np.float32)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum)
    np.testing.assert_allclose(outs[0], xs[0])
    np.testing.assert_allclose(outs[1], xs[1])


def test_async_handles():
    h = hvd.allreduce_async(np.ones(4, np.float32), op=hvd.Sum)
    out = hvd.synchronize(h)
    np.testing.assert_allclose(out, 1.0)


def test_allgather_broadcast_alltoall():
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    np.testing.assert_allclose(hvd.allgather(x), x)
    np.testing.assert_allclose(hvd.broadcast(x, 0), x)
    out, splits = hvd.alltoall(x)
    np.testing.assert_allclose(out, x)
    assert list(splits) == [2]


def test_duplicate_name_rejected():
    """Two ranks, so that the first enqueue stays in flight for as long
    as the test says (the scenario holds it by the order of a second
    collective): alone, an operation can complete before its duplicate
    arrives, and under load it did."""
    from test_eager_multiprocess import run_job
    outs = run_job("duplicate_name", 2)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_bool_and_int_dtypes():
    b = np.asarray([True, False, True])
    np.testing.assert_array_equal(hvd.broadcast(b, 0), b)
    i = np.arange(5, dtype=np.int64)
    np.testing.assert_array_equal(hvd.allreduce(i, op=hvd.Sum), i)


def test_timeline(tmp_path):
    path = str(tmp_path / "timeline.json")
    hvd.start_timeline(path)
    for i in range(3):
        hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name=f"tl.{i}")
    hvd.stop_timeline()
    raw = open(path).read().rstrip().rstrip(",")
    events = json.loads(raw + "]" if not raw.endswith("]") else raw)
    names = {e.get("name") for e in events}
    assert any(n and n.startswith("NEGOTIATE_") for n in names), names
    assert "ALLREDUCE" in names


def test_failed_native_build_raises_instead_of_loading_a_stale_core(
        monkeypatch):
    """With the sources present the core is built from them or not at
    all: the chip tool copies the tree as it stands on disk, and a
    ``.so`` left over from another state of it must never stand in for
    sources that no longer compile."""
    import subprocess

    from horovod_tpu.common import basics

    def failing_make():
        raise subprocess.CalledProcessError(
            2, ["make"], stderr=b"operations.cc:1: error: expected ';'")

    monkeypatch.setattr(basics, "_build_native", failing_make)
    monkeypatch.delenv("HOROVOD_NATIVE_LIB", raising=False)
    assert any(os.path.exists(p) for p in basics._LIB_CANDIDATES)
    with pytest.raises(OSError, match="building the native core failed"):
        basics.load_library()
