"""The flash backward's roofline reader on the small trace recorded on
the v5e that ``test_scope_reducers.py`` describes: three runs of a
program whose first matmul (``convolution_tanh_fusion``, 4.510078e-6 s)
is under ``alpha`` and whose kernel ``hvd_tiny_double`` (5.2336e-7 s) and
second matmul (``convolution_reduce_fusion``, 5.458828e-6 s) are under
``beta``. The numbers are worked out by hand."""
import os
import shutil

import pytest

from benchmark import flops_flash_bwd, harness, trace_reduce
from benchmark.reducers import flash_bwd_roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tiny-scopes.xplane.pb")
MODEL = {"n_heads": 2, "n_kv_heads": 1, "d_model": 256}


@pytest.fixture
def meas(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell")
    shutil.copy(SCOPED, tmp_path / "trace" / "cell" / "vm.xplane.pb")
    return {"trace": trace_reduce.reduce_xplane(SCOPED), "spans": [],
            "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12},
            "model": MODEL, "train": {"seq": 16, "rows_per_chip": 1}}


def test_cost_of_one_backward_against_a_hand_count():
    # one row of 16 tokens, two heads of 128 over one kv head: five
    # matmuls of the lower triangle, 10*128*16*17/2 = 174080 a head;
    # q, o, dO, dq at 2 heads and k, v, dk, dv at 1 in bf16 (2 bytes),
    # lse and delta in f32 for 2 heads
    cost = flops_flash_bwd.flash_bwd(MODEL, 16, rows=1)
    assert cost["flops"] == 2 * 174080
    assert cost["bytes"] == 16 * (2 * (4 * 256 + 4 * 128) + 2 * 4 * 2)
    cell = harness.load_json("configs", "internlm2-1.8b-12l.json")["model"]
    # the training cells' [32, 4096, 128]: 1.745 ms at 197 TFLOP/s
    assert flops_flash_bwd.flash_bwd(cell, 4096, rows=2)["flops"] == \
        32 * 10 * 128 * 4096 * 4097 / 2
    assert flops_flash_bwd.flash_bwd(cell, 4096, rows=2)["flops"] / 197e12 \
        == pytest.approx(1.745e-3, rel=1e-3)


def test_kernels_of_one_backward_share_one_least_time(meas):
    args = {"seq": "train.seq", "rows": "train.rows_per_chip"}
    # 348160 operations against 49408 bytes: 3.4816e-7 s a backward at
    # the made-up peak of 1e12 of each. One kernel name, three calls:
    # three backwards in 5.2336e-7 s.
    one = flash_bwd_roofline.reduce(
        meas, match=r"\bhvd_tiny", category="^custom-call$", cost_args=args)
    assert one == pytest.approx(100 * 3 * 3.4816e-7 / 5.2336e-7, rel=1e-4)
    # Two names from one stem (`alpha`, `beta`: three operations, three
    # calls each, 1.0492146e-5 s in all): nine calls of two kernels are
    # four and a half backwards, held to one least time each.
    two = flash_bwd_roofline.reduce(
        meas, match=r"\b(alpha|beta)", cost_args=args)
    assert two == pytest.approx(
        100 * 4.5 * 3.4816e-7 / (4.510078e-6 + 5.458828e-6 + 5.2336e-7),
        rel=1e-4)
    # no such kernel (the parent of the PR that brought them): left out
    assert flash_bwd_roofline.reduce(
        meas, match=r"\bhvd_flash_bwd", category="^custom-call$",
        cost_args=args) is None


def test_nothing_to_read_on_a_cpu_run(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    cpu = {"trace": None, "spans": [], "peak": None, "model": MODEL}
    assert flash_bwd_roofline.reduce(
        cpu, match=r"\bhvd_flash_bwd", cost_args={}) is None
