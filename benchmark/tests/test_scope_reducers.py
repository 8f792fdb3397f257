"""The scope reducers on a small trace recorded on the v5e
(``tools/record_scoped_trace.py``, PR 24): three runs of one jitted
program whose first matmul is under ``alpha`` and whose Pallas kernel
``hvd_tiny_double`` and second matmul are under ``beta``. The numbers
are worked out by hand from the file's five operations (self seconds
over the three runs): ``convolution_reduce_fusion`` (beta) 5.458828e-6,
``convolution_tanh_fusion`` (alpha) 4.510078e-6, ``hvd_tiny_double``
(beta) 5.2336e-7, ``copy-start`` 4.2422e-8 and ``copy-done`` 1.1094e-8
(no scope): 1.0545782e-5 in all."""
import os
import shutil

import pytest

from benchmark import harness, trace_reduce
from benchmark.reducers import (_scopes, compile_stats, scope_roofline,
                                scope_time_share)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tiny-scopes.xplane.pb")
TOTAL = 1.0545782e-5


@pytest.fixture
def meas(tmp_path, monkeypatch):
    """A traced run's measurements whose newest trace is the recorded
    file."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell")
    shutil.copy(SCOPED, tmp_path / "trace" / "cell" / "vm.xplane.pb")
    return {"trace": trace_reduce.reduce_xplane(SCOPED), "spans": [],
            "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12},
            "model": {"n_heads": 1, "n_kv_heads": 1, "d_model": 128}}


def test_scope_of_reads_the_names_and_nothing_else():
    f = _scopes.scope_of
    assert f("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
             "rematted_computation/attn/hvd_flash_fwd/pallas_call") == (
                 "step", "attn/hvd_flash_fwd")
    assert f("jit(step)/jvp(head)/mul") == ("step", "head")
    assert f("jit(step)/transpose(jvp(loss))/jit(log_softmax)/neg") == (
        "step", "loss")
    assert f("jit(decode)/while/body/attn/kv_gather/gather:") == (
        "decode", "attn/kv_gather")
    assert f("jit(step)/jvp()/while/body/dynamic_slice") == ("step", "")
    assert f("reduce_sum") == ("", "") and f("") == ("", "")


def test_time_share_by_scope(meas):
    share = scope_time_share.reduce
    assert share(meas, match=r"\balpha\b") == pytest.approx(
        100 * 4.510078e-6 / TOTAL, rel=1e-4)            # 42.767
    assert share(meas, match=r"\bbeta\b") == pytest.approx(
        100 * (5.458828e-6 + 5.2336e-7) / TOTAL, rel=1e-4)   # 56.726
    assert share(meas, match=r"^jit\(scoped\)/.*\bhvd_tiny_double\b"
                 ) == pytest.approx(4.963, rel=1e-3)
    # the two copies carry no scope: what a dropped scope would join
    assert share(meas, unless=r"\b(alpha|beta)\b") == pytest.approx(
        100 * (4.2422e-8 + 1.1094e-8) / TOTAL, rel=1e-3)     # 0.507
    # a name the program does not have is left out, not reported as 0
    assert share(meas, match=r"\bgamma\b") is None
    table = _scopes.scope_table(meas["_scopes"]["rows"])
    assert [row[:2] for row in table] == [
        ["scoped", "beta"], ["scoped", "alpha"],
        ["scoped", "beta/hvd_tiny_double"], ["", "(no scope)"]]
    assert sum(row[3] for row in table) == pytest.approx(100.0, abs=0.02)


def test_roofline_of_a_kernel_found_by_its_name(meas):
    # flash_fwd on one row of 16 tokens, one head of 128: 4*128*16*17/2
    # = 69632 operations against 16448 bytes, so 6.9632e-8 s at the
    # made-up peak of 1e12 of each; three calls took 5.2336e-7 s.
    value = scope_roofline.reduce(
        meas, match=r"\bhvd_tiny_double\b", cost="flash_fwd",
        cost_args={"seq": 16, "rows": 1})
    assert value == pytest.approx(100 * 3 * 6.9632e-8 / 5.2336e-7, rel=1e-4)
    # everything under `beta`, kept to the calls by their category
    assert scope_roofline.reduce(
        meas, match=r"\bbeta\b", category="^custom-call$",
        cost="flash_fwd", cost_args={"seq": 16, "rows": 1}) == value
    assert scope_roofline.reduce(meas, match=r"\bhvd_flash_fwd\b",
                                 cost="flash_fwd",
                                 cost_args={"seq": 16, "rows": 1}) is None


def test_skew_bound_and_the_planes_it_is_read_from():
    parsed = _scopes.parse(SCOPED)
    # each of the three XLA Modules events starts 1.37 ms before the
    # host enqueues the program with its run_id (DoEnqueueProgram)
    bound = _scopes.skew_bound(parsed["device_ahead_of_host_s"])
    assert bound["launches"] == 3
    assert bound["launches_the_device_began_first"] == 3
    assert bound["max_ms"] == pytest.approx(1.370026, rel=1e-4)
    assert bound["median_ms"] == pytest.approx(1.36918, rel=1e-4)
    names = [n for _, _, n in parsed["annotations"]]
    assert names == ["bench:scoped", "bench:sleep"] * 3
    # agrees with trace_reduce.py where the two read the same thing
    rows = {trace_reduce.op_name(r["name"]): r for r in parsed["rows"]}
    for short, seconds, count, _ in trace_reduce.reduce_xplane(
            SCOPED)["ops"]:
        assert rows[short]["count"] == count
        assert rows[short]["self_s"] == pytest.approx(seconds, abs=5e-9)
    kernel = rows["hvd_tiny_double.1 f32[512,512]"]
    assert kernel["category"] == "custom-call"
    assert rows["convolution_tanh_fusion f32[512,512]"]["flops"] == 2 * 512**3


def test_clock_offset_pairs_twins_from_the_end():
    ann = [(10.0, 10.5, "serve:decode"), (11.0, 11.4, "serve:decode"),
           (12.0, 12.5, "serve:decode"), (10.6, 10.7, "serve:schedule")]
    spans = [{"name": "serve:decode", "t0": t, "dur": d} for t, d in
             [(1.0, 0.3), (3.0, 0.5), (4.0, 0.4), (5.0 + 1e-5, 0.5)]]
    off = _scopes.clock_offset(spans, ann)
    assert (off["pairs"], off["matched"]) == (3, 3)
    assert off["median_s"] == pytest.approx(7.0, abs=1e-9)
    assert off["range_us"] == pytest.approx(10.0, rel=1e-3)
    assert _scopes.clock_offset([], ann) is None


def test_reducers_return_none_on_a_cpu_run(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    cpu = {"trace": None, "spans": [], "peak": None, "model": {}}
    assert scope_time_share.reduce(dict(cpu), match="attn") is None
    assert scope_time_share.reduce(dict(cpu), unless="attn") is None
    assert scope_roofline.reduce(dict(cpu), match="x", cost="flash_fwd",
                                 cost_args={}) is None
    # a trace with no TPU plane parses to nothing
    assert _scopes.parse(os.path.join(DATA, "tiny.xplane.pb")) is not None
    host_only = tmp_path / "host.xplane.pb"
    from benchmark.reducers import hvd_xplane_pb2
    space = hvd_xplane_pb2.XSpace()
    space.planes.add().name = "/host:CPU"
    host_only.write_bytes(space.SerializeToString())
    assert _scopes.parse(str(host_only)) is None


def test_compile_stats_sums_the_programs_log():
    from horovod_tpu.common import compile_cache
    value = compile_stats.reduce({})
    s = compile_cache.compile_stats()
    assert value == pytest.approx(
        s["tracing_s"] + s["lowering_s"] + s["backend_compile_s"])
