"""``trace_reduce.py`` on a small trace recorded on the v5e
(``tools/record_tiny_trace.py``, PR 23): three runs of one jitted
program, each followed by a 2 ms sleep under ``bench:sleep``."""
import os

import pytest

from benchmark import trace_reduce as tr

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.xplane.pb")


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [
        (0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == [(0, 0.5), (2.5, 3)]
    # a parent's self time is its duration less its children's
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "a"), (5.0, 9.0, "b"),
              (5.5, 6.5, "c")]
    assert sorted(tr.self_times(events)) == [
        ("a", 3.0), ("b", 3.0), ("c", 1.0), ("while", 3.0)]


def test_op_name_is_short_and_patterns_see_the_instruction():
    text = ("%broadcast.767 = f32[16,2112,8,4,128]{4,3,2,1,0:T(4,128)} "
            "broadcast(f32[16,2112,8,128]{3,2,1,0:T(8,128)} %bitcast.252)")
    assert tr.op_name(text) == "broadcast.767 f32[16,2112,8,4,128]"
    assert tr.op_name("%fusion.163 = (f32[32,2048]{1,0}, f32[32,2048,2048]"
                      "{1,2,0}) fusion(...)") == "fusion.163 f32[32,2048]"
    assert tr.COLLECTIVE.match(tr.op_name("%all-reduce-start.3 = f32[8]{0} "
                                          "all-reduce-start(...)"))


def test_tiny_trace_gives_known_numbers():
    r = tr.reduce_xplane(TINY)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(7.2813e-3, rel=1e-3)
    assert r["busy_s"] == pytest.approx(5.463e-6, rel=1e-3)
    table = {row[0]: row for row in r["ops"]}
    assert set(table) == {"fusion bf16[]", "copy-start bf16[512,512]",
                          "copy-done bf16[512,512]"}
    assert all(row[2] == 3 for row in r["ops"])
    assert table["fusion bf16[]"][1] == pytest.approx(5.414e-6, rel=1e-3)
    assert "fused_computation" in table["fusion bf16[]"][3]
    assert r["device_ops"][0][0] == "fusion bf16[]"
    # the device idles while the host sleeps under its annotation
    assert r["idle_gaps"][0][0] == "bench:sleep"
    assert r["idle_gaps"][0][1] == pytest.approx(r["window_s"], rel=0.01)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
