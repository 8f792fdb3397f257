"""Guard: the metrics snapshot ABI (``native/include/hvd/metrics.h``)
must match the Python shim's pins (``horovod_tpu/common/basics.py``) —
the same two-sided discipline as ``test_wire_abi.py`` — plus registry
unit tests driven through the ctypes test hooks: log2 bucketing edges,
counter monotonicity under concurrent increments, snapshot layout, and
Prometheus text-format validity of the rendered exposition."""

import ctypes
import os
import re
import threading

import pytest

from horovod_tpu.common import basics
from horovod_tpu.metrics import (
    hist_quantile,
    metrics,
    metrics_prometheus,
    snapshot,
)

HEADER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native", "include", "hvd", "metrics.h")


def _header_constant(name: str) -> int:
    src = open(HEADER).read()
    m = re.search(rf"constexpr\s+int\s+{name}\s*=\s*(\d+)\s*;", src)
    assert m, f"{name} not found in metrics.h — the guard needs it defined"
    return int(m.group(1))


# ---------------------------------------------------------------------------
# version / layout pins
# ---------------------------------------------------------------------------

def test_metrics_version_pins_match():
    """Header, shim, and loaded library must agree on the snapshot
    layout version (bumped on any enum/table/layout change)."""
    assert _header_constant("kMetricsVersion") == basics.METRICS_VERSION
    lib = basics.get_lib()
    assert lib.hvd_metrics_version() == basics.METRICS_VERSION


def test_snapshot_layout_matches_library_shape():
    """The packed layout is [version, n_counters, n_hists, n_buckets,
    counters..., per-hist count/sum/buckets...]; the needed-slot count
    must equal the header math and the parsed header must match the
    name-table getters."""
    lib = basics.get_lib()
    nc = lib.hvd_metrics_num_counters()
    nh = lib.hvd_metrics_num_hists()
    nb = lib.hvd_metrics_hist_buckets()
    assert nb == _header_constant("kMetricsHistBuckets")
    needed = lib.hvd_metrics_snapshot(None, 0)
    assert needed == 4 + nc + nh * (2 + nb)
    snap = snapshot()
    assert snap["version"] == basics.METRICS_VERSION
    assert len(snap["counters"]) == nc
    assert len(snap["histograms"]) == nh
    for h in snap["histograms"].values():
        assert len(h["buckets"]) == nb


def test_snapshot_truncation_is_safe():
    """A too-small buffer still reports the needed size and never
    writes past max_slots."""
    lib = basics.get_lib()
    needed = lib.hvd_metrics_snapshot(None, 0)
    buf = (ctypes.c_int64 * (needed + 8))()
    sentinel = -12345678
    for i in range(needed + 8):
        buf[i] = sentinel
    got = lib.hvd_metrics_snapshot(buf, 4)
    assert got == needed
    assert buf[0] == basics.METRICS_VERSION
    assert all(buf[i] == sentinel for i in range(4, needed + 8))


def test_name_tables_are_prometheus_clean_and_unique():
    lib = basics.get_lib()
    nc = lib.hvd_metrics_num_counters()
    nh = lib.hvd_metrics_num_hists()
    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    cnames = [lib.hvd_metrics_counter_name(i).decode() for i in range(nc)]
    hnames = [lib.hvd_metrics_hist_name(i).decode() for i in range(nh)]
    assert len(set(cnames)) == nc and len(set(hnames)) == nh
    assert not set(cnames) & set(hnames)
    for n in cnames + hnames:
        assert name_re.match(n), n
    # Prometheus conventions: monotonic counters end _total, gauges
    # (kind 1, filled at snapshot time) must not.
    for i, n in enumerate(cnames):
        kind = lib.hvd_metrics_counter_kind(i)
        assert kind in (0, 1)
        assert n.endswith("_total") == (kind == 0), (n, kind)
    # Out-of-range indices: empty string, not a crash.
    assert lib.hvd_metrics_counter_name(nc + 1) == b""
    assert lib.hvd_metrics_hist_name(-1) == b""


# ---------------------------------------------------------------------------
# registry behavior through the ctypes test hooks
# ---------------------------------------------------------------------------

@pytest.fixture()
def lib():
    lib = basics.get_lib()
    lib.hvd_metrics_reset()
    yield lib
    lib.hvd_metrics_reset()


def _quiet_counter(lib):
    """Index + name of a counter the background cycle thread never
    touches while idle: an earlier test module may leave the runtime
    initialized in this process, and its cycle loop legitimately bumps
    cycles_total / cycle_us / queue_depth — unit tests must not assume
    a frozen registry on live series."""
    nc = lib.hvd_metrics_num_counters()
    names = [lib.hvd_metrics_counter_name(i).decode() for i in range(nc)]
    return names.index("wire_encodes_total"), "wire_encodes_total"


def _quiet_hist(lib):
    nh = lib.hvd_metrics_num_hists()
    names = [lib.hvd_metrics_hist_name(i).decode() for i in range(nh)]
    return names.index("tcp_doubling_us"), "tcp_doubling_us"


def test_histogram_log2_bucketing(lib):
    """Bucket i counts v <= 2**i (cumulative-le after prefix sum):
    pin the edges the Python quantile math depends on."""
    nb = lib.hvd_metrics_hist_buckets()
    cases = {  # value -> expected bucket index
        0: 0, 1: 0,            # v <= 1 lands in bucket 0 (le=1)
        2: 1,                  # le=2
        3: 2, 4: 2,            # le=4
        5: 3, 1023: 10, 1024: 10, 1025: 11,
        (1 << 40): nb - 1,     # far past the edges: +Inf bucket
    }
    hist, name = _quiet_hist(lib)
    for v, want in cases.items():
        before = snapshot()["histograms"][name]
        lib.hvd_metrics_test_observe(hist, v)
        after = snapshot()["histograms"][name]
        delta = [a - b for a, b in zip(after["buckets"],
                                       before["buckets"])]
        assert delta[want] == 1 and sum(delta) == 1, (v, want, delta)
    h = snapshot()["histograms"][name]
    assert h["count"] == len(cases)
    # Negative observations clamp into the sum as 0 but still count.
    lib.hvd_metrics_test_observe(hist, -5)
    h2 = snapshot()["histograms"][name]
    assert h2["count"] == h["count"] + 1
    assert h2["sum"] == h["sum"]


def test_quantile_estimates_are_log2_upper_bounds(lib):
    hist, name = _quiet_hist(lib)
    for v in (100,) * 98 + (5000,) * 2:
        lib.hvd_metrics_test_observe(hist, v)
    h = snapshot()["histograms"][name]
    assert hist_quantile(h["count"], h["buckets"], 0.50) == 128.0  # 2^7
    assert hist_quantile(h["count"], h["buckets"], 0.99) == 8192.0  # 2^13
    assert hist_quantile(0, h["buckets"], 0.99) == 0.0


def test_counter_monotonic_under_concurrent_increments(lib):
    """The counters are relaxed atomics: hammering one counter from
    several threads (ctypes releases the GIL during the call, so the
    adds genuinely race) must lose no increments — the same contract
    the instrumented sites rely on under reduce_threads > 1."""
    counter, name = _quiet_counter(lib)
    per_thread, n_threads = 20_000, 8
    base = snapshot()["counters"][name]

    def hammer():
        for _ in range(per_thread):
            lib.hvd_metrics_test_add(counter, 1)

    ts = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert snapshot()["counters"][name] == base + per_thread * n_threads


def test_enable_switch_short_circuits_observations(lib):
    counter, cname = _quiet_counter(lib)
    hist, hname = _quiet_hist(lib)
    base = snapshot()["counters"][cname]
    lib.hvd_metrics_set_enabled(0)
    try:
        assert lib.hvd_metrics_enabled() == 0
        lib.hvd_metrics_test_add(counter, 7)
        lib.hvd_metrics_test_observe(hist, 7)
        snap = snapshot()
        assert snap["counters"][cname] == base
        assert snap["histograms"][hname]["count"] == 0
    finally:
        lib.hvd_metrics_set_enabled(1)
    lib.hvd_metrics_test_add(counter, 7)
    assert snapshot()["counters"][cname] == base + 7


def test_flat_metrics_covers_every_series(lib):
    counter, cname = _quiet_counter(lib)
    hist, hname = _quiet_hist(lib)
    base = snapshot()["counters"][cname]
    lib.hvd_metrics_test_add(counter, 3)
    lib.hvd_metrics_test_observe(hist, 10)
    m = metrics()
    snap = snapshot()
    for name in snap["counters"]:
        assert name in m
    for name in snap["histograms"]:
        for suffix in ("_count", "_sum", "_avg", "_p50", "_p99"):
            assert f"{name}{suffix}" in m, f"{name}{suffix}"
    assert m[cname] == base + 3
    assert m[f"{hname}_count"] == 1 and m[f"{hname}_sum"] == 10
    assert m[f"{hname}_avg"] == 10.0
    assert m[f"{hname}_p50"] == 16.0  # le upper bound of 10


# ---------------------------------------------------------------------------
# Prometheus text-format validity
# ---------------------------------------------------------------------------

# Samples may carry label sets: histogram buckets ({le="..."}) and the
# per-replica serving series ({instance="..."}, any escaped value —
# the value grammar must accept the \" \\ \n escapes _escape_label
# emits, not stop at the first backslash-escaped quote).
_LVAL = r'"(?:[^"\\]|\\.)*"'
_LABELS = (r'\{[a-zA-Z_][a-zA-Z0-9_]*=' + _LVAL
           + r'(,[a-zA-Z_][a-zA-Z0-9_]*=' + _LVAL + r')*\}')
EXPOSITION_LINE = re.compile(
    r'^(# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)|HELP .*)'
    r'|[a-zA-Z_:][a-zA-Z0-9_:]*(' + _LABELS + r')?'
    r' [-+]?([0-9.eE+-]+|inf|nan))$')


def test_prometheus_exposition_is_valid(lib):
    counter, _cname = _quiet_counter(lib)
    hist, hname = _quiet_hist(lib)
    lib.hvd_metrics_test_add(counter, 5)
    for v in (3, 50, 900):
        lib.hvd_metrics_test_observe(hist, v)
    txt = metrics_prometheus()
    assert txt.endswith("\n")
    lines = txt.rstrip("\n").splitlines()
    for line in lines:
        assert EXPOSITION_LINE.match(line), f"bad exposition line: {line!r}"
    # Every sample family is preceded by exactly one TYPE line, and
    # histogram buckets are cumulative with the +Inf bucket == _count.
    full = f"hvd_{hname}"
    buckets = []
    for line in lines:
        m = re.match(rf'^{full}_bucket{{le="([^"]+)"}} (\d+)$', line)
        if m:
            buckets.append((m.group(1), int(m.group(2))))
    assert buckets, f"no bucket lines for {full}"
    counts = [c for _le, c in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert buckets[-1][0] == "+Inf"
    assert buckets[-1][1] == 3
    assert f"{full}_count 3" in lines
    assert f"{full}_sum 953" in lines
    # le edges are the log2 bucket bounds, strictly increasing.
    les = [int(le) for le, _ in buckets[:-1]]
    assert les == sorted(les) and les[0] == 1 and all(
        b == 2 * a for a, b in zip(les, les[1:]))


def test_prometheus_includes_registered_exporters(lib):
    from horovod_tpu.metrics import register_exporter, unregister_exporter
    register_exporter("t_probe", lambda: "# TYPE t_probe gauge\nt_probe 1\n")
    try:
        txt = metrics_prometheus()
        assert "t_probe 1" in txt
        for line in txt.rstrip("\n").splitlines():
            assert EXPOSITION_LINE.match(line), line
    finally:
        unregister_exporter("t_probe")
    assert "t_probe" not in metrics_prometheus()
    # A malformed fragment (truncated TYPE line) must not 500 the
    # scrape: the dedupe pass runs OUTSIDE the per-exporter
    # try/except, so it has to tolerate garbage itself.
    register_exporter("t_sick", lambda: "# TYPE \nt_sick 1\n")
    try:
        txt = metrics_prometheus()
        assert "t_sick 1" in txt
        assert "hvd_cycles_total" in txt
    finally:
        unregister_exporter("t_sick")


def test_serve_metrics_render_through_shared_helper(lib):
    """Serving snapshots export through the SAME exposition helper
    under the serve_ prefix — one scrape covers both subsystems. N
    live engines stay distinguishable: every sample carries the
    engine's instance label (bare serve_ names used to collide across
    replicas, breaking the family and undercounting fleet sums), and
    the per-family TYPE line renders once no matter how many replicas
    export it."""
    from horovod_tpu.serve.metrics import ServeMetrics

    sm = ServeMetrics(instance="abi_a")
    sm.record_submitted()
    sm.record_first_token(0.025)
    sm2 = ServeMetrics(instance="abi_b")
    sm2.record_submitted()
    sm2.record_submitted()
    txt = metrics_prometheus()
    assert 'serve_requests_submitted{instance="abi_a"} 1' in txt
    assert 'serve_requests_submitted{instance="abi_b"} 2' in txt
    assert "hvd_cycles_total" in txt
    for line in txt.rstrip("\n").splitlines():
        assert EXPOSITION_LINE.match(line), line
    # One TYPE line per family across every exporting replica — the
    # text format allows exactly one.
    assert txt.count("# TYPE serve_requests_submitted gauge") == 1
    # Empty latency series render as no sample, not 0 (None skipped):
    # of these two instances, which generated no token. The scrape is
    # the whole process's, and an engine that an earlier test file of
    # the same worker left alive may well render the series.
    for instance in ("abi_a", "abi_b"):
        assert f'serve_p50_per_token_ms{{instance="{instance}"' not in txt
    # Default instances auto-number and never collide.
    assert ServeMetrics().instance != ServeMetrics().instance
