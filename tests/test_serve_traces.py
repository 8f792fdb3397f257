"""The request generators of ``horovod_tpu.serve.traces``: the same
arguments give the same trace, in the shape each one promises, and
``import horovod_tpu.serve`` brings them and no benchmark."""

import os
import subprocess
import sys
from collections import Counter

from horovod_tpu.serve import (
    make_multi_tenant_trace, make_shared_prefix_trace, make_trace,
)


def test_make_trace_deterministic_and_mixed():
    t1 = make_trace(16, seed=3)
    t2 = make_trace(16, seed=3)
    assert t1 == t2
    assert len(t1) == 16
    plens = {len(p) for p, _ in t1}
    news = {n for _, n in t1}
    # Genuinely mixed lengths — the regime where continuous batching
    # wins; a degenerate constant trace would test nothing.
    assert len(plens) > 3 and len(news) > 3
    assert make_trace(8, seed=4) != make_trace(8, seed=5)


def test_make_shared_prefix_trace_shape():
    t1 = make_shared_prefix_trace(12, seed=2, prefix_len=16)
    assert t1 == make_shared_prefix_trace(12, seed=2, prefix_len=16)
    assert len(t1) == 12
    first_prefix = t1[0][0][:16]
    # Every request shares the identical system prompt and appends a
    # unique suffix — the prefix-cache regime.
    assert all(p[:16] == first_prefix for p, _ in t1)
    suffixes = {tuple(p[16:]) for p, _ in t1}
    assert len(suffixes) == 12
    assert all(len(p) > 16 for p, _ in t1)


def test_make_multi_tenant_trace_shape():
    t1 = make_multi_tenant_trace(24, seed=3, n_tenants=4, prefix_len=16)
    assert t1 == make_multi_tenant_trace(24, seed=3, n_tenants=4,
                                         prefix_len=16)
    assert len(t1) == 24
    prefixes = {tuple(p[:16]) for p, _ in t1}
    # Several distinct tenants, each appearing more than once — the
    # regime where placement (not just caching) decides the hit rate.
    assert 1 < len(prefixes) <= 4
    counts = Counter(tuple(p[:16]) for p, _ in t1)
    assert max(counts.values()) > 1
    assert all(len(p) > 16 for p, _ in t1)
    assert make_multi_tenant_trace(8, seed=4) != \
        make_multi_tenant_trace(8, seed=5)


def test_importing_serve_imports_no_benchmark():
    """``horovod_tpu.serve`` holds the three generators and none of the
    benchmarks that used to ship inside the package (in a fresh process:
    this one may have imported anything)."""
    code = (
        "import sys, horovod_tpu.serve as serve\n"
        "assert not [m for m in sys.modules if m.endswith('bench')]\n"
        "names = dir(serve)\n"
        "assert {'make_trace', 'make_shared_prefix_trace',\n"
        "        'make_multi_tenant_trace'} <= set(names)\n"
        "assert not [n for n in names if n.endswith('_benchmark')]\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, cwd=root)
    assert proc.returncode == 0, proc.stderr
