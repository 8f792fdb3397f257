"""Helpers the reducers share. A reducer is ``reduce(meas, **args)``:
``meas`` is what the generator measured (``spans``, ``counters``,
``samples``, ``t_open``, ``t_close``, ``end_to_end``, ``model``, ...)
plus ``trace`` (``trace_reduce.reduce_xplane``'s result, in a traced
run), ``device`` and ``peak``. It returns a number, or ``None`` when
there is nothing to read, and the harness then leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List


def window_spans(meas: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    return [s for s in meas["spans"] if s["name"] == name
            and meas["t_open"] <= s["t0"] + s["dur"] <= meas["t_close"]]


def percentile(values: List[float], q: float) -> float:
    xs = sorted(values)
    return xs[min(len(xs) - 1, int(q / 100.0 * len(xs)))]


def lookup(meas: Dict[str, Any], dotted: str):
    node: Any = meas
    for part in dotted.split("."):
        node = node[part]
    return node

