"""Self time of the device operations traced under a named scope, as a
share of all operation time on device 0, in percent. An operation
counts when its scope path (the ``tf_op`` of its metadata, see
``_scopes.py``) has ``match`` and lacks ``unless``, either of which may
be left out. With ``match``, no operation matching means the name is not
in the program (the parent of the PR that wrote the scopes, or a
refactor that dropped one): the metric is left out, not reported as 0."""
from benchmark.reducers import _scopes


def reduce(meas, match=None, unless=None):
    parsed = _scopes.load(meas)
    if not parsed or not parsed["rows"]:
        return None
    rows = _scopes.matching(parsed["rows"], match, unless)
    if match and not rows:
        return None
    busy = sum(r["self_s"] for r in parsed["rows"])
    return 100.0 * sum(r["self_s"] for r in rows) / busy
