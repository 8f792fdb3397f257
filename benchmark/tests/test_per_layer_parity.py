"""PR 41 made ``per_layer`` one entry a reader. What each of the eight
cells reported before it (``data/per_layer_before_pr41.json``, written
from the parent's files) it still reports, by reducer and arguments
whatever the entry's name, less the twelve entries retired with their
readers. Only that direction is held: a cell may join a reader, and a
new entry may list it, with no edit here."""
import json
import os

import pytest

from benchmark import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(DATA, "per_layer_before_pr41.json")) as f:
    BEFORE = json.load(f)


def _reader(reducer, args):
    return json.dumps([reducer, args], sort_keys=True)


@pytest.mark.parametrize("cell", sorted(BEFORE["cells"]))
def test_a_cell_still_reports_what_it_reported_before_pr41(cell):
    bench = harness.load_benchmark()
    now = set()
    for m in harness.cell_metrics(bench, cell, "per_layer"):
        spec = harness.load_json("metrics", m["name"] + ".json")
        now.add(_reader(spec["reducer"], spec.get("args", {})))
    owed = [e for e in BEFORE["cells"][cell]
            if e["name"] not in BEFORE["retired"]]
    assert not [e["name"] for e in owed
                if _reader(e["reducer"], e["args"]) not in now]


def test_no_retired_name_is_left():
    assert len(BEFORE["retired"]) == 12
    names = {m["name"] for m in harness.load_benchmark()["per_layer"]}
    assert not names & set(BEFORE["retired"])
