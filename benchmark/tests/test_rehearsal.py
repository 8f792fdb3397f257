"""Each kind of cell end to end on the CPU at a tiny size: the harness's
control flow, the result object's shape, and that ``correct`` is
decided. Times and rates printed here mean nothing."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def _bench(cell, traffic, chips, e2e):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    real = {"train": "train-internlm2-12l-seq4096",
            "serve-backlog": "serve-mistral7b-batch-prefill",
            "serve-open": "serve-mistral7b-chat-steady"}[traffic["kind"]]
    if chips == 4:
        real = "train-internlm2-dp2fsdp2-seq4096"
    # The tiny cell reports what the real cell of its kind reports.
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if real in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [cell]
    bench["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": "tiny", "chips": chips, "why": "t"})
    return bench


@pytest.mark.parametrize("traffic_file,chips,e2e", [
    ("tiny-train.json", 1, ["train_tok_s_chip"]),
    ("tiny-train.json", 4, ["train_tok_s_chip"]),
    ("tiny-backlog.json", 1, ["serve_tok_s"]),
    ("tiny-open.json", 1, ["itl_mean_ms", "itl_p95_ms"]),
])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_on_cpu(traffic_file, chips, e2e, trace):
    import jax

    from benchmark import run

    traffic = _load(traffic_file)
    bench = _bench("tiny-cell", traffic, chips, e2e)
    result = run.run_cell("tiny-cell", seed=2 ** 31 + 11, seconds=1.5,
                          trace=trace, devices=jax.devices()[:chips],
                          bench=bench, config=_load("tiny-config.json"),
                          traffic=traffic)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] == chips
    if not trace:
        assert set(result["metrics"]) == set(e2e) | {"setup_s"}
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        fam = [n for n in result["metrics"] if n.startswith("compiles_in")]
        assert fam and result["metrics"][fam[0]]["value"] == 0
    json.dumps(result)


def test_command_refuses_a_cpu():
    """The command has no way past a missing TPU: exit code 2, and no
    result object on stdout."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "serve-mistral7b-batch-prefill", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "correct" not in proc.stdout
    assert "--cpu" not in subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--help"], cwd=ROOT,
        capture_output=True, text=True).stdout
