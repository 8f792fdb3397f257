"""The sparse-decoder cell's own files: the train-moe kind end to end on
the CPU at a tiny size, ``flops_moe.py`` against a hand count, and the
metrics the cell reports. Times and rates printed here mean nothing."""
import json
import os

import pytest

from benchmark import flops, flops_moe, harness

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-olmoe-1b-7b-seq4096"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_moe_cell_runs_on_cpu(trace):
    import jax

    from benchmark import run

    bench = harness.load_benchmark()
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                m["workloads"] = m["workloads"] + ["tiny-cell"]
    bench["workloads"].append({"name": "tiny-cell", "config": "tiny",
                               "traffic": "tiny", "chips": 1, "why": "t"})
    result = run.run_cell("tiny-cell", seed=2 ** 31 + 11, seconds=1.5,
                          trace=trace, devices=jax.devices()[:1],
                          bench=bench, config=_load("tiny-moe-config.json"),
                          traffic=_load("tiny-train-moe.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    if not trace:
        assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.train"]["value"] == 0
        assert m["moe_expert_load_max_over_mean.moe"]["value"] >= 1.0
        # no TPU plane in a CPU trace: the device metrics are left out
        assert "moe_experts_roofline.moe" not in m
    json.dumps(result)


def test_flops_moe_against_a_hand_count():
    m = harness.load_json("configs", "olmoe-1b-7b-1chip.json")["model"]
    # per layer: attention 4 * 2048 * 2048 = 16,777,216; router
    # 2048 * 64 = 131,072; 8 experts of 3 * 2048 * 1024 = 6,291,456
    # each = 50,331,648; head 2048 * 50304 = 103,022,592
    layer = 16_777_216 + 131_072 + 50_331_648
    assert flops_moe.active_matmul_params(m) == (
        m["n_layers"] * layer + 103_022_592)
    per_token = flops_moe.train_flops_per_token(m, 4096)
    assert per_token == (6 * flops_moe.active_matmul_params(m)
                         + 6 * m["n_layers"] * 4096 * 2048)
    # the head's share of a token at this depth and at the published 16
    head = 6 * 103_022_592
    assert head / per_token == pytest.approx(
        {3: 0.31, 2: 0.40}[m["n_layers"]], abs=0.01)
    full = dict(m, n_layers=16)
    assert head / flops_moe.train_flops_per_token(full, 4096) == \
        pytest.approx(0.08, abs=0.005)
    ffn = flops_moe.grouped_matmul(m, 4096, rows=2)
    assert ffn["flops"] == 2 * 65536 * 2048 * 1024
    assert ffn["bytes"] == 2 * (64 * 2048 * 1024 + 65536 * (2048 + 1024))
    peak = harness.peak_for("TPU v5 lite")
    assert flops.roofline_least_s(ffn, peak)["bound"] == "compute"
    # the dense kernel's cost function reads this model too (q_per_kv 1)
    assert flops.flash_fwd(m, 4096, rows=2)["flops"] == \
        2 * 16 * 4 * 128 * 4096 * 4097 / 2


def test_the_cell_reports_its_own_readers_and_the_training_cells():
    bench = harness.load_benchmark()
    mine = harness.cell_metrics(bench, CELL, "per_layer")
    # the readers written for this cell (a suffix names the first cell
    # of a reader), beside those it joined by its name on their lists
    assert {m["name"] for m in mine} >= {
        "mfu_pct.moe", "moe_experts_roofline.moe", "scope_moe_pct.moe",
        "scope_moe_dispatch_pct.moe", "scope_moe_router_pct.moe",
        "moe_expert_load_max_over_mean.moe", "scope_unnamed_pct.moe",
        "step_p50_ms.train", "flash_fwd_kernel_roofline.train"}
