"""The windowed sparse decoder's cell: the train-moe-window kind end to
end on the CPU at a tiny size (the check against
``benchmark/reference_mellum2.py``), ``flops_mellum2.py`` against counts
made another way, every reader the cell reports on a small trace
recorded on the v5e (``tools/record_mellum2_trace.py``), and the
tolerance tool's verdicts at the tiny size. Times and rates printed here
mean nothing."""
import json
import os
import shutil

import numpy as np
import pytest

from benchmark import flops_mellum2, harness, trace_reduce
from benchmark.tools import record_mellum2_trace

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-mellum2-ep4-seq8192"
TRACE = os.path.join(HERE, "data", "tiny-mellum2.xplane.pb")


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_mellum2_cell_runs_on_cpu(trace):
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
                          bench=bench,
                          config=_load("tiny-mellum2-config.json"),
                          traffic=_load("tiny-train-moe-window.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    if not trace:
        assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.train"]["value"] == 0
        assert 0 < m["moe_local_pair_share.mellum"]["value"] < 1
        assert m["moe_expert_load_max_over_mean.moe"]["value"] >= 1.0
        assert m["step_p50_ms.train"]["value"] > 0
        # no TPU plane in a CPU trace: the device metrics are left out
        assert "flash_fwd_window_roofline.mellum" not in m
    json.dumps(result)


def test_every_seed_does_the_same_work(capsys):
    """Two --seeds through the cell are one input (the configuration
    file's ``seeded_weights.seed``): the same first loss, logits and
    gradient to the last bit, the same pairs on the held experts."""
    import jax

    from benchmark import run

    bench = harness.load_benchmark()
    bench["workloads"].append({"name": "tiny-cell", "config": "tiny",
                               "traffic": "tiny", "chips": 1, "why": "t"})
    checks = []
    for seed in (2 ** 31 + 11, 2 ** 31 + 12):
        assert run.run_cell("tiny-cell", seed=seed, seconds=0.2, trace=False,
                            devices=jax.devices()[:1], bench=bench,
                            config=_load("tiny-mellum2-config.json"),
                            traffic=_load("tiny-train-moe-window.json")
                            )["correct"] is True
        said = [json.loads(line)
                for line in capsys.readouterr().out.split("\n")
                if line.startswith("{")]
        checks.append(next(d for d in said
                           if d.get("phase") == "warm")["check"])
    assert checks[0] == checks[1]
    assert 0 < checks[0]["routing"]["moe_local_pair_share"] < 1
    real = harness.load_json("configs", "mellum2-12b-ep4-8l.json")
    assert real["seeded_weights"]["seed"] == 2147483828
    assert "why" in real["seeded_weights"]


def test_visible_pairs_against_a_brute_force_count():
    for seq, window in ((64, 16), (200, 64), (96, 96), (50, 200),
                        (128, 1), (512, 128)):
        p = np.arange(seq)[:, None]
        j = np.arange(seq)[None, :]
        assert flops_mellum2.visible_pairs(seq, window) == int(
            ((j <= p) & (j > p - window)).sum()), (seq, window)
        assert flops_mellum2.visible_pairs(seq) == seq * (seq + 1) // 2
    # the cell's: ISSUE 34's 7 864 832 of 33 558 528
    assert flops_mellum2.visible_pairs(8192, 1024) == 7_864_832
    assert flops_mellum2.visible_pairs(8192) == 33_558_528


def test_flops_mellum2_against_a_count_from_shapes():
    """Per-token operations from the program's own parameter shapes:
    twice every matrix a token multiplies through, the experts at the
    share a token's choices land here, plus attention's pairs."""
    import jax

    from horovod_tpu.models import init_transformer

    config = harness.load_json("configs", "mellum2-12b-ep4-8l.json")
    m = config["model"]
    shapes = jax.eval_shape(lambda: init_transformer(
        harness.model_config(config), jax.random.PRNGKey(0)))
    lp = shapes["layers"][0]
    held, n, k = m["moe_experts_held"], m["n_experts"], m["moe_top_k"]
    layer = (sum(lp[w].size for w in ("wq", "wk", "wv", "wo"))
             + lp["moe"]["router"].size
             + k * held / n * sum(lp["moe"][w].size // held
                                  for w in ("w_gate", "w_up", "w_down")))
    assert lp["moe"]["w_gate"].shape == (16, 2304, 896)
    assert len(shapes["layers"]) == 8 and "dense_layers" not in shapes
    params = 8 * layer + shapes["lm_head"].size
    assert flops_mellum2.active_matmul_params(m) == params
    # ISSUE 34's arithmetic: 42.5 + 0.3 + 24.8 MFLOP a layer forward,
    # 15.7 (window) or 67.1 (full) of attention, 113 for the head
    assert 2 * layer == pytest.approx(67.5e6, rel=5e-3)
    attn = flops_mellum2.attention_flops_per_token(m, 8192)
    assert attn == pytest.approx(6 * 15.73e6 + 2 * 67.1e6, rel=2e-3)
    per_token = flops_mellum2.train_flops_per_token(m, 8192)
    assert per_token == 6 * params + 3 * attn
    assert per_token == pytest.approx(2.65e9, rel=5e-3)
    # the head's share at this depth and at the published 28 layers
    head = 6 * shapes["lm_head"].size
    assert head / per_token == pytest.approx(0.128, abs=0.005)
    full = dict(m, n_layers=28, layer_types=m["layer_types"][:4] * 7)
    assert head / flops_mellum2.train_flops_per_token(full, 8192) == \
        pytest.approx(0.04, abs=0.005)
    # the kernels' counts at the cell's shape [32, 8192, 128]
    fwd = flops_mellum2.flash_fwd(m, 8192, kind="sliding")
    assert fwd["flops"] == 32 * 4 * 128 * 7_864_832
    assert fwd["bytes"] == 8192 * (2 * 2 * 36 * 128 + 4 * 32)
    bwd = flops_mellum2.flash_bwd(m, 8192, kind="full")
    assert bwd["flops"] == 32 * 10 * 128 * 33_558_528
    assert bwd["bytes"] == 8192 * (2 * 4 * 36 * 128 + 2 * 4 * 32)
    assert (flops_mellum2.flash_bwd(m, 8192, kind="sliding")["flops"]
            / bwd["flops"]) == pytest.approx(0.2344, abs=1e-4)
    ffn = flops_mellum2.grouped_matmul(m, 16384.0)
    assert ffn["flops"] == 2 * 16384 * 2304 * 896
    assert ffn["bytes"] == 2 * (16 * 2304 * 896 + 16384 * (2304 + 896))


def test_the_cell_reports_its_own_readers_and_the_training_cells():
    bench = harness.load_benchmark()
    ours = harness.cell_metrics(bench, CELL, "per_layer")
    # what ISSUE 34 names, and the router's scope beside OLMoE's cell's:
    # a suffix names the cell a reader was first written for
    assert {m["name"] for m in ours} >= {
        "step_p50_ms.train", "mfu_pct.mellum", "peak_hbm_gb.train",
        "compiles_in_window.train", "device_idle_pct.train",
        "scope_attn_window_pct.mellum", "scope_attn_full_pct.mellum",
        "scope_moe_pct.moe", "scope_moe_dispatch_pct.moe",
        "scope_moe_router_pct.moe", "scope_head_loss_pct.train",
        "scope_optimizer_pct.train", "scope_unnamed_pct.moe",
        "flash_fwd_window_roofline.mellum",
        "flash_bwd_window_roofline.mellum",
        "flash_fwd_full_roofline.mellum", "flash_bwd_full_roofline.mellum",
        "moe_experts_roofline.mellum", "moe_local_pair_share.mellum",
        "moe_expert_load_max_over_mean.moe"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_tok_s_chip")["workloads"]


@pytest.fixture
def meas(tmp_path, monkeypatch):
    """A traced run's measurements whose newest trace is the recorded
    one: three steps of ``record_mellum2_trace.MODEL`` on one row of 512
    on the v5e."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell")
    shutil.copy(TRACE, tmp_path / "trace" / "cell" / "vm.xplane.pb")
    seq = record_mellum2_trace.SEQ
    return {"trace": trace_reduce.reduce_xplane(TRACE),
            "spans": [{"name": "train:step", "t0": 1.0 + i, "dur": 0.01,
                       "args": {}} for i in range(3)],
            "t_open": 0.0, "t_close": 10.0,
            "end_to_end": {"train_tok_s_chip": 1000.0},
            "device": {"memory_peak_bytes": 5e9},
            "counters": {"compiles_in_window": 0,
                         "pairs_per_layer": seq * 2,
                         "moe_local_pair_share": 0.5,
                         "moe_local_pair_share_traced": 0.5,
                         "moe_expert_load_max_over_mean": 1.5},
            "peak": harness.peak_for("TPU v5 lite"),
            "model": record_mellum2_trace.MODEL,
            "train": {"seq": seq, "rows_per_chip": 1, "chips": 1}}


def test_every_mellum_metric_reads_the_recorded_trace(meas):
    bench = harness.load_benchmark()
    values = {}
    for m in harness.cell_metrics(bench, CELL, "per_layer"):
        spec = harness.load_json("metrics", m["name"] + ".json")
        values[m["name"]] = harness.reducer(spec["reducer"]).reduce(
            meas, **spec.get("args", {}))
    assert all(v is not None for v in values.values()), values
    shares = [n for n in values if n.startswith("scope_")]
    assert all(0 < values[n] < 100 for n in shares), values
    # window and full attention are told apart by scope: each kind's
    # kernels are found, the forward twice a step (remat) ...
    for name in ("flash_fwd_window_roofline.mellum",
                 "flash_bwd_window_roofline.mellum",
                 "flash_fwd_full_roofline.mellum",
                 "flash_bwd_full_roofline.mellum",
                 "moe_experts_roofline.mellum"):
        assert 0 < values[name] < 100, (name, values[name])
    # ... and the two kinds share no kernel: the two scopes' shares of
    # the step differ, and neither holds the other
    assert values["scope_attn_window_pct.mellum"] != \
        values["scope_attn_full_pct.mellum"]


def test_a_trace_without_the_scopes_leaves_the_metrics_out(
        tmp_path, monkeypatch):
    """The parent of the PR that brought the scopes, and a CPU run:
    the readers return nothing and do not raise."""
    scoped = os.path.join(HERE, "data", "tiny-scopes.xplane.pb")
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell")
    shutil.copy(scoped, tmp_path / "trace" / "cell" / "vm.xplane.pb")
    parent = {"trace": trace_reduce.reduce_xplane(scoped), "spans": [],
              "counters": {}, "peak": harness.peak_for("TPU v5 lite"),
              "model": record_mellum2_trace.MODEL,
              "train": {"seq": 512, "rows_per_chip": 1}}
    cpu = {"trace": None, "spans": [], "counters": {}, "peak": None,
           "model": record_mellum2_trace.MODEL}
    bench = harness.load_benchmark()
    for m in harness.cell_metrics(bench, CELL, "per_layer"):
        spec = harness.load_json("metrics", m["name"] + ".json")
        if spec["reducer"] in ("flash_roofline_mellum2",
                               "held_experts_roofline"):
            for run in (parent, cpu):
                assert harness.reducer(spec["reducer"]).reduce(
                    run, **spec["args"]) is None


def test_the_tolerance_tool_refuses_every_control_at_the_tiny_size():
    """``tools/mellum2_tolerance.py``'s readings through the CPU at the
    tiny size, float32: the program is admitted, and the reference
    stored in an 8-bit float or with a mechanism left out is refused by
    at least one of the two limits on every seed."""
    import jax

    from benchmark.tools import mellum2_tolerance as tool

    traffic = dict(_load("tiny-train-moe-window.json"),
                   loss_check_tol=2e-5, logit_check_tol=1e-4,
                   grad_check_tol=1e-4)
    verdicts = tool.readings(_load("tiny-mellum2-config.json"), traffic,
                             [2 ** 31 + 5, 7], jax.devices()[:1])
    assert set(verdicts) == set(tool.CONTROLS) | {"program"}
    assert not any(verdicts.pop("program"))
    verdicts.pop("reference_in_bf16")       # the program here is float32
    for name, refused in verdicts.items():
        assert all(refused), (name, refused)
