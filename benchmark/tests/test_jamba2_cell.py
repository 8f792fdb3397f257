"""The whole served state-space decoder's cell: the serve-backlog-ssm
kind end to end on the CPU at a tiny size (chunked and padded prefill, a
selective-scan state a slot, multi-query pages, the check of tokens and
states against ``benchmark/reference_jamba2.py``), the configuration's
parameter count, the block dealing, ``flops_jamba2.py`` against hand
counts, the three reducers on made-up rows of a trace, and the metrics
the cell reports. Times and rates printed here mean nothing."""
import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_jamba2, harness, machine_pauses
from benchmark.generators import serve_backlog_hybrid, serve_backlog_ssm
from benchmark.reducers import (mfu_jamba2, scope_roofline_jamba2,
                                scope_time_share)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-jamba2-3b-chat-backlog"
CONFIG = "jamba2-3b.json"
TRAFFIC = "chat-backlog.json"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_jamba2_cell_runs_on_cpu(trace, monkeypatch, capsys):
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
                          config=_load("tiny-jamba2-config.json"),
                          traffic=_load("tiny-backlog-ssm.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    check = next(line for line in said if line.get("phase") == "check")
    assert check["check"]["tokens"] == 12
    assert check["check"]["fillers_decoding_alongside"] == 6
    assert check["check"]["state_gap_worst"] < 1e-5
    win = next(line for line in said if "machine_pauses" in line)
    assert win["window"]["blocks"] == 2
    assert win["machine_pauses"]["probe"] == "ok"
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        assert 1 <= m["state_slots_in_use.ling"]["value"] <= 8
        assert m["decode_step_p50_ms.batch"]["value"] > 0
        work = win["traced_work"]
        assert work["prefill_scanned"] >= work["prefill_tokens"] > 0
        assert work["slots_stepped"] == 9 * work["decode_calls"]
        assert work["decode_positions_seen"] >= work["decode_rows"] > 0
        # no TPU plane and no peak in a CPU trace: the device metrics
        # and the share of a peak are left out
        assert not [n for n in m if "roofline" in n or n.startswith("scope")
                    or n.startswith("mfu")]
    json.dumps(result)


def test_the_configuration_is_the_whole_published_model():
    config = harness.load_json("configs", CONFIG)
    pub, m = config["published"], config["model"]
    assert config["reduced"] == {}
    for key, value in pub.items():
        assert config[key] == value, key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    assert pub == row["config"] and config["source"] == row["source_url"]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["file"].endswith(CONFIG))
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert (m["d_model"], m["n_layers"], m["n_heads"], m["n_kv_heads"],
            m["d_ff"], m["vocab_size"], m["norm_eps"]) == (
        pub["hidden_size"], pub["num_hidden_layers"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["intermediate_size"], pub["vocab_size"], pub["rms_norm_eps"])
    assert (m["mamba_d_state"], m["mamba_d_conv"], m["mamba_expand"],
            m["mamba_dt_rank"], m["tie_embeddings"]) == (
        pub["mamba_d_state"], pub["mamba_d_conv"], pub["mamba_expand"],
        pub["mamba_dt_rank"], pub["tie_word_embeddings"])
    assert m["d_head"] * m["n_heads"] == m["d_model"]
    kinds = ["full" if i % pub["attn_layer_period"] == pub["attn_layer_offset"]
             else "mamba" for i in range(pub["num_hidden_layers"])]
    assert m["layer_types"] == kinds and kinds.count("full") == 2
    # 3 029 M parameters, counted from the program's own shapes
    import jax

    from horovod_tpu.models import init_transformer

    cfg = harness.model_config(config)
    shapes = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 3_029_337_472
    mamba = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(shapes["layers"][0]))
    attn = sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes["layers"][7]))
    assert (mamba, attn) == (104_161_472, 76_682_240)


def test_the_traffic_is_one_schedule_of_balanced_blocks():
    traffic = harness.load_json("traffic", TRAFFIC)
    blocks = serve_backlog_ssm.length_blocks(traffic)
    assert len(blocks) == 16 and all(len(b) == 64 for b in blocks)
    prompts = sorted(p for b in blocks for p, _ in b)
    outs = sorted(o for b in blocks for _, o in b)
    assert 64 <= prompts[0] and prompts[-1] <= 1024
    assert abs(prompts[512] - 256) < 6 and abs(outs[512] - 181) < 4
    assert 64 <= outs[0] and outs[-1] <= 512
    # a quarter of the prompts are longer than a chunk
    assert abs(sum(p > 512 for p in prompts) / 1024 - 0.25) < 0.01
    for key in (0, 1):
        sums = [sum(pair[key] for pair in b) for b in blocks]
        assert max(sums) - min(sums) <= 0.005 * max(sums)
    names = [serve_backlog_hybrid.vocabulary_names(seed, 65536)
             for seed in (7, 2 ** 31 + 5)]
    a, b = (serve_backlog_ssm.request_stream(traffic, 1, n) for n in names)
    first = [(next(a), next(b)) for _ in range(40)]
    assert all(x[1] == y[1] for x, y in first)
    old = [np.argsort(n) for n in names]
    assert all((old[0][x[0]] == old[1][y[0]]).all() for x, y in first)
    eng = traffic["engine"]
    assert eng["max_batch"] == 256 and eng["batch_buckets"] == [256]
    assert eng["block_size"] == 16 and eng["prefix_caching"] is False
    assert (eng["max_prompt"], eng["max_new_tokens"]) == (1024, 512)
    assert (eng["prefill_chunk"], eng["prefill_buckets"]) == (
        512, [128, 256, 512])
    model = harness.load_json("configs", CONFIG)["model"]
    assert model["max_seq"] == eng["max_prompt"] + eng["max_new_tokens"]
    # the check requests and their fillers fill every slot; a filler is
    # a whole step's prefill budget, so each check prompt is cut at whole
    # chunks, and a filler admitted first is still decoding when the
    # last check request ends
    fill = traffic["check_fillers"]
    assert fill["prompt_len"] == eng["prefill_chunk"]
    assert traffic["check_prompt_lens"] == [300, 900]
    assert fill["n"] + 2 == eng["max_batch"]
    steps = fill["n"] + 3 + traffic["check_output_len"]
    assert steps < fill["output_len"] <= eng["max_new_tokens"]
    assert traffic["window_blocks"] == 10 and traffic["queue_target"] == 4


def test_flops_jamba2_against_hand_counts():
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    work = {"decode_calls": 1, "decode_rows": 256, "prefill_calls": 2,
            "prefill_tokens": 900, "prefill_positions_seen": 300000,
            "decode_positions_seen": 100000}
    step = flops_jamba2.mamba_step(m, work)
    # 26 layers x 5120 x 16 float32 a row, read and written, and the rows
    assert step["bytes"] == 256 * 26 * (8 * 5120 * 16 + 2 * (3 * 5120 + 32))
    assert step["flops"] == 6 * 256 * 26 * 5120 * 16
    assert flops.roofline_least_s(step, peak)["bound"] == "memory"
    scan = flops_jamba2.mamba_scan(m, work)
    assert scan["flops"] == 6 * 900 * 26 * 5120 * 16
    assert scan["bytes"] == 26 * (900 * 2 * (3 * 5120 + 32)
                                  + 2 * 8 * 5120 * 16)
    assert flops.roofline_least_s(scan, peak)["bound"] == "memory"
    # a token's matrix products: the parameters outside the table, the
    # taps, gains and float32 vectors apart, twice
    per_token = flops_jamba2.matmul_flops_per_token(m)
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2560 * 2560 + 2 * 2560 * 128 + 2560 * 2560
    assert per_token == 2 * (26 * mamba + 2 * attn + 28 * 3 * 2560 * 8192)
    did = flops_jamba2.served_work(m, work)
    assert did["matmul_flops"] == (900 + 256) * per_token
    assert did["head_flops"] == 2 * (2 + 256) * 2560 * 65536
    assert did["attention_flops"] == 4 * 2 * 20 * 128 * 400000
    assert did["recurrence_flops"] == step["flops"] + scan["flops"]
    assert did["flops"] == sum(v for k, v in did.items() if k != "flops")


def test_the_reducers_read_the_scopes_against_the_counted_work(monkeypatch):
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    work = {"decode_calls": 10, "decode_rows": 2560, "prefill_calls": 4,
            "prefill_tokens": 1800, "prefill_positions_seen": 5e5,
            "decode_positions_seen": 1e6, "traced_s": 0.5}
    least_step = (flops_jamba2.mamba_step(m, work)["bytes"]
                  / peak["hbm_bytes_per_s"])
    least_scan = (flops_jamba2.mamba_scan(m, work)["bytes"]
                  / peak["hbm_bytes_per_s"])

    def row(tf_op, self_s):
        return {"name": "%fusion", "tf_op": tf_op, "category": "",
                "flops": 0.0, "bytes": 0.0, "self_s": self_s, "count": 10}

    rows = [
        row("jit(decode)/attn/attn_mamba/mamba_step/mul", least_step),
        row("jit(decode)/attn/attn_mamba/state_write/scatter", least_step),
        row("jit(decode)/attn/attn_mamba/mamba_proj/dot_general", 0.01),
        row("jit(decode)/attn/attn_full/kv_write/scatter", 0.02),
        row("jit(prefill_resume)/attn/attn_mamba/mamba_scan/while/body/mul",
            19 * least_scan),
        row("jit(prefill)/attn/attn_mamba/state_write/dynamic_update_slice",
            least_scan),
        row("jit(prefill)/attn/attn_mamba/mamba_conv/mul", 0.01),
        row("jit(prefill)/mlp/dot_general", 0.05)]
    for mod in (scope_roofline_jamba2, scope_time_share):
        monkeypatch.setattr(mod._scopes, "load", lambda meas: {"rows": rows})
    meas = {"model": m, "peak": peak, "traced_work": work}

    def read(name):
        spec = harness.load_json("metrics", name + ".json")
        return harness.reducer(spec["reducer"]).reduce(
            meas, **spec.get("args", {}))

    assert read("mamba_step_roofline.jamba") == pytest.approx(50.0)
    assert read("mamba_scan_roofline.jamba") == pytest.approx(5.0)
    busy = sum(r["self_s"] for r in rows)
    recurrence = 2 * least_step + 20 * least_scan
    assert read("scope_mamba_recurrence_pct.jamba") == pytest.approx(
        100 * recurrence / busy)
    assert read("scope_attn_mamba_pct.jamba") == pytest.approx(
        100 * (recurrence + 0.02) / busy)
    did = flops_jamba2.served_work(m, work)["flops"]
    assert read("mfu_pct.jamba") == pytest.approx(
        100 * did / 0.5 / peak["bf16_flops_per_s"])
    # the parent of this PR: no such count, no such scope
    assert scope_roofline_jamba2.reduce(
        {"model": m, "peak": peak}, match="mamba_step",
        cost="mamba_step") is None
    assert mfu_jamba2.reduce({"model": m, "peak": peak}) is None
    assert mfu_jamba2.reduce({"model": {}, "peak": peak,
                              "traced_work": work}) is None
    monkeypatch.setattr(scope_time_share._scopes, "load",
                        lambda meas: {"rows": rows[3:4]})
    assert read("scope_attn_mamba_pct.jamba") is None


def test_the_cell_reports_its_own_readers_and_the_backlog_cells():
    bench = harness.load_benchmark()
    mine = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    batch = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".batch")
             and "serve-ling3-ep4-reasoning-backlog" in m["workloads"]}
    assert len(batch) == 11 and batch <= mine
    assert mine - batch == {
        "scope_attn_mamba_pct.jamba", "scope_mamba_recurrence_pct.jamba",
        "mamba_scan_roofline.jamba", "mamba_step_roofline.jamba",
        "mfu_pct.jamba", "setup_compile_s", "peak_hbm_gb.trinity",
        "scope_unnamed_pct.trinity", "scope_attn_full_pct.trinity",
        "state_slots_in_use.ling"}
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "scope_attn_mamba_pct.jamba", "scope_mamba_recurrence_pct.jamba",
        "mamba_scan_roofline.jamba", "mamba_step_roofline.jamba",
        "mfu_pct.jamba"]
    assert len(bench["per_layer"]) <= 128
    cell = bench["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and cell["traffic"] == TRAFFIC[:-5]
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"][-1] == CELL
    assert machine_pauses is not None
