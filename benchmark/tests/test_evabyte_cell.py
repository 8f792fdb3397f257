"""The served EVA decoder's cell: the serve-backlog-eva kind end to end
on the CPU at a tiny size (chunks cut at the windows' ends, rows by slot
and summary pages, the check of tokens, of all the head's rows of logits
and of what a request leaves against ``benchmark/reference_evabyte.py``),
the configuration against the catalog and its parameter count,
``flops_evabyte.py`` against hand counts, the new reducers on made-up
rows of a trace, and the metrics the cell reports. Times and rates
printed here mean nothing."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_evabyte, harness
from benchmark.reducers import eva_attention_roofline, mfu_evabyte

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-evabyte-8l-bytedoc-backlog"
CONFIG = "evabyte-6.5b-8l.json"
TRAFFIC = "bytedoc-backlog.json"
NEW = ("mfu_pct.eva", "scope_attn_eva_pct.eva", "scope_eva_summarise_pct.eva",
       "eva_decode_roofline.eva", "eva_chunk_roofline.eva",
       "eva_summary_pages_max.eva")


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_evabyte_cell_runs_on_cpu(trace, capsys):
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
                          config=_load("tiny-evabyte-config.json"),
                          traffic=_load("tiny-backlog-eva.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    check = next(line for line in said if line.get("phase") == "check")
    check = check["check"]
    assert check["tokens"] == 28 and check["tokens_over_tol"] == 0
    assert check["fillers_decoding_alongside"] == 6
    # 115 + 14 positions: four windows of 32 closed, the boundary at 128
    # crossed by a decode step; 20 + 14: one closed by a step
    assert check["windows_closed_behind"] == [4, 1]
    assert max(check["logits_gap"], check["rows_gap"],
               check["summaries_gap"]) < 1e-5
    win = next(line for line in said if "machine_pauses" in line)
    assert win["window"]["blocks"] == 2
    assert win["state"]["summary_pages_max"] > 0
    assert win["state"]["windows_closed"] > 0
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        assert 1 <= m["state_slots_in_use.ling"]["value"] <= 8
        assert m["eva_summary_pages_max.eva"]["value"] > 0
        assert m["decode_step_p50_ms.batch"]["value"] > 0
        work = win["traced_work"]
        assert work["prefill_keys_exact"] >= work["prefill_tokens"] > 0
        assert work["decode_rows_read"] >= work["decode_rows"] > 0
        assert work["decode_summaries_read"] % (32 // 4) == 0
        # no TPU plane and no peak in a CPU trace: the device metrics
        # and the share of a peak are left out
        assert not [n for n in m if "roofline" in n or n.startswith("scope")
                    or n.startswith("mfu")]
    json.dumps(result)


def test_the_configuration_holds_the_catalog_s_keys_and_the_cut():
    config = harness.load_json("configs", CONFIG)
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        entry = next(e for e in map(json.loads, open(catalog))
                     if e["name"] == "EvaByte")
        assert config["source"] == entry["source_url"]
        assert config["published"] == entry["config"]
        for key, value in entry["config"].items():
            if key != "num_hidden_layers":
                assert config[key] == value, key
    assert config["num_hidden_layers"] == 8
    assert list(config["reduced"]) == ["num_hidden_layers"]
    m, pub = config["model"], config["published"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["intermediate_size"])
    assert (m["vocab_size"], m["head_rows"], m["eva_window"],
            m["eva_chunk"], m["rope_theta"], m["max_seq"]) == (
        pub["vocab_size"], pub["num_pred_heads"], pub["window_size"],
        pub["chunk_size"], pub["rope_theta"], pub["max_position_embeddings"])
    assert m["norm_unit_offset"] is pub["norm_add_unit_offset"]
    assert m["stream_fp32"] is pub["fp32_skip_add"]
    assert m["layer_types"] == ["eva"] * 8
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] and len(entry["why"]) <= 200


def test_the_parameters_and_the_cache_are_the_deployment_s_arithmetic():
    import jax

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve.kv_cache import init_kv_cache

    config = harness.load_json("configs", CONFIG)
    traffic = harness.load_json("traffic", TRAFFIC)
    cfg = harness.model_config(config)
    shapes = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    layer = sum(a.size for a in jax.tree.leaves(shapes["layers"][0]))
    matrices = flops_evabyte.layer_params(config["model"])
    assert matrices == 4 * 4096 ** 2 + 3 * 4096 * 11008 == 202_375_168
    assert layer == matrices + 2 * 32 * 128 + 2 * 4096
    assert shapes["lm_head"].shape == (4096, 8 * 320)
    assert flops_evabyte.head_params(config["model"]) == 10_485_760
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert 3.25e9 < 2 * total < 3.27e9                      # 3.26 GB
    eng = traffic["engine"]
    bs = eng["block_size"]
    width = (eng["max_prompt"] + eng["max_new_tokens"]) // bs
    assert width == 128 and eng["max_prompt"] + eng["max_new_tokens"] == 32768
    (kr, ks), _ = jax.eval_shape(lambda: init_kv_cache(
        cfg, eng["max_batch"] * width + 1, bs, n_slots=eng["max_batch"]
    ).k)[0], None
    assert kr.shape == (8, 17, 2048, 32, 128)
    assert ks.shape == (8, 2049, 16, 32, 128)
    assert 2 * 2 * kr.size == 4_563_402_752                # 4.56 GB
    assert 2 * 2 * ks.size == 4_297_064_448                # 4.30 GB
    # a closed window's summaries are 1/16 of its rows
    assert int(np.prod(kr.shape[2:])) == 16 * 8 * int(np.prod(ks.shape[2:]))


def test_the_traffic_is_the_issue_s():
    traffic = harness.load_json("traffic", TRAFFIC)
    from benchmark.generators import serve_backlog_ssm

    blocks = serve_backlog_ssm.length_blocks(traffic)
    pairs = [pair for b in blocks for pair in b]
    assert len(pairs) == 128
    assert min(p for p, _ in pairs) >= 8192
    assert max(p for p, _ in pairs) <= 30720
    assert min(o for _, o in pairs) >= 512 and max(o for _, o in pairs) <= 2048
    assert max(p + o for p, o in pairs) <= 32768
    assert 15000 < np.median([p for p, _ in pairs]) < 17500
    assert 950 < np.median([o for _, o in pairs]) < 1100
    eng = traffic["engine"]
    assert (eng["max_batch"], eng["batch_buckets"], eng["prefill_chunk"],
            eng["prefill_buckets"], eng["prefix_caching"]) == (
        16, [16], 1024, [256, 512, 1024], False)
    # the check: the sixth window closes at the first output, and the
    # short prompt is a whole and a padded chunk
    assert traffic["check_prompt_lens"] == [12287, 1500]
    assert (12287 + 1) % 2048 == 0 and traffic["check_fillers"]["n"] == 14


WORK = {"decode_calls": 10, "decode_rows": 160, "prefill_calls": 4,
        "prefill_tokens": 4096, "prefill_keys_exact": 3_000_000,
        "prefill_keys_summaries": 2_000_000, "decode_rows_read": 150_000,
        "decode_summaries_read": 160_000, "traced_s": 0.5}


def test_flops_evabyte_against_hand_counts():
    model = harness.load_json("configs", CONFIG)["model"]
    did = flops_evabyte.served_work(model, WORK)
    assert did["matmul_flops"] == 2 * 4256 * 8 * 202_375_168
    assert did["head_flops"] == 2 * 164 * 4096 * 2560
    pairs = 3_000_000 + 2_000_000 + 150_000 + 160_000
    assert did["attention_flops"] == 4 * 8 * 32 * 128 * pairs
    assert did["pooling_flops"] == 8 * 4256 * 8 * 32 * 128
    assert did["weight_bytes"] == 14 * 2 * (8 * 202_375_168 + 10_485_760)
    # a row or a summary: K and V of 32 x 128 in bf16, once a layer
    assert did["decode_state_bytes"] == 8 * 310_000 * 2 * 32 * 128 * 2
    assert did["flops"] == sum(did[k] for k in (
        "matmul_flops", "head_flops", "attention_flops", "pooling_flops"))
    step = flops_evabyte.decode_attention(model, WORK)
    assert step["bytes"] == did["decode_state_bytes"]
    # a decode step's attention is 1 operation a byte: the memory's
    assert step["flops"] / step["bytes"] == 1.0


def test_the_new_reducers_on_made_up_rows(monkeypatch):
    from benchmark.reducers import _scopes

    model = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    rows = [
        {"name": "a", "tf_op": "jit(decode)/attn/attn_eva/eva_window/"
         "jit(_decode)/hvd_paged_decode", "category": "custom-call",
         "self_s": 0.04, "count": 80, "flops": 0, "bytes": 0},
        {"name": "b", "tf_op": "jit(decode)/attn/attn_eva/eva_summarise/"
         "gather", "category": "fusion", "self_s": 0.01, "count": 80,
         "flops": 0, "bytes": 0},
        {"name": "c", "tf_op": "jit(prefill_resume)/attn/attn_eva/"
         "eva_window/hvd_flash_keys_fwd", "category": "custom-call",
         "self_s": 0.02, "count": 32, "flops": 0, "bytes": 0},
        {"name": "d", "tf_op": "jit(decode)/mlp/dot_general",
         "category": "fusion", "self_s": 0.05, "count": 80, "flops": 0,
         "bytes": 0}]
    monkeypatch.setattr(_scopes, "load", lambda meas: {"rows": rows})
    meas = {"traced_work": WORK, "peak": peak, "model": model}
    spec = {n: harness.load_json("metrics", n + ".json") for n in NEW}
    step = eva_attention_roofline.reduce(
        meas, **spec["eva_decode_roofline.eva"]["args"])
    needed = flops_evabyte.decode_attention(model, WORK)
    assert step == pytest.approx(
        100 * needed["bytes"] / peak["hbm_bytes_per_s"] / 0.05)
    chunk = eva_attention_roofline.reduce(
        meas, **spec["eva_chunk_roofline.eva"]["args"])
    needed = flops_evabyte.chunk_attention(model, WORK)
    assert chunk == pytest.approx(
        100 * needed["flops"] / peak["bf16_flops_per_s"] / 0.02)
    assert 0 < step <= 100 and 0 < chunk <= 100
    share = harness.reducer("scope_time_share").reduce(
        meas, **spec["scope_eva_summarise_pct.eva"]["args"])
    assert share == pytest.approx(100 * 0.01 / 0.12)
    assert harness.reducer("scope_time_share").reduce(
        meas, **spec["scope_attn_eva_pct.eva"]["args"]) == pytest.approx(
            100 * 0.07 / 0.12)
    mfu = mfu_evabyte.reduce(meas)
    assert mfu == pytest.approx(
        100 * flops_evabyte.served_work(model, WORK)["flops"] / 0.5
        / peak["bf16_flops_per_s"])
    # nothing to read: a program that lacks the spans' counts, or no
    # trace (the parent of this PR), is silent and does not raise
    bare = {k: v for k, v in WORK.items() if "decode_rows_read" != k}
    assert mfu_evabyte.reduce({**meas, "traced_work": bare}) is None
    assert eva_attention_roofline.reduce(
        {**meas, "traced_work": bare},
        **spec["eva_decode_roofline.eva"]["args"]) is None
    assert mfu_evabyte.reduce({**meas, "traced_work": {}}) is None
    monkeypatch.setattr(_scopes, "load", lambda meas: None)
    assert eva_attention_roofline.reduce(
        meas, **spec["eva_chunk_roofline.eva"]["args"]) is None


def test_the_cell_s_entries_in_benchmark_json():
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b-8l", "bytedoc-backlog", 1)
    assert len(cell["why"]) <= 200
    mine = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    assert set(NEW) <= mine
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = harness.load_json("metrics", name + ".json")
        assert entry["workloads"] == [CELL] and "workloads" not in spec
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
        assert entry["moves"] == "serve_tok_s"
    # every reader the long-context backlog cell before it shares with
    # the conv cell is read here too
    sala = {m["name"] for m in harness.cell_metrics(
        bench, "serve-minicpm-sala-longdoc-backlog", "per_layer")}
    lfm2 = {m["name"] for m in harness.cell_metrics(
        bench, "serve-lfm2-8b-a1b-assistant-backlog", "per_layer")}
    assert sala & lfm2 <= mine
    assert {m["name"] for m in harness.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_tok_s", "setup_s"}
    assert bench["workloads"][-1]["name"] == CELL
    assert len(bench["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
