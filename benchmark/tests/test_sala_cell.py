"""The sparse-attention and linear-attention decoder's cell: the
serve-backlog-longctx kind end to end on the CPU at a tiny size (chunked
and padded prefill across the dense length, compressed keys beside the
pages, a decayed state a slot, the check of tokens and states against
``benchmark/reference_minicpm_sala.py`` and the chosen blocks counted
beside it), the
configuration against the catalog and its parameter count, the block
dealing, ``flops_sala.py`` against hand counts, the reducers on made-up
rows of a trace, and the metrics the cell reports. Times and rates
printed here mean nothing."""
import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_sala, harness
from benchmark.generators import serve_backlog_longctx, serve_backlog_ssm
from benchmark.reducers import mfu_sala, scope_roofline_sala, scope_time_share

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-minicpm-sala-longdoc-backlog"
CONFIG = "minicpm-sala-8l.json"
TRAFFIC = "longdoc-backlog.json"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_sala_cell_runs_on_cpu(trace, capsys):
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
                          bench=bench, config=_load("tiny-sala-config.json"),
                          traffic=_load("tiny-backlog-longctx.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    check = next(line for line in said if line.get("phase") == "check")
    assert check["check"]["tokens"] == 12
    assert check["check"]["fillers_decoding_alongside"] == 6
    assert check["check"]["state_gap_worst"] < 1e-5
    assert check["check"]["state_dtype"] == "float32"
    assert check["check"]["selection_agreement"] == 1.0
    assert all(c["selection_size_matches"]
               for c in check["check"]["selection"])
    # one check request selects (96 + 5 positions, 37 of them past 64)
    chose, = check["check"]["selection"]
    assert chose["selection_queries"] == 96 + 5 - 64
    win = next(line for line in said if "machine_pauses" in line)
    assert win["window"]["blocks"] == 2
    assert 0 < win["selection"]["chose"] < win["selection"]["queries"]
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        assert 1 <= m["state_slots_in_use.ling"]["value"] <= 8
        assert 0 < m["sparse_selected_query_share_pct.sala"]["value"] < 100
        work = win["traced_work"]
        assert work["prefill_scanned"] >= work["prefill_tokens"] > 0
        assert work["prefill_keys_chosen"] >= work["prefill_selected"] > 0
        assert work["decode_keys_attended"] >= work["decode_rows"] > 0
        # no TPU plane and no peak in a CPU trace: the device metrics
        # and the share of a peak are left out
        assert not [n for n in m if "roofline" in n or n.startswith("scope")
                    or n.startswith("mfu")]
    json.dumps(result)


def test_the_configuration_is_the_catalog_s_cut_to_eight_layers():
    config = harness.load_json("configs", CONFIG)
    pub, m = config["published"], config["model"]
    assert set(config["reduced"]) == {"num_hidden_layers"}
    for key, value in pub.items():
        assert config[key] == (8 if key == "num_hidden_layers" else value), key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert pub == row["config"] and config["source"] == row["source_url"]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["file"].endswith(CONFIG))
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"],
            m["d_ff"], m["vocab_size"], m["norm_eps"], m["rope_theta"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["intermediate_size"], pub["vocab_size"], pub["rms_norm_eps"],
        pub["rope_theta"])
    # a lightning layer has the model's heads, q, k and v alike, and
    # rotates: what the program does without a field for it
    assert (m["n_heads"], m["d_head"], True) == (
        pub["lightning_nh"], pub["lightning_head_dim"],
        pub["lightning_use_rope"])
    assert (m["qk_norm_per_head"], m["attn_gate"]) == (
        pub["qk_norm"], pub["attn_use_output_gate"])
    assert pub["lightning_nkv"] == pub["lightning_nh"]
    assert pub["attn_use_rope"] is False and pub["use_output_gate"]
    assert m["embed_multiplier"] == pub["scale_emb"]
    assert m["residual_multiplier"] == pytest.approx(
        pub["scale_depth"] / pub["num_hidden_layers"] ** 0.5)
    assert m["logit_divisor"] == pub["hidden_size"] / pub["dim_model_base"]
    # the source's layers 9-16: two whole periods, contiguous
    kinds = [{"minicpm4": "sparse", "lightning-attn": "lightning"}[t]
             for t in pub["mixer_types"][9:17]]
    assert m["layer_types"] == kinds == (
        ["sparse"] + ["lightning"] * 6 + ["sparse"])
    # 2 820.5 M parameters, counted from the program's own shapes
    import jax

    from horovod_tpu.models import init_transformer

    cfg = harness.model_config(config)
    shapes = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree.leaves(t))
    sparse, lightning = (count(shapes["layers"][i]) for i in (0, 1))
    assert sparse == 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384 \
        + 2 * 4096 + 2 * 128
    assert lightning == 5 * 4096 * 4096 + 3 * 4096 * 16384 + 3 * 4096 + 2 * 128
    assert count(shapes) == 2 * sparse + 6 * lightning \
        + 2 * 73448 * 4096 + 4096


def test_the_traffic_is_one_schedule_of_balanced_blocks():
    traffic = harness.load_json("traffic", TRAFFIC)
    blocks = serve_backlog_ssm.length_blocks(traffic)
    size = traffic["block_requests"]
    assert len(blocks) * size == 128 and all(len(b) == size for b in blocks)
    prompts = sorted(p for b in blocks for p, _ in b)
    outs = sorted(o for b in blocks for _, o in b)
    assert 8192 <= prompts[0] and prompts[-1] <= 32768
    assert abs(prompts[64] - 16384) < 200 and abs(outs[64] - 256) < 6
    assert 128 <= outs[0] and outs[-1] <= 512
    model = harness.load_json("configs", CONFIG)["model"]
    # every prompt crosses the dense length
    assert prompts[0] >= model["sparse_dense_len"]
    eng = traffic["engine"]
    assert eng["max_batch"] == 16 and eng["batch_buckets"] == [16]
    assert eng["block_size"] == model["sparse_block"] == 64
    assert eng["prefix_caching"] is False
    assert (eng["max_prompt"], eng["max_new_tokens"]) == (32768, 512)
    assert (eng["prefill_chunk"], eng["prefill_buckets"]) == (
        1024, [256, 512, 1024])
    assert model["max_seq"] == eng["max_prompt"] + eng["max_new_tokens"]
    assert model["max_seq"] // eng["block_size"] == 520
    # the fillers: whole chunks (the check prompts are cut at whole
    # chunks behind them), past the dense length (their rows choose
    # pages beside the check's), and the first of them still decoding
    # when the last check request ends
    fill = traffic["check_fillers"]
    chunks, rest = divmod(fill["prompt_len"], eng["prefill_chunk"])
    assert rest == 0 and fill["prompt_len"] > model["sparse_dense_len"]
    assert traffic["check_prompt_lens"] == [12288, 1500]
    assert fill["n"] + 2 == eng["max_batch"]
    steps = (fill["n"] - 1) * chunks + 12 + 2 + traffic["check_output_len"]
    assert steps < fill["output_len"] <= eng["max_new_tokens"]
    assert traffic["check_state_dtype"] == "float32"
    assert "check_selection_agreement" not in traffic
    assert traffic["queue_target"] == 4 and traffic["window_blocks"] >= 4


def test_the_agreement_counts_the_choices_that_are_not_forced():
    model = {"sparse_block": 8, "sparse_init_blocks": 1, "sparse_window": 16}
    want = np.zeros((1, 100, 1, 13), bool)
    got = np.zeros((1, 100, 1, 16), bool)
    # a query at 96 (block 12): blocks 0, 11, 12 forced; 3 and 7 chosen
    want[0, 96, 0, [0, 3, 7, 11, 12]] = True
    got[0, 96, 0, [0, 3, 5, 11, 12]] = True
    out = serve_backlog_longctx.selection_agreement(got, want, model)
    assert out["selection_agreement"] == 0.5
    assert out["selection_choices"] == 2 and out["selection_queries"] == 1
    assert out["selection_size_matches"]
    got[0, 96, 0, 9] = True
    assert not serve_backlog_longctx.selection_agreement(
        got, want, model)["selection_size_matches"]


def test_flops_sala_against_hand_counts():
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    work = {"decode_calls": 1, "decode_rows": 16, "prefill_calls": 2,
            "prefill_tokens": 2000, "prefill_kernels_scored": 10 ** 6,
            "prefill_kernels_read": 1500, "prefill_keys_chosen": 4 * 10 ** 6,
            "prefill_keys_dense": 10 ** 6, "prefill_keys_read": 30000,
            "decode_keys_attended": 60000, "decode_kernels_scored": 16000}
    step = flops_sala.lightning_step(m, work)
    assert step["bytes"] == 16 * 6 * (8 * 4096 * 128 + 8 * 4096)
    assert step["flops"] == 5 * 16 * 6 * 4096 * 128
    assert flops.roofline_least_s(step, peak)["bound"] == "memory"
    scan = flops_sala.lightning_scan(m, work)
    assert scan["flops"] == 6 * 2000 * 4096 * (2 * 128 + 4 * 128)
    assert scan["bytes"] == 6 * (2000 * 8 * 4096 + 2 * 8 * 4096 * 128)
    select = flops_sala.sparse_select(m, work)
    assert select["flops"] == 2 * 2 * 32 * 128 * (10 ** 6 + 16000)
    assert select["bytes"] == 2 * 512 * (1500 + 16000 + 2016 * (1 + 1 / 16))
    attend = flops_sala.sparse_attend(m, work)
    assert attend["flops"] == 2 * 4 * 32 * 128 * (5 * 10 ** 6 + 60000)
    assert attend["bytes"] == 2 * 2 * 512 * (30000 + 60000)
    per_token = flops_sala.matmul_flops_per_token(m)
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256
    assert per_token == 2 * (2 * sparse + 6 * 5 * 4096 * 4096
                             + 8 * 3 * 4096 * 16384)
    assert per_token == pytest.approx(4.44e9, rel=2e-3)
    did = flops_sala.served_work(m, work)
    assert did["matmul_flops"] == 2016 * per_token
    assert did["head_flops"] == 2 * 18 * 4096 * 73448
    assert did["flops"] == sum(v for k, v in did.items() if k != "flops")


def test_the_reducers_read_the_scopes_against_the_counted_work(monkeypatch):
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    work = {"decode_calls": 10, "decode_rows": 160, "prefill_calls": 4,
            "prefill_tokens": 4000, "prefill_kernels_scored": 2 * 10 ** 6,
            "prefill_kernels_read": 3000, "prefill_keys_chosen": 8 * 10 ** 6,
            "prefill_keys_dense": 2 * 10 ** 6, "prefill_keys_read": 60000,
            "decode_keys_attended": 600000, "decode_kernels_scored": 160000,
            "traced_s": 0.5}

    def least(cost):
        return flops.roofline_least_s(getattr(flops_sala, cost)(m, work),
                                      peak)["least_s"]

    def row(tf_op, self_s):
        return {"name": "%fusion", "tf_op": tf_op, "category": "",
                "flops": 0.0, "bytes": 0.0, "self_s": self_s, "count": 10}

    rows = [
        row("jit(decode)/attn/attn_lightning/lightning_step/mul",
            least("lightning_step")),
        row("jit(decode)/attn/attn_lightning/state_write/scatter",
            least("lightning_step")),
        row("jit(prefill_resume)/attn/attn_lightning/lightning_scan/while/dot",
            9 * least("lightning_scan")),
        row("jit(prefill_resume)/attn/attn_lightning/state_write/dus",
            least("lightning_scan")),
        row("jit(decode)/attn/attn_sparse/sparse_select/dot_general",
            3 * least("sparse_select")),
        row("jit(prefill_resume)/attn/attn_sparse/sparse_compress/reduce",
            least("sparse_select")),
        row("jit(prefill_resume)/attn/attn_sparse/sparse_attend/while/dot",
            4 * least("sparse_attend")),
        row("jit(decode)/attn/attn_sparse/sparse_attend/kv_gather/gather",
            least("sparse_attend")),
        row("jit(decode)/attn/attn_sparse/kv_write/scatter", 0.001),
        row("jit(prefill_resume)/attn/attn_lightning/qk_norm/mul", 0.002),
        row("jit(prefill_resume)/mlp/dot_general", 0.05)]
    for mod in (scope_roofline_sala, scope_time_share):
        monkeypatch.setattr(mod._scopes, "load", lambda meas: {"rows": rows})
    meas = {"model": m, "peak": peak, "traced_work": work}

    def read(name):
        spec = harness.load_json("metrics", name + ".json")
        return harness.reducer(spec["reducer"]).reduce(
            meas, **spec.get("args", {}))

    assert read("lightning_step_roofline.sala") == pytest.approx(50.0)
    assert read("lightning_scan_roofline.sala") == pytest.approx(10.0)
    assert read("sparse_select_roofline.sala") == pytest.approx(25.0)
    assert read("sparse_attend_roofline.sala") == pytest.approx(20.0)
    busy = sum(r["self_s"] for r in rows)
    lightning = sum(r["self_s"] for r in rows[:4])
    assert read("scope_lightning_recurrence_pct.sala") == pytest.approx(
        100 * lightning / busy)
    assert read("scope_attn_lightning_pct.sala") == pytest.approx(
        100 * (lightning + 0.002) / busy)
    assert read("scope_sparse_select_pct.sala") == pytest.approx(
        100 * 4 * least("sparse_select") / busy)
    assert read("scope_attn_sparse_pct.sala") == pytest.approx(
        100 * (sum(r["self_s"] for r in rows[4:8]) + 0.001) / busy)
    did = flops_sala.served_work(m, work)["flops"]
    assert read("mfu_pct.sala") == pytest.approx(
        100 * did / 0.5 / peak["bf16_flops_per_s"])
    # the parent of this PR: no such count, no such scope
    assert scope_roofline_sala.reduce(
        {"model": m, "peak": peak}, match="sparse_attend",
        cost="sparse_attend") is None
    assert mfu_sala.reduce({"model": m, "peak": peak}) is None
    assert mfu_sala.reduce({"model": {}, "peak": peak,
                            "traced_work": work}) is None
    monkeypatch.setattr(scope_time_share._scopes, "load",
                        lambda meas: {"rows": rows[-1:]})
    assert read("scope_attn_sparse_pct.sala") is None


def test_the_cell_reports_its_own_readers_and_the_backlog_cells():
    bench = harness.load_benchmark()
    mine = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    batch = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".batch")
             and "serve-ling3-ep4-reasoning-backlog" in m["workloads"]}
    own = ["scope_attn_sparse_pct.sala", "scope_attn_lightning_pct.sala",
           "scope_sparse_select_pct.sala",
           "scope_lightning_recurrence_pct.sala",
           "sparse_select_roofline.sala", "sparse_attend_roofline.sala",
           "lightning_scan_roofline.sala", "lightning_step_roofline.sala",
           "sparse_selected_query_share_pct.sala", "mfu_pct.sala"]
    assert len(batch) == 11 and batch <= mine
    assert mine - batch == set(own) | {
        "setup_compile_s", "peak_hbm_gb.trinity",
        "scope_unnamed_pct.trinity", "state_slots_in_use.ling"}
    assert [m["name"] for m in bench["per_layer"][-10:]] == own
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in bench["per_layer"][-10:])
    assert len(bench["per_layer"]) <= 128
    cell = bench["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and cell["traffic"] == TRAFFIC[:-5]
    assert len(bench["workloads"]) == 11
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"][-1] == CELL
