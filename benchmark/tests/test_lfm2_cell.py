"""The served short-convolution decoder's cell: the serve-backlog-conv
kind end to end on the CPU at a tiny size (chunked and padded prefill,
two rows a slot, grouped-query pages, the whole mixture through the
dropless dispatch, the check of tokens and rows against
``benchmark/reference_lfm2.py``), the configuration against the catalog
and its parameter count, the block dealing, ``flops_lfm2.py`` against
hand counts, the new reducers on made-up rows of a trace, and the
metrics the cell reports. Times and rates printed here mean nothing."""
import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_lfm2, harness
from benchmark.generators import serve_backlog_hybrid, serve_backlog_ssm
from benchmark.reducers import (grouped_matmul_roofline_lfm2, mfu_lfm2,
                                scope_time_share)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-lfm2-8b-a1b-assistant-backlog"
CONFIG = "lfm2-8b-a1b-14l.json"
TRAFFIC = "assistant-backlog.json"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_lfm2_cell_runs_on_cpu(trace, capsys):
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
                          config=_load("tiny-lfm2-config.json"),
                          traffic=_load("tiny-backlog-conv.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    check = next(line for line in said if line.get("phase") == "check")
    assert check["check"]["tokens"] == 13
    assert check["check"]["fillers_decoding_alongside"] == 5
    assert check["check"]["state_gap_worst"] < 1e-5
    routing = next(line for line in said if "routing" in line)["routing"]
    assert routing["chunk"]["moe_local_pair_share"] == 1.0
    win = next(line for line in said if "machine_pauses" in line)
    assert win["window"]["blocks"] == 2
    assert win["machine_pauses"]["probe"] == "ok"
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        assert 1 <= m["state_slots_in_use.ling"]["value"] <= 8
        assert 1 <= m["moe_held_experts_touched_mean.trinity"]["value"] <= 8
        assert m["moe_expert_load_max_over_mean.trinity"]["value"] >= 1
        assert m["decode_step_p50_ms.batch"]["value"] > 0
        work = win["traced_work"]
        assert work["prefill_convolved"] >= work["prefill_tokens"] > 0
        assert "prefill_scanned" not in work
        assert work["slots_stepped"] == 8 * work["decode_calls"]
        assert work["decode_positions_seen"] >= work["decode_rows"] > 0
        assert work["pairs_dispatched"] == 2 * (
            work["prefill_convolved"] + work["slots_stepped"])
        # no TPU plane and no peak in a CPU trace: the device metrics
        # and the share of a peak are left out
        assert not [n for n in m if "roofline" in n or n.startswith("scope")
                    or n.startswith("mfu")]
    json.dumps(result)


def test_the_check_refuses_rows_not_carried_and_padding_convolved():
    """The by-slot state broken either way is refused by the FIRST conv
    layer's rows, which no router precedes, and by them alone where the
    tokens cannot tell: the request whose resumed chunk is one position
    ends with its prefill, so its slot holds the row it was handed (zeros
    where a chunk starts from nothing) beside the one it wrote (a padded
    position's where the bucket's end is written)."""
    from benchmark.generators import serve_backlog_conv as conv
    from benchmark.tools import lfm2_tolerance

    config, traffic = _load("tiny-lfm2-config.json"), _load(
        "tiny-backlog-conv.json")
    cfg = harness.model_config(config)
    engine, params, scfg = conv.seeded_engine(
        config, traffic, np.arange(cfg.vocab_size), cfg)
    prompts, results, alongside = conv.serve_check_requests(
        engine, traffic, cfg.vocab_size, np.random.default_rng(3))
    assert alongside == traffic["check_fillers"]["n"]
    assert [len(r.tokens) for r in results] == traffic["check_output_lens"]
    assert len({r.slot for r in results}) == len(results)
    raw = {}
    verdicts = lfm2_tolerance.control_verdicts(
        params, conv.reference_lfm2.sizes_of(config), traffic, scfg, prompts,
        [r.tokens for r in results],
        [conv.rows_left(engine, r.slot) for r in results],
        only=("rows_not_carried", "pads_convolved"), raw=raw)
    assert verdicts["program"]["correct"], verdicts["program"]
    for name in ("rows_not_carried", "pads_convolved"):
        assert not verdicts[name]["correct"]
        assert verdicts[name]["state_gap_first"] > 0.5
        # the request that ends with its prefill is the one that shows it
        by_request = [g[0] for g in raw[name]["rows"]]
        assert by_request.index(max(by_request)) == 0
    assert lfm2_tolerance.not_as_wanted(verdicts) == []


def test_the_configuration_is_the_catalog_s_cut_to_its_first_14_layers():
    config = harness.load_json("configs", CONFIG)
    pub, m = config["published"], config["model"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert pub == row["config"] and config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == ["layer_types", "num_hidden_layers"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 14
    assert config["layer_types"] == pub["layer_types"][:14]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["file"].endswith(CONFIG))
    assert entry["reduced"] == sorted(config["reduced"], reverse=True)
    assert entry["source"] == config["source"] and len(entry["why"]) <= 200
    # every width as published
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff_dense"],
            m["d_ff"], m["n_experts"], m["moe_top_k"], m["conv_taps"],
            m["vocab_size"], m["norm_eps"], m["n_dense_layers"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["conv_L_cache"], pub["vocab_size"],
        pub["norm_eps"], pub["num_dense_layers"])
    assert m["d_head"] * m["n_heads"] == m["d_model"]
    assert m["layer_rotary"]["full"]["theta"] == pub["rope_theta"]
    assert (m["moe_norm_topk_prob"], m["moe_route_scale"],
            m["moe_scoring"], m["moe_capacity_factor"]) == (
        pub["norm_topk_prob"], pub["routed_scaling_factor"], "sigmoid", None)
    assert "moe_experts_held" not in m                 # all 32 on the chip
    kinds = ["conv" if t == "conv" else "full"
             for t in pub["layer_types"][:14]]
    assert m["layer_types"] == kinds and kinds.count("full") == 3
    # 4 667 M parameters, counted from the program's own shapes
    import jax

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve.kv_cache import init_kv_cache

    cfg = harness.model_config(config)
    shapes = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    assert count(shapes) == 4_667_077_376
    assert (count(shapes["dense_layers"][0]), count(shapes["layers"][0]),
            count(shapes["layers"][1])) == (60_827_648, 362_877_088,
                                            369_174_560)
    assert shapes["layers"][0]["moe"]["w_gate"].shape == (32, 2048, 1792)
    # what 128 slots keep: 12 MB of rows, 6 KB of pages a position
    eng = harness.load_json("traffic", TRAFFIC)["engine"]
    kinds = init_kv_cache(cfg, 2, eng["block_size"], n_slots=1).kinds
    k, _ = jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
        cfg, 64, eng["block_size"], n_slots=eng["max_batch"])))
    rows, pages = k[kinds.index("conv")], k[kinds.index("full")]
    assert rows.shape == (11, 129, 4096) and rows.dtype == cfg.dtype
    assert 2 * pages.shape[0] * int(np.prod(pages.shape[3:])) * 2 == 6144


def test_the_traffic_is_one_schedule_of_balanced_blocks():
    traffic = harness.load_json("traffic", TRAFFIC)
    blocks = serve_backlog_ssm.length_blocks(traffic)
    assert len(blocks) == 16 and all(len(b) == 64 for b in blocks)
    prompts = sorted(p for b in blocks for p, _ in b)
    outs = sorted(o for b in blocks for _, o in b)
    assert 256 <= prompts[0] and prompts[-1] <= 2048
    assert abs(prompts[512] - 724) < 8 and abs(outs[512] - 181) < 4
    assert 64 <= outs[0] and outs[-1] <= 512
    # a third of the prompts are longer than a chunk and resume one
    assert abs(sum(p > 1024 for p in prompts) / 1024 - 1 / 3) < 0.01
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
    assert eng["max_batch"] == 128 and eng["batch_buckets"] == [128]
    assert eng["block_size"] == 16 and eng["prefix_caching"] is False
    assert (eng["max_prompt"], eng["max_new_tokens"]) == (2048, 512)
    assert (eng["prefill_chunk"], eng["prefill_buckets"]) == (
        1024, [256, 512, 1024])
    model = harness.load_json("configs", CONFIG)["model"]
    assert model["max_seq"] == eng["max_prompt"] + eng["max_new_tokens"]
    # the check requests and their fillers take every slot (none is
    # handed on while the check runs, so the rows a finished request left
    # stay to be read); a filler is a whole step's prefill budget, so
    # each check prompt is cut at whole chunks: one whose resumed chunk
    # is ONE position and which ends with its prefill (its slot holds a
    # carried row and a written one), one of a single padded chunk, one
    # of two; a filler admitted first is still decoding when the last
    # check request ends
    fill = traffic["check_fillers"]
    assert fill["prompt_len"] == eng["prefill_chunk"]
    carried, one, two = traffic["check_prompt_lens"]
    assert carried == eng["prefill_chunk"] + 1
    assert traffic["check_output_lens"][0] == 1
    assert one < eng["prefill_chunk"] < two < 2 * eng["prefill_chunk"]
    assert one not in eng["prefill_buckets"]
    assert fill["n"] + 3 == eng["max_batch"]
    steps = fill["n"] + 5 + max(traffic["check_output_lens"])
    assert steps < fill["output_len"] <= eng["max_new_tokens"]
    assert traffic["queue_target"] == 4
    assert traffic["check_first_state_tol"] < traffic["check_mean_state_tol"]


def test_flops_lfm2_against_hand_counts():
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    work = {"decode_calls": 2, "decode_rows": 256, "prefill_calls": 2,
            "prefill_tokens": 900, "prefill_positions_seen": 300000,
            "decode_positions_seen": 100000}
    conv = 2048 * 6144 + 2048 * 2048
    attn = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
    expert, dense = 3 * 2048 * 1792, 3 * 2048 * 7168
    per_token = flops_lfm2.matmul_flops_per_token(m)
    assert per_token == 2 * (11 * conv + 3 * attn + 2 * dense
                             + 12 * (2048 * 32 + 4 * expert))
    # the weights a call reads: everything but the embedding's lookup,
    # the head being the table itself; 9.33 GB less the gains and taps
    a_call = flops_lfm2.weight_bytes_a_call(m, 32)
    assert a_call == 2 * (11 * conv + 3 * attn + 2 * dense
                          + 12 * (2048 * 32 + 32 * expert) + 2048 * 65536)
    assert 9.32e9 < a_call < 9.34e9
    did = flops_lfm2.served_work(m, work)
    assert did["matmul_flops"] == (900 + 256) * per_token
    assert did["head_flops"] == 2 * (2 + 256) * 2048 * 65536
    assert did["attention_flops"] == 4 * 3 * 32 * 64 * 400000
    assert did["conv_flops"] == (900 + 256) * 11 * 8 * 2048
    assert did["weight_bytes"] == 4 * a_call
    assert did["page_bytes"] == 100000 * 6144
    assert did["row_bytes"] == 258 * 11 * 2 * 2 * 2048 * 2
    assert did["flops"] == sum(v for k, v in did.items()
                               if k.endswith("_flops"))
    assert did["bytes"] == sum(v for k, v in did.items()
                               if k.endswith("_bytes"))
    # four calls that each read every expert: memory-bound
    assert flops.roofline_least_s(did, peak)["bound"] == "memory"
    step = flops_lfm2.moe_experts_product(m, 512, 32)
    assert step["flops"] == 2 * 512 * 2048 * 1792
    assert step["bytes"] == 2 * (32 * 2048 * 1792 + 512 * (2048 + 1792))
    assert flops.roofline_least_s(step, peak)["bound"] == "memory"


def test_the_reducers_read_the_scopes_against_the_counted_work(monkeypatch):
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    work = {"decode_calls": 10, "decode_rows": 1280, "prefill_calls": 4,
            "prefill_tokens": 1800, "prefill_positions_seen": 5e5,
            "decode_positions_seen": 1e6, "traced_s": 0.5}
    least, least_1k, least_4k = (
        flops_lfm2.moe_experts_product(m, pairs, 32)["bytes"]
        / peak["hbm_bytes_per_s"] for pairs in (512, 1024, 4096))

    def row(name, tf_op, self_s, count=10, category=""):
        return {"name": name, "tf_op": tf_op, "category": category,
                "flops": 0.0, "bytes": 0.0, "self_s": self_s, "count": count}

    rows = [
        row("%ragged-dot-1 = bf16[512,1792]{1,0} custom-call(...)",
            "ragged-dot-1", 20 * least, 10, "custom-call"),
        row("%ragged-dot-2 = bf16[512,2048]{1,0} custom-call(...)",
            "ragged-dot-2", 20 * least, 10, "custom-call"),
        # chunks': more pairs, each bucket held to its own least time
        row("%ragged-dot-3 = bf16[4096,1792]{1,0} custom-call(...)",
            "ragged-dot-3", 4 * 4 * least_4k, 4, "custom-call"),
        row("%ragged-dot-4 = bf16[1024,2048]{1,0} custom-call(...)",
            "ragged-dot-4", 6 * 4 * least_1k, 6, "custom-call"),
        row("%fusion", "jit(decode)/attn/attn_conv/conv_taps/mul", 0.01),
        row("%fusion", "jit(decode)/attn/attn_conv/state_write/scatter",
            0.01),
        row("%fusion", "jit(prefill)/attn/attn_conv/conv_proj/dot_general",
            0.03),
        row("%fusion", "jit(decode)/attn/attn_full/kv_gather/gather", 0.05),
        row("%fusion", "jit(prefill)/mlp/moe_router/dot_general", 0.02)]
    for mod in (grouped_matmul_roofline_lfm2, scope_time_share):
        monkeypatch.setattr(mod._scopes, "load", lambda meas: {"rows": rows})
    meas = {"model": m, "peak": peak, "traced_work": work,
            "engine": {"max_batch": 128},
            "counters": {"moe_held_experts_touched_mean": 32.0}}

    def read(name):
        spec = harness.load_json("metrics", name + ".json")
        return harness.reducer(spec["reducer"]).reduce(
            meas, **spec.get("args", {}))

    assert read("moe_experts_step_roofline.lfm2") == pytest.approx(50.0)
    assert read("moe_experts_chunk_roofline.lfm2") == pytest.approx(25.0)
    assert read("scope_kv_gather_pct.lfm2") == pytest.approx(
        100 * 0.05 / sum(r["self_s"] for r in rows))
    busy = sum(r["self_s"] for r in rows)
    assert read("scope_attn_conv_pct.lfm2") == pytest.approx(100 * 0.05 / busy)
    assert read("scope_conv_taps_pct.lfm2") == pytest.approx(100 * 0.01 / busy)
    did = flops_lfm2.served_work(m, work, 32.0)["flops"]
    assert read("mfu_pct.lfm2") == pytest.approx(
        100 * did / 0.5 / peak["bf16_flops_per_s"])
    assert 0 < read("mfu_pct.lfm2") < 100
    # the parent of this PR, or another configuration: nothing to read
    assert mfu_lfm2.reduce({"model": m, "peak": peak, "counters": {}}) is None
    assert mfu_lfm2.reduce({"model": {}, "peak": peak, "counters": {},
                            "traced_work": work}) is None
    assert grouped_matmul_roofline_lfm2.reduce(
        {"model": {}, "peak": peak, "engine": {"max_batch": 128},
         "counters": {}}, match="^ragged-dot") is None
    monkeypatch.setattr(scope_time_share._scopes, "load",
                        lambda meas: {"rows": rows[7:]})
    assert read("scope_attn_conv_pct.lfm2") is None
    monkeypatch.setattr(grouped_matmul_roofline_lfm2._scopes, "load",
                        lambda meas: {"rows": rows[2:]})
    assert read("moe_experts_step_roofline.lfm2") is None
    monkeypatch.setattr(grouped_matmul_roofline_lfm2._scopes, "load",
                        lambda meas: {"rows": rows[:2] + rows[4:]})
    assert read("moe_experts_chunk_roofline.lfm2") is None


def test_the_cell_reports_its_own_readers_and_the_backlog_cells():
    bench = harness.load_benchmark()
    mine = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    batch = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".batch")
             and "serve-jamba2-3b-chat-backlog" in m["workloads"]}
    assert len(batch) == 19 and batch <= mine
    assert mine - batch == {
        "mfu_pct.lfm2", "scope_attn_conv_pct.lfm2", "scope_conv_taps_pct.lfm2",
        "moe_experts_step_roofline.lfm2", "moe_experts_chunk_roofline.lfm2",
        "scope_kv_gather_pct.lfm2", "setup_compile_s",
        "peak_hbm_gb.trinity", "scope_moe_pct.trinity",
        "scope_moe_router_pct.trinity", "scope_attn_full_pct.trinity",
        "scope_unnamed_pct.trinity", "moe_held_experts_touched_mean.trinity",
        "moe_expert_load_max_over_mean.trinity", "state_slots_in_use.ling"}
    assert len(bench["per_layer"]) <= 128 and len(bench["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG[:-5]
    assert len(cell["why"]) <= 200 and cell["traffic"] == TRAFFIC[:-5]
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"]
