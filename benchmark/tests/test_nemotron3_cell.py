"""The one-branch hybrid's cell: the serve-backlog-ssd kind end to end on
the CPU at a tiny size (chunked and padded prefill, an SSD state a slot,
pages of two KV heads as one row, a latent mixture held in part, the
check of tokens and states against ``benchmark/reference_nemotron3.py``),
the configuration's file against the catalog and its parameter count
from the program's own shapes, ``flops_nemotron3.py`` against hand
counts, and the reducers on made-up rows of a trace. Times and rates
printed here mean nothing."""
import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_nemotron3, harness
from benchmark.reducers import mfu_nemotron3, scope_roofline_nemotron3

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-nemotron3-super-ep4-agent-backlog"
CONFIG = "nemotron-3-super-120b-ep4-11l.json"
TRAFFIC = "agent-backlog.json"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_nemotron3_cell_runs_on_cpu(trace, capsys):
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
                          config=_load("tiny-nemotron3-config.json"),
                          traffic=_load("tiny-backlog-ssd.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    check = next(line for line in said if line.get("phase") == "check")
    assert check["check"]["tokens"] == 12
    assert check["check"]["fillers_decoding_alongside"] == 6
    assert check["check"]["state_gap_worst"] < 1e-5
    routing = next(line for line in said if "routing" in line)["routing"]
    assert routing["decode_batch"]["moe_dispatch_dropped_token_frac"] == 0
    win = next(line for line in said if "machine_pauses" in line)
    assert win["window"]["blocks"] == 2
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        assert 1 <= m["state_slots_in_use.ling"]["value"] <= 8
        assert 0 < m["moe_held_experts_touched_mean.trinity"]["value"] <= 4
        work = win["traced_work"]
        assert work["prefill_scanned"] >= work["prefill_tokens"] > 0
        assert work["slots_stepped"] == 9 * work["decode_calls"]
        # no TPU plane and no peak in a CPU trace: the device metrics
        # and the share of a peak are left out
        assert not [n for n in m if "roofline" in n or n.startswith("scope")
                    or n.startswith("mfu")]
    json.dumps(result)


def test_the_configuration_is_the_catalog_s_cut_as_stated():
    config = harness.load_json("configs", CONFIG)
    pub, m = config["published"], config["model"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert pub == row["config"] and config["source"] == row["source_url"]
    differs = {k for k, v in pub.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["file"].endswith(CONFIG))
    assert set(entry["reduced"]) == differs
    assert entry["source"] == config["source"]
    pattern = pub["hybrid_override_pattern"]
    assert config["hybrid_override_pattern"] == pattern[27:38] == (
        "MEMEMEMEM*E")
    assert m["layer_types"] == [{"M": "mamba2", "E": "ffn", "*": "full"}[c]
                                for c in pattern[27:38]]
    # no width differs from the source
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"],
            m["d_ff"], m["norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_intermediate_size"], pub["norm_eps"])
    assert (m["mamba_expand"], m["mamba_d_state"], m["mamba_d_conv"],
            m["mamba2_head_dim"], m["mamba2_groups"], m["mamba2_chunk"]) == (
        pub["expand"], pub["ssm_state_size"], pub["conv_kernel"],
        pub["mamba_head_dim"], pub["n_groups"], pub["chunk_size"])
    assert m["mamba_expand"] * m["d_model"] == (
        pub["mamba_num_heads"] * pub["mamba_head_dim"])
    assert m["mamba2_dt_range"] == [pub["time_step_min"],
                                    pub["time_step_max"],
                                    pub["time_step_floor"]]
    assert (m["n_experts"], m["moe_top_k"], m["moe_route_scale"],
            m["moe_latent"], m["moe_shared_d_ff"], m["moe_n_group"],
            m["moe_topk_group"], m["moe_norm_topk_prob"]) == (
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["routed_scaling_factor"], pub["moe_latent_size"],
        pub["moe_shared_expert_intermediate_size"], pub["n_group"],
        pub["topk_group"], pub["norm_topk_prob"])
    assert m["moe_experts_held"] == config["n_routed_experts"] == 128
    assert m["vocab_size"] == config["vocab_size"] == pub["vocab_size"] // 4
    assert m["moe_activation"] == pub["mlp_hidden_act"] == "relu2"
    # the parameters, counted from the program's own shapes
    import jax

    from horovod_tpu.models import init_transformer

    cfg = harness.model_config(config)
    shapes = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    count = lambda tree: sum(int(np.prod(a.shape))  # noqa: E731
                             for a in jax.tree.leaves(tree))
    layers = shapes["layers"]
    mamba2, ffn, attn = count(layers[0]), count(layers[1]), count(layers[9])
    experts = count({k: layers[1]["moe"][k] for k in ("w_up", "w_down")})
    assert experts == 128 * 5_505_024
    assert (mamba2, attn, ffn - experts) == (109_640_064, 35_655_680,
                                             54_530_560)
    assert count(shapes) == (5 * mamba2 + attn + 5 * ffn + 4096
                             + 2 * 32768 * 4096) == 4_648_163_712
    # the whole model from the same shapes: the name's 120 B
    whole = (40 * mamba2 + 8 * attn + 40 * (ffn - experts + 4 * experts)
             + 4096 + 2 * 131072 * 4096)
    assert round(whole / 1e9, 2) == 120.67


def test_the_traffic_is_one_schedule_of_balanced_blocks():
    from benchmark.generators import serve_backlog_ssm

    traffic = harness.load_json("traffic", TRAFFIC)
    blocks = serve_backlog_ssm.length_blocks(traffic)
    assert len(blocks) * len(blocks[0]) == traffic["n_lengths"]
    prompts = [p for b in blocks for p, _ in b]
    outs = [o for b in blocks for _, o in b]
    # stratified quantiles: the mid-points of 1024 equal slices
    assert 256 <= min(prompts) <= 258 and 4080 <= max(prompts) <= 4096
    assert 128 <= min(outs) <= 129 and 1020 <= max(outs) <= 1024
    sums = [sum(o for _, o in b) for b in blocks]
    assert max(sums) - min(sums) <= 2
    eng = traffic["engine"]
    assert eng["max_batch"] == 128 and eng["batch_buckets"] == [128]
    assert eng["prefill_chunk"] == 1024 and not eng["prefix_caching"]
    assert eng["max_prompt"] + eng["max_new_tokens"] == 5120
    over = sum(p > eng["prefill_chunk"] for p in prompts) / len(prompts)
    assert 0.45 < over < 0.55          # about half are resumed
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 14


MODEL = {"layer_types": ["mamba2", "ffn", "full"], "d_model": 8,
         "mamba_expand": 2, "mamba_d_state": 4, "mamba2_groups": 2,
         "mamba2_head_dim": 4, "n_heads": 2, "n_kv_heads": 1, "d_head": 4,
         "d_ff": 6, "moe_latent": 4, "moe_shared_d_ff": 10, "moe_top_k": 3,
         "n_experts": 8, "moe_experts_held": 2, "vocab_size": 32}
WORK = {"decode_calls": 2, "decode_rows": 10, "prefill_calls": 3,
        "prefill_tokens": 40, "prefill_positions_seen": 500,
        "decode_positions_seen": 300, "traced_s": 2.0}


def test_flops_nemotron3_against_hand_counts():
    step = flops_nemotron3.mamba2_step(MODEL, WORK)
    # Di 16, N 4: 10 rows x 1 layer
    assert step["flops"] == 5 * 10 * 16 * 4
    assert step["bytes"] == 10 * (8 * 16 * 4 + 2 * (2 * 16 + 2 * 2 * 4))
    scan = flops_nemotron3.mamba2_scan(MODEL, WORK)
    assert scan["flops"] == 5 * 40 * 16 * 4
    assert scan["bytes"] == 40 * (2 * (32 + 16) + 4 * 4) + 3 * 8 * 16 * 4
    counters = {"moe_local_pair_share": 0.5,
                "moe_held_experts_touched_mean": 1.5}
    experts = flops_nemotron3.latent_experts_step(MODEL, WORK, counters)
    pairs = 10 * 3 * 0.5
    assert experts["flops"] == 4 * pairs * 4 * 6
    assert experts["bytes"] == 2 * 1.5 * 4 * 4 * 6 + pairs * 4 * (4 + 6)
    # without counters: the share of the experts held, every one touched
    assert flops_nemotron3.held_share(MODEL) == 0.25
    per_token = flops_nemotron3.matmul_flops_per_token(MODEL, counters)
    mamba2 = 8 * (16 + 32 + 4) + 16 * 8
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8
    ffn = 8 * 8 + 2 * 8 * 4 + 2 * 8 * 10 + 3 * 0.5 * 2 * 4 * 6
    assert per_token == 2 * (mamba2 + attn + ffn)
    did = flops_nemotron3.served_work(MODEL, WORK, counters)
    assert did["head_flops"] == 2 * 13 * 8 * 32
    assert did["attention_flops"] == 4 * 2 * 4 * 800
    assert did["flops"] == sum(v for k, v in did.items() if k != "flops")


def test_the_reducers_read_made_up_rows(monkeypatch):
    from benchmark.reducers import _scopes

    def row(tf_op, self_s, name="%fusion.1 = f32[8]{0} fusion(...)",
            category="fusion"):
        return {"tf_op": tf_op, "self_s": self_s, "name": name,
                "category": category}

    rows = [row("jit(decode)/attn/attn_mamba2/mamba2_step/mul", 0.010),
            row("jit(decode)/attn/attn_mamba2/state_write/scatter", 0.010),
            # the compiler's grouped products keep no scope: a decode
            # step's are told by their rows, max_batch x moe_top_k = 6
            row("ragged-dot-none.1:", 0.3,
                "%ragged-dot-none.1 = bf16[6,6]{1,0} custom-call(...)",
                "custom-call"),
            row("ragged-dot-none:", 0.2,
                "%ragged-dot-none = bf16[6,4]{1,0} custom-call(...)",
                "custom-call"),
            row("ragged-dot-none.2:", 7.0,          # a chunk's: left out
                "%ragged-dot-none.2 = bf16[96,6]{1,0} custom-call(...)",
                "custom-call"),
            row("ragged-dot-metadata:", 9.0,
                "%ragged-dot-metadata = s32[6,2]{1,0} custom-call(...)",
                "custom-call"),
            row("jit(prefill_resume)/attn/attn_mamba2/mamba2_scan/dot",
                0.004)]
    monkeypatch.setattr(_scopes, "load", lambda meas: {"rows": rows})
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    meas = {"traced_work": WORK, "peak": peak, "model": MODEL,
            "counters": {}, "engine": {"max_batch": 2}}
    for name, row_s in (("mamba2_step_roofline.nemo", 0.020),
                        ("mamba2_scan_roofline.nemo", 0.004),
                        ("latent_experts_step_roofline.nemo", 0.5)):
        spec = harness.load_json("metrics", name + ".json")
        got = scope_roofline_nemotron3.reduce(meas, **spec["args"])
        needed = getattr(flops_nemotron3, spec["args"]["cost"])(
            MODEL, WORK, {})
        least = flops.roofline_least_s(needed, peak)["least_s"]
        assert got == pytest.approx(100 * least / row_s), name
    assert mfu_nemotron3.reduce(meas) == pytest.approx(
        100 * flops_nemotron3.served_work(MODEL, WORK, {})["flops"]
        / 2.0 / 1e9)
    # nothing to read: left out, not raised
    assert mfu_nemotron3.reduce({"traced_work": {}, "peak": peak}) is None
    assert scope_roofline_nemotron3.reduce(
        {"traced_work": None, "peak": peak}, "x", "mamba2_step") is None
    assert scope_roofline_nemotron3.reduce(
        {**meas, "model": {}}, "mamba2_step", "mamba2_step") is None
