"""Motif-3's cell: the serve-backlog-gdla kind end to end on the CPU at
a tiny size (chunked and padded prefill, rings of latents that wrap
beside latent pages, grouped differential heads, the mHC stream, a share
of PolyNorm experts, the check of served tokens against
``benchmark/reference_motif3.py``'s logits), the configuration's file
against the catalog and its parameter count from the program's own
shapes, ``flops_motif3.py`` against hand counts, and its reducer. Times and rates printed here mean nothing."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_motif3, harness
from benchmark.reducers import mfu_motif3

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-motif3-ep8-mixed-longtail-backlog"
CONFIG = "motif-3-beta-ep8-5l.json"
TRAFFIC = "mixed-longtail-backlog.json"
# the one entry the contract's 128 leave room for (PERF.md section 7
# has the eight that wait for a `benchmark` PR to make room)
NEW = ("mfu_pct.motif",)


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_motif3_cell_runs_on_cpu(trace, capsys):
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
                          config=_load("tiny-motif3-config.json"),
                          traffic=_load("tiny-backlog-gdla.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    check = next(line for line in said if line.get("phase") == "check")
    assert check["check"]["tokens"] == 12
    assert check["check"]["fillers_decoding_alongside"] == 6
    assert check["check"]["worst_logit_gap"] < 1e-3
    routing = next(line for line in said if "routing" in line)["routing"]
    assert routing["decode_batch"]["moe_dispatch_dropped_token_frac"] == 0
    win = next(line for line in said if "machine_pauses" in line)
    assert win["window"]["blocks"] == 2
    # a ring of 8 + 32 + 8 places, filled by the long prompts
    assert win["cache"]["latent_ring_positions_max"] == 48
    assert win["cache"]["latent_positions_max"] > 64
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        assert m["kv_latent_positions_max.ling"]["value"] > 64
        assert 0 < m["moe_held_experts_touched_mean.trinity"]["value"] <= 4
        work = win["traced_work"]
        assert work["decode_ring_places"] <= 8 * work["decode_rows"]
        assert work["decode_latent_positions"] >= work["decode_ring_places"]
        assert work["prefill_seen_full"] >= work["prefill_seen_window"] > 0
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
                   if r["name"] == "Motif-3-Beta")
    assert pub == row["config"] and config["source"] == row["source_url"]
    differs = {k for k, v in pub.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "n_dense_first_layers", "num_experts",
        "vocab_size"}
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["file"].endswith(CONFIG))
    assert set(entry["reduced"]) == differs
    assert entry["source"] == config["source"]
    # no width differs from the source
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["d_head"] + m["mla_rope_dim"], m["d_head"], m["mla_rope_dim"],
            m["mla_kv_rank"], m["mla_q_rank"], m["d_ff_dense"], m["d_ff"],
            m["norm_eps"], m["mla_noise_heads"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"], pub["v_head_dim"],
        pub["qk_rope_head_dim"], pub["kv_lora_rank"], pub["q_lora_rank"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["rms_norm_eps"], pub["num_noise_heads"])
    assert (m["n_experts"], m["moe_top_k"], m["moe_route_scale"],
            m["moe_norm_topk_prob"], m["moe_scoring"],
            m["moe_shared_expert"]) == (
        pub["num_experts"], pub["experts_top_k"], pub["route_scale"],
        pub["route_norm"], pub["score_func"], pub["num_shared_experts"] == 1)
    assert (m["attn_window"], m["mhc_streams"], m["mhc_sinkhorn_iters"],
            m["polynorm_scale"], m["polynorm_bias_clamp"],
            m["layer_rotary"]["mla"]["theta"]) == (
        pub["sliding_window"], pub["mhc_expansion_rate"],
        pub["mhc_sinkhorn_iters"], pub["polynorm_output_scale"],
        pub["polynorm_bias_clamp"], pub["rope_theta"])
    assert m["mla_elementwise_gate"] == pub["elementwise_attn_output_gate"]
    assert m["mla_head_gate"] == pub["headwise_attn_output_gate"]
    # the source's layers 1-5: layer i is full where (i + 1) % period == 0
    assert m["layer_types"] == [
        "mla" if (i + 1) % pub["sliding_window_period"] == 0
        else "mla_sliding" for i in range(1, 6)]
    assert m["n_dense_layers"] == config["n_dense_first_layers"] == 1
    assert m["moe_experts_held"] == config["num_experts"] == 48
    assert m["moe_expert_offset"] == 48
    assert m["vocab_size"] == config["vocab_size"] == pub["vocab_size"] // 8
    # the parameters, counted from the program's own shapes
    import jax

    from horovod_tpu.models import init_transformer

    cfg = harness.model_config(config)
    shapes = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    count = lambda tree: sum(int(np.prod(a.shape))  # noqa: E731
                             for a in jax.tree.leaves(tree))
    dense, sparse = shapes["dense_layers"][0], shapes["layers"][0]
    experts = count({k: sparse["moe"][k]
                     for k in ("w_gate", "w_up", "w_down")})
    assert experts == 48 * 3 * 4096 * 1280
    attn = count({k: v for k, v in sparse.items()
                  if k not in ("moe", "mlp_norm", "mhc_mlp", "mhc_attn")})
    # W_dq, W_uq, W_dkv, W_ukv, W_o, the gate, lambda and three norms
    assert attn == (4096 * 1024 + 1024 * 80 * 192 + 4096 * 576
                    + 512 * 16 * 256 + 8192 * 4096 + 4096 * 8192 + 4096 * 64
                    + 4096 + 1024 + 512) == 91_756_032
    assert count(sparse["mhc_attn"]) == 16384 * 24 + 24 + 3
    assert 864e6 < count(sparse) < 866e6 and 243e6 < count(dense) < 244e6
    total = count(shapes)
    assert total == count(dense) + 4 * count(sparse) + 4096 + (
        2 * 27520 * 4096)
    assert total == 3_928_283_170
    # the whole model from the same shapes: the name's 314 B
    whole = (2 * count(dense) + 51 * (count(sparse) + 7 * (
        experts + 48 * 4)) + 4096 + 2 * 220160 * 4096)
    assert 313e9 < whole < 317e9


def test_the_traffic_is_one_schedule_of_balanced_blocks():
    from benchmark.generators import serve_backlog_hybrid

    traffic = harness.load_json("traffic", TRAFFIC)
    blocks = serve_backlog_hybrid.length_blocks(traffic)
    assert len(blocks) * len(blocks[0]) == traffic["n_lengths"]
    assert len(blocks[0]) == 16
    prompts = [p for b in blocks for p, _ in b]
    outs = [o for b in blocks for _, o in b]
    long = [p for p in prompts if p >= 16384]
    assert len(long) * 8 == len(prompts) and max(long) <= 32768
    assert all(1024 <= p <= 8192 for p in prompts if p < 16384)
    assert 512 <= min(outs) and max(outs) <= 2048
    assert all(sum(p >= 16384 for p, _ in b) == 2 for b in blocks)
    sums = [sum(o for _, o in b) for b in blocks]
    assert max(sums) - min(sums) <= 2
    eng = traffic["engine"]
    assert eng["max_batch"] == 64 and eng["batch_buckets"] == [64]
    assert eng["prefill_chunk"] == 1024 and not eng["prefix_caching"]
    assert eng["block_size"] == 16
    assert eng["max_prompt"] + eng["max_new_tokens"] == 34816
    assert max(traffic["check_prompt_lens"]) >= 16384
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 15
    listed = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [CELL]}
    assert listed == set(NEW)


MODEL = {"layer_types": ["mla_sliding", "mla", "mla_sliding"],
         "n_layers": 3, "n_dense_layers": 1, "d_model": 8, "n_heads": 6,
         "n_kv_heads": 2, "d_head": 4, "mla_kv_rank": 100, "mla_rope_dim": 20,
         "mla_q_rank": 5, "mla_noise_heads": 2, "mhc_streams": 2,
         "d_ff": 6, "d_ff_dense": 10, "moe_top_k": 3, "n_experts": 8,
         "moe_experts_held": 2, "vocab_size": 32, "attn_window": 8}
WORK = {"decode_calls": 2, "decode_rows": 10, "prefill_calls": 3,
        "prefill_tokens": 40, "decode_ring_places": 70,
        "decode_latent_positions": 300, "prefill_seen_window": 200,
        "prefill_seen_full": 500, "traced_s": 2.0}


def test_flops_motif3_against_hand_counts():
    counters = {"moe_local_pair_share": 0.5}
    # without counters: the share of the experts held
    assert flops_motif3.held_share(MODEL) == 0.25
    per_token = flops_motif3.matmul_flops_per_token(MODEL, counters)
    attn = (8 * 5 + 5 * 6 * 24 + 8 * 120 + 100 * 2 * 8 + 8 * 4
            + 2 * 8 * 4 * 4)
    mhc = 2 * (16 * 8 + 16 + 32 + 16)
    sparse = 8 * 8 + 3 * 8 * 6 + 3 * 0.5 * 3 * 8 * 6
    assert per_token == 2 * (3 * (attn + mhc) + 3 * 8 * 10 + 2 * sparse)
    did = flops_motif3.served_work(MODEL, WORK, counters)
    assert did["head_flops"] == 2 * 13 * 8 * 32
    assert did["attention_flops"] == (2 * 270 + 800) * 6 * (2 * 24 + 2 * 4)
    assert did["flops"] == sum(v for k, v in did.items() if k != "flops")


def test_the_reducer_reads_what_the_calls_did():
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    meas = {"traced_work": WORK, "peak": peak, "model": MODEL,
            "counters": {}}
    assert mfu_motif3.reduce(meas) == pytest.approx(
        100 * flops_motif3.served_work(MODEL, WORK, {})["flops"] / 2.0 / 1e9)
    # nothing to read: left out, not raised
    assert mfu_motif3.reduce({"traced_work": {}, "peak": peak}) is None
    assert mfu_motif3.reduce({**meas, "model": {}}) is None
    assert mfu_motif3.reduce({**meas, "peak": None}) is None
