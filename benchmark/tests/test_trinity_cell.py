"""The served sparse decoder's cell: the serve-backlog-sparse kind end to
end on the CPU at a tiny size (chunked prefill, two kinds of cache, the
check against ``benchmark/reference_trinity.py``), ``flops_trinity.py``
against a hand count, the roofline reducer on made-up rows, and the
metrics the cell reports. Times and rates printed here mean nothing."""
import json
import os

import pytest

from benchmark import flops_trinity, harness
from benchmark.generators import serve_backlog_sparse
from benchmark.reducers import grouped_matmul_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-trinity-ep8-mixed-backlog"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_trinity_cell_runs_on_cpu(trace):
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
                          config=_load("tiny-trinity-config.json"),
                          traffic=_load("tiny-backlog-sparse.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        # window 16 + chunk 16 + block 8, and sequences of over 40
        assert m["kv_window_positions_max.trinity"]["value"] == 40
        assert 1 <= m["moe_held_experts_touched_mean.trinity"]["value"] <= 4
        assert m["moe_expert_load_max_over_mean.trinity"]["value"] >= 1.0
        assert m["decode_step_p50_ms.batch"]["value"] > 0
        # no TPU plane in a CPU trace: the device metrics are left out
        assert "moe_experts_prefill_roofline.trinity" not in m
    json.dumps(result)


def test_the_check_refuses_a_wrongly_computed_model():
    """The tokens of the reference stored in an 8-bit float, with the
    window mask left out and with the gate left out go through the
    cell's own ``verdict`` (as ``tools/trinity_tolerance.py`` puts them
    on the chip) and come out not ``correct``; the reference's own
    tokens, and those of the reference stored as the program stores
    its values, come out ``correct``. The sequence is longer than the
    tiny window's ring, as the cell's long check request is."""
    import jax
    import numpy as np

    from benchmark import reference_trinity as ref
    from benchmark.tools import trinity_tolerance as tool
    from horovod_tpu.models import init_transformer

    config = _load("tiny-trinity-config.json")
    traffic = _load("tiny-backlog-sparse.json")
    cfg = harness.model_config(config)
    params = init_transformer(cfg, jax.random.PRNGKey(3))
    sizes = ref.sizes_of(config)
    rng = np.random.default_rng(3)
    n_out = traffic["check_output_len"]
    # The controls' tokens are their own argmax along the sequence; the
    # sequence itself (what was "served") is any: random here.
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in traffic["check_prompt_lens"]]
    served = [rng.integers(0, cfg.vocab_size, n_out).tolist()
              for _ in prompts]
    traffic = dict(traffic, check_tol=2 ** -5, check_allowed_over=5)
    verdicts = tool.control_verdicts(params, sizes, traffic, prompts, served)
    assert verdicts["stored_as_bf16"]["correct"]
    for name in ("stored_as_fp8", "no_window_mask", "no_gate"):
        assert not verdicts[name]["correct"], (name, verdicts[name])
    assert not tool.as_wanted(verdicts)          # random tokens "served"
    assert tool.as_wanted({**verdicts, "program": verdicts["stored_as_bf16"]})


def test_seeded_weights_scale_the_qk_norm_gains_alone():
    import jax
    import numpy as np

    from horovod_tpu.models import init_transformer

    config = _load("tiny-trinity-config.json")
    assert config["seeded_weights"]["qk_norm_gain"] == 2.0
    cfg = harness.model_config(config)
    key = jax.random.PRNGKey(5)
    plain = init_transformer(cfg, key)
    got = serve_backlog_sparse.seeded_weights(cfg, key, config)
    same = serve_backlog_sparse.seeded_weights(cfg, key, {})
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    for (path, a), (_, b), (_, c) in zip(flat(plain), flat(got), flat(same)):
        gain = 2.0 if path[-1].key in ("q_norm", "k_norm") else 1.0
        np.testing.assert_array_equal(np.asarray(a) * gain, np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    real = harness.load_json("configs", "trinity-large-ep8-5l.json")
    assert real["seeded_weights"]["qk_norm_gain"] > 1.0
    assert "why" in real["seeded_weights"]


def test_the_warm_up_meets_every_shape_the_cell_runs():
    """Nothing compiles once the warm-up and the check are over: the
    rehearsal above is ``correct`` only with no compile in its window.
    Here: the lengths the warm-up sends."""
    sent = []

    class Engine:
        def submit(self, prompt, n_out):
            sent.append((len(prompt), n_out))

        def run_until_idle(self):
            sent.append("idle")

    import numpy as np
    scfg = serve_backlog_sparse.serve_common.serve_config(
        harness.load_json("traffic", "mixed-backlog-decode.json"))
    n = serve_backlog_sparse.warm_up(Engine(), scfg, 100,
                                     np.random.default_rng(0))
    assert n == 5 and sent[1::2] == ["idle"] * 5        # one at a time
    assert sent[0::2] == [(1280, 3), (1536, 3), (1792, 3), (2048, 3),
                          (1024, 3)]


def test_the_traffic_is_one_schedule_of_balanced_blocks():
    traffic = harness.load_json("traffic", "mixed-backlog-decode.json")
    blocks = serve_backlog_sparse.length_blocks(traffic)
    size = traffic["block_requests"]
    assert len(blocks) == 128 // size and all(len(b) == size for b in blocks)
    prompts = sorted(p for b in blocks for p, _ in b)
    outs = sorted(o for b in blocks for _, o in b)
    assert 1024 <= prompts[0] and prompts[-1] <= 8192
    assert abs(prompts[64] - 3072) < 0.05 * 3072
    assert 64 <= outs[0] and outs[-1] <= 384 and abs(outs[64] - 160) <= 9
    assert 0.25 < sum(p > 4096 for p in prompts) / 128 < 0.45
    for key in (0, 1):
        sums = [sum(pair[key] for pair in b) for b in blocks]
        assert max(sums) - min(sums) <= 0.03 * max(sums)
    a = serve_backlog_sparse.request_stream(traffic, 7, 25024)
    b = serve_backlog_sparse.request_stream(traffic, 2 ** 31 + 5, 25024)
    first = [(next(a), next(b)) for _ in range(40)]
    assert all(len(x[0]) == len(y[0]) and x[1] == y[1] for x, y in first)
    assert first[0][0][0][:8] != first[0][1][0][:8]
    # the long check request's keys wrap round a window layer's ring,
    # and the check requests and their fillers fill every slot
    from horovod_tpu.serve.kv_cache import ring_width
    eng = traffic["engine"]
    model = harness.load_json("configs", "trinity-large-ep8-5l.json")["model"]
    ring = ring_width(model["attn_window"], eng["prefill_chunk"],
                      eng["block_size"])
    assert ring == 5136 < max(traffic["check_prompt_lens"]) <= eng["max_prompt"]
    fill = traffic["check_fillers"]
    assert fill["n"] + len(traffic["check_prompt_lens"]) == eng["max_batch"]
    assert fill["prompt_len"] == eng["prefill_chunk"]
    # a filler admitted first is still decoding when the last check
    # request ends: one step a filler, one a chunk of the check
    # prompts, then the check's decode steps
    steps = fill["n"] + -(-sum(traffic["check_prompt_lens"])
                          // eng["prefill_chunk"]) + traffic["check_output_len"]
    assert steps < fill["output_len"] <= eng["max_new_tokens"]
    # every chunk a prompt is cut into fits a bucket the warm-up ran
    assert max(traffic["engine"]["prefill_buckets"]) == \
        traffic["engine"]["prefill_chunk"]


def test_flops_trinity_against_a_hand_count():
    m = harness.load_json("configs", "trinity-large-ep8-5l.json")["model"]
    peak = harness.peak_for("TPU v5 lite")
    # a chunk of 1024 tokens: 4096 pairs over 256 experts, 512 of them
    # on the 32 held experts, 16 an expert
    cost = flops_trinity.grouped_matmul(m, 4096)
    assert cost["flops"] == 2 * 512 * 3072 * 3072
    assert cost["bytes"] == 2 * (32 * 3072 * 3072 + 512 * (3072 + 3072))
    # the held experts' three matrices are 1.81 GB a layer call
    assert 3 * 2 * 32 * 3072 * 3072 == pytest.approx(1.81e9, rel=0.01)
    least = flops_trinity.grouped_matmul_min_s(m, 4096, peak)
    assert least == cost["bytes"] / peak["hbm_bytes_per_s"]   # memory-bound
    assert least == pytest.approx(0.604e9 / 819e9, rel=0.02)


def test_the_roofline_counts_chunk_sized_kernels_only(monkeypatch):
    m = harness.load_json("configs", "trinity-large-ep8-5l.json")["model"]
    peak = harness.peak_for("TPU v5 lite")
    least = flops_trinity.grouped_matmul_min_s(m, 4096, peak)

    def row(pairs, self_s, count, name="ragged-dot-none.1"):
        return {"name": f"%{name} = bf16[{pairs},3072]{{1,0}} custom-call("
                        f"bf16[{pairs},3072]{{1,0}} %a, ...)",
                "tf_op": name, "category": "custom-call", "flops": 0.0,
                "bytes": 0.0, "self_s": self_s, "count": count}

    rows = [row(4096, 4 * 2 * least, 4),            # at half the roofline
            row(128, 1.0, 100),                     # a decode step's
            row(4096, 1.0, 4, "ragged-dot-metadata.3")]
    monkeypatch.setattr(grouped_matmul_roofline._scopes, "load",
                        lambda meas: {"rows": rows})
    spec = harness.load_json("metrics",
                             "moe_experts_prefill_roofline.trinity.json")
    got = grouped_matmul_roofline.reduce({"model": m, "peak": peak},
                                         **spec["args"])
    assert got == pytest.approx(50.0)
    monkeypatch.setattr(grouped_matmul_roofline._scopes, "load",
                        lambda meas: {"rows": rows[1:]})
    assert grouped_matmul_roofline.reduce({"model": m, "peak": peak},
                                          **spec["args"]) is None


def test_the_cell_reports_its_own_readers_and_the_backlog_cells():
    bench = harness.load_benchmark()
    mine = harness.cell_metrics(bench, CELL, "per_layer")
    # the readers written for this cell (a suffix names the first cell
    # of a reader), beside those it joined by its name on their lists
    assert {m["name"] for m in mine} >= {
        "scope_attn_window_pct.trinity", "scope_attn_full_pct.trinity",
        "scope_moe_pct.trinity", "scope_moe_shared_pct.trinity",
        "scope_moe_router_pct.trinity", "scope_unnamed_pct.trinity",
        "moe_experts_prefill_roofline.trinity",
        "moe_held_experts_touched_mean.trinity",
        "kv_window_positions_max.trinity", "peak_hbm_gb.trinity",
        "decode_step_p50_ms.batch", "ttft_p50_ms.batch"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"]


def test_the_configuration_keeps_every_published_width():
    config = harness.load_json("configs", "trinity-large-ep8-5l.json")
    pub, m = config["published"], config["model"]
    for key, value in pub.items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert set(config["reduced"]) - {"n_layers"} == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"])
    assert (m["d_ff"], m["d_ff_dense"], m["attn_window"]) == (
        pub["moe_intermediate_size"], pub["intermediate_size"],
        pub["sliding_window"])
    assert (m["n_experts"], m["moe_top_k"], m["moe_route_scale"]) == (
        pub["num_experts"], pub["num_experts_per_tok"], pub["route_scale"])
    assert m["moe_experts_held"] * 8 == pub["num_experts"]
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    assert m["layer_types"] == [t.split("_")[0] for t in (
        pub["layer_types"][:1] + pub["layer_types"][:4])]
    harness.model_config(config)
