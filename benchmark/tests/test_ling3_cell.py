"""The served hybrid decoder's cell: the serve-backlog-hybrid kind end to
end on the CPU at a tiny size (chunked prefill, a recurrent state a
slot, the latent pool, the check of tokens and states against
``benchmark/reference_ling3.py``), the block dealing, ``flops_ling3.py``
against hand counts, the roofline reducer on made-up rows, and the
metrics the cell reports. Times and rates printed here mean nothing."""
import json
import os
import shutil
import signal
import time

import numpy as np
import pytest

from benchmark import flops_ling3, harness, machine_pauses, trace_reduce
from benchmark.generators import serve_backlog_hybrid
from benchmark.reducers import scope_roofline_ling3
from benchmark.tools import record_ling3_trace

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-ling3-ep4-reasoning-backlog"
CONFIG = "ling-3.0-flash-ep4-7l.json"
TRAFFIC = "reasoning-backlog-longtail.json"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_ling3_cell_runs_on_cpu(trace, monkeypatch, capsys):
    import jax

    from benchmark import run

    if not trace:
        # as if the machine had stood still for 0.2 s a tenth of a
        # second into the window
        monkeypatch.setattr(
            machine_pauses, "inside",
            lambda stood, t_open, t_close, stamps: [(t_open + 0.1, 0.2)])

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
                          config=_load("tiny-ling3-config.json"),
                          traffic=_load("tiny-backlog-hybrid.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    win = next(line for line in said if "machine_pauses" in line)
    by_clock = win["window"]["tokens"] / win["window"]["rate_by_the_clock"]
    assert by_clock == pytest.approx(win["window_s"])
    assert win["machine_pauses"]["probe"] == "ok"
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
        assert win["machine_pauses"]["at_s_for_ms"] == [[0.1, 200.0]]
        assert result["metrics"]["serve_tok_s"]["value"] == pytest.approx(
            win["window"]["tokens"] / (win["window_s"] - 0.2))
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        # the longest prompt (100) and some of its outputs
        assert 100 <= m["kv_latent_positions_max.ling"]["value"] <= 112
        assert m["state_slots_in_use.ling"]["value"] == 4
        assert 1 <= m["moe_held_experts_touched_mean.trinity"]["value"] <= 8
        assert m["moe_expert_load_max_over_mean.trinity"]["value"] >= 1.0
        assert m["decode_step_p50_ms.batch"]["value"] > 0
        # no TPU plane in a CPU trace: the device metrics are left out
        assert not [n for n in m if "roofline" in n or n.startswith("scope")]
    json.dumps(result)


def test_pauses_inside_the_window_by_hand():
    period = machine_pauses.PERIOD_S
    pauses = [(9.0, 9.5),        # before the window
              (9.95, 10.05),     # over its opening: cut to the window
              (20.0, 20.11),     # whole, and the loop stood still
              (30.0, 30.12),     # the loop finished a step in it
              (40.0, 40.1),      # a step ended in the probe's own sleep
              (49.96, 50.2)]     # over its close
    stamps = [20.0 - 0.001, 20.11 + 0.001, 30.06, 40.003, 40.098]
    got = machine_pauses.inside(pauses, 10.0, 50.0, stamps)
    assert [a for a, _ in got] == [10.0, 20.0, 40.0, 49.96]
    assert [s for _, s in got] == pytest.approx(
        [0.05 - period, 0.11 - period, 0.1 - period, 0.04 - period])
    # no probe, or one on another clock: nothing is taken out
    assert machine_pauses.inside(None, 10.0, 50.0, stamps) == []


def test_the_probe_sees_a_standstill_and_ends_with_its_parent():
    """Stopped for 0.2 s (as the machine stops every process at once)
    the probe writes down one pause of about that; told to stop, or
    once its parent is another process, it ends."""
    with machine_pauses.MachinePauses() as probe:
        assert probe.state == "ok"
        child = probe._proc
        time.sleep(0.1)
        t0 = time.perf_counter()
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(0.2)
        os.kill(child.pid, signal.SIGCONT)
        t1 = time.perf_counter()
        time.sleep(0.1)
        pauses = probe.stop()
    assert child.poll() is not None
    (a, b), = [p for p in pauses if p[0] <= t0 and p[1] >= t1]
    assert a >= t0 - 0.05 and b <= t1 + 0.2
    orphan = machine_pauses.MachinePauses(parent=1)
    child = orphan._proc
    assert child.wait(timeout=5) == 0
    assert orphan.stop() == []


def test_the_check_refuses_a_wrongly_computed_model():
    """The tokens and states of the reference stored in an 8-bit float
    and with each mechanism miscomputed go through the cell's own
    limits (as ``tools/ling3_tolerance.py`` puts them on the chip) and
    come out not ``correct``; the reference stored as the program stores
    its values comes out ``correct``."""
    import jax

    from benchmark import reference_ling3 as ref
    from benchmark.tools import ling3_tolerance as tool
    from horovod_tpu.models import init_transformer

    config = _load("tiny-ling3-config.json")
    traffic = _load("tiny-backlog-hybrid.json")
    cfg = harness.model_config(config)
    params = init_transformer(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in traffic["check_prompt_lens"]]
    served = [rng.integers(0, cfg.vocab_size,
                           traffic["check_output_len"]).tolist()
              for _ in prompts]
    traffic = dict(traffic, check_tol=2 ** -5, check_allowed_over=4,
                   check_state_tol=0.03, check_first_state_tol=0.03)
    verdicts = tool.control_verdicts(params, ref.sizes_of(config), traffic,
                                     prompts, served)
    assert set(tool.CONTROLS) == {"stored_as_bf16", "stored_as_fp8",
                                  *ref.WRONG}
    # Random tokens "served": the program's own verdict is not as
    # wanted. Nor is that of a state kept in bf16: over these 77
    # positions it lies as far from the float32 state (0.013) as the
    # state of weights stored in bf16 does (0.019), and moves no token;
    # tests/test_ling3.py holds it against a float32 program.
    assert tool.not_as_wanted(verdicts) == ["program", "state_in_bf16"], {
        k: (v["tokens_over_tol"], v["state_gap_worst"])
        for k, v in verdicts.items()}


def test_every_seed_does_the_same_work():
    """One model and one set of prompts under the names that ``--seed``
    gives the vocabulary: what two seeds serve is the same tokens, each
    under its seed's names."""
    config = _load("tiny-ling3-config.json")
    traffic = _load("tiny-backlog-hybrid.json")
    cfg = harness.model_config(config)
    served = []
    for seed in (7, 2 ** 31 + 5):
        names = serve_backlog_hybrid.vocabulary_names(seed, cfg.vocab_size)
        engine, _, _ = serve_backlog_hybrid.seeded_engine(config, traffic,
                                                          names, cfg)
        stream = serve_backlog_hybrid.request_stream(
            traffic, config["seeded_weights"]["seed"], names)
        prompts = [next(stream)[0] for _ in range(3)]
        out = engine.generate(prompts, 6)
        old = np.argsort(names)
        served.append(([old[p].tolist() for p in prompts],
                       [old[t].tolist() for t in out], prompts))
    assert served[0][:2] == served[1][:2]
    assert served[0][2] != served[1][2]


def test_the_traffic_is_one_schedule_of_balanced_blocks():
    traffic = harness.load_json("traffic", TRAFFIC)
    blocks = serve_backlog_hybrid.length_blocks(traffic)
    assert len(blocks) == 12 and all(len(b) == 32 for b in blocks)
    prompts = sorted(p for b in blocks for p, _ in b)
    outs = sorted(o for b in blocks for _, o in b)
    assert prompts[0] >= 256 and prompts[359] <= 2048
    assert 8192 <= prompts[360] and prompts[-1] <= 16384
    assert abs(prompts[180] - 724) < 30          # sqrt(256 x 2048)
    assert 256 <= outs[0] and outs[-1] <= 1024 and abs(outs[192] - 512) < 8
    assert all(sum(p >= 8192 for p, _ in b) == 2 for b in blocks)
    for key in (0, 1):
        sums = [sum(pair[key] for pair in b) for b in blocks]
        assert max(sums) - min(sums) <= 0.03 * max(sums)
    # every seed sends the same prompts, under the names it gives the
    # vocabulary
    names = [serve_backlog_hybrid.vocabulary_names(seed, 39296)
             for seed in (7, 2 ** 31 + 5)]
    a, b = (serve_backlog_hybrid.request_stream(traffic, 1, n) for n in names)
    first = [(next(a), next(b)) for _ in range(40)]
    assert all(x[1] == y[1] for x, y in first)
    assert first[0][0][0][:8] != first[0][1][0][:8]
    old = [np.argsort(n) for n in names]
    assert all((old[0][x[0]] == old[1][y[0]]).all() for x, y in first)
    eng = traffic["engine"]
    assert eng["max_batch"] == 64 and eng["batch_buckets"] == [64]
    assert eng["block_size"] == 16 and eng["prefix_caching"] is False
    assert (eng["max_prompt"], eng["max_new_tokens"]) == (16384, 1024)
    assert eng["prefill_buckets"] == [256, 512, 768, 1024]
    model = harness.load_json("configs", CONFIG)["model"]
    assert model["max_seq"] == eng["max_prompt"] + eng["max_new_tokens"]
    # the check requests and their fillers fill every slot, and a filler
    # admitted first is still decoding when the last check request ends:
    # one step a filler, one a chunk of the check prompts, then the
    # check's decode steps
    fill = traffic["check_fillers"]
    assert traffic["check_prompt_lens"] == [1300, 9000]
    assert fill["n"] + len(traffic["check_prompt_lens"]) == eng["max_batch"]
    steps = fill["n"] + sum(-(-n // eng["prefill_chunk"]) for n in
                            traffic["check_prompt_lens"]) \
        + traffic["check_output_len"]
    assert steps < fill["output_len"] <= eng["max_new_tokens"]
    assert traffic["window_blocks"] == 4 and traffic["queue_target"] == 4


def test_flops_ling3_against_hand_counts():
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    from benchmark import flops
    # a decode call of 64 rows: 6 layers x 32 heads x 128 x 128 float32
    # a row, read and written
    work = {"decode_calls": 1, "decode_rows": 64, "latent_positions": 80000,
            "prefill_calls": 2, "prefill_tokens": 1500}
    step = flops_ling3.kda_step(m, work)
    assert step["bytes"] == 64 * 6 * 32 * 128 * 128 * 4 * 2 == 1610612736
    assert step["flops"] == 7 * 64 * 6 * 32 * 128 * 128
    assert flops.roofline_least_s(step, peak)["bound"] == "memory"
    scan = flops_ling3.kda_scan(m, work)
    assert scan["flops"] == 6 * 1500 * 6 * 32 * 128 * 128
    assert scan["bytes"] == 6 * 32 * (1500 * 5 * 128 * 2 + 2 * 8 * 128 * 128)
    # 98 304 operations against 1 280 bytes a token and a head: 77 a byte
    assert flops.roofline_least_s(scan, peak)["bound"] == "memory"
    mla = flops_ling3.mla_decode(m, work)
    assert mla["bytes"] == 80000 * 576 * 2          # 1152 B a position
    assert mla["flops"] == 80000 * 32 * (2 * 576 + 2 * 512)
    assert flops.roofline_least_s(mla, peak)["bound"] == "memory"


def test_the_roofline_reads_the_scope_against_the_counted_work(monkeypatch):
    m = harness.load_json("configs", CONFIG)["model"]
    peak = harness.peak_for("TPU v5 lite")
    work = {"decode_calls": 10, "decode_rows": 640, "latent_positions": 8e5,
            "prefill_calls": 0, "prefill_tokens": 0}
    least = flops_ling3.kda_step(m, work)["bytes"] / peak["hbm_bytes_per_s"]

    def row(tf_op, self_s):
        return {"name": "%fusion", "tf_op": tf_op, "category": "",
                "flops": 0.0, "bytes": 0.0, "self_s": self_s, "count": 10}

    rows = [row("jit(decode)/attn/attn_kda/kda_step/mul", 3 * least),
            row("jit(decode)/attn/attn_kda/kda_step/dot_general", least),
            row("jit(decode)/attn/attn_kda/kda_conv/mul", 9.0),
            row("jit(prefill_resume)/attn/attn_mla/mla_attend/dot", 9.0)]
    monkeypatch.setattr(scope_roofline_ling3._scopes, "load",
                        lambda meas: {"rows": rows})
    spec = harness.load_json("metrics", "kda_step_roofline.ling.json")
    meas = {"model": m, "peak": peak, "traced_work": work}
    assert scope_roofline_ling3.reduce(meas, **spec["args"]) == \
        pytest.approx(25.0)
    # the decode program's latent attention alone, and nothing of it here
    spec = harness.load_json("metrics", "mla_decode_roofline.ling.json")
    assert scope_roofline_ling3.reduce(meas, **spec["args"]) is None
    # the parent of this PR: no such count
    assert scope_roofline_ling3.reduce(
        {"model": m, "peak": peak}, match="kda_step", cost="kda_step") is None


@pytest.fixture
def meas(tmp_path, monkeypatch):
    """A traced run's measurements whose newest trace is the recorded
    one: ``record_ling3_trace``'s three requests through a tiny engine
    on the v5e, with the engine's spans and the work they did."""
    trace = os.path.join(HERE, "data", "tiny-ling3.xplane.pb")
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell")
    shutil.copy(trace, tmp_path / "trace" / "cell" / "vm.xplane.pb")
    return {**_load("tiny-ling3-meas.json"),
            "trace": trace_reduce.reduce_xplane(trace),
            "end_to_end": {"serve_tok_s": 100.0},
            "device": {"memory_peak_bytes": 1e9},
            "counters": {"compiles_in_window": 0,
                         "kv_latent_positions_max": 1211,
                         "state_slots_in_use": 3,
                         "moe_held_experts_touched_mean": 3.5,
                         "moe_expert_load_max_over_mean": 1.5},
            "samples": {"ttft_s": [0.1, 0.2]},
            "peak": harness.peak_for("TPU v5 lite"),
            "model": record_ling3_trace.MODEL}


def test_every_ling_metric_reads_the_recorded_trace(meas):
    bench = harness.load_benchmark()
    values = {}
    for m in harness.cell_metrics(bench, CELL, "per_layer"):
        spec = harness.load_json("metrics", m["name"] + ".json")
        values[m["name"]] = harness.reducer(spec["reducer"]).reduce(
            meas, **spec.get("args", {}))
    assert all(v is not None for v in values.values()), values
    shares = [n for n in values if n.startswith("scope_")
              or "roofline" in n]
    assert all(0 < values[n] < 100 for n in shares), values
    # the recurrence is inside the kda layers' scope, which is not all
    # of the device's time
    assert (values["scope_kda_recurrence_pct.ling"]
            < values["scope_attn_kda_pct.ling"] < 90)
    work = meas["traced_work"]
    assert work["decode_calls"] >= 11 and work["prefill_calls"] >= 5
    assert work["prefill_tokens"] == sum(record_ling3_trace.PROMPTS)


def test_the_cell_reports_its_own_readers_and_the_backlog_cells():
    bench = harness.load_benchmark()
    mine = harness.cell_metrics(bench, CELL, "per_layer")
    # the readers written for this cell (a suffix names the first cell
    # of a reader), beside those it joined by its name on their lists
    assert {m["name"] for m in mine} >= {
        "scope_attn_kda_pct.ling", "scope_kda_recurrence_pct.ling",
        "scope_attn_mla_pct.ling", "kda_step_roofline.ling",
        "kda_scan_roofline.ling", "mla_decode_roofline.ling",
        "kv_latent_positions_max.ling", "state_slots_in_use.ling",
        "scope_moe_pct.trinity", "moe_experts_prefill_roofline.trinity",
        "decode_step_p50_ms.batch", "ttft_p50_ms.batch"}
    assert len(bench["per_layer"]) <= 128
    for m in mine:
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == TRAFFIC[:-5]
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"]


def test_the_configuration_keeps_every_published_width():
    config = harness.load_json("configs", CONFIG)
    pub, m = config["published"], config["model"]
    for key, value in pub.items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert set(config["reduced"]) - {"n_layers"} == {
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size"}
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["file"].endswith(CONFIG))
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"])
    assert (m["d_ff"], m["d_ff_dense"]) == (
        pub["moe_intermediate_size"], pub["intermediate_size"])
    assert (m["mla_kv_rank"], m["mla_rope_dim"], m["kda_conv"],
            m["kda_decay_floor"]) == (
        pub["kv_lora_rank"], pub["qk_rope_head_dim"],
        pub["short_conv_kernel_size"], pub["kda_lower_bound"])
    assert m["d_head"] == pub["qk_nope_head_dim"] == pub["v_head_dim"]
    assert (m["n_experts"], m["moe_top_k"], m["moe_route_scale"],
            m["moe_n_group"], m["moe_topk_group"]) == (
        pub["num_experts"], pub["num_experts_per_tok"],
        pub["routed_scaling_factor"], pub["n_group"], pub["topk_group"])
    assert m["moe_experts_held"] * 4 == pub["num_experts"]
    assert m["vocab_size"] * 4 == pub["vocab_size"]
    assert (m["rope_theta"], m["norm_eps"]) == (pub["rope_theta"],
                                                pub["rms_norm_eps"])
    # layer i of the source is mla iff (i + 1) % layer_group_size == 0:
    # the layers run stand for 0 and 6..11
    kinds = ["mla" if (i + 1) % pub["layer_group_size"] == 0 else "kda"
             for i in (0, 6, 7, 8, 9, 10, 11)]
    assert m["layer_types"] == kinds
    harness.model_config(config)
