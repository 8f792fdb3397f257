"""The served all-latent decoder's cell: the serve-backlog-shared kind
end to end on the CPU at a tiny size (documents asked four times each,
the prefix cache over latent pages, the check of a cold and a mapped ask
against ``benchmark/reference_kimi_k2.py``), the list's dealing,
``flops_kimi.py`` against hand counts, and the metrics the cell
reports. Times and rates printed here mean nothing."""
import json
import os

import pytest

from benchmark import flops_kimi, harness
from benchmark.generators import serve_backlog_shared as kind
from benchmark.reducers import mfu_kimi, scope_roofline_kimi

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-kimi-k2-ep32-repo-questions-backlog"


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_kimi_cell_runs_on_cpu(trace, capsys):
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
                          bench=bench, config=_load("tiny-kimi-config.json"),
                          traffic=_load("tiny-backlog-shared.json"))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    check = next(line["check"] for line in said if "check" in line)
    assert check["positions_mapped_by_ask"] == [0, 40]
    assert check["fillers_decoding_alongside"] == 2
    win = next(line for line in said if "machine_pauses" in line)
    assert abs(win["prefix"]["hit_share_in_window"]
               - win["prefix"]["expected"]) < 0.2
    if not trace:
        assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    else:
        m = result["metrics"]
        assert m["compiles_in_window.batch"]["value"] == 0
        assert 45 < m["prefix_hit_token_share_pct.kimi"]["value"] < 85
        assert 60 <= m["kv_latent_positions_max.ling"]["value"] <= 122
        assert 0 <= m["moe_held_experts_touched_mean.trinity"]["value"] <= 12
        assert m["decode_step_p50_ms.batch"]["value"] > 0
        # no TPU plane in a CPU trace: the device metrics are left out,
        # and without the chip's peak the step's share of it too
        assert not [n for n in m if "roofline" in n or n.startswith("scope")
                    or n.startswith("mfu")]
    json.dumps(result)


def test_the_list_is_blocks_of_documents_asked_four_times():
    traffic = harness.load_json("traffic", "repo-questions-backlog.json")
    blocks = kind.ask_blocks(traffic)
    assert len(blocks) == 24 and {len(b) for b in blocks} == {16}
    for b in blocks:
        docs = kind.documents_of(b)
        assert len(docs) == 4 and all(d % 16 == 0 and 4096 <= d <= 16384
                                      for d in docs.values())
        for place, (i, d, q, o) in enumerate(b):
            assert i == place % 4 and d == docs[i]      # 4 requests apart
            assert 64 <= q <= 512 and 64 <= o <= 256
            assert d + q <= traffic["engine"]["max_prompt"]
    prompts = [sum(d + q for _, d, q, _ in b) for b in blocks]
    outputs = [sum(o for *_, o in b) for b in blocks]
    assert max(prompts) - min(prompts) < 0.025 * min(prompts)
    assert max(outputs) - min(outputs) <= 2
    assert sum(len(kind.documents_of(b)) for b in blocks) == 96
    assert kind.expected_hit_share(blocks) == pytest.approx(0.732, abs=2e-3)
    assert blocks == kind.ask_blocks(traffic)            # one order


def test_a_document_s_asks_share_its_tokens_and_a_cycle_draws_new_ones():
    import numpy as np

    traffic = _load("tiny-backlog-shared.json")
    names = np.random.default_rng(3).permutation(128)
    stream = kind.request_stream(traffic, 7, names)
    reqs = [next(stream) for _ in range(2 * 32)]
    blocks = kind.ask_blocks(traffic)
    for b, block in enumerate(blocks):
        for place, (i, d, q, o) in enumerate(block):
            prompt, n_out = reqs[8 * b + place]
            assert len(prompt) == d + q and n_out == o
            assert prompt[:d] == reqs[8 * b + i][0][:d]   # tiny: 2 apart
    first, again = reqs[0][0], reqs[32][0]
    assert len(first) == len(again) and first[:16] != again[:16]


MODEL = {"d_model": 10, "n_heads": 2, "d_head": 4, "mla_kv_rank": 6,
         "mla_rope_dim": 2, "mla_q_rank": 5, "layer_types": ["mla"] * 3,
         "n_layers": 3, "n_dense_layers": 1, "d_ff_dense": 20, "d_ff": 8,
         "n_experts": 16, "moe_top_k": 4, "moe_experts_held": 4,
         "vocab_size": 50}
WORK = {"prefill_calls": 2, "prefill_tokens": 7, "decode_rows": 5,
        "latent_positions": 40, "prefill_positions_seen": 30,
        "prefill_latents_read": 12, "traced_s": 2.0}


def test_flops_kimi_by_hand():
    attn = flops_kimi.mla_prefill(MODEL, WORK)
    assert attn == {"flops": 3 * 2 * 30 * (2 * 6 + 2 * 4),
                    "bytes": 3 * 12 * 8 * 2}
    assert flops_kimi.mla_decode_flops(MODEL, WORK) == 40 * 3 * 2 * (16 + 12)
    # a layer's projections: 10x5 + 5x2x6 + 10x8 + 6x2x8 + 8x10 = 366
    per_token = 2 * (3 * 366 + 3 * 10 * 20
                     + 2 * (10 * 16 + 3 * 10 * 8 * (1 + 4 * 0.25)))
    assert flops_kimi.matmul_flops_per_token(MODEL, 1.0) == per_token
    did = flops_kimi.served_work(MODEL, WORK)
    assert did["matmul_flops"] == 12 * per_token
    assert did["head_flops"] == 2 * 7 * 10 * 50
    assert did["flops"] == (12 * per_token + 7000 + attn["flops"]
                            + 40 * 3 * 2 * 28)
    # half the even share on the held experts: half a routed expert
    less = flops_kimi.served_work(MODEL, WORK, 0.125)
    assert did["matmul_flops"] - less["matmul_flops"] == \
        12 * 2 * 2 * 3 * 10 * 8 * 0.5


def test_the_reducers_read_nothing_where_there_is_nothing():
    peak = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}
    meas = {"model": MODEL, "traced_work": WORK, "peak": peak,
            "counters": {}, "trace": None}
    did = flops_kimi.served_work(MODEL, WORK)
    assert mfu_kimi.reduce(meas) == pytest.approx(
        100 * did["flops"] / 2.0 / 1e6)
    # the parent's program: no traced work, no spans' ``mapped``
    assert mfu_kimi.reduce({**meas, "traced_work": {}}) is None
    assert mfu_kimi.reduce({**meas, "peak": None}) is None
    ling = {k: v for k, v in WORK.items() if not k.startswith("prefill_p")
            and k != "prefill_latents_read"}
    assert mfu_kimi.reduce({**meas, "traced_work": ling}) is None
    assert scope_roofline_kimi.reduce(
        {**meas, "traced_work": {}}, "mla_attend", "mla_prefill") is None


def test_a_pause_is_charged_what_its_call_ran_over():
    """Chunks whose time is a line in their offset, decode calls of
    40 ms; pauses of 110 ms that met a chunk that ran 60 ms over, a
    decode call that ran on time, a decode call 3 s over (two pauses
    in it), and the gap between two calls."""
    spans, t = [], 0.0

    def call(name, dur, **args):
        nonlocal t
        spans.append({"name": name, "t0": t, "dur": dur, "args": args})
        t += dur + 0.001                       # the host's own work
    for k in range(40):
        over = 0.060 if k == 17 else 0.0
        call("serve:prefill", 0.050 + 1e-5 * 1024 * k + over,
             n_tokens=1000, offset=1024 * k)
        call("serve:decode", 3.040 if k == 30 else 0.040, n_active=32)
        call("serve:prefill", 0.020, n_tokens=200, offset=4096)
    chunk = next(s for s in spans if s["args"].get("offset") == 1024 * 17)
    slow = next(s for s in spans if s["dur"] > 3)
    on_time = spans[1]
    still = [(chunk["t0"] + 0.01, 0.110), (on_time["t0"] + 0.005, 0.110),
             (slow["t0"] + 0.002, 2.900), (slow["t0"] + 2.91, 0.180),
             (spans[7]["t0"] - 0.0005, 0.110)]
    got = kind.pause_costs(still, spans, (256, 512, 768, 1024))
    costs = {round(a, 4): cost for a, _, cost in got}
    assert costs[round(chunk["t0"] + 0.01, 4)] == pytest.approx(0.060,
                                                                abs=2e-3)
    assert costs[round(on_time["t0"] + 0.005, 4)] == pytest.approx(0, abs=1e-6)
    both = [c for a, s, c in got if s in (2.900, 0.180)]
    assert sum(both) == pytest.approx(3.000, abs=1e-3)      # the call's excess
    assert both[0] / both[1] == pytest.approx(2.9 / 0.18)
    assert costs[round(spans[7]["t0"] - 0.0005, 4)] == 0.110  # between calls
    assert kind.pause_costs([], spans, (256, 512, 768, 1024)) == []
