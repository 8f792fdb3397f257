"""The yardstick's own arithmetic: lengths, the retirement-cut window,
the FLOPs functions, and the files' agreement with BENCHMARK.json."""
import json
import os

import pytest

from benchmark import flops, harness, lengths
from benchmark.generators import serve_backlog, serve_open

HERE = os.path.dirname(os.path.abspath(__file__))


def _traffic(name):
    return harness.load_json("traffic", name + ".json")


@pytest.mark.parametrize("name", ["batch-prefill", "chat-steady"])
def test_lengths_are_one_schedule_whatever_the_seed(name):
    traffic = _traffic(name)
    pairs = lengths.length_pairs(traffic)
    n = traffic["n_lengths"]

    def first_pass(seed):
        stream = lengths.request_stream(traffic, seed, vocab=32768)
        reqs = [next(stream) for _ in range(n + 3)]
        return [(len(p), o) for p, o in reqs], reqs[0][0][:8]

    (a, ids_a), (b, ids_b) = first_pass(7), first_pass(2 ** 31 + 12345)
    assert a == b == pairs + pairs[:3], "one schedule, over and over"
    assert ids_a != ids_b, "the seed makes the token ids"
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    assert all(lo <= p <= hi for p, _ in pairs)
    outs = sorted(o for _, o in pairs)
    assert outs[0] >= traffic["output_len"]["min"]
    assert outs[-1] <= traffic["output_len"]["max"]
    assert len(set(outs)) >= 12, "output lengths must differ within a cell"
    median = traffic["output_len"]["median"]
    assert abs(outs[n // 2] - median) <= 0.05 * median + 1
    # every prompt fits a bucket that the warm-up ran
    assert max(p for p, _ in pairs) <= max(traffic["engine"]["prefill_buckets"])


def test_arrival_gaps_are_one_multiset_with_the_rate_as_mean():
    traffic = _traffic("chat-steady")
    n = traffic["n_lengths"]
    gaps = lengths.arrival_gaps(traffic)
    a = [next(gaps) for _ in range(n)]
    assert [next(gaps) for _ in range(n)] == a, "the list over and over"
    assert sorted(a) == sorted(lengths.stratified(
        {"dist": "exponential", "mean": 1.0 / traffic["rate_per_s"]}, n))
    assert a != sorted(a), "short and long gaps follow each other"
    assert sum(a) / n == pytest.approx(1.0 / traffic["rate_per_s"], rel=0.02)


def _lockstep_stream(phase, slots=16, out_len=32, prefill_s=0.145,
                     decode_s=0.108, waves=12):
    """Sixteen slots that fill, decode and retire as one wave: what
    PR 22's traffic did. ``phase`` shifts the clock's zero."""
    t, tok, fin = phase, 0, 0
    stamps, tokens, finished = [], [], []
    for _ in range(waves):
        # one step: retire the last wave, prefill all slots (each emits
        # its first token), one decode
        t += slots * prefill_s + decode_s
        tok += 2 * slots
        stamps.append(t), tokens.append(tok), finished.append(fin)
        for _ in range(out_len - 2):
            t += decode_s
            tok += slots
            stamps.append(t), tokens.append(tok), finished.append(fin)
        fin += slots  # seen by the next step, first thing
    return stamps, tokens, finished


def _retirement_cuts(finished):
    """Steps after which a request was complete: the next step retired
    it first thing."""
    return [i - 1 for i in range(1, len(finished))
            if finished[i] > finished[i - 1]]


def test_window_cut_at_retirements_is_blind_to_the_clocks_phase():
    rates, by_clock = [], []
    for k in range(40):
        phase = 0.13 * k
        stamps, tokens, finished = _lockstep_stream(phase)
        # a lockstep wave is one block: every retirement ends one
        cuts = _retirement_cuts(finished)
        win = serve_backlog.window_rate(stamps, tokens, cuts, seconds=40.0)
        rates.append(win["rate"])
        t_warm = stamps[cuts[0]] + 0.37 * k % 5.0
        # what a wall-clock window of the same length would have counted
        inside = [n for s, n in zip(stamps, tokens)
                  if t_warm < s <= t_warm + 40.0]
        by_clock.append((inside[-1] - inside[0]) / 40.0)
    assert max(rates) - min(rates) < 1e-9 * max(rates)
    assert max(by_clock) - min(by_clock) > 0.01 * max(by_clock)
    wave_s = 16 * 0.145 + 31 * 0.108
    assert rates[0] == pytest.approx(16 * 32 / wave_s)


def test_window_needs_two_cuts():
    stamps, tokens = [1.0, 2.0, 3.0], [1, 2, 3]
    assert serve_backlog.window_rate(stamps, tokens, [1], 10.0) is None
    assert serve_backlog.window_rate(stamps, tokens, [], 10.0) is None
    win = serve_backlog.window_rate(stamps, tokens, [0, 1, 2], 1.5)
    assert (win["i_open"], win["i_close"], win["rate"]) == (0, 1, 1.0)


def test_token_gaps_keep_only_gaps_that_end_in_the_window():
    times = {1: [0.5, 1.0, 1.6, 2.5], 2: [1.9, 2.0]}
    gaps = serve_open.token_gaps(times, t_open=0.9, t_close=2.0)
    assert sorted(round(g, 6) for g in gaps) == [0.1, 0.5, 0.6]


def _model(name):
    return harness.load_json("configs", name + ".json")["model"]


def test_flops_against_a_hand_count():
    m = _model("internlm2-1.8b-12l")
    # per layer: wq 2048*2048, wk+wv 2*2048*1024, wo 2048*2048, SwiGLU
    # 3*2048*8192 = 62,914,560; head 2048*92544 = 189,530,112
    assert flops.matmul_params(m) == 12 * 62_914_560 + 189_530_112
    per_token = flops.train_flops_per_token(m, 4096)
    assert per_token == 6 * 944_504_832 + 6 * 12 * 4096 * 2048
    assert per_token == pytest.approx(6.27e9, rel=0.005)
    full = _model("internlm2-1.8b")
    assert flops.train_flops_per_token(full, 4096) == pytest.approx(
        11.4e9, rel=0.01)
    assert flops.total_params(full) == pytest.approx(1.889e9, rel=0.002)
    mi = _model("mistral-7b-v0.3-16l")
    # per layer: 4096*4096*2 + 2*4096*1024 + 3*4096*14336 = 218,103,808
    assert flops.matmul_params(mi) == 16 * 218_103_808 + 4096 * 32768
    assert flops.total_params(mi) == pytest.approx(3.76e9, rel=0.002)
    ff = flops.flash_fwd(m, 4096, rows=2)
    assert ff["flops"] == 2 * 16 * 4 * 128 * 4096 * 4097 / 2
    assert ff["bytes"] == 2 * 4096 * (2 * (2 * 2048 + 2 * 1024) + 4 * 16)


def test_every_metric_has_its_file_and_agrees_with_benchmark_json():
    """One entry a reader: a metric file is the reader and nothing else,
    and ``BENCHMARK.json`` alone lists the cells that report it, so a
    new cell joins a reader by its name on that list."""
    bench = harness.load_benchmark()
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    files = {f[:-len(".json")]
             for f in os.listdir(os.path.join(harness.HERE, "metrics"))}
    assert files == {m["name"] for m in bench["per_layer"]}
    readers, modules = {}, set()
    for m in bench["per_layer"]:
        spec = harness.load_json("metrics", m["name"] + ".json")
        assert set(spec) <= {"name", "layer", "unit", "better", "source",
                             "moves", "reducer", "args"}, m["name"]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e
        harness.reducer(spec["reducer"])
        modules.add(spec["reducer"])
        reader = json.dumps([spec["reducer"], spec.get("args", {}),
                             spec["moves"]], sort_keys=True)
        assert readers.setdefault(reader, m["name"]) == m["name"], \
            "one reader, two entries: list the cells under the first"
        moved = next(x for x in bench["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]
    # a reducer that no metric file names goes with its last metric
    # (modules with a leading underscore are what reducers share)
    assert modules == {
        f[:-len(".py")]
        for f in os.listdir(os.path.join(harness.HERE, "reducers"))
        if f.endswith(".py") and not f.startswith("_")
        and not f.endswith("_pb2.py")}
    for w in bench["workloads"]:
        cell, config, traffic = harness.find_cell(w["name"], bench)
        harness.generator(traffic["kind"])
        assert set(config["reduced"]) == set(next(
            c["reduced"] for c in bench["configs"]
            if c["name"] == w["config"]))
        assert harness.cell_metrics(bench, w["name"], "per_layer")
        assert len(harness.cell_metrics(bench, w["name"], "end_to_end")) >= 2
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert harness.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.peak_for("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["batch-prefill", "chat-steady"])
def test_every_block_of_the_list_holds_the_lists_mix(name):
    traffic = _traffic(name)
    blocks = lengths.length_blocks(traffic)
    outs = [sum(o for _, o in b) for b in blocks]
    prompts = [sum(p for p, _ in b) for b in blocks]
    assert max(outs) - min(outs) <= 0.03 * max(outs)
    assert max(prompts) - min(prompts) <= 0.03 * max(prompts)
    gaps = lengths.balanced_deal(
        lengths.stratified({"dist": "exponential", "mean": 1.0}, 128), 8)
    sums = [sum(b) for b in gaps]
    assert max(sums) - min(sums) <= 0.06 * max(sums)
