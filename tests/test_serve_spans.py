"""The serving engine's host phases as spans (PR 24): what
``ServeMetrics.phase`` writes, where the spans begin and end against a
fake engine clock that ticks at every read, and what a request's result
carries of them. Same tiny geometry as tests/test_serve.py, so the jit
cache holds one set of programs. Since PR 36 a device call is one
numbered record from launch to readback, and one many times longer than
its kind's median leaves a ``serve:stall`` with its cause. Since PR 37
a decode call is launched in one step and read in the next, after its
successor was launched: ``serve:decode`` is one decode step as a client
sees it, and ``serve:host_gap`` only time in which no call was in
flight."""
import gc
import json
import logging

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import engine as engine_mod
from horovod_tpu.serve import metrics as metrics_mod

TICK = 1e-3
AFTER_THE_FACT = {"serve:host_gap", "serve:queue", "serve:request",
                  "serve:stall", "serve:unfed", "serve:no_work"}
DEVICE = ("serve:prefill", "serve:decode")


class TickClock:
    """Every read is one tick later than the last, so no two points of
    the engine's timeline coincide and every span has a length."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += TICK
        return self.t


@pytest.fixture(scope="module")
def served_model():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    return cfg, init_transformer(cfg, jax.random.PRNGKey(0))


def _engine(served_model, clock=None, **kw):
    cfg, params = served_model
    knobs = dict(max_batch=4, block_size=8, max_prompt=16, max_new_tokens=8)
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs),
                       clock=clock or TickClock())


def _spans(eng, tmp_path):
    """The exported chrome spans as ``{name, t0, end, args}`` on the
    engine's clock, in the order written."""
    path = tmp_path / "spans.json"
    eng.metrics.export_chrome_trace(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    t_ref = eng.metrics.started_at
    return [{"name": e["name"], "t0": t_ref + e["ts"] * 1e-6,
             "end": t_ref + (e["ts"] + e["dur"]) * 1e-6, "args": e["args"]}
            for e in events if e["ph"] == "X"], events


@pytest.fixture
def served(served_model, tmp_path):
    """Two requests served to the end, one tagged with a trace id."""
    eng = _engine(served_model)
    a = eng.submit([5, 6, 7, 8, 9], 6, trace_id=41)
    b = eng.submit([1, 2, 3], 4)
    eng.run_until_idle()
    spans, events = _spans(eng, tmp_path)
    return eng, eng.result(a), eng.result(b), spans, events


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_host_gap_and_decode_tile_the_time_between_two_syncs(served):
    _, _, _, spans, _ = served
    decodes = _named(spans, "serve:decode")
    assert len(decodes) >= 4
    gaps = {round(g["end"], 6): g for g in _named(spans, "serve:host_gap")}
    # the first decode call follows the prefills with nothing in flight:
    # the gap before it is the host's, inside one step
    first = decodes[0]
    assert first["args"]["ahead"] is False
    gap = gaps[round(first["t0"], 6)]
    assert gap["t0"] == pytest.approx(
        _named(spans, "serve:prefill")[-1]["end"], abs=1e-6)
    assert gap["args"]["across_steps"] is False
    # a later one was launched with its predecessor in flight: it
    # starts where that one's read ended, and no time between two reads
    # is anybody's host gap. The one exception: request b ends, the
    # batch of one fits a smaller bucket, and the call in flight is
    # read before the next is launched, in the same step
    ahead = [d["args"]["ahead"] for d in decodes]
    assert ahead == [False, True, True, False, True]
    for prev, cur in zip(decodes, decodes[1:]):
        if cur["args"]["ahead"]:
            assert cur["t0"] == pytest.approx(prev["end"], abs=1e-6)
            assert round(cur["t0"], 6) not in gaps
        else:
            gap = gaps[round(cur["t0"], 6)]
            assert gap["t0"] == pytest.approx(prev["end"], abs=1e-6)
            assert gap["args"]["across_steps"] is False
    # what the host does between two reads lies inside the later span:
    # the post of the call read, the next step's schedule and, where a
    # call was launched behind this one, its prep (and its launch)
    inner = [s for s in spans if s["name"] in (
        "serve:decode_post", "serve:schedule", "serve:decode_prep")]
    for cur, nxt in zip(decodes, decodes[1:]):
        if not cur["args"]["ahead"]:
            continue
        parts = sorted((s for s in inner
                        if cur["t0"] <= s["t0"] and s["end"] <= cur["end"]),
                       key=lambda s: s["t0"])
        assert [s["name"] for s in parts] == [
            "serve:decode_post", "serve:schedule"] + [
            "serve:decode_prep"] * nxt["args"]["ahead"]
        for x, y in zip(parts, parts[1:]):
            assert x["end"] <= y["t0"]           # they do not overlap


def test_no_host_gap_lies_over_a_call_in_flight(served):
    eng, _, _, spans, _ = served
    decodes = _named(spans, "serve:decode")
    for g in _named(spans, "serve:host_gap"):
        for d in decodes:
            # a decode span runs from launch (or the previous read) to
            # its own read: a gap may touch it, not overlap it
            assert g["end"] <= d["t0"] + 1e-6 or d["end"] <= g["t0"] + 1e-6
    snap = eng.metrics.snapshot()
    assert snap["decode_ahead_total"] == sum(
        d["args"]["ahead"] for d in decodes) == len(decodes) - 2
    assert snap["decode_steps"] == len(decodes)
    # a call in flight was read with nothing launched behind it twice:
    # when the batch came to fit a smaller bucket, and at the end
    assert {c: snap[f"decode_drains_{c}_total"]
            for c in metrics_mod.DRAIN_CAUSES} == {
        "prefill": 0, "bucket": 1, "idle": 1, "admit": 0, "migrate": 0}
    assert snap["decode_drains_total"] == 2


def test_a_decode_span_ends_when_the_host_holds_its_tokens(served_model):
    """After every step: the tokens counted and the decode spans
    written are those the sequences hold, never those of the call in
    flight; each token's stamp is the end of the span it came out of."""
    clock = TickClock()
    eng = _engine(served_model, clock=clock)
    rids = [eng.submit([5, 6, 7, 8, 9], 6, trace_id=41),
            eng.submit([1, 2, 3], 4, trace_id=42)]
    seqs = {}
    while eng.pending:
        eng.step()
        now = clock.t
        seqs.update({s.rid: s for s in eng._active})
        held = sum(len(s.generated) for s in seqs.values())
        decodes = [e for e in eng.metrics._events
                   if e["name"] == "serve:decode"]
        assert eng.metrics.tokens_generated == held == len(seqs) + sum(
            e["args"]["n_active"] for e in decodes)
        assert eng.metrics.decode_steps == len(decodes)
        ends = {tid: [eng.metrics.started_at + (e["ts"] + e["dur"]) * 1e-6
                      for e in decodes if tid in e["args"]["traces"]]
                for tid in (41, 42)}
        for s in seqs.values():
            assert len(s.token_times) == len(s.generated)
            assert s.token_times[1:] == pytest.approx(ends[s.trace],
                                                      abs=1e-6)
            assert all(t <= now for t in s.token_times)
        fl = eng._in_flight
        if fl is not None:
            # launched, numbered, and in no span and no counter yet
            assert all(fl.call.call > e["args"]["call"] for e in decodes)
    assert [len(eng.result(r).tokens) for r in rids] == [6, 4]


def test_queue_prefill_and_first_token_agree(served):
    _, res_a, res_b, spans, _ = served
    queues = _named(spans, "serve:queue")
    prefills = _named(spans, "serve:prefill")
    assert len(queues) == 2 and len(prefills) == 2
    sched = _named(spans, "serve:schedule")[0]
    assert sched["args"] == {"retired": 0, "expired": 0, "admitted": 2,
                             "queue": 0}
    for res, q, p in zip((res_a, res_b), queues, prefills):
        assert q["t0"] == pytest.approx(res.submitted_at, abs=1e-6)
        assert q["end"] == pytest.approx(sched["t0"], abs=1e-6)  # admission
        assert q["end"] <= p["t0"]
        assert p["end"] == pytest.approx(res.first_token_at, abs=1e-6)
    assert queues[0]["args"] == {"trace": 41} and queues[1]["args"] == {}
    # prefill -> prefill inside one step: a host gap that crosses none
    gap = [g for g in _named(spans, "serve:host_gap")
           if abs(g["end"] - prefills[1]["t0"]) < 1e-6]
    assert len(gap) == 1 and gap[0]["args"]["across_steps"] is False
    assert gap[0]["t0"] == pytest.approx(prefills[0]["end"], abs=1e-6)


def test_token_times_one_per_token_on_the_spans_ends(served):
    _, res_a, res_b, spans, _ = served
    ends = [round(s["end"], 6) for s in _named(spans, "serve:decode")]
    for res in (res_a, res_b):
        ts = res.token_times
        assert len(ts) == len(res.tokens)
        assert ts[0] == res.first_token_at
        assert all(b > a for a, b in zip(ts, ts[1:]))
        assert all(round(t, 6) in ends for t in ts[1:])
        assert ts[-1] < res.finished_at        # retired by the next step


def test_request_span_says_what_the_result_says(served):
    _, res_a, res_b, spans, _ = served
    reqs = _named(spans, "serve:request")
    assert len(reqs) == 2
    by_n = {r["args"]["n_prompt"]: r for r in reqs}
    for res in (res_a, res_b):
        r = by_n[res.n_prompt]
        assert r["t0"] == pytest.approx(res.submitted_at, abs=1e-6)
        assert r["end"] == pytest.approx(res.finished_at, abs=1e-6)
        a = r["args"]
        assert a["n_out"] == len(res.tokens)
        assert a["ttft_ms"] == pytest.approx(
            1e3 * (res.first_token_at - res.submitted_at))
        gaps = [y - x for x, y in zip(res.token_times, res.token_times[1:])]
        assert a["itl_mean_ms"] == pytest.approx(1e3 * sum(gaps) / len(gaps))
        assert a["itl_max_ms"] == pytest.approx(1e3 * max(gaps))
        assert 0 < a["queue_ms"] < a["ttft_ms"]
    assert by_n[5]["args"]["trace"] == 41 and "trace" not in by_n[3]["args"]


def test_device_spans_keep_their_args_and_lose_the_pool_gauges(served):
    _, _, _, spans, events = served
    first = _named(spans, "serve:prefill")[0]["args"]
    assert (first["n_tokens"], first["offset"], first["trace"]) == (5, 0, 41)
    for d in _named(spans, "serve:decode"):
        assert 1 <= d["args"]["n_active"] <= 2
        assert d["args"].get("traces", [41]) == [41]
    assert _named(spans, "serve:decode")[0]["args"]["traces"] == [41]
    for s in _named(spans, "serve:decode") + _named(spans, "serve:prefill"):
        # until the jitted call returned: one clock read after launch
        assert s["args"]["dispatch_ms"] == pytest.approx(1e3 * TICK)
        assert not {"blocks_in_use", "blocks_cached"} & set(s["args"])
        # from the span's start (for a call launched ahead: the end of
        # the read before) until its result was ready; the copy, one
        # more read of the clock, ends the span
        assert s["args"]["ready_ms"] == pytest.approx(
            1e3 * (s["end"] - s["t0"] - TICK), abs=1e-3)
        if s["name"] == "serve:prefill":
            # launched, dispatched, ready and copied inside one block
            assert s["end"] - s["t0"] == pytest.approx(3 * TICK, abs=1e-6)
    # the counter track is written once a step, not once a span
    counters = [e for e in events if e["ph"] == "C"]
    assert len(counters) == len(_named(spans, "serve:schedule"))
    assert {e["name"] for e in counters} == {"kv_blocks"}


class Recorder:
    """Stands in for ``TraceAnnotation``: keeps what was opened, with
    its stats, in ``Recorder.opened`` (the collector's ``serve:gc``,
    which may open anywhere, left out)."""
    opened = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        if self.name != "serve:gc":
            Recorder.opened.append((self.name, self.stats))

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recorded(served_model, monkeypatch, tmp_path):
    """One request served with every annotation recorded: (the
    annotations opened in order, the spans in order of their start)."""
    monkeypatch.setattr(Recorder, "opened", [])
    monkeypatch.setattr(metrics_mod, "TraceAnnotation", Recorder)
    eng = _engine(served_model)
    eng.submit([5, 6, 7], 3)
    eng.run_until_idle()
    spans, _ = _spans(eng, tmp_path)
    return Recorder.opened, sorted(
        (s for s in spans if s["name"] not in AFTER_THE_FACT),
        key=lambda s: s["t0"])


def test_every_span_has_a_twin_annotation_of_its_name(recorded):
    opened, spans = recorded
    written = [s["name"] for s in spans]
    assert [n for n, _ in opened if n.count(":") == 1] == written
    # the second decode call is launched before the first is read
    assert [(n, st["call"]) for n, st in opened if n.count(":") == 2] == [
        ("serve:prefill:dispatch", 1),
        ("serve:prefill:wait", 1), ("serve:prefill:readback", 1),
        ("serve:decode:dispatch", 2), ("serve:decode:dispatch", 3)] + [
        ("serve:decode" + part, call) for call in (2, 3)
        for part in (":wait", ":readback")]
    assert set(written) == {"serve:schedule", "serve:prefill_prep",
                            "serve:prefill", "serve:prefill_post",
                            "serve:decode_prep", "serve:decode_plan",
                            "serve:decode", "serve:decode_post"}


def test_a_device_call_has_one_number_on_its_span_twin_and_nested(recorded):
    opened, spans = recorded
    calls = [s["args"]["call"] for s in spans if s["name"] in DEVICE]
    assert calls == [1, 2, 3]                    # unique and rising
    assert not any("call" in s["args"] for s in spans
                   if s["name"] not in DEVICE)
    twins = [(n, st) for n, st in opened if n in DEVICE]
    assert [st for _, st in twins] == [{"call": c} for c in calls]
    for (name, stats) in twins:
        nested = [st for n, st in opened if n.startswith(name + ":")
                  and st == stats]
        assert len(nested) == 3      # :dispatch, :wait, :readback
    assert all(st == {} for n, st in opened if n.count(":") == 1
               and n not in DEVICE)


def test_two_engines_number_their_calls_apart_and_share_one_gc_hook(
        served_model):
    engines = [_engine(served_model) for _ in range(2)]
    for eng in engines:
        eng.submit([5, 6, 7], 2)
        eng.run_until_idle()
        calls = [e["args"]["call"] for e in eng.metrics._events
                 if e["name"] in DEVICE]
        assert calls == [1, 2]
    hooks = [cb for cb in gc.callbacks
             if isinstance(cb, metrics_mod._GcWatch)]
    assert len(hooks) == 1
    assert all(eng.metrics._gc is hooks[0] for eng in engines)


def test_idle_engine_records_nothing_and_owns_no_gap(served_model, tmp_path):
    clock = TickClock()
    eng = _engine(served_model, clock=clock)
    eng.step()
    eng.step()
    assert len(eng.metrics._events) == 0
    eng.submit([5, 6, 7], 2)
    eng.run_until_idle()
    clock.t += 50.0                  # nobody asks for anything
    eng.step()
    eng.submit([1, 2, 3], 2)
    eng.run_until_idle()
    spans, _ = _spans(eng, tmp_path)
    assert len(_named(spans, "serve:request")) == 2
    assert max(g["end"] - g["t0"]
               for g in _named(spans, "serve:host_gap")) < 1.0


def test_span_buffer_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(metrics_mod, "MAX_SAMPLES", 8)
    m = metrics_mod.ServeMetrics(clock=TickClock())
    for i in range(20):
        with m.phase("serve:schedule", i=i):
            pass
    assert [e["args"]["i"] for e in m._events] == list(range(12, 20))
    # a phase that raises closes its annotation and writes no span
    with pytest.raises(RuntimeError):
        with m.phase("serve:decode", device=True):
            raise RuntimeError("device fell over")
    assert [e["args"]["i"] for e in m._events] == list(range(12, 20))


# -- a stalled call leaves its cause (PR 36) ---------------------------


class SetClock:
    """A clock that stands still until the test moves it."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class Result:
    """What a jitted call returned: ready after ``wait`` seconds."""

    def __init__(self, clock, wait):
        self.clock, self.wait, self.asked = clock, wait, []

    def copy_to_host_async(self):
        self.asked.append("copy")

    def block_until_ready(self):
        self.asked.append("ready")
        self.clock.t += self.wait
        return self


def _device_call(m, clock, *, gap=2e-3, dispatch=1e-3, wait=20e-3,
                 readback=1e-3, name="serve:decode", inside=None):
    """One device phase as the engine writes it, each part as long as
    the test says on the clock it moves."""
    clock.t += gap

    def to_host(out):
        clock.t += readback
        if inside:
            inside()
        return out

    out = Result(clock, wait)
    with m.phase(name, device=True) as ph:
        with ph.dispatch():
            clock.t += dispatch
        assert ph.read(out, to_host) is out
    # the copy is asked for before the wait, not after it
    assert out.asked == ["copy", "ready"]
    return ph


def test_a_call_launched_ahead_spans_from_the_read_before_it_to_its_own():
    """The two ends of a device call by hand, as the engine's decode
    uses them: b is launched while a is in flight."""
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    _device_call(m, clock, name="serve:prefill")    # ends at +24 ms
    t_idle = clock.t
    clock.t += 2e-3
    a = m.launch("serve:decode", n_active=2, ahead=False)
    a_launched = clock.t
    with a.dispatch():
        clock.t += 1e-3
    clock.t += 3e-3                  # the step returns; the next begins
    m.record_step(clock.t)
    b = m.launch("serve:decode", n_active=2, ahead=True)
    with b.dispatch():
        clock.t += 1e-3
    out_a = Result(clock, 8e-3)
    a.read(out_a, lambda out: out)
    clock.t += 0.5e-3
    m.finish(a)
    a_end = clock.t
    assert (a.t0, a.dur) == (a_launched, pytest.approx(13.5e-3))
    assert a.args["dispatch_ms"] == pytest.approx(1.0)
    assert a.args["ready_ms"] == pytest.approx(13.0)
    # b queued behind a until here: its span starts now, its dispatch
    # lies before it
    assert b.t0 == a_end
    clock.t += 2e-3                  # post, the next step's schedule
    b.read(Result(clock, 10e-3), lambda out: out)
    clock.t += 0.5e-3
    m.finish(b)
    assert b.dur == pytest.approx(12.5e-3) and b.end == clock.t
    assert b.args["dispatch_ms"] == pytest.approx(1.0)
    assert b.args["ready_ms"] == pytest.approx(12.0)
    assert b.part() == "wait"
    # one host gap, before a: none while a call was in flight
    gaps = [e for e in m._events if e["name"] == "serve:host_gap"]
    assert [(g["dur"], g["args"]) for g in gaps] == [
        (pytest.approx(2e3), {"across_steps": False})]
    assert gaps[0]["ts"] == pytest.approx((t_idle - m.started_at) * 1e6)
    spans = [e for e in m._events if e["name"] == "serve:decode"]
    assert [e["args"]["call"] for e in spans] == [a.call, b.call]
    assert spans[1]["ts"] == pytest.approx(spans[0]["ts"] + spans[0]["dur"])
    # and the next call, launched with nothing in flight, has one again
    clock.t += 1e-3
    c = _device_call(m, clock, gap=0.0)
    assert len([e for e in m._events if e["name"] == "serve:host_gap"]) == 2


def test_a_stall_of_a_call_launched_ahead_is_found_at_its_read(caplog):
    """A step late: the call that stalls was launched in the step
    before the one that reads it, and its span, the stall and the
    WARNING are written when its read ends."""
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    prev = m.launch("serve:decode", ahead=False)
    with prev.dispatch():
        clock.t += 1e-3
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        for i in range(14):
            cur = m.launch("serve:decode", ahead=True)
            with cur.dispatch():
                clock.t += 1e-3
            # the thirteenth call runs 220 ms: seen when it is read, in
            # the iteration after the one that launched it
            prev.read(Result(clock, 219e-3 if i == 13 else 19e-3),
                      lambda out: out)
            m.finish(prev)
            assert m.stalls_total == (1 if i == 13 else 0)
            stalled, prev = prev, cur
    (stall,) = _stalls(m)
    assert stall["args"]["call"] == stalled.call == cur.call - 1
    assert (stall["args"]["of"], stall["args"]["part"]) == (
        "serve:decode", "wait")
    assert stall["dur"] == pytest.approx(2.2e5)
    assert stall["args"]["typical_ms"] == pytest.approx(20.0)
    assert len(caplog.records) == 1


def _stalls(m):
    return [e for e in m._events if e["name"] == "serve:stall"]


@pytest.mark.parametrize("part,longer", [
    ("dispatch", dict(dispatch=199e-3)),
    ("wait", dict(wait=218e-3)),
    ("readback", dict(readback=199e-3)),
    ("host_gap", dict(gap=100e-3)),
])
def test_a_call_ten_times_its_median_is_one_stall_with_its_part(
        part, longer, caplog):
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        for _ in range(12):
            _device_call(m, clock)              # 22 ms, after 2 ms
        assert m.stalls_total == 0 and not caplog.records
        ph = _device_call(m, clock, **longer)   # 220 ms, or after 100
        _device_call(m, clock)
    assert m.stalls_total == 1
    (stall,) = _stalls(m)
    a = stall["args"]
    of = "serve:host_gap" if part == "host_gap" else "serve:decode"
    assert (a["of"], a["part"], a["call"]) == (of, part, ph.call)
    assert a["typical_ms"] == pytest.approx(2.0 if part == "host_gap"
                                            else 22.0)
    assert stall["dur"] == pytest.approx(1e5 if part == "host_gap"
                                         else 2.2e5)
    assert (a["gc_ms"], a["gc_gen"], a["compiles"]) == (0.0, None, [])
    # CPU time is counted from a mark at most 0.1 s older than the span
    assert 0 <= a["cpu_ms"] and 0 <= a["process_cpu_ms"]
    assert stall["dur"] / 1e3 <= a["cpu_over_ms"] <= stall["dur"] / 1e3 + (
        100 + 22 + 2)
    (record,) = caplog.records
    assert record.levelno == logging.WARNING
    assert of in record.getMessage() and part in record.getMessage()
    # the same under both exports
    assert m.snapshot()["stalls_total"] == 1
    assert 'serve_stalls_total{instance="%s"} 1' % m.instance \
        in m.prometheus()


def test_a_call_three_times_its_median_is_no_stall(caplog):
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        for _ in range(12):
            _device_call(m, clock)
        _device_call(m, clock, wait=64e-3)      # 66 ms: past the floor
        _device_call(m, clock, gap=6e-3)
        # eight medians, and still under the floor of 50 ms
        _device_call(m, clock, gap=40e-3)
    assert m.stalls_total == 0 and not _stalls(m) and not caplog.records
    assert m.snapshot()["stalls_total"] == 0


def test_the_cpu_clocks_are_read_once_a_tenth_of_a_second_not_once_a_call(
        monkeypatch):
    import time as time_mod
    reads = []
    real = time_mod.thread_time
    monkeypatch.setattr(time_mod, "thread_time",
                        lambda: reads.append(1) or real())
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    for _ in range(50):
        _device_call(m, clock)                  # 24 ms from one to the next
    assert len(reads) == 1 + 50 // 5            # the first at reset()
    _device_call(m, clock, wait=218e-3)
    assert len(reads) == 1 + 50 // 5 + 1        # a stall reads them too


def test_a_kind_is_not_judged_before_it_has_its_samples():
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    for _ in range(metrics_mod.STALL_MIN_SAMPLES - 1):
        _device_call(m, clock)
    _device_call(m, clock, wait=5.0)            # a first call compiles
    assert m.stalls_total == 0


def test_a_collection_inside_a_stalled_call_shows_on_it():
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    for _ in range(12):
        _device_call(m, clock)
    heap = [[i] for i in range(200_000)]        # something to walk
    _device_call(m, clock, readback=300e-3, inside=gc.collect)
    del heap
    (stall,) = _stalls(m)
    assert stall["args"]["part"] == "readback"
    assert stall["args"]["gc_ms"] > 0 and stall["args"]["gc_gen"] == 2
    # the pause is kept as (start, duration, generation)
    start, dur, gen = [p for p in m._gc.pauses if p[2] == 2][-1]
    assert dur * 1e3 <= stall["args"]["gc_ms"] + 1e-9
    # and the next call, with no collection in it, is clean again
    _device_call(m, clock, readback=300e-3)
    assert _stalls(m)[-1]["args"]["gc_gen"] in (None, 0, 1)


def test_a_compile_inside_a_stalled_call_is_named(monkeypatch):
    import time as time_mod
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    for _ in range(12):
        _device_call(m, clock)
    now = time_mod.time()
    monkeypatch.setattr(metrics_mod, "compile_stats", lambda: {"recent": [
        {"at": now - 30.0, "kind": "compile", "fun_name": "warm_up",
         "seconds": 2.0},
        {"at": now + 60.0, "kind": "compile", "fun_name": "decode",
         "seconds": 120.0}]})
    _device_call(m, clock, dispatch=1.0)
    (stall,) = _stalls(m)
    assert stall["args"]["part"] == "dispatch"
    assert stall["args"]["compiles"] == ["decode"]


def test_the_engine_s_own_run_has_no_stall(served):
    eng, _, _, spans, _ = served
    assert not _named(spans, "serve:stall")
    assert eng.metrics.snapshot()["stalls_total"] == 0


# -- every second the device is unfed has a cause (PR 52) --------------


def _reaches(served_model, cause):
    """A tiny engine run to the end on a course that reads a decode
    call for ``cause`` (or a prefill's token) and launches again."""
    if cause == "idle":
        # one slot: the first request's last call leaves no row, and
        # the second waits in the queue for its slot
        eng = _engine(served_model, max_batch=1)
        eng.submit([5, 6, 7], 3)
        eng.submit([1, 2, 3], 3)
    elif cause == "admit":
        # two slots, three requests: one ends while one goes on
        eng = _engine(served_model, max_batch=2)
        eng.submit([5, 6, 7, 8, 9], 6)
        eng.submit([1, 2, 3], 2)
        eng.submit([4, 5, 6], 2)
    elif cause == "prefill":
        # a request arrives while a decode call is in flight and a
        # slot is free
        eng = _engine(served_model)
        eng.submit([5, 6, 7, 8, 9], 6)
        for _ in range(3):
            eng.step()
        eng.submit([1, 2, 3], 2)
    else:
        # "bucket": one of two ends and nobody waits; "prefill_read":
        # any request at all
        eng = _engine(served_model)
        eng.submit([5, 6, 7, 8, 9], 6)
        eng.submit([1, 2, 3], 4)
    eng.run_until_idle()
    return eng


CAUSES = ("prefill", "admit", "bucket", "idle", "prefill_read")


@pytest.fixture(scope="module", params=CAUSES)
def reached(request, served_model, tmp_path_factory):
    eng = _reaches(served_model, request.param)
    spans, _ = _spans(eng, tmp_path_factory.mktemp("unfed"))
    return request.param, eng, spans


def test_unfed_says_why_no_successor_was_in_flight(reached):
    cause, eng, spans = reached
    unfed = _named(spans, "serve:unfed")
    assert {u["args"]["why"] for u in unfed} <= set(metrics_mod.UNFED_WHYS)
    mine = [u for u in unfed if u["args"]["why"] == cause]
    assert mine
    by_call = {s["args"]["call"]: s["name"] for s in spans
               if s["name"] in DEVICE}
    after = "serve:prefill" if cause == "prefill_read" else "serve:decode"
    for u in mine:
        assert by_call[u["args"]["after"]] == after
        assert u["args"]["before"] > u["args"]["after"]
    if cause != "prefill_read":
        # every drain was followed by a launch but the run's last,
        # for `idle`, which nothing follows
        drains = eng.metrics.snapshot()[f"decode_drains_{cause}_total"]
        assert len(mine) == drains - (cause == "idle") > 0


def test_unfed_is_readback_host_and_dispatch(reached):
    _, _, spans = reached
    gaps = {round(g["t0"], 6): g for g in _named(spans, "serve:host_gap")}
    unfed = _named(spans, "serve:unfed")
    assert len(unfed) == len(gaps)
    for u in unfed:
        a = u["args"]
        assert a["readback_ms"] + a["host_ms"] + a["dispatch_ms"] == \
            pytest.approx(1e3 * (u["end"] - u["t0"]), abs=1e-3)
        # the read's copy and the dispatch are one tick each on this
        # clock; the host's part is the host gap inside
        assert a["readback_ms"] == pytest.approx(1e3 * TICK)
        assert a["dispatch_ms"] == pytest.approx(1e3 * TICK)
        gap = gaps[round(u["t0"] + a["readback_ms"] * 1e-3, 6)]
        assert a["host_ms"] == pytest.approx(
            1e3 * (gap["end"] - gap["t0"]), abs=1e-3)
        assert a["across_steps"] == gap["args"]["across_steps"]


def test_unfed_host_time_is_its_phases_and_the_unnamed_rest(reached):
    _, _, spans = reached
    named = [s for s in spans if s["name"] not in AFTER_THE_FACT
             and s["name"] not in DEVICE]
    for u in _named(spans, "serve:unfed"):
        a = u["args"]
        assert sum(a["phases"].values()) + a["unnamed_ms"] == \
            pytest.approx(a["host_ms"])
        assert a["unnamed_ms"] > 0          # the clock ticks between phases
        lo = u["t0"] + a["readback_ms"] * 1e-3
        hi = lo + a["host_ms"] * 1e-3
        inside: dict = {}
        for s in named:
            if lo - 1e-7 <= s["t0"] and s["end"] <= hi + 1e-7:
                inside[s["name"]] = inside.get(s["name"], 0.0) + 1e3 * (
                    s["end"] - s["t0"])
        # a step() that began in between names the time outside any
        outside = a["phases"].pop("outside_step", None)
        assert (outside is not None) == a["across_steps"]
        assert a["phases"] == pytest.approx(inside, abs=1e-3)
        if a["why"] == "prefill_read":
            assert "serve:prefill_post" in a["phases"]
        else:
            assert "serve:decode_post" in a["phases"]


def test_no_unfed_lies_over_a_call_in_flight(reached):
    _, _, spans = reached
    unfed = _named(spans, "serve:unfed") + _named(spans, "serve:no_work")
    assert unfed
    for d in (s for s in spans if s["name"] in DEVICE):
        # on the device's queue or running: from the jitted call's
        # return (before its span began, for a call launched ahead)
        # until its result was ready
        a = d["args"]
        lo = d["t0"] + (0.0 if a.get("ahead") else a["dispatch_ms"] * 1e-3)
        hi = d["t0"] + a["ready_ms"] * 1e-3
        for u in unfed:
            assert u["end"] <= lo + 1e-6 or hi <= u["t0"] + 1e-6
            if u["args"]["before"] == a["call"]:
                assert u["end"] == pytest.approx(lo, abs=1e-6)
            if u["args"]["after"] == a["call"]:
                assert u["t0"] == pytest.approx(hi, abs=1e-6)


def test_the_unfed_counters_are_the_spans_sums_and_restart(reached):
    cause, eng, spans = reached
    snap = eng.metrics.snapshot()
    unfed = _named(spans, "serve:unfed")
    assert snap["device_unfed_s_total"] == pytest.approx(
        sum(u["end"] - u["t0"] for u in unfed), abs=1e-6)
    assert set(snap["device_unfed_s_by_why"]) == set(metrics_mod.UNFED_WHYS)
    for why, s in snap["device_unfed_s_by_why"].items():
        assert s == pytest.approx(sum(
            u["end"] - u["t0"] for u in unfed if u["args"]["why"] == why),
            abs=1e-6)
    assert snap["device_unfed_s_by_why"][cause] > 0
    assert snap["device_no_work_s_total"] == pytest.approx(sum(
        s["end"] - s["t0"] for s in _named(spans, "serve:no_work")),
        abs=1e-6)
    text = eng.metrics.prometheus()
    assert f"serve_device_unfed_s_by_why_{cause}{{" in text
    assert "serve_device_unfed_s_total{" in text
    assert "serve_device_no_work_s_total{" in text


def test_the_unfed_counters_restart_at_reset(served_model):
    eng = _reaches(served_model, "bucket")
    assert eng.metrics.snapshot()["device_unfed_s_total"] > 0
    eng.metrics.reset()
    snap = eng.metrics.snapshot()
    assert snap["device_unfed_s_total"] == snap["device_no_work_s_total"] == 0
    assert not any(snap["device_unfed_s_by_why"].values())
    # the interval that was open at reset() is dropped with the spans
    eng.submit([1, 2, 3], 2)
    eng.run_until_idle()
    names = [e["name"] for e in eng.metrics._events]
    assert "serve:no_work" not in names
    snap = eng.metrics.snapshot()
    assert snap["device_unfed_s_total"] == pytest.approx(sum(
        e["dur"] for e in eng.metrics._events
        if e["name"] == "serve:unfed") * 1e-6, abs=1e-6)


def test_an_engine_out_of_work_writes_no_work_and_no_unfed(
        served_model, tmp_path):
    clock = TickClock()
    eng = _engine(served_model, clock=clock)
    eng.submit([5, 6, 7], 2)
    eng.run_until_idle()
    before = len([e for e in eng.metrics._events
                  if e["name"] == "serve:unfed"])
    clock.t += 50.0                  # nobody asks for anything
    eng.step()
    eng.submit([1, 2, 3], 2)
    eng.run_until_idle()
    spans, _ = _spans(eng, tmp_path)
    waits = _named(spans, "serve:no_work")
    assert len(waits) == 1
    assert waits[0]["end"] - waits[0]["t0"] > 50.0
    prefills = _named(spans, "serve:prefill")
    assert waits[0]["args"] == {"after": prefills[1]["args"]["call"] - 1,
                                "before": prefills[1]["args"]["call"]}
    # the wait is the traffic's: in no serve:unfed and not in its sum
    unfed = _named(spans, "serve:unfed")
    assert max(u["end"] - u["t0"] for u in unfed) < 1.0
    assert eng.metrics.snapshot()["device_unfed_s_total"] < 1.0
    assert eng.metrics.snapshot()["device_no_work_s_total"] == \
        pytest.approx(waits[0]["end"] - waits[0]["t0"], abs=1e-6)
    # the last call of the first request was drained for `idle`, and
    # nothing followed it but the wait
    assert len([u for u in unfed if u["args"]["why"] == "idle"]) == 0
    assert len(unfed) > before


def test_unfed_by_the_clock_and_none_for_a_call_launched_ahead():
    """The parts by hand on a clock the test moves: a call read with
    nothing behind it, then b launched while a is in flight."""
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    _device_call(m, clock, name="serve:prefill")     # ready at +23 ms
    ready = clock.t - 1e-3
    with m.phase("serve:prefill_post"):
        clock.t += 0.5e-3
    clock.t += 1.5e-3
    a = m.launch("serve:decode", n_active=2, ahead=False)
    with a.dispatch():
        clock.t += 1e-3
    unfed = [e for e in m._events if e["name"] == "serve:unfed"]
    assert len(unfed) == 1
    assert unfed[0]["ts"] == pytest.approx((ready - m.started_at) * 1e6)
    assert unfed[0]["dur"] == pytest.approx(4e3)
    assert unfed[0]["args"] == {
        "readback_ms": pytest.approx(1.0), "host_ms": pytest.approx(2.0),
        "dispatch_ms": pytest.approx(1.0),
        "phases": {"serve:prefill_post": pytest.approx(0.5)},
        "unnamed_ms": pytest.approx(1.5), "why": "prefill_read",
        "after": a.call - 1, "before": a.call, "across_steps": False}
    b = m.launch("serve:decode", n_active=2, ahead=True)
    with b.dispatch():
        clock.t += 1e-3
    a.read(Result(clock, 8e-3), lambda out: out)
    m.finish(a)
    with m.phase("serve:decode_post"):   # b is in flight: nobody's
        clock.t += 1e-3
    b.read(Result(clock, 10e-3), lambda out: out)
    clock.t += 0.5e-3
    m.finish(b)
    m.record_decode_drain("admit")
    clock.t += 2e-3
    m.record_step(clock.t)
    _device_call(m, clock, gap=0.0, name="serve:prefill")
    unfed = [e for e in m._events if e["name"] == "serve:unfed"]
    assert [(e["args"]["after"], e["args"]["why"]) for e in unfed] == [
        (a.call - 1, "prefill_read"), (b.call, "admit")]
    assert unfed[1]["args"]["phases"] == {
        "outside_step": pytest.approx(2.0)}
    assert unfed[1]["args"]["unnamed_ms"] == pytest.approx(0.0, abs=1e-9)
    assert unfed[1]["args"]["across_steps"] is True
    assert unfed[1]["dur"] == pytest.approx(3.5e3)
    assert m.snapshot()["device_unfed_s_by_why"]["admit"] == \
        pytest.approx(3.5e-3)


def test_a_round_of_several_launches_is_unfed_between_them():
    """A speculative round's draft phase launches k times inside one
    call: between two of them the device has nothing, and no read's
    end is stamped, so the interval reads as dispatch."""
    clock = SetClock()
    m = metrics_mod.ServeMetrics(clock=clock)
    with m.phase("serve:spec_draft", device=True) as draft:
        for _ in range(2):
            with draft.dispatch():
                clock.t += 1e-3
            draft.read(Result(clock, 5e-3), lambda out: out)
            clock.t += 0.5e-3
    unfed = [e for e in m._events if e["name"] == "serve:unfed"]
    assert len(unfed) == 1
    assert unfed[0]["dur"] == pytest.approx(1.5e3)
    assert unfed[0]["args"]["after"] == unfed[0]["args"]["before"] \
        == draft.call
    assert unfed[0]["args"]["why"] == "spec"
    assert (unfed[0]["args"]["readback_ms"], unfed[0]["args"]["host_ms"],
            unfed[0]["args"]["dispatch_ms"]) == (0.0, 0.0,
                                                 pytest.approx(1.5))
