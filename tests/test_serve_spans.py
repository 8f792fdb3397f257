"""The serving engine's host phases as spans (PR 24): what
``ServeMetrics.phase`` writes, where the spans begin and end against a
fake engine clock that ticks at every read, and what a request's result
carries of them. Same tiny geometry as tests/test_serve.py, so the jit
cache holds one set of programs."""
import json

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import engine as engine_mod
from horovod_tpu.serve import metrics as metrics_mod

TICK = 1e-3
AFTER_THE_FACT = {"serve:host_gap", "serve:queue", "serve:request"}


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
    inner = [s for s in spans if s["name"] in (
        "serve:decode_post", "serve:schedule", "serve:decode_prep")]
    for prev, cur in zip(decodes, decodes[1:]):
        gap = gaps[round(cur["t0"], 6)]
        # from the end of one device call's sync to the first line of
        # the next one's dispatch: nothing between two syncs is missed
        assert gap["t0"] == pytest.approx(prev["end"], abs=1e-6)
        assert gap["args"]["across_steps"] is True
        parts = sorted((s for s in inner
                        if gap["t0"] <= s["t0"] and s["end"] <= gap["end"]),
                       key=lambda s: s["t0"])
        assert [s["name"] for s in parts] == [
            "serve:decode_post", "serve:schedule", "serve:decode_prep"]
        for x, y in zip(parts, parts[1:]):
            assert x["end"] <= y["t0"]           # they do not overlap
        # the gap is its three parts and the clock reads between them
        covered = sum(s["end"] - s["t0"] for s in parts)
        assert gap["end"] - gap["t0"] - covered == pytest.approx(
            4 * TICK, abs=1e-6)


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
        # until the jitted call returned: one clock read into the span
        assert s["args"]["dispatch_ms"] == pytest.approx(1e3 * TICK)
        assert not {"blocks_in_use", "blocks_cached"} & set(s["args"])
    # the counter track is written once a step, not once a span
    counters = [e for e in events if e["ph"] == "C"]
    assert len(counters) == len(_named(spans, "serve:schedule"))
    assert {e["name"] for e in counters} == {"kv_blocks"}


def test_every_span_has_a_twin_annotation_of_its_name(served_model,
                                                      monkeypatch, tmp_path):
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(metrics_mod, "TraceAnnotation", Recorder)
    monkeypatch.setattr(engine_mod, "TraceAnnotation", Recorder)
    eng = _engine(served_model)
    eng.submit([5, 6, 7], 3)
    eng.run_until_idle()
    spans, _ = _spans(eng, tmp_path)
    written = [s["name"] for s in sorted(spans, key=lambda s: s["t0"])
               if s["name"] not in AFTER_THE_FACT]
    nested = [n for n in opened if n.count(":") == 2]
    assert [n for n in opened if n.count(":") == 1] == written
    assert nested == ["serve:prefill:dispatch", "serve:prefill:sync"] + [
        "serve:decode:dispatch", "serve:decode:sync"] * 2
    assert set(written) == {"serve:schedule", "serve:prefill",
                            "serve:decode_prep", "serve:decode",
                            "serve:decode_post"}


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
