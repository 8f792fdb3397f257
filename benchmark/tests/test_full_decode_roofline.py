"""``full_decode_roofline_lfm2`` on the small recorded trace of
``test_scope_reducers.py`` (``beta`` stands for ``attn_full``: 5.458828e-6
+ 5.2336e-7 s under it), against ``flops_lfm2.served_work``'s own count
of the decode rows' pages and operations."""
import pytest

from benchmark import flops_lfm2, harness
from benchmark.reducers import full_decode_roofline_lfm2
from test_scope_reducers import meas  # noqa: F401  (the fixture)

MODEL = {"layer_types": ["conv", "full", "conv", "full"], "n_layers": 4,
         "n_dense_layers": 1, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "d_head": 16, "d_ff": 32, "d_ff_dense": 128, "n_experts": 4,
         "moe_top_k": 2, "vocab_size": 256, "conv_taps": 3}
WORK = {"decode_calls": 3, "decode_rows": 12, "prefill_calls": 1,
        "prefill_tokens": 40, "prefill_positions_seen": 820,
        "decode_positions_seen": 600, "traced_s": 1.0}


def test_the_share_is_the_pages_least_time_over_the_scope_s(meas):  # noqa: F811
    meas.update(model=MODEL, traced_work=WORK)
    got = full_decode_roofline_lfm2.reduce(meas, match=r"\bbeta\b")
    # two full layers, K and V, 2 heads of 16, bf16, a position a decode
    # row saw; the chunk's positions are not the step's
    page_bytes = 600 * 2 * 2 * 2 * 16 * 2
    assert flops_lfm2.served_work(MODEL, WORK)["page_bytes"] == page_bytes
    flops = 4.0 * 2 * 4 * 16 * 600
    least = max(page_bytes / 1e12, flops / 1e12)
    assert got == pytest.approx(
        100 * least / (5.458828e-6 + 5.2336e-7), rel=1e-4)


def test_nothing_to_read_gives_none(meas, monkeypatch, tmp_path):  # noqa: F811
    reduce = full_decode_roofline_lfm2.reduce
    assert reduce({**meas, "model": MODEL}, match=r"\bbeta\b") is None
    meas.update(model=MODEL, traced_work=WORK)
    assert reduce(meas, match=r"\bno_such_scope\b") is None
    # the parent of a PR that brought another configuration: no count
    assert reduce({**meas, "model": {"n_layers": 2}},
                  match=r"\bbeta\b") is None
    # no trace at all (a parsed one is kept in ``meas``: a fresh one)
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "empty"))
    assert reduce({"peak": meas["peak"], "model": MODEL,
                   "traced_work": WORK}, match=r"\bbeta\b") is None
