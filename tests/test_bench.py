"""bench.py round-over-round regression gate (round-4 verdict #2: the
host-plane drop rode in silently because nothing compared rounds)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def test_find_regressions_flags_nested_drop():
    prev = {"value": 2658.5, "vs_baseline": 12.8,
            "extra": {"host_allreduce_busbw_gbps_np4": {"1MB": 0.431},
                      "transformer_mfu_pct": 56.1}}
    cur = {"value": 2613.8, "vs_baseline": 12.6,
           "extra": {"host_allreduce_busbw_gbps_np4": {"1MB": 0.217},
                     "transformer_mfu_pct": 56.3}}
    regs = bench.find_regressions(prev, cur)
    # The halved busbw is flagged; the 1.7% primary drift is not.
    assert "extra.host_allreduce_busbw_gbps_np4.1MB" in regs
    flagged = regs["extra.host_allreduce_busbw_gbps_np4.1MB"]
    assert flagged["prev"] == 0.431 and flagged["cur"] == 0.217
    assert flagged["drop_pct"] > 45
    assert "value" not in regs


def test_find_regressions_algo_arm_keys():
    """The per-algorithm busbw arms gate like any throughput key, and
    the selection-table dump (strings) never participates."""
    prev = {"extra": {"host_allreduce_busbw_hd_gbps_np4": {"64KB": 0.010},
                      "collective_algo_table_np4": {"65536": "hd"}}}
    cur = {"extra": {"host_allreduce_busbw_hd_gbps_np4": {"64KB": 0.005},
                     "collective_algo_table_np4": {"65536": "ring"}}}
    regs = bench.find_regressions(prev, cur)
    assert "extra.host_allreduce_busbw_hd_gbps_np4.64KB" in regs
    assert not any("collective_algo_table" in k for k in regs)


def test_find_regressions_measured_selection_key_directions():
    """ISSUE 13 keys: the measured-model and hand-band busbw arms gate
    higher-is-better like every throughput key; the synthesized-table
    and audit dumps (strings) never participate; topology_probe_ms is
    tracked but UNGATED in both directions — a ~40 ms measurement under
    ±30% box swings would make a 10% latency gate pure weather."""
    prev = {"extra": {
        "host_allreduce_busbw_measured_gbps_np4": {"16MB": 0.224},
        "host_allreduce_busbw_handbands_gbps_np4": {"16MB": 0.198},
        "collective_algo_synth_table_np4": {"16777216": "hd"},
        "collective_algo_audit_np4": {
            "16777216": {"default": "ring", "measured": "hd"}},
        "topology_probe_ms": 71.0,
    }}
    cur = {"extra": {
        "host_allreduce_busbw_measured_gbps_np4": {"16MB": 0.100},
        "host_allreduce_busbw_handbands_gbps_np4": {"16MB": 0.100},
        "collective_algo_synth_table_np4": {"16777216": "ring"},
        "collective_algo_audit_np4": {},
        "topology_probe_ms": 400.0,
    }}
    regs = bench.find_regressions(prev, cur)
    assert "extra.host_allreduce_busbw_measured_gbps_np4.16MB" in regs
    assert "extra.host_allreduce_busbw_handbands_gbps_np4.16MB" in regs
    assert not any("synth_table" in k or "audit" in k for k in regs)
    assert not any("topology_probe_ms" in k for k in regs)
    # ...and a probe-time IMPROVEMENT is not flagged either (truly
    # direction-less, not latency-inverted).
    cur2 = {"extra": {"topology_probe_ms": 10.0}}
    assert bench.find_regressions(prev, cur2) == {}


def test_find_regressions_ignores_improvements_and_new_metrics():
    prev = {"value": 100.0, "extra": {"old_only": 5.0}}
    cur = {"value": 150.0, "extra": {"new_only": 1.0}}
    # Improvement and non-shared keys never trip the gate.
    assert bench.find_regressions(prev, cur) == {}


def test_find_regressions_latency_keys_are_lower_is_better():
    """`serve_p50/p99_*_ms` keys regress when they RISE: the old
    higher-is-better comparison reported a latency blowup as an
    improvement and a latency win as a drop."""
    prev = {"extra": {"serve_p99_per_token_ms": 10.0,
                      "serve_p50_first_token_ms": 40.0,
                      "serve_tokens_per_sec_per_chip": 1000.0}}
    # Latency rose 50% -> flagged (with rise_pct, not drop_pct).
    cur = {"extra": {"serve_p99_per_token_ms": 15.0,
                     "serve_p50_first_token_ms": 40.0,
                     "serve_tokens_per_sec_per_chip": 1000.0}}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {"extra.serve_p99_per_token_ms"}
    assert regs["extra.serve_p99_per_token_ms"]["rise_pct"] == 50.0
    # Latency halved -> a WIN, not a drop; throughput halved -> still
    # flagged the usual way. Both directions in one payload.
    cur2 = {"extra": {"serve_p99_per_token_ms": 5.0,
                      "serve_p50_first_token_ms": 40.0,
                      "serve_tokens_per_sec_per_chip": 500.0}}
    regs2 = bench.find_regressions(prev, cur2)
    assert "extra.serve_p99_per_token_ms" not in regs2
    assert "extra.serve_tokens_per_sec_per_chip" in regs2


def test_find_regressions_skips_directionless_counters():
    # Step counts / eviction totals / high-water gauges have no
    # better-or-worse direction; swings must not trip the gate.
    prev = {"extra": {"serve_decode_steps": 290.0,
                      "serve_prefix_block_evictions": 40.0,
                      "serve_prefix_kv_high_water": 81.0}}
    cur = {"extra": {"serve_decode_steps": 150.0,
                     "serve_prefix_block_evictions": 0.0,
                     "serve_prefix_kv_high_water": 120.0}}
    assert bench.find_regressions(prev, cur) == {}


def test_find_regressions_telemetry_key_directions():
    """ISSUE 5 derived keys: the log2-bucket cycle tail and the
    autotune-coupled fusion fill are trajectory-only (ungated — a
    power-of-two jump or a threshold retune is not a regression), while
    wire_bytes_saved_pct is a real higher-is-better efficiency metric
    and stays gated."""
    prev = {"extra": {"host_allreduce_cycle_us_p99": 2048.0,
                      "host_allreduce_fusion_fill_pct": 12.0,
                      "wire_bytes_saved_pct": 62.0}}
    cur = {"extra": {"host_allreduce_cycle_us_p99": 8192.0,
                     "host_allreduce_fusion_fill_pct": 3.0,
                     "wire_bytes_saved_pct": 30.0}}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {"extra.wire_bytes_saved_pct"}
    assert regs["extra.wire_bytes_saved_pct"]["drop_pct"] > 50


def test_find_regressions_mesh_compression_key_directions():
    """ISSUE 9 keys: the in-jit compression arms (transformer_mfu_int8 /
    _bf16 / _comp_none and their tokens/sec twins) are throughput
    metrics — higher is better, gated on drops, and an int8 speedup
    over the none arm never flags."""
    prev = {"extra": {"transformer_mfu_int8": 66.0,
                      "transformer_mfu_bf16": 64.0,
                      "transformer_mfu_comp_none": 60.0,
                      "transformer_int8_tokens_per_sec_per_chip": 2.2e4}}
    cur = {"extra": {"transformer_mfu_int8": 40.0,       # drop: flags
                     "transformer_mfu_bf16": 70.0,       # gain: silent
                     "transformer_mfu_comp_none": 59.0,  # noise: silent
                     "transformer_int8_tokens_per_sec_per_chip": 1.1e4}}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {"extra.transformer_mfu_int8",
                         "extra.transformer_int8_tokens_per_sec_per_chip"}
    assert regs["extra.transformer_mfu_int8"]["drop_pct"] > 35


def test_find_regressions_fsdp_compression_key_directions():
    """ISSUE 14 keys: the fsdp-plane compression arms
    (transformer_mfu_fsdp_comp_{none,bf16,int8} and their tokens/sec
    twins) gate exactly like the dp arms — higher-is-better throughput,
    flagged on drops only — and the bus-wire payload's resolved
    ``iouring`` mode string rides along ungated (non-numeric)."""
    prev = {"extra": {"transformer_mfu_fsdp_comp_int8": 64.0,
                      "transformer_mfu_fsdp_comp_bf16": 62.0,
                      "transformer_mfu_fsdp_comp_none": 57.0,
                      "transformer_fsdp_comp_int8_tokens_per_sec_per_chip":
                          2.0e4,
                      "host_allreduce_busbw_sendv_gbps_np4": {
                          "iouring": "syscall"}}}
    cur = {"extra": {"transformer_mfu_fsdp_comp_int8": 40.0,  # drop: flags
                     "transformer_mfu_fsdp_comp_bf16": 68.0,  # gain: silent
                     "transformer_mfu_fsdp_comp_none": 56.0,  # noise: silent
                     "transformer_fsdp_comp_int8_tokens_per_sec_per_chip":
                         1.2e4,
                     "host_allreduce_busbw_sendv_gbps_np4": {
                         "iouring": "batched"}}}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {
        "extra.transformer_mfu_fsdp_comp_int8",
        "extra.transformer_fsdp_comp_int8_tokens_per_sec_per_chip"}
    assert regs["extra.transformer_mfu_fsdp_comp_int8"]["drop_pct"] > 35


def test_find_regressions_router_key_directions():
    """ISSUE 8 `serve_router_*` keys: hit rates and throughput gate
    higher-is-better, `*_ms` latency keys gate on RISE, and the fleet
    tallies (`*_count`: handoffs moved, replicas present) are
    direction-less and ungated."""
    prev = {"extra": {"serve_router_prefix_hit_rate": 0.60,
                      "serve_router_tokens_per_sec_per_chip": 200.0,
                      "serve_router_p99_first_token_ms": 400.0,
                      "serve_router_handoff_count": 32.0,
                      "serve_router_replica_count": 4.0}}
    cur = {"extra": {"serve_router_prefix_hit_rate": 0.20,
                     "serve_router_tokens_per_sec_per_chip": 205.0,
                     "serve_router_p99_first_token_ms": 900.0,
                     "serve_router_handoff_count": 2.0,
                     "serve_router_replica_count": 8.0}}
    regs = bench.find_regressions(prev, cur)
    # Hit-rate collapse and latency blowup flag; count swings never do.
    assert set(regs) == {"extra.serve_router_prefix_hit_rate",
                         "extra.serve_router_p99_first_token_ms"}
    assert regs["extra.serve_router_prefix_hit_rate"]["drop_pct"] > 60
    assert regs["extra.serve_router_p99_first_token_ms"]["rise_pct"] > 100
    # Both directions of the gated keys: a hit-rate WIN plus a
    # throughput drop flags only the throughput.
    cur2 = {"extra": {"serve_router_prefix_hit_rate": 0.90,
                      "serve_router_tokens_per_sec_per_chip": 100.0,
                      "serve_router_p99_first_token_ms": 200.0,
                      "serve_router_handoff_count": 32.0,
                      "serve_router_replica_count": 4.0}}
    regs2 = bench.find_regressions(prev, cur2)
    assert set(regs2) == {"extra.serve_router_tokens_per_sec_per_chip"}


def test_find_regressions_spec_key_directions():
    """ISSUE 12 `serve_spec_*` keys: accept rate and tokens/sec gate
    higher-is-better (an accept-rate collapse is a draft/acceptance
    regression even when throughput hides it), `_ms` keys ride the
    latency inversion, and the round tally (`_count`) is
    direction-less and ungated."""
    prev = {"extra": {"serve_spec_accept_rate": 0.95,
                      "serve_spec_tokens_per_sec": 900.0,
                      "serve_spec_over_plain": 1.8,
                      "serve_spec_p99_first_token_ms": 50.0,
                      "serve_spec_verify_rounds_count": 40.0}}
    cur = {"extra": {"serve_spec_accept_rate": 0.40,      # flags
                     "serve_spec_tokens_per_sec": 910.0,
                     "serve_spec_over_plain": 1.9,
                     "serve_spec_p99_first_token_ms": 120.0,  # flags
                     "serve_spec_verify_rounds_count": 10.0}}  # silent
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {"extra.serve_spec_accept_rate",
                         "extra.serve_spec_p99_first_token_ms"}
    assert regs["extra.serve_spec_accept_rate"]["drop_pct"] > 50
    assert regs["extra.serve_spec_p99_first_token_ms"]["rise_pct"] > 100
    # The speedup ratio itself gates on drops like any throughput key.
    cur2 = {"extra": {"serve_spec_accept_rate": 0.95,
                      "serve_spec_tokens_per_sec": 900.0,
                      "serve_spec_over_plain": 0.9,
                      "serve_spec_p99_first_token_ms": 50.0,
                      "serve_spec_verify_rounds_count": 40.0}}
    assert set(bench.find_regressions(prev, cur2)) == \
        {"extra.serve_spec_over_plain"}


def test_find_regressions_latency_family_key_directions():
    """ISSUE 15 keys: the small-op latency family's p50 `*_us` leaves
    (locked and off arms alike) regress when they RISE; the p99 twins
    carry the `_us_p99` leaf suffix and are UNGATED (this box's p99
    swings 3-6x with scheduler noise — a 10% gate would flag pure
    weather); the steady_lock_p50_speedup ratio gates like a
    throughput key (flags on drops); the engaged flag is a bool and
    never participates."""
    prev = {"extra": {
        "host_allreduce_latency_us_p50_locked_np4": {"4B_us": 80.0,
                                                     "64KB_us": 300.0},
        "host_allreduce_latency_us_p99_locked_np4": {"4B_us_p99": 200.0},
        "host_allreduce_latency_us_p50_off_np4": {"4B_us": 140.0},
        "steady_lock_p50_speedup": 1.75,
        "steady_lock_engaged": True,
    }}
    cur = {"extra": {
        "host_allreduce_latency_us_p50_locked_np4": {"4B_us": 160.0,  # rise
                                                     "64KB_us": 250.0},
        "host_allreduce_latency_us_p99_locked_np4": {
            "4B_us_p99": 900.0},  # 4.5x p99 swing: weather, ungated
        "host_allreduce_latency_us_p50_off_np4": {"4B_us": 145.0},
        "steady_lock_p50_speedup": 0.9,                       # drop: flags
        "steady_lock_engaged": False,
    }}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {
        "extra.host_allreduce_latency_us_p50_locked_np4.4B_us",
        "extra.steady_lock_p50_speedup"}
    assert regs["extra.host_allreduce_latency_us_p50_locked_np4.4B_us"][
        "rise_pct"] == 100.0
    assert regs["extra.steady_lock_p50_speedup"]["drop_pct"] > 45
    # A latency WIN never flags.
    cur2 = {"extra": {
        "host_allreduce_latency_us_p50_locked_np4": {"4B_us": 40.0,
                                                     "64KB_us": 150.0},
        "steady_lock_p50_speedup": 2.5,
    }}
    assert bench.find_regressions(prev, cur2) == {}


def test_find_regressions_persistent_arm_key_directions():
    """ISSUE 17 keys: the persistent arm's p50 `*_us` leaves gate
    exactly like the locked/off arms (regress on RISE), the
    steady_persistent_p50_speedup ratio gates like a throughput key,
    and the flat raw-socket ping-pong floor — whose trailing `_np4`
    tag would default the direction to higher-is-better — is pinned
    lower-is-better via the `_us_p50_np4` suffix."""
    prev = {"extra": {
        "host_allreduce_latency_us_p50_persistent_np4": {"4B_us": 50.0},
        "host_allreduce_latency_us_p99_persistent_np4": {"4B_us_p99": 150.0},
        "steady_persistent_p50_speedup": 1.6,
        "raw_socket_pingpong_us_p50_np4": 20.0,
    }}
    cur = {"extra": {
        "host_allreduce_latency_us_p50_persistent_np4": {"4B_us": 100.0},
        "host_allreduce_latency_us_p99_persistent_np4": {
            "4B_us_p99": 600.0},  # p99 swing: weather, ungated
        "steady_persistent_p50_speedup": 0.8,             # drop: flags
        "raw_socket_pingpong_us_p50_np4": 40.0,           # rise: flags
    }}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {
        "extra.host_allreduce_latency_us_p50_persistent_np4.4B_us",
        "extra.steady_persistent_p50_speedup",
        "extra.raw_socket_pingpong_us_p50_np4"}
    assert regs["extra.raw_socket_pingpong_us_p50_np4"]["rise_pct"] == 100.0
    # Wins in every key never flag (the ping-pong DROP is a win).
    assert bench.find_regressions(cur, prev) == {}


def test_find_regressions_threshold_boundary():
    prev = {"value": 100.0}
    assert bench.find_regressions(prev, {"value": 91.0}) == {}
    assert "value" in bench.find_regressions(prev, {"value": 89.0})


def test_previous_bench_picks_newest_round(tmp_path):
    for n, v in ((3, 10.0), (4, 20.0)):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "rc": 0, "parsed": {"value": v}}))
    prev = bench._previous_bench(str(tmp_path))
    assert prev == {"value": 20.0}


def test_previous_bench_absent_or_corrupt(tmp_path):
    assert bench._previous_bench(str(tmp_path)) is None
    (tmp_path / "BENCH_r01.json").write_text("{not json")
    assert bench._previous_bench(str(tmp_path)) is None


def test_find_regressions_skips_persisted_regression_subtree():
    """A round that was itself flagged persists its `regression` gate
    output; the next round must not flatten it into spurious
    regression.<metric>.prev comparisons (only real metrics compare)."""
    prev = {"value": 100.0,
            "regression": {"extra.busbw.1MB": {"prev": 0.4, "cur": 0.2,
                                               "drop_pct": 50.0}}}
    cur = {"value": 99.0,
           "regression": {"extra.busbw.1MB": {"prev": 0.4, "cur": 0.05,
                                              "drop_pct": 87.5}}}
    assert bench.find_regressions(prev, cur) == {}
    # Nested dicts named `regression` below top level are real metrics
    # and still compare.
    prev2 = {"extra": {"regression": {"m": 10.0}}}
    cur2 = {"extra": {"regression": {"m": 5.0}}}
    assert "extra.regression.m" in bench.find_regressions(prev2, cur2)


def test_find_regressions_sendv_key_directions():
    """ISSUE 10 transport keys: the vectored-transport busbw arm and
    its bytes-per-syscall coalescing ratio are real higher-is-better
    metrics (fewer, fatter syscalls is the win the zero-copy transport
    is gated on); the transport-mode string rides along ungated."""
    prev = {"extra": {"host_allreduce_busbw_sendv_gbps_np4": {
        "16MB": 1.2, "transport": "vectored", "bytes_per_syscall": 60000}}}
    cur = {"extra": {"host_allreduce_busbw_sendv_gbps_np4": {
        "16MB": 0.6, "transport": "zerocopy", "bytes_per_syscall": 200}}}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {
        "extra.host_allreduce_busbw_sendv_gbps_np4.16MB",
        "extra.host_allreduce_busbw_sendv_gbps_np4.bytes_per_syscall"}


def test_find_regressions_elastic_churn_key_directions():
    """ISSUE 16 keys: the chaos harness's churn-recovery latencies
    (`elastic_recovery_ms`, `steady_relock_after_join_ms`) gate
    lower-is-better via the `_ms` leaf suffix — a rise flags, a drop
    is an improvement and never does."""
    prev = {"extra": {"elastic_recovery_ms": 320.0,
                      "steady_relock_after_join_ms": 700.0}}
    cur = {"extra": {"elastic_recovery_ms": 650.0,
                     "steady_relock_after_join_ms": 550.0}}
    regs = bench.find_regressions(prev, cur)
    assert "extra.elastic_recovery_ms" in regs
    assert regs["extra.elastic_recovery_ms"]["rise_pct"] > 100
    assert "extra.steady_relock_after_join_ms" not in regs
    regs2 = bench.find_regressions(
        {"extra": {"steady_relock_after_join_ms": 700.0}},
        {"extra": {"steady_relock_after_join_ms": 1200.0}})
    assert "extra.steady_relock_after_join_ms" in regs2


def test_find_regressions_moe_dispatch_key_directions():
    """ISSUE 18 keys: the MoE dispatch arms
    (`moe_tokens_per_sec_{gspmd,none,bf16,int8}`) are throughput
    metrics — higher is better, gated on drops, an int8 win over the
    gspmd reference never flags — and `moe_dispatch_bytes_saved_pct`
    is a static efficiency metric that gates higher-is-better like
    `wire_bytes_saved_pct` (a drop means the codec's byte accounting
    or block geometry regressed, which no tokens/sec noise excuses)."""
    prev = {"extra": {"moe_tokens_per_sec_gspmd": 9.0e3,
                      "moe_tokens_per_sec_none": 9.1e3,
                      "moe_tokens_per_sec_bf16": 1.1e4,
                      "moe_tokens_per_sec_int8": 1.3e4,
                      "moe_dispatch_bytes_saved_pct": 74.5}}
    cur = {"extra": {"moe_tokens_per_sec_gspmd": 8.8e3,   # noise: silent
                     "moe_tokens_per_sec_none": 9.2e3,    # noise: silent
                     "moe_tokens_per_sec_bf16": 7.0e3,    # drop: flags
                     "moe_tokens_per_sec_int8": 1.6e4,    # gain: silent
                     "moe_dispatch_bytes_saved_pct": 49.0}}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {"extra.moe_tokens_per_sec_bf16",
                         "extra.moe_dispatch_bytes_saved_pct"}
    assert regs["extra.moe_tokens_per_sec_bf16"]["drop_pct"] > 35
    assert regs["extra.moe_dispatch_bytes_saved_pct"]["drop_pct"] > 30
    # A single-device round (gspmd key only) against a full round must
    # not flag the absent island keys.
    assert bench.find_regressions(
        prev, {"extra": {"moe_tokens_per_sec_gspmd": 8.9e3}}) == {}


def test_find_regressions_migration_key_directions():
    """ISSUE 19 satellite: the direct-migration A/B keys gate in the
    right directions — `serve_migration_p50_ms` rides the latency
    inversion (a rise is the regression), the speedup ratio and the
    byte savings gate higher-is-better, and the move tally is a
    direction-less counter."""
    prev = {"extra": {"serve_migration_p50_ms": 6.0,
                      "serve_migration_direct_over_relayed": 1.5,
                      "serve_migration_bytes_saved_pct": 50.0,
                      "serve_migration_direct_count": 48.0}}
    # Direct path got slower AND lost its edge AND stopped saving
    # bytes; the count swing must not trip anything.
    cur = {"extra": {"serve_migration_p50_ms": 9.0,
                     "serve_migration_direct_over_relayed": 1.0,
                     "serve_migration_bytes_saved_pct": 0.0,
                     "serve_migration_direct_count": 16.0}}
    regs = bench.find_regressions(prev, cur)
    assert set(regs) == {"extra.serve_migration_p50_ms",
                         "extra.serve_migration_direct_over_relayed",
                         "extra.serve_migration_bytes_saved_pct"}
    assert regs["extra.serve_migration_p50_ms"]["rise_pct"] == 50.0
    # Latency fell, ratio rose, savings held: a clean round reports
    # nothing (the count stays ungated in this direction too).
    cur2 = {"extra": {"serve_migration_p50_ms": 4.0,
                      "serve_migration_direct_over_relayed": 1.8,
                      "serve_migration_bytes_saved_pct": 50.0,
                      "serve_migration_direct_count": 96.0}}
    assert bench.find_regressions(prev, cur2) == {}


def test_find_regressions_trace_observability_keys_ungated():
    """ISSUE 20 satellite: the observability-tax keys are trajectory
    keys — `serve_trace_overhead_pct` swinging up (or down: LESS
    overhead must never read as a higher-is-better drop) and
    `flight_dump_ms` multiplying must trip nothing. `_dump_ms` must
    stay in UNGATED_SUFFIXES or the `_ms` suffix would latency-gate
    it."""
    prev = {"extra": {"serve_trace_overhead_pct": 1.5,
                      "flight_dump_ms": 0.4}}
    cur = {"extra": {"serve_trace_overhead_pct": 0.2,   # improvement
                     "flight_dump_ms": 4.0}}            # 10x rise
    assert bench.find_regressions(prev, cur) == {}
    cur2 = {"extra": {"serve_trace_overhead_pct": 30.0,
                      "flight_dump_ms": 0.1}}
    assert bench.find_regressions(prev, cur2) == {}
    assert "_dump_ms" in bench.UNGATED_SUFFIXES
    assert "_overhead_pct" in bench.UNGATED_SUFFIXES


_PARENT_DRIVE = r"""
import json, os, subprocess, sys
sys.path.insert(0, {root!r})
import bench

real_run = subprocess.run


def fake_run(cmd, **kw):
    # No chip here: every worker is replaced by a stub that prints its
    # tagged line, and the MoE one dies after printing — the parent's
    # own code path (spawn, parse, budget gates, exit code) is real.
    flag = cmd[-1]
    tags = {{"--resnet-worker": 'RESNET {{"value": 123.4}}',
            "--transformer-worker": 'TFEXTRA {{"transformer_std_mfu_pct": 1.0}}',
            "--moe-worker": 'MOEEXTRA {{"moe_tokens_per_sec_gspmd": 2.0}}',
            "--serve-worker": 'SERVEEXTRA {{"serve_tokens_per_sec_per_chip": 3.0}}',
            "--elastic-chaos-worker": 'ELASTICEXTRA {{"elastic_recovery_ms": 4.0}}'}}
    rc = 7 if flag == "--moe-worker" else 0
    return real_run([sys.executable, "-c",
                     "import sys; print(%r); sys.exit(%d)" % (tags[flag], rc)],
                    **kw)


subprocess.run = fake_run
try:
    bench.main()
except SystemExit as e:
    print("EXIT", e.code)
print("JAX_IN_PARENT", "jax" in sys.modules)
"""


def test_parent_never_imports_jax_and_fails_on_dead_worker(tmp_path):
    """One process per chip: a parent that has touched JAX holds the
    chip and its workers cannot get it, so ``main()`` must run every
    worker without ``jax`` ever entering its ``sys.modules``. A worker
    that dies keeps what it printed but makes the run exit non-zero —
    a crashed arm must not look like a skipped one."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, BENCH_SKIP_BUS="1")
    proc = subprocess.run(
        [sys.executable, "-c", _PARENT_DRIVE.format(root=root)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert lines[-1] == "JAX_IN_PARENT False", proc.stdout
    assert lines[-2] == "EXIT 1", proc.stdout
    payload = json.loads(lines[-3])
    assert payload["value"] == 123.4
    assert payload["extra"]["moe_tokens_per_sec_gspmd"] == 2.0
    assert payload["extra"]["elastic_recovery_ms"] == 4.0
    assert payload["failed_workers"] == ["--moe-worker: rc=7"]
    assert "--moe-worker rc=7" in proc.stderr


def test_no_swallowed_worker_failures():
    """The three ``except Exception: pass`` that let a chip-less worker
    print nothing and exit 0 are gone, and stay gone."""
    src = open(bench.__file__).read()
    assert "except Exception:\n        pass" not in src
