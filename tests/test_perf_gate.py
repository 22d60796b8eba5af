"""Perf-gate logic tests (ref tools/ci_op_benchmark.sh — the CI gate must
actually fire on a regression; the round-2 op gate never ran because it
looked for the snapshot at the wrong path, VERDICT r2 weak #3)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import perf_gate  # noqa: E402


def test_op_snapshot_path_exists():
    """The committed snapshot must be where the gate looks for it."""
    assert os.path.exists(perf_gate.OP_SNAPSHOT), perf_gate.OP_SNAPSHOT
    with open(perf_gate.OP_SNAPSHOT) as fh:
        snap = json.load(fh)
    times = perf_gate._op_times(snap)
    assert len(times) >= 50, f"want >=50 hot ops, have {len(times)}"


def test_op_gate_fails_on_seeded_regression(tmp_path):
    with open(perf_gate.OP_SNAPSHOT) as fh:
        snap = json.load(fh)
    slow = [dict(e, paddle_gpu_time=e["paddle_gpu_time"] * 2.0)
            for e in snap]
    p = tmp_path / "slow.json"
    p.write_text(json.dumps(slow))
    assert perf_gate.op_gate(str(p), op_tolerance=0.25) == 1


def test_op_gate_passes_identical(tmp_path):
    with open(perf_gate.OP_SNAPSHOT) as fh:
        snap = json.load(fh)
    p = tmp_path / "same.json"
    p.write_text(json.dumps(snap))
    assert perf_gate.op_gate(str(p), op_tolerance=0.25) == 0


def test_compare_ops_tolerance_boundary():
    old = {"matmul": 1.0, "relu": 2.0}
    new = {"matmul": 1.24, "relu": 2.6}
    bad = perf_gate.compare_ops(old, new, 0.25)
    assert [b[0] for b in bad] == ["relu"]


def test_suite_compare_flags_regressions_and_missing():
    baseline = {"a_tok_s": 100000.0, "b_img_s": 2000.0, "c_tok_s": 50.0}
    rows = [{"metric": "a_tok_s", "value": 99000.0},   # within 7%
            {"metric": "b_img_s", "value": 1500.0}]    # regressed; c missing
    bad = perf_gate.compare_suite(baseline, rows, 0.07)
    names = sorted(b[0] for b in bad)
    assert names == ["b_img_s", "c_tok_s"]


def test_suite_gate_with_rows(monkeypatch, tmp_path):
    """suite_gate end-to-end against an injected baseline + rows."""
    snap = tmp_path / "model_bench_baseline.json"
    snap.write_text(json.dumps({"m1": 100.0}))
    monkeypatch.setattr(perf_gate, "MODEL_SNAPSHOT", str(snap))
    assert perf_gate.suite_gate(0.07, rows=[{"metric": "m1",
                                             "value": 99.0}]) == 0
    assert perf_gate.suite_gate(0.07, rows=[{"metric": "m1",
                                             "value": 80.0}]) == 1


def test_model_snapshot_exists_and_covers_driver_configs():
    assert os.path.exists(perf_gate.MODEL_SNAPSHOT), perf_gate.MODEL_SNAPSHOT
    with open(perf_gate.MODEL_SNAPSHOT) as fh:
        base = json.load(fh)
    for want in ("gpt2_small", "ernie", "1p3b", "long_context", "resnet50"):
        assert any(want in k for k in base), (want, list(base))


def test_ratio_gate_flags_slow_fit_path():
    """The hapi_fit row is gated AGAINST the same run's hand-rolled gpt2
    row (no committed baseline needed for a new metric)."""
    rows = [{"metric": "gpt2_small_pretrain_tokens_per_sec_per_chip",
             "value": 100000.0},
            {"metric": "hapi_fit_tokens_per_sec", "value": 85000.0}]
    bad = perf_gate.compare_ratios(rows)
    assert len(bad) == 1 and bad[0][0] == "hapi_fit_tokens_per_sec"
    rows[1]["value"] = 95000.0
    assert perf_gate.compare_ratios(rows) == []
    # either metric missing: skipped (baseline comparison flags missing)
    assert perf_gate.compare_ratios(rows[:1]) == []


def test_suite_has_hapi_fit_row():
    import bench
    assert "hapi_fit" in bench.SUITE


def test_suite_has_spec_rows():
    import bench
    assert "serving_spec" in bench.SUITE
    assert "decode_spec" in bench.SUITE


def test_ratio_gate_holds_spec_serving_to_nonspec():
    """serving_spec is gated >= 1.0x the SAME-RUN serving row: exact
    greedy equivalence means speculation may never lose throughput."""
    rows = [{"metric": "gpt2_serving_8stream_device_tokens_per_sec_per_chip",
             "value": 10000.0},
            {"metric":
             "gpt2_serving_spec_8stream_device_tokens_per_sec_per_chip",
             "value": 9500.0}]
    bad = perf_gate.compare_ratios(rows)
    assert len(bad) == 1 and bad[0][0].startswith("gpt2_serving_spec")
    rows[1]["value"] = 10000.0  # exactly 1.0x passes
    assert perf_gate.compare_ratios(rows) == []
    rows[1]["value"] = 14000.0
    assert perf_gate.compare_ratios(rows) == []


def test_suite_has_paged_row():
    import bench
    assert "serving_paged" in bench.SUITE


def test_ratio_gate_holds_paged_serving_to_dense():
    """serving_paged (16 streams through the page pool) is gated >= 1.0x
    the SAME-RUN dense serving row: the page-table indirection must pay
    for itself at 2x the admitted concurrency."""
    rows = [{"metric": "gpt2_serving_8stream_device_tokens_per_sec_per_chip",
             "value": 10000.0},
            {"metric":
             "gpt2_serving_paged_16stream_device_tokens_per_sec_per_chip",
             "value": 9000.0}]
    bad = perf_gate.compare_ratios(rows)
    assert len(bad) == 1 and bad[0][0].startswith("gpt2_serving_paged")
    rows[1]["value"] = 11000.0
    assert perf_gate.compare_ratios(rows) == []


def test_pool_leak_gate_fires_on_leaked_pages():
    """A paged row whose pool did not drain to 0 (refcount bug) fails
    the suite gate; 0 leaked (or a row without the key) passes."""
    rows = [{"metric": "paged", "metrics": {"kv_pages_leaked": 3}},
            {"metric": "dense", "metrics": {}}]
    assert perf_gate.compare_pool_leaks(rows) == [("paged", 3)]
    rows[0]["metrics"]["kv_pages_leaked"] = 0
    assert perf_gate.compare_pool_leaks(rows) == []


def test_host_timed_device_metric_fails_suite():
    """A *device* throughput row that fell back to host wall timing
    (broken profiler trace on a TPU run) must fail with a named cause,
    never gate wall clock against device baselines."""
    rows = [{"metric": "gpt2_serving_8stream_device_tokens_per_sec_per_chip",
             "value": 9000.0, "timing": "host"},
            {"metric": "resnet50_input_pipeline_imgs_per_sec",
             "value": 100.0, "timing": "host"},   # host metric: fine
            {"metric": "gpt2_greedy_decode_device_tokens_per_sec_per_chip",
             "value": 9000.0, "timing": "device"}]
    assert perf_gate.compare_timing_fallbacks(rows) == [
        "gpt2_serving_8stream_device_tokens_per_sec_per_chip"]


def test_suite_has_moe_rows():
    import bench
    assert "gpt2_moe" in bench.SUITE
    assert "serving_moe" in bench.SUITE


def test_error_rows_fail_suite_loudly(monkeypatch, tmp_path):
    """A crashed suite row (bench.py run_suite records {"error": ...}
    instead of aborting the sweep) must be a NAMED gate failure — and
    must not crash the other comparators that expect "value"."""
    rows = [{"metric": "m1", "value": 100.0},
            {"metric": "gpt2_moe", "suite_row": "gpt2_moe",
             "error": "ValueError: dtype crash (rc=1)"}]
    bad = perf_gate.compare_error_rows(rows)
    assert len(bad) == 1 and bad[0][0] == "gpt2_moe"
    assert "dtype crash" in bad[0][1]
    # the valueless row must not break the other comparators
    assert perf_gate.compare_ratios(rows) == []
    assert perf_gate.compare_suite({"m1": 100.0}, rows, 0.07) == []
    snap = tmp_path / "model_bench_baseline.json"
    snap.write_text(json.dumps({"m1": 100.0}))
    monkeypatch.setattr(perf_gate, "MODEL_SNAPSHOT", str(snap))
    assert perf_gate.suite_gate(0.07, rows=rows) == 1
    assert perf_gate.suite_gate(0.07, rows=rows[:1]) == 0


def test_moe_active_ratio_gate():
    """The MoE flagship row embeds its SAME-RUN dense-reference ratio at
    matched active params (vs_dense_active_params); the gate holds it
    >= 0.6x on device AND host-timed (CPU smoke) runs alike."""
    row = {"metric": "gpt2_moe_pretrain_tokens_per_sec_cpu_smoke",
           "value": 4000.0, "vs_dense_active_params": 0.55}
    bad = perf_gate.compare_moe_active_ratio([row])
    assert bad == [(row["metric"], 0.55)]
    row["vs_dense_active_params"] = 0.72
    assert perf_gate.compare_moe_active_ratio([row]) == []
    # rows without the key (every non-MoE row) are skipped
    assert perf_gate.compare_moe_active_ratio([{"metric": "x",
                                                "value": 1.0}]) == []


def test_ratio_gate_holds_moe_serving_to_dense():
    """serving_moe runs the IDENTICAL workload as the dense serving row
    (same streams/prompt/new_tokens), so a cross-row floor is sound
    there; gpt2_moe deliberately has NO cross-row gate (different batch
    size vs the headline row) — its matched-config gate is the embedded
    vs_dense_active_params ratio."""
    assert not any(m.startswith("gpt2_moe_pretrain")
                   for m, _, _ in perf_gate.RATIO_GATES)
    rows = [{"metric": "gpt2_serving_8stream_device_tokens_per_sec_per_chip",
             "value": 10000.0},
            {"metric":
             "gpt2_moe_serving_8stream_device_tokens_per_sec_per_chip",
             "value": 2000.0}]
    bad = perf_gate.compare_ratios(rows)
    assert len(bad) == 1 and bad[0][0].startswith("gpt2_moe_serving")
    rows[1]["value"] = 2600.0    # >= 0.25x
    assert perf_gate.compare_ratios(rows) == []


def _slo_row(ti_p=50.0, ti_f=100.0, gp_p=90.0, gp_f=100.0, lossless=True):
    return {"metric": "gpt2_serving_slo_mixed_priority_x",
            "value": 1.0,
            "metrics": {"interactive_ttft_p99_ms_priority": ti_p,
                        "interactive_ttft_p99_ms_fifo": ti_f,
                        "batch_goodput_tokens_per_s_priority": gp_p,
                        "batch_goodput_tokens_per_s_fifo": gp_f,
                        "scheduling_lossless": lossless}}


def test_slo_scheduling_gate():
    """serving_slo embeds its own same-run FIFO baseline: interactive
    ttft_p99 must land <= 0.75x FIFO, batch goodput must hold >= 0.8x
    FIFO, and no request may finish short of its token budget."""
    assert perf_gate.compare_slo_scheduling([_slo_row()]) == []
    # scheduler degraded to FIFO: interactive saw no benefit
    bad = perf_gate.compare_slo_scheduling([_slo_row(ti_p=80.0)])
    assert len(bad) == 1 and "FIFO" in bad[0][1]
    # preemption/replay cratered batch throughput below the floor
    bad = perf_gate.compare_slo_scheduling([_slo_row(gp_p=70.0)])
    assert len(bad) == 1 and "goodput" in bad[0][1]
    # a stream finished short (or errored): work was dropped, not
    # re-queued — hard fail regardless of the latency numbers
    bad = perf_gate.compare_slo_scheduling([_slo_row(lossless=False)])
    assert len(bad) == 1 and "token budget" in bad[0][1]
    # boundary: exactly at ceiling and floor passes
    assert perf_gate.compare_slo_scheduling(
        [_slo_row(ti_p=75.0, gp_p=80.0)]) == []
    # rows without the embedded evidence (every other suite row) skip
    assert perf_gate.compare_slo_scheduling(
        [{"metric": "x", "value": 1.0}]) == []
