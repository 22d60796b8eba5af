"""The per-layer readers that join the program's own names to a trace
(``benchmark/phase_times.py`` and the eight readers of PR 26), on a
synthetic ``XLA Ops`` list and a fake census with hand-counted values.
CPU only: the numbers here are counts of nanoseconds written below, never
a device's."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = "gpt-tiny-rehearsal.train-s64"
SITE = "parallel.sharded_train_step"
MS = 1e6  # ns

READERS = ("fwd_ms", "bwd_ms", "opt_ms", "head_ce_ms",
           "phase_unattributed_share", "step_trace_lower_s",
           "step_compile_s", "analysis_pass_s")

# (phase, component, mixed) by instruction name, as
# observability/programs.py phase_census gives it
CENSUS = {
    "fusion.1": ("fwd", "attn", False),
    "convolution_add_fusion.2": ("fwd", "lm_head", False),
    "subtract_reduce_fusion.3": ("fwd", "ce", True),
    "multiply_reduce_fusion.4": ("bwd", "mlp", True),
    "fusion.5": ("bwd", "lm_head", False),
    "multiply_reduce_fusion.6": ("clip", "clip", False),
    "subtract_convert_fusion.7": ("update", "update", True),
    "copy-start.8": ("other", "", False),
    "while.9": ("other", "", False),
    "fusion.10": ("fwd", "mlp", False),
}

# one step's events, (own name, start ms, duration ms); fusion.10 runs
# inside while.9 (3 of its 5 ms), custom-call.11 is not in the census
STEP = (("fusion.1", 0, 10), ("convolution_add_fusion.2", 10, 4),
        ("subtract_reduce_fusion.3", 14, 2),
        ("multiply_reduce_fusion.4", 16, 20), ("fusion.5", 36, 6),
        ("multiply_reduce_fusion.6", 42, 1),
        ("subtract_convert_fusion.7", 43, 8), ("copy-start.8", 51, 1),
        ("while.9", 52, 5), ("fusion.10", 53, 3),
        ("custom-call.11", 57, 2.5))
STEP_MS, STEPS = 60.0, 3   # a step every 60 ms: 0.5 ms idle at its end


def _ops():
    return [(f"%{name} = bf16[8,128]{{1,0}} fusion(%multiply_reduce_fusion.4)",
             (s * STEP_MS + start) * MS, dur * MS)
            for s in range(STEPS) for name, start, dur in STEP]


def _run(census=CENSUS, traced=True, peaks=True, history=None,
         monkeypatch=None):
    from benchmark import phase_times, trace_reduce

    class Registry:
        def phase_census(self, site):
            assert site == SITE
            return census

        def snapshot(self):
            return {"sites": {SITE: {"history": history or []}}}

    if monkeypatch is not None:
        monkeypatch.setattr(phase_times, "_registry", Registry)
    ops = _ops()
    return {
        "config": {"program": {"jit_site": SITE}, "parameters_millions": 2.0,
                   "training": {"param_dtype": "bfloat16",
                                "moment_dtype": "float32",
                                "master_weights": False}},
        "peaks": {"hbm_bytes_per_s": 1e9} if peaks else None,
        "facts": {"traced": {"ops": ops,
                             "busy_s": trace_reduce.busy_ns(ops) / 1e9}
                  if traced else None},
        "notes": []}


def _read(name, run):
    from benchmark import run as harness
    return harness.load_module("layer_metrics", name).read(run)


HISTORY = [
    {"build": 1, "compile_s": 30.0, "trace_s": 9.0, "lower_s": 4.0,
     "backend_compile_s": 15.0, "cache_hit": False, "analysis_s": 2.5,
     "analysis": {"census_s": 0.4}},
    {"build": 2, "compile_s": 8.0, "trace_s": 3.0, "lower_s": 1.0,
     "backend_compile_s": 3.5, "cache_hit": True, "analysis_s": 0.5,
     "analysis": None},
]


@pytest.mark.parametrize("name, want", [
    ("fwd_ms", 10 + 4 + 2 + 3),            # fusion.10 counts under its own
    ("bwd_ms", 20 + 6),
    ("opt_ms", 1 + 8),
    ("head_ce_ms", 4 + 2 + 6),             # lm_head fwd + ce + lm_head bwd
    # copy-start 1 + the while's own 2 (5 less its body's 3) + the op
    # the census does not hold 2.5, over 59.5 ms busy
    ("phase_unattributed_share", 100 * (1 + 2 + 2.5) / 59.5),
    ("step_trace_lower_s", 9 + 4 + 3 + 1),
    ("step_compile_s", 15 + 3.5),
    ("analysis_pass_s", 2.5 + 0.5),
])
def test_reader_gives_the_hand_counted_value(monkeypatch, name, want):
    run = _run(history=HISTORY, monkeypatch=monkeypatch)
    assert _read(name, run) == pytest.approx(want, rel=1e-9)


def test_phase_times_add_up_to_busy_and_count_the_steps(monkeypatch):
    from benchmark import phase_times
    run = _run(monkeypatch=monkeypatch)
    times = phase_times.phase_times(run)
    assert times["steps"] == STEPS
    assert times["busy"] == pytest.approx(
        1e9 * run["facts"]["traced"]["busy_s"])
    assert sum(times["by_phase"].values()) == pytest.approx(times["busy"])
    assert sum(times["by_component"].values()) == pytest.approx(times["busy"])
    whole = sum(_read(n, run) for n in ("fwd_ms", "bwd_ms", "opt_ms")) \
        + phase_times.ms(times, "other", "absent")
    assert whole == pytest.approx(59.5)
    # mixed fusions: the ce fusion 2, the weight gradient 20, the update 8
    assert times["mixed"] == pytest.approx(STEPS * 30 * MS)
    # computed once, announced once
    assert phase_times.phase_times(run) is times
    assert sum("phase census" in n for n in run["notes"]) == 1
    # the update's least by bytes: (2+2+4+4) + (2+4+4) = 22 B a parameter
    _read("opt_ms", run)
    assert any("22 B x 2.0 M" in n and "44.00 ms" in n for n in run["notes"])


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("missing", ["census", "traced", "peaks", "clock"])
def test_reader_returns_none_where_its_source_is_missing(monkeypatch, name,
                                                         missing):
    build = name.endswith("_s")
    run = _run(census=None if missing == "census" else CENSUS,
               traced=missing != "traced", peaks=missing != "peaks",
               history=[{"build": 1, "compile_s": 30.0}]
               if missing == "clock" else HISTORY, monkeypatch=monkeypatch)
    needs = {"clock", "peaks"} if build else {"census", "traced"}
    if name == "opt_ms":
        needs.add("peaks")
    if missing in needs:
        assert _read(name, run) is None
    else:
        assert _read(name, run) is not None


def test_a_program_without_a_census_or_a_clock_reads_none(monkeypatch):
    """The parent of PR 26: its registry has no ``phase_census`` and its
    build records no split.  The readers return ``None``, never raise."""
    from benchmark import phase_times

    class OldRegistry:
        def snapshot(self):
            return {"sites": {}}

    run = _run()
    monkeypatch.setattr(phase_times, "_registry", OldRegistry)
    assert [_read(n, run) for n in READERS] == [None] * len(READERS)


def test_manifest_gives_the_new_readers_to_both_training_cells():
    from benchmark import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for cell in ("gpt3-1.3b.train-s1024", "gpt2-medium.train-s1024"):
        _, layer = harness.cell_metrics(manifest, cell)
        assert set(READERS) <= {m["name"] for m in layer}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert {by_name[n]["moves"] for n in READERS if n.endswith("_s")} \
        == {"setup_s"}


@pytest.mark.parametrize("workload", [
    TINY, "gated-conv-tiny-rehearsal.train-s64"])
def test_rehearsal_cell_runs_traced_and_reads_no_time_on_the_cpu(capsys,
                                                                 workload):
    """Every per-layer reader of the manifest is asked, the attention
    readers too, on a GPT and on a model that has no heads to name."""
    from benchmark import run as harness
    rc = harness.main(["--workload", workload, "--seed", "3000000023",
                       "--seconds", "0.3", "--trace", "1"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {}
    assert not any(n in k for k in line["rehearsal"] for n in READERS)


@pytest.mark.parametrize("chips", [1, 4])
def test_train_step_mfu_is_count_times_rate_over_peak(chips):
    """The reader multiplies what the driver put in ``facts``: the count
    of the module the configuration names (here the rehearsal's model that
    is no GPT, 497,664 operations a token) and the window's rate."""
    from benchmark import run as harness
    cfg = harness.load_json("configs", "gated-conv-tiny-rehearsal")
    count = harness.config_module(cfg, "op_count", "op_counts")
    run = {"config": cfg, "peaks": {"bf16_flops_per_s": 1e12},
           "chips": chips, "notes": [],
           "facts": {"tokens_per_s": 1e6, "seqlen": 64,
                     "train_flops_per_token":
                         count.train_flops_per_token(cfg, 64)}}
    assert _read("train_step_mfu", run) == pytest.approx(
        49.7664 / chips, rel=1e-12)
    run["peaks"] = None   # a rehearsal: no share of a peak from a CPU run
    assert _read("train_step_mfu", run) is None
