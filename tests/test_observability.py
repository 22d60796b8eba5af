"""Runtime telemetry subsystem (observability/): registry semantics,
serving + compiled-fit instrumentation, chrome-trace counter events,
and the perf-gate recompilation tripwire.

Lean by design: one tiny serving-engine run and one 2-step fit carry all
the integration assertions (tier-1 is compile-bound on the CPU)."""

import json
import os
import re
import sys
import threading

import numpy as np
from conftest import join_within

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import hapi, io, nn, optimizer as optim
from paddle_hackathon_tpu.observability import (MetricRegistry, get_registry,
                                                snapshot_delta)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_labels():
    r = MetricRegistry()
    c = r.counter("reqs_total", "requests")
    c.labels(engine="a").inc()
    c.labels(engine="a").inc(2)
    c.labels(engine="b").inc(5)
    assert c.labels(engine="a").value == 3
    assert r.total("reqs_total") == 8
    assert r.total("reqs_total", engine="b") == 5
    g = r.gauge("depth")
    g.set(4)
    g.dec()
    assert g.value == 3
    # counters are monotonic; families are type-stable
    import pytest
    with pytest.raises(ValueError):
        c.labels(engine="a").inc(-1)
    with pytest.raises(ValueError):
        r.gauge("reqs_total")


def test_histogram_buckets_and_quantiles():
    r = MetricRegistry()
    h = r.histogram("lat_seconds", buckets=(0.001, 0.01, 0.1, 1.0)).labels()
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):   # 5.0 -> +Inf bucket
        h.observe(v)
    assert h.count == 5
    assert abs(h.sum - 5.0605) < 1e-9
    snap = r.snapshot()["metrics"]["lat_seconds"]["series"][0]
    # cumulative bucket counts
    assert snap["buckets"] == {"0.001": 1, "0.01": 3, "0.1": 4, "1": 4,
                               "+Inf": 5}
    # quantiles interpolate inside the right bucket
    assert 0.001 <= snap["p50"] <= 0.01
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(0.99)
    # re-registering with the SAME buckets is fine; different buckets
    # would silently misfile observations, so it raises
    r.histogram("lat_seconds", buckets=(0.001, 0.01, 0.1, 1.0))
    r.histogram("lat_seconds")   # buckets unspecified: don't-care
    import pytest
    with pytest.raises(ValueError):
        r.histogram("lat_seconds", buckets=(1.0, 2.0))


def test_expose_text_parses_as_prometheus():
    r = MetricRegistry()
    r.counter("a_total", "with \"quotes\"").labels(k='v"q').inc()
    r.gauge("g").set(1.5)
    r.histogram("h_seconds", unit="s").observe(0.02)
    text = r.expose_text()
    line_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*='
        r'"(?:[^"\\]|\\.)*"(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
        r' [^ ]+$')
    kinds = {}
    for ln in text.splitlines():
        if not ln.strip():
            continue
        if ln.startswith("# TYPE"):
            _, _, name, kind = ln.split()
            kinds[name] = kind
            continue
        if ln.startswith("#"):
            continue
        assert line_re.match(ln), ln
    assert kinds == {"a_total": "counter", "g": "gauge",
                     "h_seconds": "histogram"}
    # histogram exposition: cumulative buckets + sum + count, with +Inf
    assert 'h_seconds_bucket{le="+Inf"} 1' in text
    assert "h_seconds_sum 0.02" in text
    assert "h_seconds_count 1" in text


def test_histogram_tracks_max_for_overflow_bucket():
    r = MetricRegistry()
    h = r.histogram("lat2_seconds", buckets=(0.1, 1.0)).labels()
    assert np.isnan(h.max)
    for v in (0.05, 0.5, 300.0):   # 300 s lands in the +Inf bucket
        h.observe(v)
    assert h.max == 300.0
    # the tail quantile interpolates up to the OBSERVED max instead of
    # clamping to buckets[-1]=1.0 (which silently under-reported any
    # latency past the top bound)
    assert h.quantile(1.0) == 300.0
    assert h.quantile(0.9) > 1.0
    assert h.quantile(0.3) <= 1.0             # low ranks unaffected
    s = r.snapshot()["metrics"]["lat2_seconds"]["series"][0]
    assert s["max"] == 300.0                  # surfaced in snapshot()
    h2 = r.histogram("empty_seconds").labels()
    assert r.snapshot()["metrics"]["empty_seconds"]["series"][0]["max"] \
        is None
    # in-range observations keep the old interpolation: inside the
    # covering bucket, never pushed up toward the observed max
    assert h2.observe(0.5) is None
    assert 0.46 < h2.quantile(0.5) <= 1.0


def test_expose_text_hostile_label_values():
    r = MetricRegistry()
    hostile = 'back\\slash "quote"\nnewline'
    r.counter("hostile_total", 'help with \\ and\nnewline').labels(
        k=hostile).inc()
    text = r.expose_text()
    # label value escaping per the text exposition format: \ " and LF
    assert (r'k="back\\slash \"quote\"\nnewline"') in text
    # one metric line must stay ONE line (a raw newline would split it)
    metric_lines = [ln for ln in text.splitlines()
                    if ln.startswith("hostile_total")]
    assert len(metric_lines) == 1 and metric_lines[0].endswith(" 1.0")
    # HELP text escapes backslash + newline too
    help_lines = [ln for ln in text.splitlines() if ln.startswith("# HELP")]
    assert help_lines == [r"# HELP hostile_total help with \\ and\nnewline"]


def test_snapshot_delta():
    r = MetricRegistry()
    c = r.counter("ticks_total")
    h = r.histogram("t_seconds")
    g = r.gauge("depth")
    c.inc(10)
    h.observe(1.0)
    g.set(7)
    s1 = r.snapshot()
    c.inc(5)
    h.observe(2.0)
    h.observe(3.0)
    g.set(2)
    d = snapshot_delta(s1, r.snapshot())
    m = d["metrics"]
    assert m["ticks_total"]["series"][0]["value"] == 5       # subtracted
    assert m["t_seconds"]["series"][0]["count"] == 2
    assert m["t_seconds"]["series"][0]["sum"] == 5.0
    assert m["depth"]["series"][0]["value"] == 2             # gauges: current


def test_thread_safety_smoke():
    r = MetricRegistry()
    c = r.counter("n_total").labels()
    h = r.histogram("v_seconds").labels()

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    join_within(ts, 60, "the metric writers")
    assert c.value == 4000
    assert h.count == 4000


def test_disabled_registry_records_nothing():
    r = MetricRegistry(enabled=False)
    r.counter("c_total").inc(5)
    r.gauge("g").set(1)
    r.histogram("h").observe(1.0)
    snap = r.snapshot()["metrics"]
    assert snap["c_total"]["series"][0]["value"] == 0
    assert snap["h"]["series"][0]["count"] == 0
    r.enable()
    r.counter("c_total").inc()
    assert r.total("c_total") == 1


# ---------------------------------------------------------------------------
# serving instrumentation
# ---------------------------------------------------------------------------

def test_serving_engine_metrics():
    from paddle_hackathon_tpu.inference import ServingEngine
    from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(3)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4, auto_run=False)
    rs = np.random.RandomState(5)
    reqs = [eng.submit(rs.randint(0, 128, (6,)).astype(np.int32), 8)
            for _ in range(2)]
    eng.run_until_idle()
    assert all(r.done for r in reqs)

    reg = get_registry()
    eid = eng._engine_id
    # the back-compat stats view reads the same counters
    assert eng.stats["requests"] == 2
    assert eng.stats["tokens"] == 16
    assert dict(eng.stats)["ticks"] == eng.stats["ticks"] > 0
    assert reg.total("serving_tokens_total", engine=eid) == 16
    assert reg.total("serving_requests_total", engine=eid) == 2
    # per-request latency series populated
    assert eng._h_ttft.count == 2 and eng._h_ttft.quantile(0.5) > 0
    assert eng._h_tpot.count == 2
    assert eng._h_e2e.count == 2
    # tick durations split by flavor: this run prefills then decodes
    assert eng._h_tick["prefill"].count > 0
    assert eng._h_tick["decode"].count > 0
    assert eng._h_tick["spec"].count == 0
    # occupancy/queue gauges exist (post-drain: empty)
    assert reg.total("serving_batch_occupancy", engine=eid) == 0
    assert reg.total("serving_queue_depth", engine=eid) == 0
    # every tick flavor that ran was counted as a program build
    builds = reg.total("jit_builds_total", engine=eid)
    assert builds >= 2, builds
    # and the whole thing exports as Prometheus text
    text = reg.expose_text()
    assert f'serving_ttft_seconds_count{{engine="{eid}"}} 2' in text
    # shutdown drops this engine's series from the registry (engine churn
    # must not grow it forever) while the stats view keeps its handles
    eng.shutdown()
    assert reg.total("serving_tokens_total", engine=eid) == 0
    assert f'engine="{eid}"' not in reg.expose_text()
    assert eng.stats["tokens"] == 16


# ---------------------------------------------------------------------------
# compiled-fit instrumentation
# ---------------------------------------------------------------------------

class _DS(io.Dataset):
    def __init__(self, n=8, d=10):
        rng = np.random.RandomState(0)
        self.x = rng.randn(n, d).astype(np.float32)
        self.y = (self.x.sum(1) > 0).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def test_compiled_fit_metrics(tmp_path):
    reg = get_registry()
    before = reg.snapshot()
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(10, 8), nn.ReLU(), nn.Linear(8, 2))
    model = hapi.Model(net)
    model.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                       parameters=net.parameters()),
                  loss=nn.CrossEntropyLoss())
    snap_path = str(tmp_path / "snap.json")
    cb = hapi.callbacks.MetricsCallback(log_freq=1, snapshot_path=snap_path,
                                        verbose=0)
    model.fit(_DS(), epochs=1, batch_size=4, verbose=0, log_freq=1,
              callbacks=[cb])
    assert model._fit_used_compiled
    delta = snapshot_delta(before, reg.snapshot())["metrics"]

    def series(name, **labels):
        for s in delta[name]["series"]:
            if all(s["labels"].get(k) == v for k, v in labels.items()):
                return s
        raise AssertionError(f"{name} {labels} missing from delta")

    # 2 steps at log_freq=1: the step after the compile window is timed
    assert series("train_step_seconds", path="hapi_compiled")["count"] >= 1
    assert series("train_tokens_per_sec", path="hapi_compiled")["value"] > 0
    assert series("jit_builds_total",
                  site="hapi.compiled_trainer")["value"] == 1
    assert series("jit_build_seconds",
                  site="hapi.compiled_trainer")["count"] == 1
    assert series("input_wait_seconds", site="device_prefetch")["count"] >= 2
    # MetricsCallback persisted a loadable snapshot with the delta section
    saved = json.load(open(snap_path))
    assert "delta_from_train_begin" in saved
    assert "train_step_seconds" in saved["metrics"]


# ---------------------------------------------------------------------------
# chrome-trace counter events + cross-stack merge
# ---------------------------------------------------------------------------

def test_chrome_trace_counter_events(tmp_path):
    from paddle_hackathon_tpu.profiler import (Profiler, export_chrome_tracing,
                                               make_scheduler, merge_traces)
    out = str(tmp_path / "tr")
    p = Profiler(scheduler=make_scheduler(closed=0, ready=0, record=1,
                                          repeat=1),
                 on_trace_ready=export_chrome_tracing(out, "rank0"),
                 use_device_tracer=False)
    reg = get_registry()
    p.start()
    reg.counter("tick_counter_total").labels(engine="tr").inc()
    reg.gauge("tick_depth").labels(engine="tr").set(5)
    p.stop()
    path = os.path.join(out, os.listdir(out)[0])
    trace = json.load(open(path))
    counters = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
    names = {e["name"] for e in counters}
    assert "tick_counter_total{engine=tr}" in names
    assert "tick_depth{engine=tr}" in names
    assert all("value" in e["args"] for e in counters)
    # updates outside a recording window are NOT mirrored
    reg.gauge("tick_depth").labels(engine="tr").set(9)
    from paddle_hackathon_tpu import profiler as prof_mod
    assert not prof_mod._recorder.counters

    # counter events survive the cluster merge under the new pid
    merged = merge_traces([path], align_marker=None)
    mc = [e for e in merged["traceEvents"] if e.get("ph") == "C"]
    assert len(mc) == len(counters)
    assert all(e["pid"] == 0 for e in mc)


def test_cross_stack_mixed_named_unnamed_pids(tmp_path):
    """Named ranks keep their encoded pid; unnamed files deterministically
    take the free ones (the old code renumbered EVERYTHING on collision)."""
    from paddle_hackathon_tpu.profiler import merge_traces
    from paddle_hackathon_tpu.profiler.cross_stack import _assign_ranks

    paths = []
    for fname in ("worker1_step3.json", "adhoc.json"):
        fp = tmp_path / fname
        json.dump({"traceEvents": [
            {"name": "step", "ph": "X", "pid": 99, "tid": 1,
             "ts": 10.0, "dur": 1.0}]}, open(fp, "w"))
        paths.append(str(fp))

    assert _assign_ranks(sorted(paths)) == [0, 1]   # adhoc first (sorted)
    merged = merge_traces(paths)
    by_pid = {e["pid"] for e in merged["traceEvents"] if e.get("ph") == "X"}
    assert by_pid == {0, 1}
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("name") == "process_name"}
    assert any(n.startswith("rank 1 (worker1") for n in names)
    # named collision (two files claiming rank 0) -> positional fallback
    clash = [str(tmp_path / "rank0_a.json"), str(tmp_path / "rank-0_b.json")]
    for c in clash:
        json.dump({"traceEvents": []}, open(c, "w"))
    assert _assign_ranks(sorted(clash)) == [0, 1]


# ---------------------------------------------------------------------------
# perf-gate tripwire + dump tool
# ---------------------------------------------------------------------------

def test_perf_gate_compile_count_tripwire():
    import perf_gate
    rows = [
        {"metric": "serving", "value": 1.0,
         "metrics": {"jit_builds_warm": 4, "jit_builds_total": 4}},
        {"metric": "serving_spec", "value": 1.0,
         "metrics": {"jit_builds_warm": 4, "jit_builds_total": 6}},
        {"metric": "gpt2", "value": 1.0},   # no telemetry: skipped
    ]
    assert perf_gate.compare_metrics(rows) == [("serving_spec", 4, 6)]
    assert perf_gate.compare_metrics(rows[:1]) == []


def test_metrics_dump_render_and_diff(capsys):
    import metrics_dump
    r = MetricRegistry()
    r.counter("n_total").labels(engine="e").inc(3)
    r.gauge("depth").set(2)
    r.histogram("t_seconds").observe(0.5)
    s1 = r.snapshot()
    r.counter("n_total").labels(engine="e").inc(4)
    r.gauge("depth").set(9)
    s2 = r.snapshot()
    n = metrics_dump.render(s1)
    assert n == 3
    out = capsys.readouterr().out
    assert "n_total{engine=e}" in out and "histogram" in out
    n = metrics_dump.render_diff(s1, s2)
    assert n == 2   # counter delta + gauge change; histogram unchanged
    out = capsys.readouterr().out
    assert "+4" in out and "2 -> 9" in out


def test_metrics_dump_diff_added_and_removed_series(capsys):
    """Families/children present in only one snapshot (engine churn
    drops labelled series; new sites appear mid-run) render as
    added/removed instead of raising or silently vanishing."""
    import metrics_dump
    r = MetricRegistry()
    r.counter("churn_total").labels(engine="old").inc(2)
    r.gauge("old_depth").set(1)
    s1 = r.snapshot()
    r.drop_labels(engine="old")          # series gone from s2
    del r._families["old_depth"]         # whole family gone from s2
    r.counter("churn_total").labels(engine="new").inc(5)
    r.histogram("fresh_seconds").observe(0.25)   # family only in s2
    s2 = r.snapshot()
    n = metrics_dump.render_diff(s1, s2)
    out = capsys.readouterr().out
    assert n == 4
    rows = {ln.split()[0]: " ".join(ln.split()[1:])
            for ln in out.splitlines()}
    assert rows["churn_total{engine=new}"] == "+5 (added)"
    assert rows["fresh_seconds"] == "+1 obs (added) sum +0.25"
    assert rows["churn_total{engine=old}"] == "(removed)"
    assert rows["old_depth"] == "(removed)"
    # symmetric direction still renders (nothing raises)
    assert metrics_dump.render_diff(s2, s1) == 4
