"""Event-level observability: span API, flight recorder, crash dumps,
and the HTTP introspection server.

Lean by design (tier-1 is compile-bound on the CPU): the pure-host tests
carry the API semantics; the two tests that compile a model (serving
under a recording Profiler, the compiled-fit watchdog) are marked
``slow`` and run only without ``-m 'not slow'``."""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu.observability import (flight, get_flight_recorder,
                                                get_registry, tracing)


@pytest.fixture(autouse=True)
def _tracing_off_after():
    """Tracing and the flight ring are process-global; leave them clean."""
    yield
    tracing.disable_tracing()
    tracing.set_span_sink(None)
    get_flight_recorder().clear()


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------

def test_span_api_and_disabled_noop():
    rec = get_flight_recorder()
    rec.clear()
    sink_events = []
    tracing.set_span_sink(
        lambda name, t0, t1, tid, attrs: sink_events.append(
            (name, t0, t1, tid, attrs)))

    # disabled (the default): every entry point is a shared no-op
    assert not tracing.tracing_enabled()
    with tracing.span("off.cm", a=1) as sp:
        sp.set_attrs(b=2)
    h = tracing.start_span("off.explicit")
    tracing.end_span(h, c=3)
    tracing.add_span("off.retro", 0, 10)
    assert sink_events == []
    assert [e for e in rec.events() if e["kind"] == "span"] == []

    tracing.enable_tracing()
    with tracing.span("on.outer", a=1):
        inner = tracing.start_span("on.inner", _tid=7)
        inner.set_attrs(rid=42)
        tracing.end_span(inner, committed=3)
    tracing.add_span("on.retro", 100, 5100, _tid=9, rid=42)

    names = [e[0] for e in sink_events]
    assert names == ["on.inner", "on.outer", "on.retro"]  # close order
    by_name = {e[0]: e for e in sink_events}
    _, t0, t1, tid, attrs = by_name["on.inner"]
    assert t1 >= t0 and tid == 7
    assert attrs == {"rid": 42, "committed": 3}   # end attrs merge
    assert by_name["on.outer"][4] == {"a": 1}
    assert by_name["on.outer"][3] == threading.get_ident()
    assert by_name["on.retro"][1:4] == (100, 5100, 9)
    # finished spans also land in the always-on flight ring
    fl = [e for e in rec.events() if e["kind"] == "span"]
    assert {e["name"] for e in fl} == {"on.inner", "on.outer", "on.retro"}
    retro = next(e for e in fl if e["name"] == "on.retro")
    assert retro["dur_us"] == 5 and retro["rid"] == 42
    # double-end is a no-op, not a duplicate event
    h2 = tracing.start_span("on.once")
    h2.end()
    h2.end()
    assert sum(1 for e in sink_events if e[0] == "on.once") == 1
    # attrs named after envelope keys must shadow, not TypeError, the
    # traced hot path (they only hit the flight ring while armed)
    tracing.add_span("on.hostile", 0, 7000, name="x", dur_us=1,
                     kind="y", ts=2)
    ev = [e for e in rec.events() if e["kind"] == "span"][-1]
    assert ev["name"] == "on.hostile" and ev["dur_us"] == 7
    assert ev["kind"] == "span"   # envelope wins over the ts/kind attrs


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_and_dump(tmp_path):
    fr = flight.FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("tick", n=i)
    evs = fr.events()
    assert len(evs) == 8                       # bounded: ring, not a log
    assert [e["n"] for e in evs] == list(range(12, 20))   # newest kept
    d = fr.dump()
    assert d["capacity"] == 8 and d["dropped"] == 12
    assert d["perf_ns"] > 0 and d["pid"] == os.getpid()
    p = fr.dump_to_file(str(tmp_path / "f.json"))
    loaded = json.load(open(p))
    assert [e["n"] for e in loaded["events"]] == [e["n"] for e in evs]
    # fields named after the envelope keys record fine (kind is
    # positional-only; ts/kind shadowed on read, never a TypeError)
    fr.record("tick", kind="shadowed", ts=99, n=21)
    assert fr.events()[-1]["kind"] == "tick" and fr.events()[-1]["n"] == 21
    # disabled recorder drops events without growing
    fr.enabled = False
    fr.record("tick", n=99)
    assert len(fr.events()) == 8
    fr.clear()
    assert fr.events() == [] and fr.dump()["dropped"] == 0


def test_crash_dump_env_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("PHT_FLIGHT_DIR", str(tmp_path))
    rec = get_flight_recorder()
    rec.clear()
    rec.record("tick", n=1)
    with pytest.warns(UserWarning, match="flight-recorder dump"):
        path = flight.crash_dump("unit.test", ValueError("boom"))
    assert path is not None and path.startswith(str(tmp_path))
    d = json.load(open(path))
    kinds = [e["kind"] for e in d["events"]]
    assert kinds == ["tick", "crash"]
    crash = d["events"][-1]
    assert crash["origin"] == "unit.test"
    assert crash["error"] == "ValueError" and crash["message"] == "boom"


def test_merge_traces_flight_overlay(tmp_path):
    """A flight dump lands on the merged cluster timeline as instant
    events (placed via its paired ts/perf_ns clock anchor)."""
    from paddle_hackathon_tpu.profiler import merge_traces
    fr = flight.FlightRecorder(capacity=8)
    fr.record("tick", n=1)
    fp = fr.dump_to_file(str(tmp_path / "flight.json"))
    rank = tmp_path / "rank0_step1.json"
    json.dump({"traceEvents": [{"name": "step", "ph": "X", "pid": 9,
                                "tid": 1, "ts": 10.0, "dur": 1.0}]},
              open(rank, "w"))
    merged = merge_traces([str(rank)], flight_paths=[fp])
    inst = [e for e in merged["traceEvents"] if e.get("ph") == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "flight:tick"
    assert inst[0]["pid"] == 1                 # own row above rank 0
    assert inst[0]["args"]["n"] == 1 and inst[0]["ts"] > 0
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("name") == "process_name"}
    assert any(n.startswith("flight (") for n in names)
    # a dump without the clock anchor is skipped, never mis-placed
    bad = tmp_path / "old.json"
    json.dump({"ts": 1.0, "events": [{"ts": 1.0, "kind": "x"}]},
              open(bad, "w"))
    with pytest.warns(UserWarning, match="perf_ns anchor"):
        merged = merge_traces([str(rank)], flight_paths=[str(bad)])
    assert not [e for e in merged["traceEvents"] if e.get("ph") == "i"]
    # align rebases ranks to marker-t=0 while flight rows keep absolute
    # perf-clock time — the combination would misplace the overlay, so
    # the API (not just the CLI) refuses it
    with pytest.raises(ValueError, match="align_marker"):
        merge_traces([str(rank)], align_marker="step", flight_paths=[fp])


# ---------------------------------------------------------------------------
# serving engine: crash post-mortem (no device program runs — fast)
# ---------------------------------------------------------------------------

def _tiny_engine(auto_run=False, **kw):
    from paddle_hackathon_tpu.inference import ServingEngine
    from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                         auto_run=auto_run, **kw)


def test_serving_step_crash_writes_flight_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("PHT_FLIGHT_DIR", str(tmp_path))
    rec = get_flight_recorder()
    rec.clear()
    eng = _tiny_engine()
    # poison the device tick BEFORE it ever compiles: the crash path is
    # pure host work, so this test stays cheap
    def boom(*a, **k):
        raise RuntimeError("forced tick failure")
    monkeypatch.setattr(eng, "_run_tick", boom)
    req = eng.submit(np.arange(6, dtype=np.int32), 4)
    with pytest.warns(UserWarning, match="flight-recorder dump"), \
            pytest.raises(RuntimeError, match="forced tick failure"):
        eng.run_until_idle()
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight_")]
    assert len(dumps) == 1
    d = json.load(open(tmp_path / dumps[0]))
    # the post-mortem carries the failing request's lifecycle history
    # (submit + admit) and names the crash origin
    req_evs = [e for e in d["events"]
               if e["kind"] == "req" and e.get("rid") == req.rid]
    assert [e["phase"] for e in req_evs] == ["submit", "admit"]
    assert req_evs[0]["prompt_len"] == 6 and req_evs[1]["slot"] == 0
    crash = d["events"][-1]
    assert crash["kind"] == "crash"
    assert crash["origin"] == f"serving.step[{eng._engine_id}]"
    assert crash["error"] == "RuntimeError"


def test_beacon_lifecycle():
    """remove_beacon forgets a cleanly-stopped activity so
    /healthz?max_age doesn't 503 forever on a dead-but-fine beacon."""
    tracing.heartbeat("unit.gone")
    assert "unit.gone" in tracing.beacon_ages()
    tracing.remove_beacon("unit.gone")
    assert "unit.gone" not in tracing.beacon_ages()
    tracing.remove_beacon("unit.gone")   # idempotent


def test_single_driver_guard_is_not_a_crash(tmp_path, monkeypatch):
    """The single-driver usage error must NOT write flight dumps or
    append 'crash' events: a caller retrying step() against a live
    auto_run loop would flood the dump dir and evict the ring's real
    history."""
    monkeypatch.setenv("PHT_FLIGHT_DIR", str(tmp_path))
    rec = get_flight_recorder()
    rec.clear()
    eng = _tiny_engine()
    other = threading.Thread(target=lambda: None)
    with eng._lock:
        eng._running = True
        eng._loop_thread = other
    for _ in range(3):   # retries stay dump-free too
        with pytest.raises(RuntimeError, match="auto_run loop"):
            eng.step()
    with eng._lock:
        eng._running = False
        eng._loop_thread = None
    assert not [f for f in os.listdir(tmp_path) if f.startswith("flight_")]
    assert not [e for e in rec.events() if e["kind"] == "crash"]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_loop_failall_leaves_terminal_marks(tmp_path, monkeypatch):
    """When the auto_run loop dies, every in-flight request gets a
    terminal 'req fail' flight mark and its lifecycle spans closed —
    the failing requests are what the post-mortem most needs."""
    import warnings as _w
    monkeypatch.setenv("PHT_FLIGHT_DIR", str(tmp_path))
    rec = get_flight_recorder()
    rec.clear()
    eng = _tiny_engine(auto_run=True)
    def boom(*a, **k):
        raise RuntimeError("loop tick failure")
    monkeypatch.setattr(eng, "_run_tick", boom)
    with _w.catch_warnings():
        _w.simplefilter("ignore")   # crash-dump warning from loop thread
        req = eng.submit(np.arange(6, dtype=np.int32), 4)
        req.wait(timeout=30)
        eng._loop_thread.join(timeout=30)   # thread exception lands here
    assert isinstance(req.error, RuntimeError)
    fails = [e for e in rec.events()
             if e["kind"] == "req" and e.get("phase") == "fail"]
    assert [e["rid"] for e in fails] == [req.rid]
    assert fails[0]["where"] == "slot"
    assert fails[0]["error"] == "RuntimeError"


# ---------------------------------------------------------------------------
# introspection server (no engine needed — fast)
# ---------------------------------------------------------------------------

def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_introspection_server_endpoints():
    from paddle_hackathon_tpu.observability.server import \
        start_introspection_server

    class FakeEngine:
        def introspect_requests(self):
            return {"engine": "fake", "pending": 1,
                    "slots": [{"rid": 7, "slot": 0}, None]}

    src = FakeEngine()
    tracing.register_introspection_source("fake", src)
    tracing.heartbeat("unit.beacon")
    reg = get_registry()
    reg.counter("introspect_unit_total", "endpoint smoke").inc(3)
    rec = get_flight_recorder()
    rec.clear()
    rec.record("tick", n=1)
    srv = start_introspection_server(0)
    try:
        st, body = _get(srv.url + "/metrics")
        assert st == 200 and b"introspect_unit_total 3" in body

        st, body = _get(srv.url + "/healthz")
        health = json.loads(body)
        assert st == 200 and health["ok"]
        assert health["beacons"]["unit.beacon"] < 60
        # staleness turns into 503 only when the caller asks
        st, body = _get(srv.url + "/healthz?max_age=1e-9")
        assert st == 503 and not json.loads(body)["ok"]
        assert "unit.beacon" in json.loads(body)["stale"]
        # malformed/non-finite thresholds are 400, never a silent 200
        # (NaN compares False against every age)
        for bad in ("oops", "nan", "inf"):
            st, _ = _get(srv.url + f"/healthz?max_age={bad}")
            assert st == 400, bad

        st, body = _get(srv.url + "/debug/flight")
        fl = json.loads(body)
        assert st == 200 and fl["events"][-1] == {
            "ts": fl["events"][-1]["ts"], "kind": "tick", "n": 1}

        st, body = _get(srv.url + "/debug/requests")
        tables = json.loads(body)["sources"]
        assert st == 200 and tables["fake"]["slots"][0]["rid"] == 7

        st, body = _get(srv.url + "/nope")
        assert st == 404 and "/metrics" in json.loads(body)["endpoints"]
    finally:
        srv.stop()
        tracing.unregister_introspection_source("fake")
    # weak registration: a dropped source vanishes without unregister
    tracing.register_introspection_source("fake2", FakeEngine())
    assert "fake2" not in tracing.introspection_tables()


# ---------------------------------------------------------------------------
# acceptance: one serving run -> one trace with ticks + counters + request
# spans; live introspection of the real engine              (compiles: slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_trace_counters_spans_and_introspection(tmp_path):
    from paddle_hackathon_tpu.observability.server import \
        start_introspection_server
    from paddle_hackathon_tpu.profiler import (Profiler,
                                               export_chrome_tracing,
                                               make_scheduler)
    eng = _tiny_engine()
    out = str(tmp_path / "tr")
    p = Profiler(scheduler=make_scheduler(closed=0, ready=0, record=1,
                                          repeat=1),
                 on_trace_ready=export_chrome_tracing(out, "rank0"),
                 use_device_tracer=False)
    rs = np.random.RandomState(5)
    p.start()
    assert tracing.tracing_enabled()   # profiler armed the span layer
    reqs = [eng.submit(rs.randint(0, 128, (6,)).astype(np.int32), 8)
            for _ in range(2)]
    eng.run_until_idle()
    p.stop()
    assert not tracing.tracing_enabled()
    assert all(r.done for r in reqs)

    files = os.listdir(out)
    assert len(files) == 1             # ONE trace for the whole run
    trace = json.load(open(os.path.join(out, files[0])))
    evs = trace["traceEvents"]
    slices = [e for e in evs if e.get("ph") == "X"]
    names = {e["name"] for e in slices}
    # tick slices for both program flavors this run used
    assert "serving.tick.prefill" in names
    assert "serving.tick.decode" in names
    # PR 4 counter events on the same timeline
    counters = {e["name"] for e in evs if e.get("ph") == "C"}
    assert any(n.startswith("serving_ticks_total") for n in counters)
    # per-request spans carrying the REAL request ids
    rid_spans = [e for e in slices
                 if e.get("args") and "rid" in e["args"]]
    assert {e["args"]["rid"] for e in rid_spans} == {r.rid for r in reqs}
    for want in ("serving.request", "serving.request.queued",
                 "serving.prefill_chunk", "serving.decode"):
        assert want in {e["name"] for e in rid_spans}, want
    life = [e for e in rid_spans if e["name"] == "serving.request"]
    assert all(e["args"]["tokens"] == 8 for e in life)

    # the four endpoints serve THIS engine's run
    srv = start_introspection_server(0)
    try:
        st, body = _get(srv.url + "/metrics")
        assert st == 200
        eid = eng._engine_id
        assert f'serving_ttft_seconds_count{{engine="{eid}"}} 2' \
            in body.decode()
        st, body = _get(srv.url + "/healthz")
        assert st == 200
        # the sync drain (run_until_idle) dropped the beacon, same as
        # the auto_run idle-drain: a cleanly idle engine must not 503
        # /healthz?max_age, so only LIVE activity appears here
        assert f"serving.{eid}" not in json.loads(body)["beacons"]
        st, body = _get(srv.url + "/debug/flight")
        assert st == 200
        kinds = {e["kind"] for e in json.loads(body)["events"]}
        assert {"req", "tick", "span"} <= kinds
        st, body = _get(srv.url + "/debug/requests")
        table = json.loads(body)["sources"][eid]
        assert st == 200 and table["pending"] == 0
        assert table["slots"] == [None, None]   # drained
    finally:
        srv.stop()
    eng.shutdown()
    assert eng._engine_id not in tracing.introspection_tables()
    # clean shutdown drops the beacon: no forever-503 on ?max_age
    assert f"serving.{eng._engine_id}" not in tracing.beacon_ages()


# ---------------------------------------------------------------------------
# non-finite watchdog                                        (compiles: slow)
# ---------------------------------------------------------------------------

class _DS(paddle.io.Dataset):
    def __init__(self, n=8, d=10):
        rng = np.random.RandomState(0)
        self.x = rng.randn(n, d).astype(np.float32)
        self.y = (self.x.sum(1) > 0).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _nan_model():
    from paddle_hackathon_tpu import hapi, nn, optimizer as optim

    class NaNLoss(nn.CrossEntropyLoss):
        def forward(self, x, y):
            return super().forward(x, y) * float("nan")

    paddle.seed(7)
    net = nn.Sequential(nn.Linear(10, 8), nn.ReLU(), nn.Linear(8, 2))
    model = hapi.Model(net)
    model.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                       parameters=net.parameters()),
                  loss=NaNLoss())
    return model


def test_fit_epochs_zero_is_not_a_crash(tmp_path, monkeypatch):
    """fit(epochs=0) (e.g. resume logic with zero remaining epochs)
    returns empty logs — no NameError, no spurious crash dump."""
    monkeypatch.setenv("PHT_FLIGHT_DIR", str(tmp_path))
    logs = _nan_model().fit(_DS(), epochs=0, verbose=0, jit_compile=False)
    assert logs == {}
    assert not [f for f in os.listdir(tmp_path) if f.startswith("flight_")]


@pytest.mark.slow
def test_nonfinite_watchdog(tmp_path, monkeypatch):
    monkeypatch.setenv("PHT_FLIGHT_DIR", str(tmp_path))
    reg = get_registry()
    rec = get_flight_recorder()
    rec.clear()
    before = reg.total("train_nonfinite_total")

    with pytest.raises(ValueError, match="nan_policy"):
        _nan_model().fit(_DS(), epochs=1, nan_policy="explode")

    # raise policy: abort at the FIRST log_freq sync with a clear error,
    # and the crashed fit leaves a flight dump
    with pytest.warns(UserWarning, match="flight-recorder dump"), \
            pytest.raises(FloatingPointError, match="non-finite"):
        _nan_model().fit(_DS(), epochs=1, batch_size=4, verbose=0,
                         log_freq=1, nan_policy="raise")
    assert reg.total("train_nonfinite_total") == before + 1
    nf = [e for e in rec.events() if e["kind"] == "train.nonfinite"]
    assert nf and nf[0]["loss"] == "nan" and nf[0]["step"] == 0
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight_")]
    assert len(dumps) == 1
    d = json.load(open(tmp_path / dumps[0]))
    assert d["events"][-1]["origin"] == "hapi.Model.fit"

    # default policy: count + record, keep training — one count per bad
    # step (the epoch-end sync skips a final step a log_freq fetch
    # already watched: one bad step must not inflate the NaN rate by 2)
    logs = _nan_model().fit(_DS(), epochs=1, batch_size=4, verbose=0,
                            log_freq=1)
    assert np.isnan(logs["loss"])
    assert reg.total("train_nonfinite_total") == before + 3

    # eager path: losses are host floats every step (train_batch
    # float()s them), so the watchdog has no log_freq=0 hole and no
    # missed epoch tail — nan_policy="raise" fires on the FIRST step
    with pytest.warns(UserWarning, match="flight-recorder dump"), \
            pytest.raises(FloatingPointError, match="non-finite"):
        _nan_model().fit(_DS(), epochs=1, batch_size=4, verbose=0,
                         log_freq=0, jit_compile=False,
                         nan_policy="raise")
    snap = reg.snapshot()["metrics"]["train_nonfinite_total"]["series"]
    assert any(s["labels"].get("path") == "hapi_eager" and s["value"] >= 1
               for s in snap)


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """``{event name: count}`` over the host planes of the one
    ``.xplane.pb`` a ``jax.profiler`` session wrote."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import trace_reduce
    planes = trace_reduce.load_planes(trace_reduce.find_xplane(trace_dir))
    out = {}
    for pname, lines in planes.items():
        if pname.startswith(trace_reduce.HOST_PLANE_PREFIX):
            for events in lines.values():
                for name, _, dur in events:
                    assert dur >= 0
                    out[name] = out.get(name, 0) + 1
    return out


def test_armed_span_and_train_step_are_in_the_profilers_trace(tmp_path):
    """The program's spans and the step annotation land in the same
    ``.xplane.pb`` as the device's ops would: read back from a CPU
    session.  A disarmed span, and ``add_span``, write nothing there."""
    import jax
    import jax.numpy as jnp
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                                 param_sharding_spec)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
        max_position_embeddings=8, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec)
    ids = jnp.zeros((2, 8), jnp.int32)
    state, _ = step(state, ids, ids, jax.random.key(0))     # compiles

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("test.disarmed", n=1):
            pass
        state, _ = step(state, ids, ids, jax.random.key(1))  # disarmed
        tracing.enable_tracing()
        with tracing.span("test.armed", n=2):
            tracing.add_span("test.retro", 0, 10)
        h = tracing.start_span("test.explicit")
        for i in range(2):
            state, loss = step(state, ids, ids, jax.random.key(2 + i))
        tracing.end_span(h, steps=2)
        loss.block_until_ready()
    finally:
        jax.profiler.stop_trace()
        tracing.disable_tracing()
    seen = _host_events(str(tmp_path))
    # the step annotation is unconditional; the spans only when armed
    assert seen.get("train_step") == 3
    assert seen.get("train.dispatch") == 2 and seen.get("train.rebind") == 2
    assert seen.get("test.armed") == 1 and seen.get("test.explicit") == 1
    assert "test.disarmed" not in seen and "test.retro" not in seen
    # and the armed spans went to the flight ring as before
    names = [e["name"] for e in get_flight_recorder().events()
             if e["kind"] == "span"]
    assert names.count("train.dispatch") == 2 and "test.retro" in names


def test_disarmed_span_is_the_shared_noop_and_enters_no_annotation():
    assert not tracing.tracing_enabled()
    sp = tracing.span("off", a=1)
    assert sp is tracing.span("off2") and not hasattr(sp, "_annotation")
    tracing.enable_tracing()
    armed = tracing.start_span("on", a=1)
    assert armed._annotation is not None
    armed.end()
    armed.end()       # closing twice leaves the annotation closed once
