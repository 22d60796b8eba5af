"""Request-level SLO telemetry: SlidingWindowHistogram semantics, the
request lifecycle record, the /load capacity report (golden schema),
beacon GC, /healthz max_age validation, and trainer MFU accounting.

Lean by design (tier-1 is compile-bound on the CPU): one tiny serving
engine carries the lifecycle + /load acceptance assertions, one tiny
compiled fit carries MFU/phase attribution; everything else is pure
host work."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest
from conftest import join_within

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu.observability import (SlidingWindowHistogram,
                                                get_registry, tracing)


# ---------------------------------------------------------------------------
# SlidingWindowHistogram: percentile correctness + window expiry
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_swh_percentile_correctness():
    clk = _Clock()
    h = SlidingWindowHistogram(window_s=60.0, slices=6,
                               buckets=(1.0, 2.0, 4.0, 8.0), clock=clk)
    # 100 samples uniform over the (0, 1] bucket, 100 over (1, 2]
    for _ in range(100):
        h.observe(0.5)
        h.observe(1.5)
    assert h.count == 200
    assert h.max == 1.5
    # p50 sits exactly at the first bucket's upper bound (rank 100 of
    # 200 closes bucket (0,1]); p75 interpolates half into (1,2]
    assert h.quantile(0.5) == pytest.approx(1.0)
    assert h.quantile(0.75) == pytest.approx(1.5)
    assert h.quantile(0.25) == pytest.approx(0.5)
    # tail past the top bound interpolates toward the OBSERVED max,
    # exactly like the lifetime Histogram
    h.observe(100.0)
    assert h.quantile(1.0) == pytest.approx(100.0)
    p = h.percentiles()
    assert set(p) == {"count", "mean", "max", "p50", "p95", "p99"}
    assert p["count"] == 201 and p["max"] == 100.0
    assert p["p50"] <= p["p95"] <= p["p99"] <= 100.0
    # snapshot is JSON-strict (no NaN ever)
    json.dumps(h.snapshot(), allow_nan=False)


def test_swh_window_expiry():
    clk = _Clock()
    h = SlidingWindowHistogram(window_s=6.0, slices=3,
                               buckets=(0.1, 1.0), clock=clk)
    h.observe(0.05)            # slice epoch 0
    clk.t = 2.5
    h.observe(0.5)             # slice epoch 1
    assert h.count == 2
    clk.t = 6.5                # epochs {0} expired, {1, 2, 3} live
    assert h.count == 1 and h.quantile(0.5) > 0.1
    clk.t = 100.0              # everything expired
    assert h.count == 0
    assert np.isnan(h.quantile(0.5)) and np.isnan(h.max)
    assert h.percentiles() is None
    assert h.snapshot()["values"] is None
    # the ring is reused after expiry, not poisoned by stale counts
    h.observe(0.5)
    assert h.count == 1 and h.sum == 0.5


def test_swh_torn_first_observe_reads_as_empty():
    """A reader racing the FIRST observe of an otherwise-empty window
    can see the count bump before the max update (observe is lock-free
    by design).  That read must report empty — never leak -inf into the
    strict-JSON /load body — and the next consistent read sees the
    sample."""
    clk = _Clock()
    h = SlidingWindowHistogram(window_s=6.0, slices=3,
                               buckets=(0.1, 1.0), clock=clk)
    h.observe(0.5)
    # reproduce the torn intermediate state deliberately (white-box):
    # counts/count/sum written, max still at the reset sentinel
    w = h._wins[0]
    w[4] = float("-inf")
    assert h.count == 0 and h.percentiles() is None
    assert np.isnan(h.quantile(0.5))
    json.dumps(h.snapshot(), allow_nan=False)   # strict-JSON clean
    w[4] = 0.5                                  # the max lands
    assert h.count == 1 and h.percentiles()["max"] == 0.5


def test_swh_rejects_bad_config():
    with pytest.raises(ValueError):
        SlidingWindowHistogram(window_s=0)
    with pytest.raises(ValueError):
        SlidingWindowHistogram(slices=0)


def test_swh_thread_safety_smoke():
    h = SlidingWindowHistogram(window_s=60.0, slices=4)

    def work():
        for _ in range(2000):
            h.observe(0.001)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    join_within(ts, 60, "the histogram writers")
    # mid-window (no rotation in flight): nothing may be lost
    assert h.count == 8000


# ---------------------------------------------------------------------------
# beacon GC (dead workers must not false-trip a router health probe)
# ---------------------------------------------------------------------------

def test_beacon_gc_drops_dead_thread_owner():
    t = threading.Thread(target=lambda: tracing.heartbeat("unit.worker"))
    t.start()
    join_within([t], 30, "the beacon's owner")
    # the owning thread exited without cleanup: the beacon must NOT sit
    # at an ever-growing age and 503 every ?max_age probe — GC at read
    assert "unit.worker" not in tracing.beacon_ages()
    assert "unit.worker" not in tracing._beacons   # removed, not hidden


def test_pinned_beacon_survives_owner_exit():
    def crash_path():
        tracing.heartbeat("unit.crashed")
        tracing.pin_beacon("unit.crashed")   # what the engine loop does

    t = threading.Thread(target=crash_path)
    t.start()
    join_within([t], 30, "the beacon's owner")
    # pinned = the crashed-loop alert: it ages forever on purpose
    assert "unit.crashed" in tracing.beacon_ages()
    tracing.remove_beacon("unit.crashed")
    # pin on a never-beaten name creates it (age from now)
    tracing.pin_beacon("unit.fresh_pin")
    assert tracing.beacon_ages()["unit.fresh_pin"] < 60
    tracing.remove_beacon("unit.fresh_pin")


def test_live_thread_beacon_is_kept():
    tracing.heartbeat("unit.alive")          # owner: this (live) thread
    assert "unit.alive" in tracing.beacon_ages()
    tracing.remove_beacon("unit.alive")


# ---------------------------------------------------------------------------
# introspection server: /healthz validation + /load envelope (no engine)
# ---------------------------------------------------------------------------

def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture
def srv():
    from paddle_hackathon_tpu.observability.server import \
        start_introspection_server
    s = start_introspection_server(0)
    yield s
    s.stop()


def test_healthz_max_age_validation_and_stale_names(srv):
    tracing.heartbeat("unit.h")
    try:
        # malformed / non-finite / negative thresholds: 400 naming the
        # bad value, never a handler 500 and never a silent 200
        for bad in ("oops", "", "nan", "-inf", "-1", "1//2"):
            st, body = _get(srv.url + f"/healthz?max_age={bad}")
            assert st == 400, bad
            assert json.loads(body)["got"] == bad
        # the unhealthy body NAMES the failing beacons (stalest first),
        # not just an ages dict the alert line would have to parse
        st, body = _get(srv.url + "/healthz?max_age=1e-9")
        payload = json.loads(body)
        assert st == 503 and not payload["ok"]
        assert "unit.h" in payload["stale_beacons"]
        assert payload["stale"]["unit.h"] >= 0
    finally:
        tracing.remove_beacon("unit.h")


def test_load_endpoint_envelope_and_source_errors(srv):
    class FakeEngine:
        def load_report(self):
            return {"version": 1, "engine": "fake", "slots": {"free": 3}}

    class BrokenEngine:
        def load_report(self):
            raise RuntimeError("snapshot torn")

    fake, broken = FakeEngine(), BrokenEngine()
    tracing.register_load_source("fake", fake)
    tracing.register_load_source("broken", broken)
    try:
        st, body = _get(srv.url + "/load")
        payload = json.loads(body)
        assert st == 200
        assert payload["version"] == 1 and payload["ts"] > 0
        assert payload["engines"]["fake"]["slots"]["free"] == 3
        # a failing source reports its error; the router poll survives
        assert "RuntimeError" in payload["engines"]["broken"]["error"]
        # /load is advertised to a lost caller
        st, body = _get(srv.url + "/nope")
        assert st == 404 and "/load" in json.loads(body)["endpoints"]
    finally:
        tracing.unregister_load_source("fake")
        tracing.unregister_load_source("broken")
    # weak registration: a dropped engine vanishes without unregister
    tracing.register_load_source("gone", FakeEngine())
    assert "gone" not in tracing.load_reports()


# ---------------------------------------------------------------------------
# MFU accounting units (no device work)
# ---------------------------------------------------------------------------

def test_train_flops_per_token_formula():
    from paddle_hackathon_tpu import nn
    from paddle_hackathon_tpu.cost_model import train_flops_per_token

    net = nn.Linear(10, 8)                       # 88 params
    assert train_flops_per_token(net) == 6.0 * 88
    # GPT-shaped config adds the 12*L*h*s attention term
    from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    n_params = sum(int(p.size) for p in m.parameters())
    base = train_flops_per_token(m)
    assert base == 6.0 * n_params
    with_attn = train_flops_per_token(m, seqlen=16)
    assert with_attn == base + 12.0 * 2 * 32 * 16


def test_device_peak_flops_env_override(monkeypatch):
    from paddle_hackathon_tpu.cost_model import device_peak_flops
    monkeypatch.setenv("PHT_PEAK_FLOPS", "2.5e12")
    assert device_peak_flops() == 2.5e12
    # a typo'd override warns and falls back to the device-kind table
    # (which has no CPU entry, so None here) — never a silent disable
    monkeypatch.setenv("PHT_PEAK_FLOPS", "not-a-number")
    with pytest.warns(UserWarning, match="PHT_PEAK_FLOPS"):
        assert device_peak_flops() is None


def test_mfu_and_phase_gauges_from_compiled_fit(monkeypatch):
    """Model.fit's compiled path sets tokens/s, MFU and the per-phase
    attribution at its existing log_freq sync points (no extra host
    syncs — the gauges derive only from timestamps the loop already
    takes)."""
    from paddle_hackathon_tpu import hapi, io, nn, optimizer as optim
    monkeypatch.setenv("PHT_PEAK_FLOPS", "1e12")

    class _DS(io.Dataset):
        def __init__(self, n=8, d=10):
            rng = np.random.RandomState(0)
            self.x = rng.randn(n, d).astype(np.float32)
            self.y = (self.x.sum(1) > 0).astype(np.int64)

        def __len__(self):
            return len(self.x)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

    paddle.seed(7)
    net = nn.Sequential(nn.Linear(10, 8), nn.ReLU(), nn.Linear(8, 2))
    model = hapi.Model(net)
    model.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                       parameters=net.parameters()),
                  loss=nn.CrossEntropyLoss())
    model.fit(_DS(), epochs=1, batch_size=4, verbose=0, log_freq=1)
    assert model._fit_used_compiled
    snap = get_registry().snapshot()["metrics"]

    def val(name, **labels):
        for s in snap[name]["series"]:
            if all(s["labels"].get(k) == v for k, v in labels.items()):
                return s["value"]
        raise AssertionError(f"{name} {labels} missing")

    assert val("train_tokens_per_sec", path="hapi_compiled") > 0
    mfu = val("train_mfu", path="hapi_compiled")
    assert 0 < mfu < 1          # a tiny MLP is nowhere near peak
    phases = {ph: val("train_phase_seconds_per_step",
                      path="hapi_compiled", phase=ph)
              for ph in ("dispatch", "host_wait", "device")}
    assert all(v >= 0 for v in phases.values())
    assert sum(phases.values()) > 0


# ---------------------------------------------------------------------------
# acceptance: one tiny engine run -> complete lifecycle record + the
# /load golden schema (HTTP and direct), goodput, SLO windows
# ---------------------------------------------------------------------------

# "draining" joined in the fleet PR (router contract bump within
# version 1); paged engines additionally carry a "prefix_digest" block
_LOAD_KEYS = {"version", "engine", "ts", "running", "draining", "tickno",
              "slots", "queue", "modes", "slo", "goodput", "admission",
              "sessions", "scheduler"}
_SLO_SERIES = {"ttft", "tpot", "e2e", "queue_wait"}
_CLASSES = {"interactive", "default", "batch"}


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


def test_request_lifecycle_and_load_report_golden(srv):
    eng = _tiny_engine()
    eid = eng._engine_id
    rs = np.random.RandomState(5)

    # an IDLE engine already serves a well-formed report (router boot)
    rep0 = eng.load_report()
    assert set(rep0) == _LOAD_KEYS and rep0["version"] == 1
    assert rep0["slots"] == {"max": 2, "active": 0, "free": 2}
    assert rep0["slo"]["ttft"] is None          # no traffic yet
    assert rep0["goodput"]["ratio"] is None
    # dense headroom: max_len minus the write-window reserve
    assert rep0["admission"]["headroom_tokens"] == 64 - 4

    reqs = [eng.submit(rs.randint(0, 128, (6,)).astype(np.int32), 8)
            for _ in range(2)]
    eng.run_until_idle()
    assert all(r.done for r in reqs)

    # --- the complete submit -> admit -> first token -> finish record
    for r in reqs:
        lc = r.lifecycle
        assert lc["rid"] == r.rid and lc["prompt_len"] == 6
        assert lc["aborted"] is False and lc["tokens"] == 8
        assert (lc["t_submit"] <= lc["t_admit"] <= lc["t_first_token"]
                <= lc["t_finish"])
        # the derived SLO durations land next to the stamps
        assert lc["ttft_s"] == pytest.approx(
            lc["t_first_token"] - lc["t_submit"])
        assert lc["e2e_s"] == pytest.approx(
            lc["t_finish"] - lc["t_submit"])
        assert lc["queue_s"] >= 0 and lc["ttft_s"] > 0
        assert 0 < lc["tpot_s"] <= lc["e2e_s"]

    # --- rolling windows saw the run
    assert eng._slo["ttft"].count == 2
    assert eng._slo["queue_wait"].count == 2
    assert eng._slo["e2e"].count == 2
    assert eng._slo["tpot"].count >= 1          # per-tick decode samples

    # --- /load golden schema (the router contract, pinned key-by-key)
    rep = eng.load_report()
    assert set(rep) == _LOAD_KEYS
    assert rep["version"] == 1 and rep["engine"] == eid
    assert set(rep["slots"]) == {"max", "active", "free"}
    assert set(rep["queue"]) == {"depth", "oldest_wait_s", "classes"}
    # per-priority-class queue split (the fleet router's class-aware
    # scoring input): always all three classes, zero when idle
    assert set(rep["queue"]["classes"]) == _CLASSES
    for c in _CLASSES:
        assert set(rep["queue"]["classes"][c]) == {"depth",
                                                   "oldest_wait_s"}
    assert set(rep["modes"]) == {"cache", "spec_k", "quant", "moe", "pp"}
    assert rep["modes"] == {"cache": "dense", "spec_k": 0, "quant": False,
                            "moe": False, "pp": 1}
    assert set(rep["slo"]) == {"window_s", "classes"} | _SLO_SERIES
    assert set(rep["slo"]["classes"]) == _CLASSES
    for c in _CLASSES:
        assert set(rep["slo"]["classes"][c]) == {"ttft", "queue_wait"}
    # default-class traffic landed in the default per-class windows
    assert rep["slo"]["classes"]["default"]["ttft"]["count"] == 2
    assert rep["slo"]["classes"]["interactive"]["ttft"] is None
    assert set(rep["scheduler"]) == {"preemptions", "preempt_replay_tokens",
                                     "preempt", "preempt_limit",
                                     "prefill_budget", "priority_aging_s"}
    assert rep["scheduler"]["preemptions"] == 0
    for k in _SLO_SERIES:
        series = rep["slo"][k]
        assert set(series) == {"count", "mean", "max", "p50", "p95", "p99"}
        assert series["p50"] <= series["p99"] <= series["max"] * 1.0001
    assert set(rep["goodput"]) == {"completed_tokens", "aborted_tokens",
                                   "ratio"}
    assert rep["goodput"] == {"completed_tokens": 16, "aborted_tokens": 0,
                              "ratio": 1.0}
    assert set(rep["admission"]) == {"reserve_tokens", "headroom_tokens"}
    # drained: all slots free again
    assert rep["slots"]["free"] == 2 and rep["queue"]["depth"] == 0

    # --- the same document over HTTP, strict-JSON clean
    st, body = _get(srv.url + "/load")
    payload = json.loads(body)
    assert st == 200 and payload["version"] == 1
    assert set(payload["engines"][eid]) == _LOAD_KEYS
    assert payload["engines"][eid]["goodput"]["completed_tokens"] == 16
    # and mirrored into /debug/requests as "<eid>.load"
    st, body = _get(srv.url + "/debug/requests")
    assert set(json.loads(body)["sources"][f"{eid}.load"]) == _LOAD_KEYS

    # --- shutdown drops the engine from the router's poll
    eng.shutdown()
    assert eid not in tracing.load_reports()
    st, body = _get(srv.url + "/load")
    assert eid not in json.loads(body)["engines"]


@pytest.mark.slow
def test_paged_load_report_headroom_counts_evictable_pages():
    """The paged admission headroom is "would this request fit RIGHT
    NOW" — and admission EVICTS cache-only prefix pages to cover a
    shortfall, so the report must count free + evictable, not the free
    list alone (a warm prefix cache would otherwise read as a nearly
    full replica and repel the router from ample capacity)."""
    from paddle_hackathon_tpu.inference.paged import pages_for
    eng = _tiny_engine(cache_mode="paged", page_size=8)
    reserve = 4   # max(chunk, spec_k+1)
    # a 2-full-page prompt: its pages land in the prefix cache at finish
    req = eng.submit(np.arange(16, dtype=np.int32), 4)
    eng.run_until_idle()
    assert req.done
    rep = eng.load_report()["admission"]
    assert rep["kv_pages_evictable"] == 2          # the cached pages
    assert rep["kv_pages_in_use"] == 2             # held by the cache
    free_eff = rep["kv_pages_free"] + rep["kv_pages_evictable"]
    n = rep["headroom_tokens"]
    # slot_cap (max_len - reserve = 60) binds before the pool here;
    # the POOL bound alone must be the exact allocator inverse over
    # free + evictable
    from paddle_hackathon_tpu.inference.paged import tokens_admittable
    pool_bound = tokens_admittable(free_eff, reserve, 8)
    assert n == min(pool_bound, 64 - reserve)
    assert pages_for(min(n, pool_bound), reserve, 8) <= free_eff
    eng.shutdown()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_aborted_request_lifecycle_and_crashed_beacon(monkeypatch,
                                                     tmp_path):
    """When the auto_run loop dies, every in-flight request's lifecycle
    record terminates with the abort stamp (the goodput ledger's
    aborted side), and the engine PINS its beacon so the crash still
    alerts via /healthz?max_age even though the loop thread (the
    beacon's owner) is gone — the dead-worker GC must not eat it.
    Cheap: the tick is poisoned before anything compiles."""
    import warnings as _w
    monkeypatch.setenv("PHT_FLIGHT_DIR", str(tmp_path))
    eng = _tiny_engine(auto_run=True)

    def boom(*a, **k):
        raise RuntimeError("forced tick failure")

    monkeypatch.setattr(eng, "_run_tick", boom)
    with _w.catch_warnings():
        _w.simplefilter("ignore")   # crash-dump warning from loop thread
        req = eng.submit(np.arange(6, dtype=np.int32), 4)
        req.wait(timeout=30)
        eng._loop_thread.join(timeout=30)
    assert isinstance(req.error, RuntimeError)
    lc = req.lifecycle
    assert lc["aborted"] is True and lc["tokens"] == 0
    assert lc["error"] == "RuntimeError" and lc["where"] == "slot"
    assert lc["t_submit"] <= lc["t_admit"] <= lc["t_abort"]
    assert "t_finish" not in lc
    # the crashed loop's beacon survived its owner thread's exit
    # (pinned), so going stale IS still the alert
    assert f"serving.{eng._engine_id}" in tracing.beacon_ages()
    tracing.remove_beacon(f"serving.{eng._engine_id}")
