"""Program observatory (observability/programs.py): signature capture,
retrace-cause taxonomy, registry semantics, the instrument_jit fallback
fix, to_static wiring, the /debug/programs endpoint, and the
gate/report/dump surfaces.

Lean by design (tier-1 is compile-bound on the CPU): almost everything
here is pure-host — numpy callables through instrument_jit's
signature-probe fallback, fake AOT handles for the analysis harvest —
and the one test that really compiles (to_static) traces a scalar
multiply."""

import io
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest
from conftest import join_within

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu.core import flags
from paddle_hackathon_tpu.observability import (MetricRegistry,
                                                get_flight_recorder,
                                                get_registry, instrument_jit,
                                                programs, sanitizers,
                                                tracing)
from paddle_hackathon_tpu.observability.programs import (
    ProgramRegistry, capture_signature, diff_signatures,
    get_program_registry, program_analysis, signature_from_spec_key)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

_SITE_N = [0]


def _site(prefix="t"):
    """Unique site label per test: the program registry and the default
    metric registry are process-global."""
    _SITE_N[0] += 1
    return f"{prefix}.programs_test.{_SITE_N[0]}"


# ---------------------------------------------------------------------------
# signature capture + cause taxonomy
# ---------------------------------------------------------------------------

def test_capture_signature_names_and_avals():
    def fn(ids, mask):
        return ids

    sig = capture_signature(
        (np.zeros((8, 512), np.float32), np.ones((8,), np.int32)),
        {"temp": 0.7}, fn=fn)
    assert sig[0][:2] == ("aval", "arg[0] `ids`")
    assert sig[0][2:4] == ((8, 512), "f32")
    assert sig[1][:2] == ("aval", "arg[1] `mask`")
    assert sig[1][3] == "i32"
    assert sig[2][:3] == ("static", "kw `temp`", "0.7")


def test_capture_signature_nested_tree_paths():
    tree = {"w": np.zeros((4, 4), np.float32), "b": np.zeros((4,))}
    sig = capture_signature((tree,))
    labels = [e[1] for e in sig]
    assert any("arg[0]" in l and "'w'" in l for l in labels), labels
    assert any("'b'" in l for l in labels), labels


def test_cause_shape_change():
    def fn(ids):
        return ids

    prev = capture_signature((np.zeros((8, 512), np.float32),), fn=fn)
    cur = capture_signature((np.zeros((8, 640), np.float32),), fn=fn)
    assert diff_signatures(prev, cur) == \
        ["arg[0] `ids`: f32[8,512]→f32[8,640]"]


def test_cause_static_value_change():
    prev = capture_signature((np.zeros((2,), np.float32),), {"spec_k": 4})
    cur = capture_signature((np.zeros((2,), np.float32),), {"spec_k": 6})
    assert diff_signatures(prev, cur) == ["static kw `spec_k`: 4→6"]


def test_cause_dtype_flip():
    prev = capture_signature((np.zeros((4,), np.float32),))
    cur = capture_signature((np.zeros((4,), np.int32),))
    (cause,) = diff_signatures(prev, cur)
    assert "dtype/weak_type flip" in cause and "f32[4]" in cause \
        and "i32[4]" in cause


def test_cause_tree_structure_change():
    prev = capture_signature(({"a": np.zeros((2,))},))
    cur = capture_signature(({"a": np.zeros((2,)), "b": np.zeros((2,))},))
    (cause,) = diff_signatures(prev, cur)
    assert cause == "new arg tree structure (1→2 leaves)"


def test_cause_identical_signature_names_eviction():
    sig = capture_signature((np.zeros((2,)),))
    (cause,) = diff_signatures(sig, sig)
    assert "eviction" in cause


def test_first_build_has_no_cause():
    assert diff_signatures(None, capture_signature((1,))) == []


def test_signature_from_spec_key():
    key = (("T", (8, 512), "float32"), ("S", 4), ("O", "Mesh"))
    sig = signature_from_spec_key(key, training=True)
    assert sig[0] == ("aval", "arg[0]", (8, 512), "f32", False, None)
    assert sig[1] == ("static", "arg[1]", "4")
    assert sig[2] == ("static", "arg[2]", "<Mesh>")
    assert sig[3] == ("static", "training", "True")
    # training-mode flip is a diffable cause
    (cause,) = diff_signatures(
        sig, signature_from_spec_key(key, training=False))
    assert cause == "static training: True→False"


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_record_build_history_bounded_and_totals():
    prog = ProgramRegistry(history=4)
    site = _site()
    reg = MetricRegistry()
    for n in (8, 16, 24, 32, 40, 48):
        prog.record_build(
            site, signature=capture_signature((np.zeros((n,)),)),
            compile_s=0.5, registry=reg)
    s = prog.snapshot()["sites"][site]
    assert s["builds"] == 6
    assert len(s["history"]) == 4            # bounded window
    assert s["history"][0]["build"] == 3     # oldest retained
    assert abs(s["compile_seconds_total"] - 3.0) < 1e-9
    assert "f64[40]" in s["history"][-1]["cause"]
    # jit_compile_seconds rode along
    fam = reg.snapshot()["metrics"]["jit_compile_seconds"]
    assert fam["series"][0]["count"] == 6


def test_registry_thread_safety_under_lock_sanitizer():
    with sanitizers.lock_sanitizer():
        prog = ProgramRegistry()   # lock created while sanitizer armed
        reg = MetricRegistry(enabled=False)
        sites = [_site("thr") for _ in range(4)]
        sigs = [capture_signature((np.zeros((n,)),)) for n in range(50)]

        def worker(site):
            for sig in sigs:
                if prog.is_new_signature(site, sig):
                    prog.record_build(site, signature=sig, registry=reg)

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in sites]
        for t in threads:
            t.start()
        join_within(threads, 60, "the registry writers")
        snap = prog.snapshot()
        assert sum(s["builds"] for s in snap["sites"].values()) == 200
    sanitizers.reset_lock_graph()


def test_eviction_counts_and_forgets_signature():
    prog = ProgramRegistry()
    site = _site()
    reg = MetricRegistry()
    sig = capture_signature((np.zeros((4,)),))
    prog.record_build(site, signature=sig, registry=reg)
    assert not prog.is_new_signature(site, sig)
    prog.record_eviction(site, registry=reg)
    s = prog.snapshot()["sites"][site]
    assert s["evictions"] == 1
    assert reg.total("jit_cache_evictions_total", site=site) == 1.0
    assert any(e.get("kind") == "program_evict" and e.get("site") == site
               for e in get_flight_recorder().events())


# ---------------------------------------------------------------------------
# instrument_jit: the fallback bugfix (satellite) + observatory reporting
# ---------------------------------------------------------------------------

def test_fallback_counts_every_distinct_signature():
    """Pin the bugfix: without ``_cache_size`` the old wrapper recorded
    only the FIRST call — now the registry's signature set detects
    every distinct-signature build, and steady-state repeats stay
    uncounted."""
    reg = MetricRegistry()
    site = _site("fb")

    def tick(ids, mask):         # numpy callable: no _cache_size
        return ids.sum() + mask.sum()

    w = instrument_jit(tick, site=site, registry=reg)
    a, m = np.zeros((8, 16), np.float32), np.ones((8,), np.float32)
    w(a, m)
    w(a, m)
    w(a, m)
    assert reg.total("jit_builds_total", site=site) == 1.0
    w(np.zeros((8, 24), np.float32), m)     # distinct signature: build 2
    assert reg.total("jit_builds_total", site=site) == 2.0
    w(np.zeros((8, 24), np.float32), m)     # seen again: steady state
    assert reg.total("jit_builds_total", site=site) == 2.0
    s = get_program_registry().snapshot()["sites"][site]
    assert s["builds"] == 2
    assert s["history"][-1]["cause"] == "arg[0] `ids`: f32[8,16]→f32[8,24]"
    ev = [e for e in get_flight_recorder().events()
          if e.get("kind") == "program_build" and e.get("site") == site]
    assert [e["build"] for e in ev] == [1, 2]
    assert ev[-1]["cause"] == s["history"][-1]["cause"]


def test_instrument_jit_real_jit_cache_path():
    import jax
    import jax.numpy as jnp
    reg = MetricRegistry()
    site = _site("jit")
    w = instrument_jit(jax.jit(lambda x: x * 2), site=site, registry=reg)
    w(jnp.ones((4,)))
    w(jnp.ones((4,)))
    w(jnp.ones((8,)))
    assert reg.total("jit_builds_total", site=site) == 2.0
    s = get_program_registry().snapshot()["sites"][site]
    assert s["builds"] == 2 and "f32[4]" in s["history"][-1]["cause"]


def test_disabled_registry_pays_nothing():
    reg = MetricRegistry(enabled=False)
    site = _site("off")
    w = instrument_jit(lambda x: x, site=site, registry=reg)
    w(np.zeros((2,)))
    assert site not in get_program_registry().snapshot()["sites"]


# ---------------------------------------------------------------------------
# analysis harvest (PHT_PROGRAM_ANALYSIS)
# ---------------------------------------------------------------------------

class _FakeMem:
    argument_size_in_bytes = 1024
    output_size_in_bytes = 256
    temp_size_in_bytes = 4096
    generated_code_size_in_bytes = 512


# two Mosaic custom calls as XLA:TPU prints them (v5e, jax 0.9.0), next
# to a custom call that is not Mosaic's
_FAKE_HLO = """
  %quant_matmul.1 = bf16[16,2304]{1,0} custom-call(%pad.0, %w_q.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(tick)/quant_matmul/pallas_call" stack_frame_id=6}, backend_config={"custom_call_config":{"body":"TUzvUg"}}
  %quant_matmul.2 = bf16[16,768]{1,0} custom-call(%pad.1, %w_q.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(tick)/while/body/quant_matmul/pallas_call"}
  %paged_decode.3 = bf16[8,1,12,64]{3,2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(tick)/paged_decode/pallas_call"}
  %transpose_jvp_flash_packed_bwd_dq__.1 = bf16[1,1024,128]{2,1,0} custom-call(%q, %do), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(flash_packed_bwd_dq))/pallas_call" stack_frame_id=176}
  %topk = f32[8,4]{1,0} custom-call(%x), custom_call_target="TopK"
"""


class _FakeCompiled:
    def as_text(self):
        return _FAKE_HLO

    def memory_analysis(self):
        return _FakeMem()

    def cost_analysis(self):
        return [{"flops": 99.0}]


class _FakeLowered:
    def compile(self):
        return _FakeCompiled()


def _fake_fn(x):
    return x


_fake_fn.lower = lambda *a, **k: _FakeLowered()


def test_analysis_harvest_gauges_and_rows():
    reg = MetricRegistry()
    site = _site("an")
    with program_analysis():
        assert programs.analysis_enabled()
        get_program_registry().record_build(
            site, args=(np.zeros((4,)),), fn=_fake_fn, registry=reg)
    s = get_program_registry().snapshot()["sites"][site]
    assert s["analysis"] == {"args_bytes": 1024, "outputs_bytes": 256,
                             "temp_bytes": 4096, "generated_bytes": 512,
                             "flops": 99.0,
                             # the kernel census: which Pallas kernels the
                             # EXECUTABLE holds (chip_smoke.py's evidence)
                             "mosaic_kernels": {"quant_matmul": 2,
                                                "paged_decode": 1,
                                                "flash_packed_bwd_dq": 1}}
    assert reg.total("program_hbm_bytes", site=site, kind="temp") == 4096
    assert reg.total("program_flops", site=site) == 99.0


def test_analysis_off_by_default(monkeypatch):
    monkeypatch.delenv("PHT_PROGRAM_ANALYSIS", raising=False)
    assert not programs.analysis_enabled()
    reg = MetricRegistry()
    site = _site("anoff")
    get_program_registry().record_build(
        site, args=(np.zeros((4,)),), fn=_fake_fn, registry=reg)
    assert get_program_registry().snapshot()["sites"][site]["analysis"] \
        is None


# ---------------------------------------------------------------------------
# compile spans on the dedicated lane
# ---------------------------------------------------------------------------

def test_compile_span_rides_compiles_lane():
    spans = []
    tracing.set_span_sink(
        lambda name, t0, t1, tid, attrs: spans.append((name, tid, attrs)))
    tracing.enable_tracing()
    try:
        site = _site("lane")
        get_program_registry().record_build(
            site, signature=capture_signature((np.zeros((2,)),)),
            compile_s=0.25, registry=MetricRegistry(enabled=False))
    finally:
        tracing.disable_tracing()
        tracing.set_span_sink(None)
    (name, tid, attrs) = [s for s in spans if s[0] == f"compile:{site}"][0]
    assert tid == programs.COMPILES_LANE_TID
    assert attrs["lane"] == "compiles" and attrs["build"] == 1


def test_chrome_export_names_compiles_lane(tmp_path):
    from paddle_hackathon_tpu import profiler

    class _Prof:
        step_num = 0
        _events = [type("E", (), {
            "name": "compile:x", "event_type": "Compile",
            "tid": programs.COMPILES_LANE_TID, "start": 0, "end": 1000,
            "args": None})()]
        _counter_events = ()

    handler = profiler.export_chrome_tracing(str(tmp_path))
    path = handler(_Prof())
    evs = json.load(open(path))["traceEvents"]
    meta = [e for e in evs if e.get("ph") == "M"]
    assert meta and meta[0]["args"]["name"] == "compiles"
    assert meta[0]["tid"] == programs.COMPILES_LANE_TID


# ---------------------------------------------------------------------------
# to_static wiring (satellite): user-level retraces + evictions
# ---------------------------------------------------------------------------

def test_to_static_builds_and_evictions_reach_registry():
    @paddle.jit.to_static
    def double(x):
        return x * 2

    site = "to_static.double"
    prog = get_program_registry()
    reg = get_registry()
    b0 = reg.total("jit_builds_total", site=site)
    base = prog.snapshot()["sites"].get(site, {}).get("builds", 0)
    t = paddle.to_tensor(np.ones((4, 4), np.float32))
    double(t)
    double(t)                                     # steady state
    double(paddle.to_tensor(np.ones((4, 8), np.float32)))   # retrace
    s = prog.snapshot()["sites"][site]
    assert s["builds"] == base + 2 and s["kind"] == "to_static"
    assert s["history"][-1]["cause"] == "arg[0]: f32[4,4]→f32[4,8]"
    assert reg.total("jit_builds_total", site=site) == b0 + 2.0
    # a 1-entry cache turns every new signature into an eviction
    e0 = prog.snapshot()["sites"][site]["evictions"]
    flags.set_flags({"jit_cache_size": 1})
    try:
        double(paddle.to_tensor(np.ones((2, 2), np.float32)))
        double(paddle.to_tensor(np.ones((3, 3), np.float32)))
    finally:
        flags.set_flags({"jit_cache_size": 256})
    assert prog.snapshot()["sites"][site]["evictions"] > e0
    assert reg.total("jit_cache_evictions_total", site=site) > 0


# ---------------------------------------------------------------------------
# HTTP + introspection surfaces
# ---------------------------------------------------------------------------

def test_debug_programs_endpoint():
    from paddle_hackathon_tpu.observability.server import \
        start_introspection_server
    site = _site("http")
    get_program_registry().record_build(
        site, signature=capture_signature((np.zeros((8, 16)),)),
        compile_s=0.1, registry=MetricRegistry(enabled=False))
    srv = start_introspection_server(0)
    try:
        doc = json.load(urllib.request.urlopen(
            f"{srv.url}/debug/programs"))
        assert doc["version"] == 1 and site in doc["sites"]
        assert doc["sites"][site]["builds"] == 1
        # 404 body advertises the endpoint
        try:
            urllib.request.urlopen(f"{srv.url}/nope")
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert "/debug/programs" in json.load(e)["endpoints"]
    finally:
        srv.stop()


def test_registry_is_introspection_source():
    site = _site("intro")
    get_program_registry().record_build(
        site, signature=capture_signature((np.zeros((2,)),)),
        registry=MetricRegistry(enabled=False))
    tables = tracing.introspection_tables()
    assert "programs" in tables
    assert site in tables["programs"]["sites"]


# ---------------------------------------------------------------------------
# gate + report + dump surfaces
# ---------------------------------------------------------------------------

def test_gate_failure_prints_recorded_cause(capsys):
    import perf_gate
    cause = "arg[0] `ids`: f32[8,512]→f32[8,640]"
    rows = [{"metric": "serving_spec", "value": 1.0,
             "metrics": {"jit_builds_warm": 4, "jit_builds_total": 6},
             "programs": {"compile_seconds_total": 1.5,
                          "sites": {"serving.tick_b8": {
                              "builds": 6,
                              "causes": [f"build 6: {cause}"]}}}}]
    assert perf_gate.retrace_causes(rows, "serving_spec") == \
        [("serving.tick_b8", f"build 6: {cause}")]
    assert perf_gate.suite_gate(0.07, rows=rows) == 1
    out = capsys.readouterr().out
    assert "recompiled in steady state" in out
    assert f"retrace cause: serving.tick_b8: build 6: {cause}" in out
    # rows without a programs block degrade to a pointer, not a crash
    del rows[0]["programs"]
    assert perf_gate.suite_gate(0.07, rows=rows) == 1
    assert "no recorded causes" in capsys.readouterr().out


def test_program_report_render_causes_and_diff(capsys):
    import program_report
    prog = ProgramRegistry()
    reg = MetricRegistry(enabled=False)
    prog.record_build("a.site", compile_s=2.0,
                      signature=capture_signature((np.zeros((8, 16)),)),
                      registry=reg)
    snap1 = prog.snapshot()
    prog.record_build("a.site", compile_s=1.0,
                      signature=capture_signature((np.zeros((8, 24)),)),
                      registry=reg)
    prog.record_build("b.site", compile_s=0.5,
                      signature=capture_signature((np.ones((2,)),)),
                      registry=reg)
    snap2 = prog.snapshot()
    assert program_report.render(snap2) == 2
    out = capsys.readouterr().out
    assert "2 sites" in out
    assert out.index("a.site") < out.index("b.site")   # compile-time rank
    program_report.render_causes(snap2, site="a.site")
    assert "f64[8,16]→f64[8,24]" in capsys.readouterr().out
    assert program_report.render_diff(snap1, snap2) == 2
    out = capsys.readouterr().out
    assert "a.site: +1 builds" in out and "(new site)" in out
    assert "build 2:" in out
    program_report.render_diff(snap2, snap2)
    assert "no program builds" in capsys.readouterr().out


def test_metrics_dump_humanizes_bytes(capsys):
    import metrics_dump
    r = MetricRegistry()
    r.gauge("program_hbm_bytes", unit="B").labels(
        site="s", kind="temp").set(1536)
    metrics_dump.render(r.snapshot())
    out = capsys.readouterr().out
    assert "1,536 (1.5KiB)" in out


def test_analysis_row_renders_human_bytes(capsys):
    import program_report
    prog = ProgramRegistry()
    with program_analysis():
        prog.record_build(_site("hb"), args=(np.zeros((4,)),), fn=_fake_fn,
                          registry=MetricRegistry(enabled=False))
    program_report.render(prog.snapshot())
    out = capsys.readouterr().out
    assert "temp=4.0KiB" in out and "flops=99" in out


# ---------------------------------------------------------------------------
# donation map in signatures
# ---------------------------------------------------------------------------

def test_donation_map_recorded_in_signature():
    with sanitizers.donation_sanitizer():
        w = sanitizers.sanitize_donation(lambda x: x, donate_argnums=(0,))
        assert w._pht_donate_argnums == (0,)
    sig = capture_signature((np.zeros((2,)),),
                            donated=w._pht_donate_argnums)
    assert sig[-1] == ("static", "donated", "(0,)")


# ---------------------------------------------------------------------------
# phase census + build clock (the train step names its own phases)
# ---------------------------------------------------------------------------

# a compiled step as XLA:TPU prints it (v5e, jax 0.9.0), cut to one
# instruction of each kind the census tells apart
_PHASE_HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,64]) -> bf16[8,64] {
  %param_0.1 = bf16[8,64]{1,0} parameter(0)
  ROOT %tanh.1 = bf16[8,64]{1,0} tanh(%param_0.1), metadata={op_name="jit(train_step)/jvp(mlp)/tanh" stack_frame_id=7}
}

%bitcast_fusion.1 (bitcast_input.1: bf16[8,64]) -> bf16[8,64] {
  %bitcast_input.1 = bf16[8,64]{1,0} parameter(0)
  ROOT %bitcast.9 = bf16[8,64]{1,0} bitcast(%bitcast_input.1)
}

%fused_computation.2 (param_0.2: bf16[8,64], param_1.2: bf16[8,64]) -> (f32[], bf16[64,64,1]) {
  %param_0.2 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %fusion.90 = bf16[8,64]{1,0} fusion(%param_0.2), kind=kLoop, calls=%bitcast_fusion.1
  %param_1.2 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} parameter(1)
  %fusion.91 = bf16[8,64]{1,0} fusion(%param_1.2), kind=kLoop, calls=%fused_computation.9, metadata={op_name="params[\\'gpt.w\\']"}
  %convolution.2 = bf16[64,64,1]{1,0,2:T(8,128)(2,1)} convolution(%fusion.90, %fusion.91), window={size=8}, dim_labels=0fb_0io->bf0, metadata={op_name="jit(train_step)/transpose(jvp(attn))/dot_general" stack_frame_id=83}
  %convert.2 = f32[64,64]{1,0} convert(%convolution.2), metadata={op_name="jit(train_step)/clip/convert_element_type"}
  %reduce_sum.2 = f32[]{:T(128)} reduce(%convert.2, %constant.2), dimensions={0,1}, to_apply=%region_1.1, metadata={op_name="jit(train_step)/clip/reduce_sum"}
  ROOT %tuple.2 = (f32[]{:T(128)}, bf16[64,64,1]{1,0,2:T(8,128)(2,1)}) tuple(%reduce_sum.2, %convolution.2)
}

%fused_computation.9 (param_0.9: bf16[8,64]) -> bf16[8,64] {
  ROOT %param_0.9 = bf16[8,64]{1,0} parameter(0)
}

%region_1.1 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[]{:T(128)} parameter(0), metadata={op_name="reduce_sum"}
  %b.1 = f32[]{:T(128)} parameter(1), metadata={op_name="reduce_sum"}
  ROOT %add.1 = f32[]{:T(128)} add(%a.1, %b.1), metadata={op_name="jit(train_step)/clip/reduce_sum"}
}

%fused_computation.3 (param_0.3: bf16[64], param_1.3: f32[64]) -> (bf16[64], f32[64]) {
  %param_0.3 = bf16[64]{0} parameter(0)
  %param_1.3 = f32[64]{0} parameter(1)
  %mul.3 = f32[64]{0} multiply(%param_1.3, %param_1.3), metadata={op_name="jit(train_step)/clip/mul"}
  %sub.3 = f32[64]{0} subtract(%param_1.3, %mul.3), metadata={op_name="jit(train_step)/update/sub"}
  ROOT %tuple.3 = (bf16[64]{0}, f32[64]{0}) tuple(%param_0.3, %sub.3)
}

%fused_computation.4 (param_0.4: bf16[8,64]) -> bf16[8,64] {
  %param_0.4 = bf16[8,64]{1,0} parameter(0)
  %dot.4 = bf16[8,64]{1,0} dot(%param_0.4, %param_0.4), metadata={op_name="jit(train_step)/jvp(lm_head)/dot_general"}
  %dot.5 = bf16[8,64]{1,0} dot(%dot.4, %param_0.4), metadata={op_name="jit(train_step)/transpose(jvp(lm_head))/dot_general"}
  ROOT %add.4 = bf16[8,64]{1,0} add(%dot.4, %dot.5), metadata={op_name="jit(train_step)/jvp(ce)/add"}
}

%body.1 (arg.1: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %arg.1 = (s32[], bf16[8,64]{1,0}) parameter(0)
  %get-tuple-element.1 = bf16[8,64]{1,0} get-tuple-element(%arg.1), index=1
  %fusion.20 = bf16[8,64]{1,0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(mlp)/while/body/tanh"}
  ROOT %tuple.20 = (s32[], bf16[8,64]{1,0}) tuple(%get-tuple-element.1, %fusion.20)
}

%cond.1 (arg.2: (s32[], bf16[8,64])) -> pred[] {
  %arg.2 = (s32[], bf16[8,64]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.1 (params__w.1: bf16[8,64], opt__m.1: f32[64]) -> (bf16[8,64], f32[64]) {
  %params__w.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0), sharding={replicated}, metadata={op_name="params[\\'gpt.w\\']"}
  %opt__m.1 = f32[64]{0:T(1024)} parameter(1), metadata={op_name="opt_state[\\'gpt.w\\'][\\'m\\']"}
  %copy-start.1 = (bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, bf16[8,64]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%params__w.1)
  %fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%params__w.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(mlp)/tanh" stack_frame_id=7}
  %flash_packed_fwd.1 = bf16[8,64]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attn)/flash_packed_fwd/pallas_call"}
  %clamp.1 = bf16[8,64]{1,0} clamp(%fusion.1, %fusion.1, %fusion.1), metadata={op_name="jit(train_step)/jvp(embed)/jit(clip)/max"}
  %multiply_reduce_fusion.2 = (f32[]{:T(128)}, bf16[64,64,1]{1,0,2:T(8,128)(2,1)}) fusion(%fusion.1, %clamp.1), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(train_step)/clip/reduce_sum" stack_frame_id=177}
  %subtract_convert_fusion.3 = (bf16[64]{0}, f32[64]{0}) fusion(%params__w.1, %opt__m.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/update/convert_element_type"}
  %fusion.4 = bf16[8,64]{1,0} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.4, metadata={op_name="jit(train_step)/jvp(ce)/add"}
  %while.1 = (s32[], bf16[8,64]{1,0}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/jvp(mlp)/while"}
  %sqrt.1 = f32[]{:T(128)} sqrt(%get-tuple-element.9), metadata={op_name="jit(train_step)/clip/sqrt"}
  %add.9 = s32[]{:T(128)} add(%copy.9, %constant.9), metadata={op_name="jit(train_step)/add"}
  ROOT %tuple.9 = (bf16[8,64]{1,0}, f32[64]{0}) tuple(%fusion.4, %opt__m.1)
}
"""


def test_phase_census_on_a_hand_written_executable():
    census = programs.phase_census(_PHASE_HLO)
    assert {k: census[k] for k in census if "." in k and not k.startswith(
        ("params", "opt", "tuple", "arg", "get-tuple"))} == {
        "copy-start.1": ("other", "", False),           # no metadata
        "fusion.1": ("fwd", "mlp", False),
        "flash_packed_fwd.1": ("fwd", "attn", False),
        # jit(clip) is jnp.clip, not the clip scope
        "clamp.1": ("fwd", "embed", False),
        # a weight gradient with the clip's sum of squares fused in: named
        # by its root (clip), mixed, and given to its one matmul; the
        # parameter path on a nested fusion is no phase
        "multiply_reduce_fusion.2": ("bwd", "attn", True),
        # clip's scaling fused into the update: mixed, no matmul, its own
        "subtract_convert_fusion.3": ("update", "update", True),
        # two matmuls of two phases: mixed, keeps its own
        "fusion.4": ("fwd", "ce", True),
        "while.1": ("fwd", "mlp", False),
        "fusion.20": ("fwd", "mlp", False),             # the while's body
        "lt.1": ("other", "", False),                   # and its condition
        "sqrt.1": ("clip", "clip", False),
        "add.9": ("other", "", False),                  # under no scope
    }
    # nothing from inside a fused computation or a reduce's region
    assert not {"tanh.1", "convolution.2", "add.1", "dot.4"} & set(census)
    assert programs.phase_counts(census) == {
        "other": 10, "fwd": 6, "bwd": 1, "update": 1, "clip": 1, "mixed": 3}
    assert programs.phase_census("no computation here") == {}


# a scheduled step cut to what the placement tells apart: the compiler's
# own instructions (no metadata) between named ones, text order = run order
_PLACED_HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,64]) -> bf16[8,64] {
  %param_0.1 = bf16[8,64]{1,0} parameter(0)
  ROOT %tanh.1 = bf16[8,64]{1,0} tanh(%param_0.1)
}

%body.1 (arg.1: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %arg.1 = (s32[], bf16[8,64]{1,0}) parameter(0)
  %get-tuple-element.1 = bf16[8,64]{1,0} get-tuple-element(%arg.1), index=1
  %copy-start.5 = (bf16[8,64]{1,0:S(1)}, bf16[8,64]{1,0}, u32[]{:S(2)}) copy-start(%get-tuple-element.1)
  %get-tuple-element.2 = s32[]{:T(128)} get-tuple-element(%arg.1), index=0
  %copy.6 = s32[]{:T(128)} copy(%get-tuple-element.2)
  %copy-done.5 = bf16[8,64]{1,0:S(1)} copy-done(%copy-start.5)
  %convolution.5 = bf16[8,64]{1,0} convolution(%copy-done.5, %copy-done.5), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/jvp(kda)/kda_proj/while/body/dot_general"}
  ROOT %tuple.5 = (s32[]{:T(128)}, bf16[8,64]{1,0}) tuple(%copy.6, %convolution.5)
}

%cond.1 (arg.2: (s32[], bf16[8,64])) -> pred[] {
  %arg.2 = (s32[], bf16[8,64]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.1 (p.1: bf16[8,64], q.1: bf16[8,64]) -> (bf16[8,64], bf16[8,64], bf16[8,64]) {
  %p.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="params[\\'w\\']"}
  %q.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.1 = bf16[8,64]{1,0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(mlp)/tanh"}
  %copy-start.1 = (bf16[8,64]{1,0:S(1)}, bf16[8,64]{1,0}, u32[]{:S(2)}) copy-start(%fusion.1)
  %slice-start.1 = ((bf16[8,64]{1,0}), bf16[4,64]{1,0:S(1)}, s32[]{:S(2)}) slice-start(%p.1), slice={[0:4], [0:64]}
  %slice-start.2 = ((bf16[8,64]{1,0}), bf16[4,64]{1,0:S(1)}, s32[]{:S(2)}) slice-start(%p.1), slice={[4:8], [0:64]}
  %copy-done.1 = bf16[8,64]{1,0:S(1)} copy-done(%copy-start.1)
  %fusion.2 = bf16[8,64]{1,0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(attn))/mul"}
  %copy.2 = bf16[8,64]{0,1} copy(%fusion.2)
  %copy.3 = bf16[8,64]{1,0} copy(%copy.2)
  %fusion.3 = bf16[8,64]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(moe)/experts/add"}
  %fusion.4 = bf16[8,64]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/update/sub"}
  %slice-done.1 = bf16[4,64]{1,0:S(1)} slice-done(%slice-start.1)
  %slice-done.2 = bf16[4,64]{1,0:S(1)} slice-done(%slice-start.2)
  %custom-call.2 = bf16[8,64]{1,0:S(1)} custom-call(%slice-done.1, %slice-done.2), custom_call_target="ConcatBitcast"
  %ragged-dot-none.1 = bf16[8,64]{1,0} custom-call(%fusion.3, /*index=1*/%custom-call.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply_reduce_fusion.5 = bf16[8,64]{1,0} fusion(%ragged-dot-none.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/clip/reduce_sum"}
  %custom-call.1 = bf16[8,64]{1,0} custom-call(%q.1), custom_call_target="Mystery"
  %copy.4 = bf16[8,64]{1,0} copy(%fusion.4)
  %tuple.0 = (s32[], bf16[8,64]{1,0}) tuple(%constant.0, %multiply_reduce_fusion.5)
  %while.1 = (s32[], bf16[8,64]{1,0}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/jvp(kda)/kda_proj/while"}
  %get-tuple-element.9 = bf16[8,64]{1,0} get-tuple-element(%while.1), index=1
  %transpose.9 = bf16[8,64]{0,1} transpose(%get-tuple-element.9), dimensions={1,0}
  ROOT %tuple.9 = (bf16[8,64]{1,0}, bf16[8,64]{1,0}, bf16[8,64]{0,1}) tuple(%copy.4, %custom-call.1, %transpose.9)
}
"""


@pytest.mark.parametrize("name, want", [
    # made in fwd/mlp, read in bwd/attn: a prefetch's wait is its reader's
    ("copy-start.1", ("bwd", "attn", "consumer")),
    ("copy-done.1", ("bwd", "attn", "consumer")),       # as its start
    # a chain of two copies, the second read in two components: the
    # first reader in the text's order, for both
    ("copy.2", ("fwd", "moe/experts", "consumer")),
    ("copy.3", ("fwd", "moe/experts", "consumer")),
    # only the root reads it: what made its operand
    ("copy.4", ("update", "update", "producer")),
    # nothing named reads it or made its operand
    ("custom-call.1", ("other", "", "unplaced")),
    # a kernel computes: where its operands were made, though the clip's
    # sum of squares reads it first
    ("ragged-dot-none.1", ("fwd", "moe/experts", "producer")),
    # the sliced prefetch of a parameter that only the kernel reads goes
    # with the kernel, through the view that joins the slices
    ("slice-start.2", ("fwd", "moe/experts", "consumer")),
    ("slice-done.1", ("fwd", "moe/experts", "consumer")),
    ("custom-call.2", ("fwd", "moe/experts", "consumer")),
    # a loop's body: its parameter's element prefetched for a named matmul
    ("copy-start.5", ("fwd", "kda/kda_proj", "consumer")),
    ("copy-done.5", ("fwd", "kda/kda_proj", "consumer")),
    # carried from the body's parameter to its root: the walk ends there
    ("copy.6", ("other", "", "unplaced")),
    # past a named loop's element to the root: the loop made it
    ("transpose.9", ("fwd", "kda/kda_proj", "producer")),
])
def test_placed_census_on_a_hand_written_executable(name, want):
    assert programs.placed_census(_PLACED_HLO)[name] == want


def test_placed_census_holds_the_unnamed_that_run_and_leaves_the_census():
    census = programs.phase_census(_PLACED_HLO)
    before = dict(census)
    placed = programs.placed_census(_PLACED_HLO, census)
    assert census == before
    # the unnamed instructions only, and none that never runs as an op
    assert all(census[k][:2] == ("other", "") for k in placed)
    assert not {"p.1", "q.1", "tuple.0", "get-tuple-element.9", "fusion.2",
                "while.1", "tanh.1", "lt.1"} & set(placed)
    assert len(placed) == 16
    assert programs.placed_counts(placed) == {
        "consumer": 11, "producer": 3, "unplaced": 2}
    # the hand-written step of the census test: its one pair feeds nothing
    assert programs.placed_census(_PHASE_HLO)["copy-start.1"] == \
        ("other", "", "unplaced")
    assert programs.placed_census("no computation here") == {}


@pytest.fixture(scope="module")
def toy_train_step():
    """One toy GPT step through ``make_sharded_train_step``, built under
    the analysis pass and called twice."""
    import time

    import jax
    import jax.numpy as jnp
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                                 param_sharding_spec)
    site = "parallel.sharded_train_step"
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec)
    ids = jnp.zeros((2, 16), jnp.int32)
    before = get_program_registry().snapshot()["sites"].get(
        site, {"builds": 0})["builds"]
    with program_analysis():
        t0 = time.perf_counter()
        state, _ = step(state, ids, ids, jax.random.key(0))
        wall = time.perf_counter() - t0
        state, loss = step(state, ids, ids, jax.random.key(1))
    assert np.isfinite(float(loss))
    snap = get_program_registry().snapshot()["sites"][site]
    return {"site": site, "wall": wall, "snap": snap,
            "new": [h for h in snap["history"] if h["build"] > before]}


def test_phase_census_of_a_compiled_toy_train_step(toy_train_step):
    census = get_program_registry().phase_census(toy_train_step["site"])
    phases = {p for p, _, _ in census.values()}
    assert {"fwd", "bwd", "clip", "update"} <= phases
    # a GPT's step has GPT's components, not the other stacks' five
    assert {c for _, c, _ in census.values()} - {""} \
        == set(programs.PHASE_COMPONENTS) - {"gdn", "moe", "kda", "mla",
                                             "dsa"}
    # forward and backward of every scope of the model are told apart
    for c in ("embed", "attn", "mlp", "ln_f", "lm_head", "ce"):
        assert {("fwd", c), ("bwd", c)} <= {(p, k) for p, k, _
                                            in census.values()}, c
    # the snapshots carry counts, not names
    counts = toy_train_step["snap"]["analysis"]["phases"]
    assert counts == programs.phase_counts(census)
    assert sum(counts[p] for p in programs.PHASES if p in counts) \
        == len(census)
    assert get_program_registry().bench_block()["sites"][
        toy_train_step["site"]]["phases"] == counts
    assert get_program_registry().phase_census("no.such.site") is None


def test_placed_census_of_a_compiled_toy_train_step(toy_train_step):
    registry = get_program_registry()
    census = registry.phase_census(toy_train_step["site"])
    placed = registry.placed_census(toy_train_step["site"])
    # a map beside the census, over what the census could not name
    assert placed and set(placed) <= set(census)
    assert all(census[k][:2] == ("other", "") for k in placed)
    assert {via for _, _, via in placed.values()} <= set(programs.PLACED_VIA)
    assert any(p != ("other", "") for *p, _ in placed.values())
    counts = toy_train_step["snap"]["analysis"]["placed"]
    assert counts == programs.placed_counts(placed)
    assert sum(counts.values()) == len(placed)
    assert registry.placed_census("no.such.site") is None


# what an RMSNorm and a residual are made of, forward and backward (the
# backward's holds the call of the mixer's checkpoint and the views that
# cost nothing)
_NORM_AND_RESIDUAL = {"add", "add_any", "broadcast_in_dim",
                      "convert_element_type", "div", "mul", "reduce_sum",
                      "rsqrt", "square", "reshape", "remat2"}


@pytest.mark.parametrize("workload, scope", [
    ("ling3-tiny-rehearsal.train-s64", "kda"),
    ("qwen3-next-tiny-rehearsal.train-s64", "gdn")])
def test_a_delta_rule_mixer_names_its_projections_and_gates(workload, scope):
    """The lowered step of the model's rehearsal configuration: the four
    parts are on the name stacks in both phases, and what stands under the
    mixer's scope outside every part is its layer's norm and residual."""
    import re

    import jax
    import jax.numpy as jnp
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import run as harness
    from benchmark.drivers import train_steps
    cell_file = harness.load_json("workloads", workload)
    config = harness.load_json("configs", cell_file["config"])
    cell = harness.Cell(cell_file, config, seed=0)
    spec = harness.config_module(config, "reference", "reference") \
        .param_spec(config)
    dtype = jnp.dtype(config["training"]["param_dtype"])
    step, state, _ = train_steps.build_program(
        cell, {k: jnp.zeros(shape, dtype) for k, shape in spec.items()})
    mesh = jax.tree.leaves(state["params"])[0].sharding.mesh
    ids = jnp.zeros((cell_file["traffic"]["batch"],
                     cell_file["traffic"]["seqlen"]), jnp.int32)
    with jax.set_mesh(mesh):
        text = step._jitted.lower(
            state["params"], state["opt_state"], state["step"], (ids, ids),
            jax.random.key(0), jnp.float32(1e-4)).as_text(debug_info=True)
    made_of = {}
    for stack in set(re.findall(r'loc\("(jit\([^"]*)"', text)):
        made_of.setdefault(programs._phase_of(stack), set()).add(
            stack.rsplit("/", 1)[-1])
    for phase in ("fwd", "bwd"):
        for part in ("proj", "gates", "conv", "rule"):
            assert (phase, f"{scope}/{scope}_{part}") in made_of, (phase, part)
        assert "dot_general" in made_of[(phase, f"{scope}/{scope}_proj")]
        assert {"rsqrt", "logistic"} <= made_of[
            (phase, f"{scope}/{scope}_gates")]
        # no matmul, slice, gate or loop is left outside a part
        assert made_of[(phase, scope)] <= _NORM_AND_RESIDUAL, (
            phase, made_of[(phase, scope)] - _NORM_AND_RESIDUAL)
    assert "dot_general" not in made_of[("fwd", f"{scope}/{scope}_gates")]


def _toy_fit_program(front_end):
    """A two-layer MLP with a global-norm clip through ``Model.fit``'s
    compiled path or the auto-parallel ``Engine``, built under the
    analysis pass; the site its program is recorded under."""
    import jax
    from paddle_hackathon_tpu import hapi, io as pio, nn, parallel
    from paddle_hackathon_tpu import optimizer as optim

    class DS(pio.Dataset):
        x = np.random.RandomState(0).randn(16, 8).astype(np.float32)

        def __len__(self):
            return len(self.x)

        def __getitem__(self, i):
            return self.x[i], np.int64(self.x[i].sum() > 0)

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 2))
    opt = optim.Adam(learning_rate=1e-2, parameters=net.parameters(),
                     grad_clip=nn.ClipGradByGlobalNorm(1.0))
    prev = parallel.get_mesh()
    try:
        with program_analysis():
            if front_end == "hapi":
                m = hapi.Model(net)
                m.prepare(optimizer=opt, loss=nn.CrossEntropyLoss())
                m.fit(DS(), epochs=1, batch_size=8, verbose=0,
                      shuffle=False, jit_compile=True,
                      steps_per_execution=2)
                assert m._fit_used_compiled
                return "hapi.compiled_trainer"
            from paddle_hackathon_tpu.parallel.auto_parallel import (
                Engine, ProcessMesh)
            Engine(net, loss=nn.CrossEntropyLoss(), optimizer=opt,
                   process_mesh=ProcessMesh([0], dim_names=["dp"])).fit(
                       DS(), epochs=1, batch_size=8, verbose=0)
            return "parallel.engine_train_step"
    finally:
        parallel.set_mesh(prev)


@pytest.mark.parametrize("front_end", ["sharded", "hapi", "engine"])
def test_every_front_end_names_clip_and_update(front_end, request):
    """The ``clip`` / ``update`` scopes are written once, in
    ``optimizer/``, so all three trainers' programs carry them."""
    site = request.getfixturevalue("toy_train_step")["site"] \
        if front_end == "sharded" else _toy_fit_program(front_end)
    census = get_program_registry().phase_census(site)
    counts = programs.phase_counts(census)
    assert counts.get("clip", 0) > 0 and counts.get("update", 0) > 0, counts


def test_program_report_prints_the_two_censuses_counts(toy_train_step,
                                                       capsys):
    import program_report
    program_report.render({"sites": {toy_train_step["site"]:
                                     toy_train_step["snap"]}})
    out = capsys.readouterr().out
    analysis = toy_train_step["snap"]["analysis"]
    assert f"census: phases other={analysis['phases']['other']}" in out
    placed = analysis["placed"]
    assert ("unnamed placed by consumer={consumer} producer={producer} "
            "unplaced={unplaced}".format(**placed)) in out
    # a site that was not analysed gets no census row
    program_report.render({"sites": {"plain": {"builds": 1}}})
    assert "census" not in capsys.readouterr().out


def test_build_record_says_where_the_seconds_went(toy_train_step):
    (rec,) = toy_train_step["new"]       # the second call recorded nothing
    parts = [rec[k] for k in programs.BUILD_CLOCK_KEYS]
    assert all(v >= 0 for v in parts) and rec["trace_s"] > 0
    assert rec["analysis_s"] >= 0 and rec["cache_hit"] is False
    # disjoint stretches of the first call's wall clock; the analysis
    # pass's own events went to it, not to the build
    assert sum(parts) <= rec["compile_s"]
    assert rec["compile_s"] + rec["analysis_s"] <= toy_train_step["wall"]
    assert set(rec["analysis_split"]) == set(programs.BUILD_CLOCK_KEYS) \
        | {"cache_hit"}
    assert 0 <= rec["analysis"]["census_s"] <= rec["analysis_s"]


def test_build_clock_counts_nested_spans_once():
    programs.start_build_clock()
    assert programs.read_build_clock() == {
        "trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
        "cache_hit": False}
    trace, lower, backend = programs._BUILD_EVENTS
    # an outer trace 0..10 that inlines a jitted function (2..3) and
    # compiles a small program eagerly (4..6), then lowers and compiles
    for ev, t0, t1 in ((trace, 2.0, 3.0), (trace, 4.0, 4.5),
                       (lower, 4.5, 5.0), (backend, 5.0, 6.0),
                       (trace, 0.0, 10.0), (lower, 10.0, 13.0),
                       (backend, 13.5, 20.0), ("/jax/other", 0.0, 99.0)):
        programs._on_build_span(ev, t0, t1, fun_name="f")
    programs._on_build_duration(programs._CACHE_RETRIEVAL_EVENT, 0.25)
    assert programs.read_build_clock() == {
        "trace_s": 10.0, "lower_s": 3.0, "backend_compile_s": 6.5,
        "cache_hit": True}
    programs.start_build_clock()
    assert programs.read_build_clock()["trace_s"] == 0.0


def test_instrumented_jit_build_record_and_compile_span_carry_the_clock():
    import jax
    import jax.numpy as jnp
    spans = []
    tracing.set_span_sink(
        lambda name, t0, t1, tid, attrs: spans.append((name, attrs)))
    tracing.enable_tracing()
    try:
        site = _site("clock")
        w = instrument_jit(jax.jit(lambda x: jnp.tanh(x) * 2), site=site,
                           registry=MetricRegistry())
        w(jnp.ones((4,)))
        w(jnp.ones((4,)))
    finally:
        tracing.disable_tracing()
        tracing.set_span_sink(None)
    (rec,) = get_program_registry().snapshot()["sites"][site]["history"]
    assert rec["trace_s"] > 0 and rec["backend_compile_s"] > 0
    assert sum(rec[k] for k in programs.BUILD_CLOCK_KEYS) <= rec["compile_s"]
    assert "analysis_s" not in rec      # no analysis pass was asked for
    (attrs,) = [a for n, a in spans if n == f"compile:{site}"]
    assert {k: attrs[k] for k in programs.BUILD_CLOCK_KEYS} \
        == {k: rec[k] for k in programs.BUILD_CLOCK_KEYS}


def test_build_record_carries_what_its_kernels_said_of_themselves():
    """The packed flash kernels' wrapper states their executed score
    share while the program is traced; the record of that build, and of
    no other, carries it (``chip_smoke.py`` prints it for the trainer)."""
    import jax
    import jax.numpy as jnp
    from paddle_hackathon_tpu.incubate.nn.kernels import (
        flash_attention_packed as fap)
    heads, d = 2, 64

    def attend(causal):
        return lambda x: jax.grad(lambda a: jnp.sum(
            fap.flash_attention_packed(a, heads, causal, 0.125).astype(
                jnp.float32)))(x)
    qkv = jnp.ones((1, 512, 3 * heads * d), jnp.bfloat16)
    want = fap.executed_score_share(512, 512, heads, d, qkv.dtype, True)
    assert want == 0.625                    # one cell, four strips
    reg = MetricRegistry()
    site = _site("facts")
    for causal in (True, False):            # two builds at one site
        instrument_jit(jax.jit(attend(causal)), site=site, registry=reg)(qkv)
    plain = _site("facts")
    instrument_jit(jax.jit(jnp.tanh), site=plain, registry=reg)(qkv)
    sites = get_program_registry().snapshot()["sites"]
    assert [h["kernel_facts"] for h in sites[site]["history"]] == [
        {"executed_score_share": [want]}, {"executed_score_share": [1.0]}]
    assert "kernel_facts" not in sites[plain]["history"][0]
    # facts die with the build they were said in
    programs.note_kernel_fact("k", 2)
    programs.note_kernel_fact("k", 1)
    assert programs.read_kernel_facts() == {"k": [1, 2]}
    programs.start_build_clock()
    assert programs.read_kernel_facts() == {}
