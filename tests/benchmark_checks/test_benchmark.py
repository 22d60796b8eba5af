"""Tests of the benchmark itself (``benchmark/``), on the CPU at a toy
size.  This directory is one of ``BENCHMARK.json``'s ``paths``, under
``tests/`` so that tier-1 collects it:

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_checks -q

They load no libtpu and report no device number.  The harness runs end to
end on the rehearsal cell; the yardstick's arithmetic is held to hand
counts; the plain reference is held to the program's float32 forward and
to ``jax.grad`` of itself; the control (float8) and each fault a training
cell can have come out as not correct.  A second rehearsal cell runs a
language model that is no GPT (``gated_conv_lm.py`` here, its reference,
count, configuration and workload under ``benchmark/``): the harness
takes an architecture by its files alone.
"""

import glob
import json
import math
import os
import re

import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmark")
TINY = "gpt-tiny-rehearsal.train-s64"
TOY = "gated-conv-tiny-rehearsal.train-s64"   # a model that is no GPT
REHEARSALS = (TINY, TOY)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _names_what_exists(cfg):
    """A configuration's file names its reference and its operation count
    by files that are there, and the Mosaic kernels its step has to hold
    as name -> calls."""
    for key, kind in (("reference", "reference"), ("op_count", "op_counts")):
        assert os.path.isfile(os.path.join(BENCH, kind, cfg[key] + ".py"))
    kernels = cfg["program"]["mosaic_kernels"]
    assert all(NAME.match(k) and type(n) is int and n > 0
               for k, n in kernels.items())


# ------------------------------------------------------------- the manifest

def test_manifest_names_units_and_files():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for x in m["end_to_end"]:
        assert 0 < x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        assert set(x.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", x["name"] + ".py"))
        assert "mfu" not in x["name"] or x["unit"] == "%"
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        on_file = _load("configs", c["name"])
        assert on_file["reduced"] == c["reduced"]
        assert on_file["source"] == c["source"]
        _names_what_exists(on_file)
        assert on_file["program"]["mosaic_kernels"]   # a cell's step has some
    assert {w["config"] for w in m["workloads"]} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = _load("workloads", w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert not cell.get("rehearsal")
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", cell["driver"] + ".py"))
        limits = cell["check"]["limits"]
        assert all(isinstance(v, float) and v > 0 for v in limits.values())


@pytest.mark.parametrize("workload", REHEARSALS)
def test_rehearsal_cell_is_found_by_name_and_is_no_cell(workload):
    m = _manifest()
    assert workload not in {w["name"] for w in m["workloads"]}
    cell = _load("workloads", workload)
    assert cell["rehearsal"] is True
    assert cell["config"] not in {c["name"] for c in m["configs"]}
    _names_what_exists(_load("configs", cell["config"]))


def test_the_harness_names_no_architecture():
    """What is general takes an architecture through the names in its
    configuration's file: no general file names one itself."""
    words = {os.path.splitext(f)[0]
             for f in os.listdir(os.path.join(BENCH, "op_counts"))
             if f.endswith(".py")}
    assert {"gpt", "gated_conv"} <= words
    general = glob.glob(os.path.join(BENCH, "*.py")) \
        + glob.glob(os.path.join(BENCH, "drivers", "*.py")) + [
        os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        for m in _manifest()["per_layer"] if "workloads" not in m]
    assert len(general) >= 14
    for path in general:
        with open(path) as f:
            text = f.read().lower()
        assert not [w for w in words if w in text], path


# ------------------------------------------------------------ the yardstick

@pytest.mark.parametrize("config, gflop, params_m", [
    ("gpt3-1.3b", 8.47, 1313.7), ("gpt2-medium", 2.42, 354.9)])
def test_flops_per_token_hand_counts(config, gflop, params_m):
    from benchmark import run as harness
    from benchmark.reference import gpt_f32
    cfg = _load("configs", config)
    h, L, v, s = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"], 1024
    by_hand = 6 * (L * 12 * h * h + v * h) + 12 * L * h * s
    count = harness.config_module(cfg, "op_count", "op_counts")
    assert count.train_flops_per_token(cfg, s) == by_hand
    assert round(by_hand / 1e9, 2) == gflop
    n = sum(math.prod(shape) for shape in gpt_f32.param_spec(cfg).values())
    assert round(n / 1e6, 1) == params_m == cfg["parameters_millions"]


TOY_FLOPS = 497664   # by hand, below


def test_the_toys_count_is_its_own_hand_count():
    """h 64, inner 128, 2 blocks, 4 taps, vocabulary 512: per block the
    fused input projection 64 x 256 and the output projection 128 x 64,
    the untied head 64 x 512; nothing for the embedding's lookup, nothing
    that grows with the sequence.  A GPT's count of the same file would
    need ``num_heads`` and a tied head."""
    from benchmark import run as harness
    cfg = _load("configs", _load("workloads", TOY)["config"])
    count = harness.config_module(cfg, "op_count", "op_counts")
    by_hand = 6 * (2 * (64 * 256 + 128 * 64) + 64 * 512) + 6 * 2 * 4 * 128
    assert by_hand == TOY_FLOPS
    assert count.train_flops_per_token(cfg, 64) == by_hand \
        == count.train_flops_per_token(cfg, 1024)
    ref = harness.config_module(cfg, "reference", "reference")
    n = sum(math.prod(shape) for shape in ref.param_spec(cfg).values())
    assert round(n / 1e6, 1) == cfg["parameters_millions"]


@pytest.mark.parametrize("key, value", [("op_count", None),
                                        ("op_count", "no_such_count"),
                                        ("reference", None)])
def test_a_configuration_that_names_no_module_ends_by_the_harness(
        monkeypatch, key, value):
    """No default and no ``KeyError``: the run ends in set-up, before any
    weight is drawn, by the harness's own message."""
    from benchmark import run as harness
    real = harness.load_json

    def load_json(kind, name):
        got = real(kind, name)
        if kind == "configs":
            got = {k: v for k, v in got.items() if k != key}
            if value is not None:
                got[key] = value
        return got
    monkeypatch.setattr(harness, "load_json", load_json)
    with pytest.raises(SystemExit) as end:
        harness.main(["--workload", TINY, "--seed", "1", "--seconds", "0.1"])
    kind = {"op_count": "op_counts", "reference": "reference"}[key]
    assert f"no {kind}/" in str(end.value)
    assert str(end.value).startswith("benchmark: ")


@pytest.mark.parametrize("required, census, missing", [
    ({"a": 2, "b": 6}, {"a": 2, "b": 6}, 0),
    ({"a": 2, "b": 6}, {"a": 2, "b": 5}, 1),
    ({"a": 2, "b": 6}, {"b": 6}, 2),              # an absent name: all of it
    ({"a": 2, "b": 6}, {"a": 9, "b": 4, "c": 7}, 2),   # a surplus pays nothing
    ({}, {"c": 7}, 0)])                           # a step that needs none
def test_kernels_missing_counts_calls_by_name(required, census, missing):
    from benchmark.drivers import train_steps
    assert train_steps.kernels_missing(required, census) == missing


def test_attention_work_and_roofline_side():
    from benchmark import flops, peaks
    chip = peaks.load_peaks("TPU v5 lite")
    fwd = flops.causal_attention_work(6, 16, 1024, 128, 2, backward=False)
    assert fwd["flops"] == 4 * 6 * 16 * 1024 * 1024 / 2 * 128
    assert fwd["bytes"] == 4 * 6 * 1024 * 16 * 128 * 2
    bwd = flops.causal_attention_work(6, 16, 1024, 128, 2, backward=True)
    assert bwd["flops"] == 2.5 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    least = flops.roofline_seconds(fwd, chip)
    assert least["bound"] == "compute"
    assert least["seconds"] == fwd["flops"] / 197e12
    tiny = {"flops": 1.0, "bytes": 819e9}
    assert flops.roofline_seconds(tiny, chip) == {"seconds": 1.0,
                                                  "bound": "memory"}


def test_peaks_unknown_kind_raises_and_no_environment_override(monkeypatch):
    from benchmark import peaks
    monkeypatch.setenv("PHT_PEAK_FLOPS", "1")
    chip = peaks.load_peaks("TPU v5 lite")
    assert chip["bf16_flops_per_s"] == 197e12
    assert chip["hbm_bytes_per_s"] == 819e9 and chip["hbm_bytes"] == 2 ** 34
    with pytest.raises(peaks.UnknownDevice):
        peaks.load_peaks("cpu")


def test_trace_reduce_on_a_synthetic_trace():
    from benchmark import trace_reduce as tr
    ops = [("%while.1 = ...", 0, 100),          # parent
           ("%fusion.2 = f32[8] fusion(...)", 10, 30),   # nested
           ("%fusion.3 = f32[8] fusion(...)", 50, 20),   # nested
           ("%jvp_flash_packed_fwd_.7 = custom-call", 150, 50),
           ("%copy.9 = ...", 180, 40)]          # overlaps the kernel's end
    assert tr.busy_ns(ops) == 100 + 70
    assert tr.gaps(ops) == [(100, 50)]
    own = tr.self_time_by_name(ops)
    assert own["%while.1 = ..."] == 50           # its children taken out
    assert own["%fusion.2 = f32[8] fusion(...)"] == 30
    assert sum(own.values()) >= tr.busy_ns(ops)  # overlap counted per op
    assert tr.time_of(ops, ("flash_packed_fwd",)) == 50
    assert tr.count_of(ops, ("flash_packed_fwd",)) == 1
    # an op that consumes a kernel's output names it among its operands,
    # and is not the kernel
    dq = "%transpose_jvp_flash_packed_bwd_dq__.4"
    bwd = [(dq + " = bf16[6,1024,2048]{2,1,0} custom-call(%p.1)", 300, 40),
           ("%convolution_add_fusion.8 = bf16[6,1024,6144]{2,1,0} fusion("
            f"bf16[6,1024,2048]{{2,1,0}} {dq}, bf16[6144]{{0}} %p.2)",
            340, 25)]
    assert tr.time_of(ops + bwd, ("flash_packed_bwd_dq",)) == 40
    assert tr.count_of(ops + bwd, ("flash_packed_bwd_dq",)) == 1
    assert tr.own_name(bwd[1][0]) == "%convolution_add_fusion.8"
    assert tr.op_kind("%fusion.189 = bf16[4,8]{1,0} fusion(x)") == \
        "fusion bf16[4,8]"
    assert tr.op_kind("%multiply_reduce_fusion.9 = (f32[], bf16[8,4]{1,0}, "
                      "bf16[4]{0}) fusion(bf16[9,9] %p)") == \
        "multiply_reduce_fusion bf16[8,4]"
    assert tr.op_kind("%jvp_flash_packed_fwd_.33 = (..) custom-call()") == \
        "jvp_flash_packed_fwd_"
    planes = {"/device:TPU:0": {"XLA Ops": ops},
              "/host:CPU": {"main": [("bench.wait", 90, 70),
                                     ("other", 0, 1000)]}}
    red = tr.reduce(planes, ("bench.wait",))
    assert red["busy_s"] == 170e-9 and red["window_s"] == 220e-9
    assert red["idle_gaps"] == [["bench.wait", 50e-9]]
    assert tr.reduce({"/host:CPU": {}}) is None


# ------------------------------------------------------------ the reference

def _tiny_setup(seed=11, batch=2, seqlen=32):
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.reference import gpt_f32
    cfg = _load("configs", "gpt-tiny-rehearsal")
    spec = gpt_f32.param_spec(cfg)
    params = weights.make_params(seed, spec, jnp.float32)
    (ids, labels), = weights.make_batches(seed, 1, batch, seqlen,
                                          cfg["vocab_size"])
    return cfg, spec, params, ids, labels


def test_reference_equals_the_programs_float32_forward():
    import numpy as np
    import paddle_hackathon_tpu as paddle
    from benchmark.drivers import train_steps
    from benchmark.reference import gpt_f32
    cfg, _, params, ids, _ = _tiny_setup()
    prog = cfg["program"]
    model = train_steps._resolve(prog["model"])(
        train_steps._resolve(prog["config"])(
            **{k: cfg[k] for k in prog["config_keys"]}))
    for k, p in model.named_parameters():
        p._set_value(params[k])
    model.eval()
    got = np.asarray(model(paddle.to_tensor(np.asarray(ids)))._value)
    want = np.asarray(gpt_f32.logits_fn(params, ids, cfg))
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() + 1e-6


def test_reference_block_by_block_gradients_equal_jax_grad():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import gpt_f32
    cfg, _, params, ids, labels = _tiny_setup()

    def loss_fn(p):
        logits = gpt_f32.logits_fn(p, ids, cfg)
        lse = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - picked)

    want_loss, want = jax.value_and_grad(loss_fn)(params)
    got = {}
    loss = gpt_f32.grads_pass(params, ids, labels, cfg, got.__setitem__)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert set(got) == set(want)
    for k in want:
        scale = float(jnp.abs(want[k]).max()) + 1e-12
        assert float(jnp.abs(got[k] - want[k]).max()) < 1e-4 * scale, k


# -------------------------------------------------------- the harness, whole

def _run(capsys, *extra, workload=TINY, seed=3000000019, seconds="0.3"):
    from benchmark import run as harness
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", seconds, *extra])
    out = capsys.readouterr()
    return rc, out


@pytest.mark.parametrize("workload, flops_per_token", [
    (TINY, 6 * (2 * 12 * 64 * 64 + 512 * 64) + 12 * 2 * 64 * 64),
    (TOY, TOY_FLOPS)])
def test_run_end_to_end_on_the_rehearsal_cell(capsys, workload,
                                              flops_per_token):
    rc, out = _run(capsys, "--trace", "0", workload=workload)
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["attempted"] >= 1 and line["failed"] == 0
    # a CPU run never prints a number under a device metric's name
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert set(line["rehearsal"]) == {"cpu_rehearsal.train_tokens_per_s",
                                      "cpu_rehearsal.setup_s"}
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
        assert f"check {name} = " in out.err   # each number beside its limit
    assert out.err.strip().splitlines()[-1].startswith("benchmark: check ")
    # the count is the one the configuration's file names
    assert f"operations a token at s64: {float(flops_per_token)!r} " \
        in out.err


def test_a_real_cell_refuses_to_run_without_the_chip(capsys):
    rc, out = _run(capsys, "--trace", "0", workload="gpt2-medium.train-s1024")
    assert rc != 0 and out.out.strip() == ""


def _state_unchanged(real_build):
    """The fault: a step that returns its state as it got it."""
    import jax

    def build(cell, params):
        step, state, model = real_build(cell, params)

        def broken(state, ids, labels, key):
            copy = jax.tree.map(lambda a: a.copy(), state)
            _, loss = step(copy, ids, labels, key)
            return state, loss
        return broken, state, model
    return build


def _half_batch(real_build):
    """The fault: half of the batch left out, the mean over the rest."""
    def build(cell, params):
        step, state, model = real_build(cell, params)

        def broken(state, ids, labels, key):
            half = ids.shape[0] // 2
            return step(state, ids[:half], labels[:half], key)
        return broken, state, model
    return build


@pytest.mark.parametrize("workload", REHEARSALS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault,
                                                   workload):
    from benchmark import run as harness
    driver = harness.load_module("drivers", "train_steps")
    monkeypatch.setattr(driver, "build_program", fault(driver.build_program))
    rc, out = _run(capsys, "--trace", "0", workload=workload)
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc != 0 and line["correct"] is False
    failed = [k for k, c in line["checks"].items()
              if not c["value"] <= c["limit"]]
    assert set(failed) & {"grad_norm_gap", "change_norm_gap"}, failed


@pytest.mark.parametrize("workload, control_fails", [(TINY, True),
                                                     (TOY, False)])
def test_control_gives_the_harness_verdict_on_program_control_and_fault(
        capsys, workload, control_fails):
    """``control.py`` at a size a test run can hold: the program on two
    seeds comes out correct; the reference put in its place in float8 (the
    control) and with half of the batch left out (the fault) comes out not
    correct on three, by the harness's own verdict under the rehearsal
    cell's limits.  The model that is no GPT goes through the same file;
    at its toy size the float8 control does not separate (its workload's
    ``limits_note`` has the readings), so there only the fault is held."""
    from benchmark import control
    rc = control.main(["--workload", workload, "--seeds", "4,5",
                       "--control-seeds", "5,6,3000000007"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    got = {}
    for rec in lines:
        got.setdefault(rec["kind"], []).append(rec["correct"])
        assert set(rec["checks"]) == {"grad_norm_gap", "change_norm_gap"}
    assert got["program"] == [True] * 2
    assert got["fault_half_batch"] == [False] * 3
    assert len(got["control_fp8"]) == 3 and len(got) == 3
    if control_fails:
        assert rc == 0 and got["control_fp8"] == [False] * 3


def test_compare_leaves_dead_leaves_out_and_takes_the_worst_live_leaf():
    from benchmark.drivers import train_steps
    ref = {"losses": [10.0], "grad_norm": {"a": 1.0, "b": 2.0, "dead": 1e-9},
           "change_norm": {"a": 0.01, "b": 0.01, "dead": 0.01}}
    prog = {"losses": [10.001],
            "grad_norm": {"a": 1.0, "b": 2.1, "dead": 1e-2},   # round-off
            "change_norm": {"a": 0.0101, "b": 0.01, "dead": 0.03}}
    gaps, worst = train_steps.compare(prog, ref, 1e-3)
    assert worst["leaves_left_out"] == 1
    assert worst["grad_norm"]["leaf"] == "b"
    assert gaps["grad_norm_gap"] == pytest.approx(0.05)
    assert gaps["change_norm_gap"] == pytest.approx(0.01)
    assert gaps["loss_gap_1"] == pytest.approx(1e-4)
    prog["grad_norm"]["a"] = float("nan")
    gaps, _ = train_steps.compare(prog, ref, 1e-3)
    assert gaps["grad_norm_gap"] == float("inf")
    checks = train_steps.checks_of(gaps, {"grad_norm_gap": 0.1}, [("x", 0)])
    assert [c[0] for c in checks] == ["grad_norm_gap", "x"]
    from benchmark import run as harness
    assert not harness.verdict(checks)
    assert harness.verdict([("x", 0, 0), ("y", 0.05, 0.1)])
