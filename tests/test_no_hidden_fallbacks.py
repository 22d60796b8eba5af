"""No fallback that hides the device (PR 21), checked where no chip is.

Every way a run could *appear* to have used the chip without it is shown
to fail loudly: ``chip_smoke.main()`` without a TPU, a phase that raises,
``bench.py`` with no chip and no explicit CPU request, a bench row that
raises, a device probe that cannot reach the platform it was pointed at, a
launcher asked for several processes per TPU host, and a parent process
that would hold the chip its children need.  Plus the compile-cache
helper's placement rules.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types
import warnings

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load("chip_smoke")


@pytest.fixture()
def bench():
    return _load("bench")


# ---------------------------------------------------------------------------
# chip_smoke.main() always demands the chip
# ---------------------------------------------------------------------------

def test_main_exits_nonzero_without_a_chip(cs, capsys):
    assert jax.default_backend() == "cpu"
    assert cs.main() != 0
    assert capsys.readouterr().out == ""        # no result line


def test_main_fails_when_a_phase_raises(cs, monkeypatch, capsys, tmp_path):
    from paddle_hackathon_tpu.core import compile_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # tests never switch the persistent cache on (conftest.py)
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: str(tmp_path))

    def boom(**kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")
    monkeypatch.setattr(cs, "phase_kernels", boom)
    with warnings.catch_warnings():             # main() edits the filters
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            cs.main()
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    non-zero, no result."""
    dst = tmp_path / "chip_smoke.py"
    dst.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(dst)], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


# ---------------------------------------------------------------------------
# the compile-cache helper
# ---------------------------------------------------------------------------

def test_cache_helper_sets_nothing_when_placed_from_outside(monkeypatch):
    from paddle_hackathon_tpu.core import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
    assert compile_cache.enable_compile_cache() == "/somewhere/placed"
    assert calls == []                          # jax reads the variable
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable_compile_cache() == compile_cache.DEFAULT_DIR
    assert calls == [("jax_compilation_cache_dir",
                      compile_cache.DEFAULT_DIR)]


def test_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    path = os.path.join(ROOT, "paddle_hackathon_tpu", "core",
                        "compile_cache.py")
    code = ("import runpy, sys; "
            "print(runpy.run_path(sys.argv[1])['DEFAULT_DIR'])")
    seen = {subprocess.run([sys.executable, "-c", code, path], cwd=cwd,
                           capture_output=True, text=True, check=True,
                           timeout=60).stdout.strip()
            for cwd in (ROOT, str(tmp_path))}
    assert seen == {os.path.join(ROOT, ".jax_compile_cache")}
    ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
    assert ".jax_compile_cache/" in ignored
    # the tool's copy of the tree must not carry a populated cache either
    assert ".jax_compile_cache/" in open(
        os.path.join(ROOT, ".chiprunignore")).read().split()


# ---------------------------------------------------------------------------
# bench.py: no chip -> no number
# ---------------------------------------------------------------------------

def test_bench_needs_a_chip_or_an_explicit_cpu_request(bench, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._cpu_requested() is True
    for val in (None, "tpu,cpu"):
        if val is None:
            monkeypatch.delenv("JAX_PLATFORMS")
        else:
            monkeypatch.setenv("JAX_PLATFORMS", val)
        with pytest.raises(RuntimeError, match="needs a TPU"):
            bench._cpu_requested()       # backend is cpu, nobody asked
        monkeypatch.setattr(sys, "argv", ["bench.py"])
        with pytest.raises(RuntimeError, match="needs a TPU"):
            bench.main()                 # python bench.py exits non-zero
        with pytest.raises(RuntimeError, match="needs a TPU"):
            bench._trace_device_ms(lambda: None)   # never host-timed


def test_bench_host_timed_row_cannot_keep_a_device_metric_name(bench):
    m = "gpt2_serving_8stream_device_tokens_per_sec_per_chip"
    assert bench._row_metric(m, "device") == m
    renamed = bench._row_metric(m, "host")
    assert renamed.endswith("_cpu_smoke") and "device" not in renamed


def test_bench_suite_exits_nonzero_when_a_row_raises(bench, monkeypatch,
                                                     capsys):
    monkeypatch.setattr(bench, "SUITE", {"good": None, "boom": None,
                                         "tail": None})
    spawned_with_backend = []

    def fake_run(args, capture_output=True, text=True, timeout=None):
        from jax._src import xla_bridge
        spawned_with_backend.append(bool(xla_bridge._backends))
        name = args[args.index("--one") + 1]
        if name == "boom":
            return types.SimpleNamespace(returncode=1, stdout="",
                                         stderr="ValueError: dtype crash")
        return types.SimpleNamespace(
            returncode=0, stderr="",
            stdout=json.dumps({"metric": name, "value": 1.0}) + "\n")
    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--suite"])
    assert bench.main() == 1
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    # the failed row is named, and the rows after it still ran
    assert [r["metric"] for r in rows] == ["good", "boom", "tail"]
    assert "dtype crash" in rows[1]["error"] and "value" not in rows[1]
    monkeypatch.setattr(bench, "SUITE", {"good": None})
    assert bench.main() == 0


def test_spawning_parents_hold_no_backend():
    """bench.py --suite and tools/adjudicate_attention.py start children
    that need the chip; a parent that had initialised a backend would
    hold it.  Importing them (and walking their spawn loops) must leave
    jax's backend table empty."""
    code = """
import subprocess, sys, types
sys.path.insert(0, {root!r}); sys.path.insert(0, {root!r} + "/tools")
from jax._src import xla_bridge
import bench, adjudicate_attention
seen = []
def fake_run(args, **kw):
    seen.append(bool(xla_bridge._backends))
    return types.SimpleNamespace(returncode=0, stderr="",
                                 stdout='{{"metric": "m", "value": 1}}')
subprocess.run = fake_run
bench.SUITE = {{"a": None, "b": None}}
bench.run_suite()
sys.argv = ["adjudicate_attention.py", "--impls", "packed,splash"]
adjudicate_attention.main()
assert seen == [False] * 4, seen
assert not xla_bridge._backends
print("NO_BACKEND")
""".format(root=ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "NO_BACKEND" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# device selection never drops to the CPU by itself
# ---------------------------------------------------------------------------

def test_default_place_is_what_jax_selected_or_its_error(monkeypatch):
    from paddle_hackathon_tpu.core import device
    assert device._default_place() == device.Place("cpu", 0)

    def unreachable(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", unreachable)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        device._default_place()          # not Place("cpu")
    assert device.set_device("cpu") == device.Place("cpu", 0)   # explicit


def test_predictor_does_not_take_any_device_when_the_chip_is_missing(
        monkeypatch):
    from paddle_hackathon_tpu import inference
    real = jax.devices

    def devices(backend=None):
        if backend is None:
            raise RuntimeError("Unable to initialize backend 'tpu'")
        return real(backend)
    monkeypatch.setattr(jax, "devices", devices)
    cfg = inference.Config("no_such_model")
    assert cfg.use_gpu()                 # "the accelerator" is the default
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        inference.create_predictor(cfg)


def test_launcher_refuses_several_processes_per_tpu_host(monkeypatch):
    from paddle_hackathon_tpu.distributed.launch.context import (Context,
                                                                 parse_args)
    from paddle_hackathon_tpu.distributed.launch.controllers import (
        CollectiveController, UnsupportedLaunchError, children_platform)
    assert children_platform({"JAX_PLATFORMS": "tpu,cpu"}) == "tpu"
    assert children_platform({"JAX_PLATFORMS": "cpu"}) == "cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    ctl = CollectiveController(Context(parse_args(
        ["--nproc_per_node", "2", "train.py"])))
    with pytest.raises(UnsupportedLaunchError, match="one process per host"):
        ctl.build_pod()
    # the supported form on a TPU host: one process driving all chips
    CollectiveController(Context(parse_args(["train.py"]))).build_pod()


def test_planner_has_no_default_peak_for_an_unknown_device():
    import jax.numpy as jnp

    from paddle_hackathon_tpu import parallel
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        parallel.plan_mesh(object(), 8, (jnp.zeros((8, 4), jnp.int32),))
