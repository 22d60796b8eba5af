"""pht-lint: seeded-violation fixtures, the baseline workflow, CLI exit
codes, and the tier-1 gate — the repo-wide run must be CLEAN (zero
unsuppressed findings), so any new hot-path sync / retrace hazard /
lock inversion breaks the suite here instead of landing.

Rule catalog and workflow: docs/STATIC_ANALYSIS.md.  Pure AST work —
no engine compiles, the whole module stays in the lean tier-1 budget
(~7s, dominated by the one repo-wide walk).
"""

import collections
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.pht_lint import (BaselineError, DEFAULT_BASELINE,  # noqa: E402
                            changed_paths, default_paths, load_baseline,
                            run_lint)
from tools.pht_lint.__main__ import main as lint_main  # noqa: E402

FIXTURES = os.path.join(ROOT, "tests", "fixtures", "lint")

_EXPECT_RE = re.compile(r"#\s*expect:\s*((?:PHT\d{3}[\s,]*)+)")


def _expected(path):
    """(line, rule) -> count, parsed from the fixture's own comments."""
    out = collections.Counter()
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            m = _EXPECT_RE.search(line)
            if m:
                for rule in m.group(1).replace(",", " ").split():
                    out[(i, rule)] += 1
    return out


def _actual(path):
    findings, suppressed, unused = run_lint(paths=[path],
                                            baseline_path=None)
    assert not suppressed and not unused
    return collections.Counter((f.line, f.rule) for f in findings)


# ------------------------------------------------------------ fixtures
@pytest.mark.parametrize("name", ["pht001_hot_sync.py",
                                  "pht002_retrace.py",
                                  "pht003_locks.py",
                                  "pht004_nondet.py",
                                  "pht005_labels.py",
                                  "pht006_donation.py",
                                  "pht007_tracer.py",
                                  "pht008_specs.py",
                                  "pht009_races.py",
                                  "pht010_checkact.py"])
def test_seeded_violations_detected_at_exact_lines(name):
    """Every seeded violation fires at the exact file:line — and ONLY
    there (the Counter equality also rejects extra findings, so the
    fixtures' negative shapes — cold_path, shielded_branch_ok,
    host_side_ok — are asserted clean by the same comparison)."""
    path = os.path.join(FIXTURES, name)
    expected = _expected(path)
    assert expected, f"{name} has no # expect: comments"
    assert _actual(path) == expected


def test_clean_fixture_has_zero_findings():
    assert _actual(os.path.join(FIXTURES, "clean_hot.py")) == {}


def test_fixture_findings_carry_func_and_hint():
    findings, _, _ = run_lint(
        paths=[os.path.join(FIXTURES, "pht001_hot_sync.py")],
        baseline_path=None)
    for f in findings:
        assert f.func and f.hint and f.message
        assert f.file.startswith("tests/fixtures/lint/")
        assert re.search(r":\d+: PHT\d{3}", f.render())


# ------------------------------------------------------ repo-wide gate
def test_repo_wide_lint_is_clean():
    """THE gate: zero unsuppressed findings across the package, tools
    and bench driver, and zero unused baseline entries (a fixed finding
    must take its suppression with it).  The same walk feeds the
    --stats plumbing and the walk's own CPU budget below: the linter
    rides the tier-1 suite, so a slow new rule is a test failure here."""
    stats = {}
    findings, suppressed, unused = run_lint(stats=stats)
    assert findings == [], "unsuppressed pht-lint findings:\n" + "\n".join(
        f.render() for f in findings)
    assert unused == [], f"stale baseline entries (fixed? delete them): " \
                         f"{unused}"
    # the declared hot roots must actually exist in the walked scope —
    # a rename that silently drops a root would turn PHT001 off there
    assert any(f.rule == "PHT001" for f in suppressed), \
        "no PHT001 suppressions: did the hot-root annotations vanish?"
    # stats shape: every pass timed, every rule counted (incl. the new
    # PHT009/PHT010), and the whole-scope walk within its ~10s budget
    assert set(stats["passes"]) == {"rules", "flow", "races", "locks"}
    for rule in ("PHT001", "PHT003", "PHT006", "PHT009", "PHT010"):
        assert rule in stats["rule_counts"], stats["rule_counts"]
    assert stats["files"] > 100   # whole scope, not a partial walk
    # budget on process-CPU seconds net of GC, not wall: the walk is
    # single-threaded pure CPU, so cpu_s == wall on an idle box but —
    # unlike wall — does not flake when the (already over-budget)
    # tier-1 suite shares the box with other load, and — unlike gross
    # CPU — does not flake when this test runs INSIDE the suite, where
    # every collection triggered by the walk's allocations scans the
    # jax + compiled-program heap the suite has piled up
    assert stats["gc_cpu_s"] >= 0.0
    assert stats["cpu_s"] < 10.0, (
        f"repo-wide pht-lint burned {stats['cpu_s']:.1f} CPU-s — over "
        "the ~10s budget; profile the passes (python -m tools.pht_lint "
        f"--stats) and make the slow rule leaner: {stats['passes']}")


def test_default_scope_covers_the_hot_modules():
    paths = {os.path.relpath(p, ROOT) for p in default_paths()}
    for rel in ("paddle_hackathon_tpu/inference/serving.py",
                "paddle_hackathon_tpu/hapi/compiled.py",
                "paddle_hackathon_tpu/nn/decode.py",
                "tools/metrics_dump.py", "tools/perf_gate.py",
                "bench.py"):
        assert rel in paths, rel
    assert not any("fixtures" in p for p in paths)


def test_new_telemetry_code_is_label_cardinality_clean():
    """The SLO telemetry this round added (lifecycle records, the /load
    report, the MFU gauges) must not smuggle per-request values into
    metric labels: PHT005 over exactly those modules, baseline on (the
    two justified bounded-loop suppressions stay suppressed)."""
    telem = [os.path.join(ROOT, rel) for rel in (
        "paddle_hackathon_tpu/inference/serving.py",
        "paddle_hackathon_tpu/observability/metrics.py",
        "paddle_hackathon_tpu/observability/server.py",
        "paddle_hackathon_tpu/observability/tracing.py",
        "paddle_hackathon_tpu/hapi/model.py",
        "paddle_hackathon_tpu/parallel/auto_parallel.py",
    )]
    findings, suppressed, _ = run_lint(paths=telem,
                                       baseline_path=DEFAULT_BASELINE)
    assert [f.render() for f in findings if f.rule == "PHT005"] == []
    # the rule actually ran here: the two justified per-topology loops
    # (expert label, device label) are suppressed, not invisible
    assert sum(f.rule == "PHT005" for f in suppressed) >= 2


# ------------------------------------------- PHT006-008 (flow) units
def test_underkeyed_cache_key_is_caught(tmp_path):
    """The generalized ring_attention seq_local hazard: dropping a
    captured local from the cache_key must lint (PR 7 caught this class
    by hand; the pre-ZeRO check must catch it mechanically)."""
    src = open(os.path.join(ROOT, "paddle_hackathon_tpu", "parallel",
                            "sequence.py"), encoding="utf-8").read()
    broken = src.replace(
        'cache_key=("ring_xla", axis, n, causal, float(scale_), seq_local)',
        'cache_key=("ring_xla", axis, n, causal, float(scale_))')
    assert broken != src, "ring_xla cache_key moved — update this test"
    p = tmp_path / "sequence.py"
    p.write_text(broken)
    findings, _, _ = run_lint(paths=[str(p)], baseline_path=None,
                              repo_root=str(tmp_path))
    assert any(f.rule == "PHT007" and "seq_local" in f.message
               for f in findings), [f.render() for f in findings]
    # and the shipped file keys the capture: clean
    ok, _, _ = run_lint(paths=[os.path.join(
        ROOT, "paddle_hackathon_tpu", "parallel", "sequence.py")],
        baseline_path=None)
    assert not any(f.rule == "PHT007" for f in ok)


def test_donation_flow_sees_through_wrappers(tmp_path):
    """instrument_jit/sanitize_donation wrapping must not hide the
    donate_argnums from PHT006 — the repo's donation sites are all
    wrapped (hapi/compiled.py is the template)."""
    p = tmp_path / "m.py"
    p.write_text(
        "import jax\n"
        "from paddle_hackathon_tpu.observability.metrics import "
        "instrument_jit\n\n\n"
        "def _step(s, b):\n"
        "    return s + b\n\n\n"
        "class T:\n"
        "    def __init__(self):\n"
        "        self._jit = instrument_jit(\n"
        "            jax.jit(_step, donate_argnums=(0,)), site='x')\n\n"
        "    def run(self, b):\n"
        "        out = self._jit(self.state, b)\n"
        "        return self.state\n")
    findings, _, _ = run_lint(paths=[str(p)], baseline_path=None,
                              repo_root=str(tmp_path))
    assert [f.rule for f in findings] == ["PHT006"]
    assert "self.state" in findings[0].message


def test_spec_drift_resolves_create_mesh_axes(tmp_path):
    """PHT008 reads axis names out of parallel/api.py's create_mesh
    dict literal, not just jax.sharding.Mesh ctors."""
    p = tmp_path / "m.py"
    p.write_text(
        "import jax\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "from paddle_hackathon_tpu.parallel.api import create_mesh\n\n"
        "m = create_mesh({'dp': 2, 'mp': 4})\n\n\n"
        "def place(arr):\n"
        "    return jax.device_put(arr, NamedSharding(m, P('tp')))\n")
    findings, _, _ = run_lint(paths=[str(p)], baseline_path=None,
                              repo_root=str(tmp_path))
    assert [f.rule for f in findings] == ["PHT008"]
    assert "tp" in findings[0].message


# --------------------------------------------- PHT009/PHT010 (races)
def test_serving_tickno_annotation_is_load_bearing(tmp_path):
    """The `# pht-lint: gil-atomic` claims on serving.py's driver-only
    _tickno reads are WHY the repo-wide lint is clean: strip one and
    PHT009 must fire on that exact read (the annotation is a reviewed
    contract, not a comment)."""
    src = open(os.path.join(ROOT, "paddle_hackathon_tpu", "inference",
                            "serving.py"), encoding="utf-8").read()
    marker = "np.int32(self._tickno), **self._pt_kw())  # pht-lint: gil-atomic"
    broken = src.replace(
        marker, "np.int32(self._tickno), **self._pt_kw())", 1)
    assert broken != src, "tickno annotation moved — update this test"
    p = tmp_path / "serving.py"
    p.write_text(broken)
    findings, _, _ = run_lint(paths=[str(p)], baseline_path=None,
                              repo_root=str(tmp_path))
    assert any(f.rule == "PHT009" and "_tickno" in f.message
               for f in findings), [f.render() for f in findings]
    # and the shipped file is PHT009-clean (the repo-wide gate pins the
    # rest of the scope; this pins the specific file the rule targets)
    ok, _, _ = run_lint(paths=[os.path.join(
        ROOT, "paddle_hackathon_tpu", "inference", "serving.py")],
        baseline_path=None)
    assert not any(f.rule in ("PHT009", "PHT010") for f in ok), \
        [f.render() for f in ok if f.rule in ("PHT009", "PHT010")]


def test_cli_stats_text(capsys):
    rc = lint_main([os.path.join(FIXTURES, "pht009_races.py"),
                    "--no-baseline", "--stats"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "pht-lint stats:" in out
    assert "PHT009=5" in out
    assert "pass races" in out


def test_cli_stats_json(capsys):
    import json
    rc = lint_main([os.path.join(FIXTURES, "pht010_checkact.py"),
                    "--no-baseline", "--format", "json", "--stats"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["stats"]["rule_counts"]["PHT010"] == 2
    assert set(doc["stats"]["passes"]) == {"rules", "flow", "races",
                                           "locks"}
    assert doc["stats"]["files"] == 1


# ------------------------------------------------------------ baseline
def test_baseline_entries_all_have_reasons():
    entries = load_baseline(DEFAULT_BASELINE)
    assert entries, "baseline exists and is non-empty"
    for e in entries:
        assert e["reason"].strip(), e


def test_baseline_missing_reason_is_an_error(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text('[[suppress]]\nrule = "PHT001"\n'
                 'file = "x.py"\nfunc = "f"\n')
    with pytest.raises(BaselineError, match="no reason"):
        load_baseline(str(p))


def test_baseline_unknown_key_and_bad_syntax_are_errors(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text('[[suppress]]\nrule = "PHT001"\nfile = "x.py"\n'
                 'func = "f"\nreason = "r"\nseverity = "low"\n')
    with pytest.raises(BaselineError, match="unknown key"):
        load_baseline(str(p))
    p.write_text('[[suppress]]\nrule = PHT001\n')
    with pytest.raises(BaselineError, match="double-quoted"):
        load_baseline(str(p))


def test_baseline_suppresses_matching_findings(tmp_path):
    fixture = os.path.join(FIXTURES, "pht004_nondet.py")
    p = tmp_path / "b.toml"
    p.write_text('[[suppress]]\nrule = "PHT004"\n'
                 'file = "tests/fixtures/lint/pht004_nondet.py"\n'
                 'func = "frozen_entropy"\n'
                 'reason = "seeded on purpose"\n')
    findings, suppressed, unused = run_lint(paths=[fixture],
                                            baseline_path=str(p))
    assert {f.func for f in suppressed} == {"frozen_entropy"}
    assert len(suppressed) == 3
    # findings in OTHER functions are not covered by the entry
    assert {f.func for f in findings} == {"_noise_helper",
                                          "aliased_entropy",
                                          "nested_scope",
                                          "nested_scope.inner"}
    assert unused == []


def test_baseline_matching_and_unused_detection_cover_race_rules(tmp_path):
    """PHT009/PHT010 suppressions ride the same (rule, file, func)
    matching and unused-entry detection as PHT001-008 — and the same
    reason-required strictness (the loader is rule-agnostic, this pins
    that the NEW rules' findings actually match entries)."""
    fixture = os.path.join(FIXTURES, "pht009_races.py")
    p = tmp_path / "b.toml"
    p.write_text(
        '[[suppress]]\nrule = "PHT009"\n'
        'file = "tests/fixtures/lint/pht009_races.py"\n'
        'func = "Dispatcher._loop"\n'
        'reason = "seeded fixture; invariant: the loop thread is the '
        'only mutator of replicas/inflight"\n'
        '[[suppress]]\nrule = "PHT010"\n'
        'file = "never/was.py"\nfunc = "g"\nreason = "obsolete"\n')
    findings, suppressed, unused = run_lint(paths=[fixture],
                                            baseline_path=str(p))
    assert {f.func for f in suppressed} == {"Dispatcher._loop"}
    assert all(f.rule == "PHT009" for f in suppressed)
    # findings in other functions stay unsuppressed...
    assert {f.func for f in findings} == {"Dispatcher._scan",
                                          "PoolUser._work",
                                          "DebugHandler.do_GET"}
    # ...and the stale PHT010 entry is detected as unused
    assert [e["rule"] for e in unused] == ["PHT010"]


def test_unused_baseline_entry_is_reported(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text('[[suppress]]\nrule = "PHT001"\n'
                 'file = "never/was.py"\nfunc = "g"\n'
                 'reason = "obsolete"\n')
    _, _, unused = run_lint(
        paths=[os.path.join(FIXTURES, "clean_hot.py")],
        baseline_path=str(p))
    assert len(unused) == 1 and unused[0]["file"] == "never/was.py"


# ------------------------------------------------------------ CLI
def test_cli_exit_codes(tmp_path, capsys):
    # findings -> 1
    assert lint_main([os.path.join(FIXTURES, "pht001_hot_sync.py"),
                      "--no-baseline"]) == 1
    # clean -> 0
    assert lint_main([os.path.join(FIXTURES, "clean_hot.py")]) == 0
    # malformed baseline -> 2 (perf_gate convention: broken != regression)
    bad = tmp_path / "bad.toml"
    bad.write_text('[[suppress]]\nrule = "PHT001"\n')
    assert lint_main([os.path.join(FIXTURES, "clean_hot.py"),
                      "--baseline", str(bad)]) == 2
    # --changed and explicit paths are exclusive -> 2
    assert lint_main(["--changed", "somefile.py"]) == 2
    # an explicit path that is missing or unparseable must NOT report a
    # 'clean' lint that never ran -> 2
    assert lint_main([os.path.join(FIXTURES, "does_not_exist.py")]) == 2
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    assert lint_main([str(broken)]) == 2
    capsys.readouterr()


def test_cli_json_format(capsys):
    import json
    rc = lint_main([os.path.join(FIXTURES, "pht003_locks.py"),
                    "--no-baseline", "--format", "json"])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert {f["rule"] for f in out["findings"]} == {"PHT003"}
    assert all(f["line"] and f["hint"] for f in out["findings"])


def test_changed_paths_stay_in_scope():
    """--changed (the pre-PR check) only ever lints scope files that
    exist — whatever the current worktree diff happens to be."""
    for p in changed_paths():
        rel = os.path.relpath(p, ROOT)
        assert rel.endswith(".py") and os.path.exists(p)
        assert rel.startswith(("paddle_hackathon_tpu/", "tools/")) \
            or rel == "bench.py"


def test_full_lock_graph_catches_straddling_cycle(tmp_path):
    """A lock-order cycle whose two halves live in a changed and an
    UNCHANGED module is invisible to a diff-only graph — the --changed
    mode must build PHT003 over the whole scope."""
    d = tmp_path / "tools"
    d.mkdir()
    (d / "mod_a.py").write_text(
        "import threading\n"
        "from tools import mod_b\n"
        "_lock_a = threading.Lock()\n\n\n"
        "def take_a():\n"
        "    with _lock_a:\n"
        "        pass\n\n\n"
        "def take_a_then_b():\n"
        "    with _lock_a:\n"
        "        mod_b.take_b()\n")
    changed = d / "mod_b.py"
    changed.write_text(
        "import threading\n"
        "from tools import mod_a\n"
        "_lock_b = threading.Lock()\n\n\n"
        "def take_b():\n"
        "    with _lock_b:\n"
        "        pass\n\n\n"
        "def take_b_then_a():\n"
        "    with _lock_b:\n"
        "        mod_a.take_a()\n")
    partial, _, _ = run_lint(paths=[str(changed)], baseline_path=None,
                             repo_root=str(tmp_path))
    assert not any("cycle" in f.message for f in partial)
    full, _, _ = run_lint(paths=[str(changed)], baseline_path=None,
                          repo_root=str(tmp_path), full_lock_graph=True)
    assert any(f.rule == "PHT003" and "cycle" in f.message
               for f in full), [f.render() for f in full]


def test_changed_paths_include_branch_commits(tmp_path):
    """On a feature branch, committing the diff must not turn the
    pre-PR check vacuously green: files in commits since the merge-base
    with main stay in scope."""
    import subprocess

    def git(*a):
        subprocess.run(["git", *a], cwd=tmp_path, check=True,
                       capture_output=True, timeout=60)
    git("init", "-b", "main")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "seed.py").write_text("x = 1\n")
    git("add", "."); git("commit", "-m", "seed")
    git("checkout", "-b", "feat")
    (tmp_path / "tools" / "newmod.py").write_text("y = 2\n")
    git("add", "."); git("commit", "-m", "feat work")
    got = {os.path.relpath(p, tmp_path)
           for p in changed_paths(repo_root=str(tmp_path))}
    assert got == {"tools/newmod.py"}


def test_changed_paths_include_untracked_files(tmp_path):
    """A brand-new (never git-added) module is exactly the file the
    pre-PR check must not skip.  Scratch repo, not the live one — a
    tier-1 timeout kill mid-test must not leave a stray probe file."""
    import subprocess

    def git(*a):
        subprocess.run(["git", *a], cwd=tmp_path, check=True,
                       capture_output=True, timeout=60)
    git("init", "-b", "main")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "seed.py").write_text("x = 1\n")
    git("add", "."); git("commit", "-m", "seed")
    (tmp_path / "tools" / "untracked.py").write_text("y = 2\n")
    got = {os.path.relpath(p, tmp_path)
           for p in changed_paths(repo_root=str(tmp_path))}
    assert got == {"tools/untracked.py"}


def test_deep_call_chain_does_not_blind_lock_analysis(tmp_path):
    """Regression: acquires() used to memoize DEPTH-TRUNCATED results,
    so an unrelated deep chain reaching a function first permanently
    hid its lock from later shallow queries — a real cycle went
    unreported depending on definition order."""
    chain = "\n\n".join(
        f"def g{i}():\n    g{i + 1}()" for i in range(8))
    src = f"""import threading

_lock_b = threading.Lock()
_lock_c = threading.Lock()


def deep_entry():
    g0()


{chain}


def g8():
    with _lock_b:
        pass


def shallow_entry():
    with _lock_c:
        g8()


def reverse():
    with _lock_b:
        with _lock_c:
            pass
"""
    p = tmp_path / "deepchain.py"
    p.write_text(src)
    findings, _, _ = run_lint(paths=[str(p)], baseline_path=None,
                              repo_root=str(tmp_path))
    assert any(f.rule == "PHT003" and "cycle" in f.message
               for f in findings), [f.render() for f in findings]


def test_relative_imports_resolve_from_package_init():
    """module_dotted() strips '__init__', so a package __init__'s
    level-1 import is relative to base_dotted ITSELF — resolving one
    level higher silently blinded PHT003 to package-__init__ modules."""
    from tools.pht_lint.callgraph import index_module
    mi = index_module(os.path.join(
        ROOT, "paddle_hackathon_tpu", "observability", "__init__.py"), ROOT)
    assert mi.imports["make_lock"] == \
        "paddle_hackathon_tpu.observability.sanitizers.make_lock"
    # and from a plain module, the existing behavior is unchanged
    mi2 = index_module(os.path.join(
        ROOT, "paddle_hackathon_tpu", "observability", "metrics.py"), ROOT)
    assert mi2.imports["make_lock"] == \
        "paddle_hackathon_tpu.observability.sanitizers.make_lock"


def test_cli_partial_scope_does_not_flag_unused_baseline(capsys):
    """Linting one file must not advise deleting live suppressions that
    simply live elsewhere (they are only provably stale repo-wide)."""
    rc = lint_main([os.path.join(FIXTURES, "clean_hot.py")])
    assert rc == 0
    assert "unused baseline entry" not in capsys.readouterr().err
