"""The per-layer readers that place what has no name stack
(``benchmark/placed_times.py`` and the five readers of PR 37), on a
synthetic ``XLA Ops`` list, a fake census and a fake placed map with
hand-counted values.  CPU only: the numbers here are counts of nanoseconds
written below, never a device's."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SITE = "parallel.sharded_train_step"
MS = 1e6  # ns

READERS = ("step_unplaced_share", "async_copy_wait_ms", "layout_change_ms",
           "kda_gates_ms", "gdn_gates_ms")

# (phase, component, mixed), as observability/programs.py phase_census
CENSUS = {
    "fusion.1": ("fwd", "kda/kda_proj", False),
    "fusion.2": ("fwd", "kda/kda_gates", False),
    "fusion.3": ("bwd", "kda/kda_gates", False),
    "fusion.4": ("bwd", "kda/kda_proj", False),
    "fusion.5": ("fwd", "kda", False),            # the norm and residual
    "fusion.6": ("fwd", "gdn/gdn_gates", False),
    "fusion.7": ("bwd", "gdn/gdn_proj", False),
    "copy.8": ("bwd", "kda/kda_rule", False),     # a layout copy with a name
    "copy-start.9": ("other", "", False),
    "copy-done.9": ("other", "", False),
    "slice-done.10": ("other", "", False),
    "copy.11": ("other", "", False),
    "reshape.12": ("other", "", False),
    "ragged-dot-none.13": ("other", "", False),
    "custom-call.14": ("other", "", False),
    "while.15": ("fwd", "moe/experts", False),
}
# (phase, component, via), as placed_census: over the unnamed only
PLACED = {
    "copy-start.9": ("bwd", "kda/kda_gates", "consumer"),
    "copy-done.9": ("bwd", "kda/kda_gates", "consumer"),
    "slice-done.10": ("fwd", "moe/experts", "consumer"),
    "copy.11": ("fwd", "kda", "producer"),
    "reshape.12": ("other", "", "unplaced"),
    "ragged-dot-none.13": ("fwd", "moe/experts", "producer"),
    "custom-call.14": ("other", "", "unplaced"),
}
# one step's events, (own name, start ms, duration ms); slice-done.10 runs
# inside while.15 (1 of its 3 ms) and twice a step; fusion.16 is not in the
# census
STEP = (("fusion.1", 0, 5), ("fusion.2", 5, 4), ("fusion.3", 9, 7),
        ("fusion.4", 16, 6), ("fusion.5", 22, 1), ("fusion.6", 23, 3),
        ("fusion.7", 26, 2), ("copy.8", 28, 1.5), ("copy-start.9", 29.5, 0.5),
        ("copy-done.9", 30, 2), ("while.15", 32, 3), ("slice-done.10", 33, 0.5),
        ("slice-done.10", 34, 0.5), ("copy.11", 35, 1.25),
        ("reshape.12", 36.25, 0.75), ("ragged-dot-none.13", 37, 2),
        ("custom-call.14", 39, 0.25), ("fusion.16", 39.25, 0.5))
STEP_MS, STEPS, BUSY_MS = 40.0, 3, 39.75


def _ops():
    return [(f"%{name} = bf16[8,128]{{1,0}} fusion(%copy-done.9)",
             (s * STEP_MS + start) * MS, dur * MS)
            for s in range(STEPS) for name, start, dur in STEP]


def _run(monkeypatch, census=CENSUS, placed=PLACED, traced=True,
         has_placed=True):
    from benchmark import phase_times, trace_reduce

    class Registry:
        def phase_census(self, site):
            assert site == SITE
            return census

        def snapshot(self):
            return {"sites": {}}

    if has_placed:
        Registry.placed_census = lambda self, site: placed
    monkeypatch.setattr(phase_times, "_registry", Registry)
    ops = _ops()
    return {"config": {"program": {"jit_site": SITE}}, "peaks": None,
            "facts": {"traced": {"ops": ops,
                                 "busy_s": trace_reduce.busy_ns(ops) / 1e9}
                      if traced else None},
            "notes": []}


def _read(name, run):
    from benchmark import run as harness
    return harness.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name, want", [
    # the reshape 0.75 + the custom call 0.25 + the op the census does not
    # hold 0.5, over 39.75 ms busy
    ("step_unplaced_share", 100 * (0.75 + 0.25 + 0.5) / BUSY_MS),
    # copy-start 0.5 + copy-done 2 + two slice-done of 0.5
    ("async_copy_wait_ms", 0.5 + 2 + 2 * 0.5),
    # the named copy 1.5 + the placed copy 1.25 + the unplaced reshape 0.75
    ("layout_change_ms", 1.5 + 1.25 + 0.75),
    # by name stack, both phases: what is placed there is in the note
    ("kda_gates_ms", 4 + 7),
    ("gdn_gates_ms", 3),
])
def test_reader_gives_the_hand_counted_value(monkeypatch, name, want):
    assert _read(name, _run(monkeypatch)) == pytest.approx(want, rel=1e-9)


def test_placed_times_add_up_to_busy_and_say_how_each_op_was_placed(
        monkeypatch):
    from benchmark import phase_times, placed_times
    run = _run(monkeypatch)
    times = placed_times.placed_times(run)
    assert times["steps"] == STEPS
    assert times["busy"] == pytest.approx(
        1e9 * run["facts"]["traced"]["busy_s"])
    assert times["busy"] == pytest.approx(
        phase_times.phase_times(run)["busy"])
    assert sum(times["by_component"].values()) == pytest.approx(times["busy"])
    per_step = {k: v / STEPS / MS for k, v in times["by_how"].items()}
    assert per_step == pytest.approx({
        "named": 5 + 4 + 7 + 6 + 1 + 3 + 2 + 1.5 + (3 - 1),
        "consumer": 0.5 + 2 + 1, "producer": 1.25 + 2, "unplaced": 1.5})
    # what was `other` now stands under the component that waits for it
    by = {k: v / STEPS / MS for k, v in times["by_component"].items()}
    assert by[("bwd", "kda/kda_gates")] == pytest.approx(7 + 0.5 + 2)
    assert by[("fwd", "moe/experts")] == pytest.approx(2 + 1 + 2)
    assert by[("fwd", "kda")] == pytest.approx(1 + 1.25)
    assert by[("other", "")] == pytest.approx(1.0)
    assert by[("absent", "")] == pytest.approx(0.5)
    # computed once, announced once
    assert placed_times.placed_times(run) is times
    assert sum("placed census" in n for n in run["notes"]) == 1


def test_the_notes_say_who_waits_and_what_is_left(monkeypatch):
    run = _run(monkeypatch)
    for name in READERS:
        _read(name, run)
    notes = "\n".join(run["notes"])
    assert "consumer 3, producer 2, unplaced 2" in notes
    assert "unplaced, ms a step by kind of op: reshape 0.750, fusion " \
        "bf16[8,128] 0.500, custom-call 0.250" in notes
    assert "3.0 `*-done` ops a step" in notes
    assert "bwd/kda/kda_gates 2.500, fwd/moe/experts 1.000" in notes
    assert "with a name stack 1.500" in notes
    assert "kda: kda_proj 5.000 fwd + 6.000 bwd, kda_gates 4.000 fwd + " \
        "7.000 bwd, outside every part 1.000 fwd + 0.000 bwd" in notes
    assert "placed under it, by part: kda_gates 2.500, outside a part 1.250" \
        in notes
    assert "gdn: gdn_proj 0.000 fwd + 2.000 bwd, gdn_gates 3.000 fwd" in notes
    assert notes.endswith("placed under it, by part: nothing")


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("missing", ["placed_census", "trace", "census",
                                     "build"])
def test_reader_returns_none_where_its_source_is_missing(monkeypatch, name,
                                                         missing):
    """The parent of PR 37 has no ``placed_census``; a run without a trace,
    a program without a census and a site no build of which was analysed
    read ``None`` alike, and nothing raises."""
    run = _run(monkeypatch, has_placed=missing != "placed_census",
               traced=missing != "trace",
               census=None if missing == "census" else CENSUS,
               placed=None if missing == "build" else PLACED)
    assert _read(name, run) is None


def test_a_mixer_reader_is_silent_on_a_step_without_its_scope(monkeypatch):
    census = {k: v for k, v in CENSUS.items() if "gdn" not in v[1]}
    run = _run(monkeypatch, census=census)
    assert _read("gdn_gates_ms", run) is None
    assert _read("kda_gates_ms", run) == pytest.approx(11.0)


def test_manifest_gives_the_general_readers_to_the_cells_it_can():
    """The three readers that name no scope stand on the lists of the two
    cells whose reader sets no accepted test pins; each hybrid cell's set
    is held by equality in its own test file, so they, and the two mixer
    readers (files here, no entry yet), wait for the ``benchmark`` PR that
    may edit those files (ROADMAP B0)."""
    from benchmark import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    general = {"step_unplaced_share", "async_copy_wait_ms",
               "layout_change_ms"}
    for cell in ("gpt3-1.3b.train-s1024", "gpt2-medium.train-s1024"):
        _, layer = harness.cell_metrics(manifest, cell)
        assert {m["name"] for m in layer} & set(READERS) == general
    for cell in ("qwen3-next-80b-a3b.train-s4096",
                 "ling-3.0-flash.train-s4096"):
        _, layer = harness.cell_metrics(manifest, cell)
        assert not {m["name"] for m in layer} & set(READERS)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert {by_name[n]["moves"] for n in general} == {"train_tokens_per_s"}
    for name in READERS:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
