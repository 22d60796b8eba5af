"""Render or diff program-observatory snapshots.

A snapshot is the JSON served at ``/debug/programs`` (or
``ProgramRegistry.snapshot()`` saved by hand)::

    import json
    from paddle_hackathon_tpu.observability import get_program_registry
    json.dump(get_program_registry().snapshot(), open("progs.json", "w"))

Usage::

    python tools/program_report.py progs.json              # top sites
    python tools/program_report.py --causes progs.json     # cause history
    python tools/program_report.py before.json after.json  # diff

The single-snapshot view ranks sites by total compile seconds — the
"where does my compile time go" read — with builds/evictions and the
latest HBM analysis row when ``PHT_PROGRAM_ANALYSIS`` harvested one.
``--causes`` appends each site's bounded retrace-cause history (the
forensic read: WHY did build N happen).  The diff shows only sites
whose builds/evictions/compile-seconds moved between the snapshots,
with the causes recorded in between — "what recompiled during this
run, and why".  Reading rules and the cause taxonomy:
``docs/OBSERVABILITY.md``, "Program observatory".
"""

import argparse
import json
import sys


def _human_bytes(v):
    v = float(v)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(v) < 1024.0 or unit == "TiB":
            return f"{v:.0f}{unit}" if unit == "B" else f"{v:.1f}{unit}"
        v /= 1024.0


def _analysis_str(a):
    if not a:
        return ""
    parts = [f"{kind}={_human_bytes(a[f'{kind}_bytes'])}"
             for kind in ("args", "outputs", "temp", "generated")
             if a.get(f"{kind}_bytes") is not None]
    if a.get("flops"):
        parts.append(f"flops={a['flops']:.3g}")
    return "  ".join(parts)


def _census_str(a):
    """The analysed build's two censuses as counts: instructions by phase
    (``mixed`` fusions among them) and, of those without a name stack,
    how many the dataflow placed (``programs.placed_census``)."""
    if not a or not a.get("phases"):
        return ""
    out = "phases " + " ".join(f"{k}={v}" for k, v in a["phases"].items())
    if a.get("placed"):
        out += "; unnamed placed by " + " ".join(
            f"{k}={v}" for k, v in a["placed"].items())
    if a.get("census_s") is not None:
        out += f"; {a['census_s']:.3f}s"
    return out


def _ranked(snap):
    return sorted(snap.get("sites", {}).items(),
                  key=lambda kv: (-kv[1].get("compile_seconds_total", 0.0),
                                  kv[0]))


def render(snap, out=None):
    """Top compile-time sites, one aligned line each (+ analysis row)."""
    out = out or sys.stdout
    sites = _ranked(snap)
    out.write(f"programs: {snap.get('builds_total', 0)} builds, "
              f"{snap.get('compile_seconds_total', 0.0):.3f}s compile "
              f"across {len(sites)} sites\n")
    width = max((len(name) for name, _ in sites), default=0)
    for name, s in sites:
        out.write(f"  {name:<{width}}  "
                  f"{s.get('compile_seconds_total', 0.0):>8.3f}s  "
                  f"builds={s.get('builds', 0)}  "
                  f"evictions={s.get('evictions', 0)}  "
                  f"kind={s.get('kind', '?')}\n")
        analysis = _analysis_str(s.get("analysis"))
        if analysis:
            out.write(f"  {'':<{width}}  hbm: {analysis}\n")
        census = _census_str(s.get("analysis"))
        if census:
            out.write(f"  {'':<{width}}  census: {census}\n")
    return len(sites)


def render_causes(snap, out=None, site=None):
    """Per-site retrace-cause history (bounded window, build order)."""
    out = out or sys.stdout
    n = 0
    for name, s in _ranked(snap):
        if site is not None and name != site:
            continue
        causes = [h for h in s.get("history", ()) if h.get("cause")]
        out.write(f"{name}: {s.get('builds', 0)} builds, "
                  f"{len(causes)} with recorded causes\n")
        for h in causes:
            out.write(f"  build {h['build']} "
                      f"({h.get('compile_s', 0.0):.3f}s): {h['cause']}\n")
        n += len(causes)
    return n


def render_diff(prev, cur, out=None):
    """Sites whose builds/evictions/compile-seconds moved, with the
    causes recorded in between (history entries newer than the previous
    snapshot's build count)."""
    out = out or sys.stdout
    ps = prev.get("sites", {})
    rows = 0
    for name, s in _ranked(cur):
        old = ps.get(name, {})
        db = s.get("builds", 0) - old.get("builds", 0)
        de = s.get("evictions", 0) - old.get("evictions", 0)
        ds = s.get("compile_seconds_total", 0.0) \
            - old.get("compile_seconds_total", 0.0)
        if not db and not de:
            continue
        tag = " (new site)" if name not in ps else ""
        out.write(f"{name}: +{db} builds, +{de} evictions, "
                  f"+{ds:.3f}s compile{tag}\n")
        for h in s.get("history", ()):
            if h.get("build", 0) > old.get("builds", 0) and h.get("cause"):
                out.write(f"  build {h['build']}: {h['cause']}\n")
        rows += 1
    for name in sorted(set(ps) - set(cur.get("sites", {}))):
        out.write(f"{name}: (removed)\n")
        rows += 1
    if not rows:
        out.write("(no program builds between snapshots)\n")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="render one /debug/programs snapshot, or diff two")
    ap.add_argument("snapshot", help="program-registry snapshot JSON")
    ap.add_argument("snapshot2", nargs="?",
                    help="later snapshot: show what recompiled in between")
    ap.add_argument("--causes", action="store_true",
                    help="append per-site retrace-cause history")
    ap.add_argument("--site", default=None,
                    help="restrict --causes to one site label")
    args = ap.parse_args(argv)
    with open(args.snapshot) as f:
        snap = json.load(f)
    if args.snapshot2 is not None:
        with open(args.snapshot2) as f:
            snap2 = json.load(f)
        render_diff(snap, snap2)
        return 0
    render(snap)
    if args.causes or args.site:
        render_causes(snap, site=args.site)
    return 0


if __name__ == "__main__":
    sys.exit(main())
