"""What a traced window's device time belongs to once the ops without a
name stack are placed: the shared part of the per-layer readers
``step_unplaced_share``, ``async_copy_wait_ms``, ``layout_change_ms`` and
of the two delta-rule mixers' ``*_gates_ms``.

``phase_times`` joins a trace's ``XLA Ops`` events to the program's phase
census by instruction name; what the compiler made itself (asynchronous
copies between its two memories, layout copies, the kernels a grouped
matmul expands to) has no name stack and lands in ``other``.  Since PR 37
the program keeps a second map beside the census
(``observability/programs.py placed_census``: every such instruction ->
``(phase, component, via)`` by the arrays it moves: its consumer, else its
producer).  Here an op goes to the census where that names it, else to the
placed map, else it is unplaced; the times by component add up to the
traced busy time as ``phase_times``'s do.  That supersedes the sentence in
``scope_times.py`` that the census cannot place XLA's own grouped-matmul
kernels: the placed map does, so a reader needs no instruction names.

A program without ``placed_census`` (every commit before PR 37), without a
census, or a run without a trace gives ``None``: the readers then leave
their metric out of the line.
"""

from __future__ import annotations

from benchmark import phase_times, scope_times, trace_reduce

ASYNC = ("-start", "-done")                    # the ends of an op's kind
LAYOUT = ("copy", "reshape", "transpose", "bitcast")
UNNAMED = ("other", "")


def placed_of(run):
    """The program's placed census of the cell's jit site, or ``None``."""
    lookup = getattr(phase_times._registry(), "placed_census", None)
    if lookup is None:
        return None
    return lookup(run["config"]["program"]["jit_site"])


def split(ops, census, placed) -> dict:
    """``ops`` (``[(event name, start, duration)]``) one row an
    instruction: ``rows`` = ``[(own name, kind, exclusive ns, events,
    phase, component, how)]`` with ``how`` one of ``named`` (the census
    has a phase or a component for it), ``consumer`` / ``producer`` (the
    placed map's ``via``) or ``unplaced`` (neither reaches it, or the
    census does not hold it: phase ``absent``); ``by_component``
    (``{(phase, component): ns}``), ``by_how`` and ``busy``, their sum."""
    events = {}
    for name, _, _ in ops:
        events[name] = events.get(name, 0) + 1
    rows, by_component = [], {}
    by_how = dict.fromkeys(("named", "consumer", "producer", "unplaced"),
                           0.0)
    for name, ns in trace_reduce.self_time_by_name(ops).items():
        own = trace_reduce.own_name(name).lstrip("%")
        place, how = census.get(own, ("absent", ""))[:2], "named"
        if place in (UNNAMED, ("absent", "")):
            phase, component, how = placed.get(own, place + ("unplaced",))
            if how != "unplaced":
                place = (phase, component)
        rows.append((own, trace_reduce.op_kind(name), ns, events[name])
                    + place + (how,))
        by_component[place] = by_component.get(place, 0.0) + ns
        by_how[how] += ns
    return {"rows": rows, "by_component": by_component, "by_how": by_how,
            "busy": sum(by_how.values())}


def placed_times(run):
    """``split`` of the run's traced window with ``steps`` from
    ``phase_times``, computed once per run and announced once in the
    notes; ``None`` without a trace, a census, a placed census, or a step
    in the trace."""
    if "placed_times" not in run:
        times = phase_times.phase_times(run)
        placed = placed_of(run) if times is not None else None
        out = None
        if placed is not None:
            out = split(run["facts"]["traced"]["ops"],
                        phase_times.census_of(run), placed)
            out["steps"] = times["steps"]
            vias = [p[2] for p in placed.values()]
            run["notes"].append(
                f"placed census: {len(placed)} instructions without a name "
                "stack (" + ", ".join(
                    f"{v} {vias.count(v)}"
                    for v in ("consumer", "producer", "unplaced"))
                + "); exclusive device ms a step: " + ", ".join(
                    f"{k} {v / out['steps'] / 1e6:.3f}"
                    for k, v in out["by_how"].items())
                + f"; sum {out['busy'] / out['steps'] / 1e6:.3f}; what was "
                "`other`, by the component it is placed in: "
                + by_place(out, [r for r in out["rows"]
                                 if r[6] in ("consumer", "producer")], 40))
        run["placed_times"] = out
    return run["placed_times"]


def rows_ms(times, rows) -> float:
    """Milliseconds a step of the given rows."""
    return sum(r[2] for r in rows) / times["steps"] / 1e6


def by_place(times, rows, n=12) -> str:
    """``rows`` summed by ``phase/component``, ms a step, largest first."""
    out = {}
    for _, _, ns, _, phase, component, _ in rows:
        key = f"{phase}/{component or '-'}"
        out[key] = out.get(key, 0.0) + ns
    return ", ".join(f"{k} {v / times['steps'] / 1e6:.3f}"
                     for k, v in trace_reduce.top(out, n))


def by_kind(times, rows) -> str:
    """``rows`` summed by kind of op, ms a step, largest first."""
    out = {}
    for _, kind, ns, *_ in rows:
        out[kind] = out.get(kind, 0.0) + ns
    return ", ".join(f"{k} {v / times['steps'] / 1e6:.3f}"
                     for k, v in trace_reduce.top(out))


def mixer_parts_ms(run, scope: str, gates: str, proj: str):
    """Exclusive device ms a step under ``scope/gates`` by name stack,
    both phases, or ``None`` where the program has no such scope.  The
    note: ``proj`` and ``gates`` forward + backward, the placed ops under
    ``scope`` by part, and what is left under ``scope`` outside every
    part (the layer's norm and residual)."""
    times, named = placed_times(run), phase_times.phase_times(run)
    if times is None:
        return None
    value = scope_times.scope_ms(named, scope, gates)
    if value <= 0:
        return None
    placed = {}
    for _, _, ns, _, _, component, how in times["rows"]:
        head, _, part = component.partition("/")
        if head == scope and how in ("consumer", "producer"):
            placed[part or "outside a part"] = placed.get(
                part or "outside a part", 0.0) + ns
    run["notes"].append(
        f"{scope}: " + ", ".join(
            f"{part or 'outside every part'} "
            f"{scope_times.scope_ms(named, scope, part, ('fwd',)):.3f} fwd + "
            f"{scope_times.scope_ms(named, scope, part, ('bwd',)):.3f} bwd"
            for part in (proj, gates, ""))
        + " ms a step by name stack; placed under it, by part: "
        + (", ".join(f"{k} {v / times['steps'] / 1e6:.3f}"
                     for k, v in trace_reduce.top(placed)) or "nothing"))
    return value
