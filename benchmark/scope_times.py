"""Device time of a traced window by the program's nested scopes: what
``phase_times`` gives by component, summed over a scope and its parts.

The phase census names a component ``"parent"`` or ``"parent/part"`` (a
scope nested in another, ``observability/programs.py
PHASE_SUBCOMPONENTS``).  XLA's own grouped-matmul kernels (``ragged-dot``
custom calls) lose the name stack when the compiler expands them, so the
census cannot place them; a reader that knows which scope issues them adds
their time by instruction name (``unscoped_ms``).
"""

from __future__ import annotations

from benchmark import phase_times, trace_reduce


def scope_ms(times, scope: str, part=None, phases=None) -> float:
    """Exclusive device ms a step under ``scope`` (all its parts, or the
    one ``part``; ``part=""`` is the scope's own ops outside any part),
    in ``phases`` (default: all)."""
    total = 0.0
    for (phase, component), ns in times["by_component"].items():
        head, _, tail = component.partition("/")
        if head != scope or (part is not None and tail != part):
            continue
        if phases is None or phase in phases:
            total += ns
    return total / times["steps"] / 1e6


def unscoped_ms(run, times, prefixes) -> float:
    """Exclusive device ms a step of the ops whose own instruction name
    starts with one of ``prefixes`` and that the census gives no
    component."""
    census = phase_times.census_of(run) or {}
    total = 0.0
    for name, ns in trace_reduce.self_time_by_name(
            run["facts"]["traced"]["ops"]).items():
        own = trace_reduce.own_name(name).lstrip("%")
        if own.startswith(tuple(prefixes)) \
                and not census.get(own, ("", "", False))[1]:
            total += ns
    return total / times["steps"] / 1e6


def program_counters(run):
    """What the cell's jit site last returned beside its loss (the
    program observatory's ``counters``), or ``None``: a program without
    them, as every commit before the counters, gives nothing."""
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    read = getattr(get_program_registry(), "counters", None)
    return read(run["config"]["program"]["jit_site"]) if read else None
