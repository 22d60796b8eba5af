"""What the train step's own names say about a traced window, and what
its build record says about set-up: the shared part of the per-layer
readers ``fwd_ms``, ``bwd_ms``, ``opt_ms``, ``head_ce_ms``,
``phase_unattributed_share`` (device trace) and ``step_trace_lower_s``,
``step_compile_s``, ``analysis_pass_s`` (build record).

The program names its phases itself: ``jax.named_scope`` in the compiled
step, and a phase census in its program observatory
(``observability/programs.py phase_census``) that maps every instruction of
the executable that can run as a device op to ``(phase, component,
mixed)``.  A device trace's ``XLA Ops`` event carries the instruction's
own name before `` = `` (``trace_reduce.own_name``); the join of the two
is here.  A program that has no census or no build clock (every commit
before PR 26) gives ``None``, and so does a run without a trace: the
readers then leave their metric out of the line.
"""

from __future__ import annotations

import collections

from benchmark import trace_reduce

PHASES = ("fwd", "bwd", "clip", "update", "other")
BUILD_KEYS = ("trace_s", "lower_s", "backend_compile_s", "analysis_s")


def _registry():
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    return get_program_registry()


def census_of(run):
    """The program's phase census of the cell's jit site, or ``None``."""
    lookup = getattr(_registry(), "phase_census", None)
    if lookup is None:
        return None
    return lookup(run["config"]["program"]["jit_site"]) or None


def split(ops, census) -> dict:
    """Exclusive device time (ns) of ``ops`` (``[(event name, start,
    duration)]``) by the census: ``by_phase`` (every phase of ``PHASES``,
    an op the census does not hold under ``absent``), ``by_component``
    (``{(phase, component): ns}``), ``mixed`` (fusions the census marks
    mixed) and ``busy`` (their sum: ``trace_reduce.busy_ns`` where events
    nest properly).  ``steps`` is the event count that most instructions
    of the census share: one event per step for an instruction of the
    entry computation."""
    by_phase = dict.fromkeys(PHASES + ("absent",), 0.0)
    by_component = {}
    mixed = 0.0
    for name, t in trace_reduce.self_time_by_name(ops).items():
        phase, component, is_mixed = census.get(
            trace_reduce.own_name(name).lstrip("%"), ("absent", "", False))
        by_phase[phase] += t
        key = (phase, component)
        by_component[key] = by_component.get(key, 0.0) + t
        if is_mixed:
            mixed += t
    calls = collections.Counter(
        trace_reduce.own_name(name).lstrip("%") for name, _, _ in ops)
    shared = collections.Counter(n for name, n in calls.items()
                                 if name in census)
    steps = shared.most_common(1)[0][0] if shared else 0
    return {"by_phase": by_phase, "by_component": by_component,
            "mixed": mixed, "busy": sum(by_phase.values()), "steps": steps}


def phase_times(run):
    """``split`` of the run's traced window, computed once per run and
    announced once in the notes; ``None`` without a trace, a census, or
    a step in the trace."""
    if "phase_times" not in run:
        traced = run["facts"].get("traced")
        census = census_of(run) if traced is not None else None
        out = split(traced["ops"], census) if census else None
        if out is not None and not (out["steps"] and out["busy"] > 0):
            out = None
        if out is not None:
            per_step = 1e-6 / out["steps"]
            run["notes"].append(
                f"phase census: {len(census)} instructions, "
                f"{out['steps']} steps traced; exclusive device ms a step "
                "by phase: " + ", ".join(
                    f"{k} {v * per_step:.3f}"
                    for k, v in out["by_phase"].items())
                + f"; other + absent {ms(out, 'other', 'absent'):.3f}; "
                f"sum {out['busy'] * per_step:.3f} against busy_s / steps "
                f"{1e3 * traced['busy_s'] / out['steps']:.3f}; by phase "
                "and component: " + ", ".join(
                    f"{p}/{c or '-'} {v * per_step:.3f}" for (p, c), v in
                    sorted(out["by_component"].items(),
                           key=lambda kv: -kv[1])))
        run["phase_times"] = out
    return run["phase_times"]


def ms(times, *phases) -> float:
    """Milliseconds a step of the given phases."""
    return sum(times["by_phase"][p] for p in phases) / times["steps"] / 1e6


def component_ms(times, *components) -> float:
    """Milliseconds a step of the given components, whatever the phase."""
    return sum(v for (_, c), v in times["by_component"].items()
               if c in components) / times["steps"] / 1e6


def build_seconds(run, key):
    """Sum of ``key`` (one of ``BUILD_KEYS``) over the build records of
    the cell's jit site: every build of a run is in set-up (the check
    ``builds_in_window`` holds the window to none).  ``None`` where no
    record carries the key."""
    site = _registry().snapshot()["sites"].get(
        run["config"]["program"]["jit_site"])
    values = [h[key] for h in (site or {}).get("history", ()) if key in h]
    if not values:
        return None
    if "build_clock" not in run:
        run["build_clock"] = True
        run["notes"].append("build records of the step: " + "; ".join(
            ", ".join(f"{k} {v}" for k, v in h.items()
                      if k not in ("ts", "cause", "analysis"))
            + (f", census_s {h['analysis'].get('census_s')}"
               if h.get("analysis") else "")
            for h in site["history"]))
    return sum(values)
