"""Seconds of set-up the step's builds spent tracing the Python step and
lowering it to a module (``trace_s`` + ``lower_s`` of the program's build
records for the cell's jit site, from jax's own build events)."""

from benchmark import phase_times

KEYS = ("trace_s", "lower_s")


def read(run, keys=KEYS):
    if run["peaks"] is None:  # a CPU rehearsal: no time under a metric
        return None
    values = [phase_times.build_seconds(run, k) for k in keys]
    if any(v is None for v in values):
        return None
    return sum(values)
