"""Seconds of set-up the step's builds spent in the backend's compile, or
in the persistent cache's retrieval where that hit (``backend_compile_s``
of the program's build records for the cell's jit site)."""

from benchmark.layer_metrics import step_trace_lower_s


def read(run):
    return step_trace_lower_s.read(run, ("backend_compile_s",))
