"""Seconds of set-up the program observatory's analysis pass took on the
step's builds (``analysis_s``: the AOT lower and compile, the kernel and
phase censuses, the memory and cost analyses)."""

from benchmark.layer_metrics import step_trace_lower_s


def read(run):
    return step_trace_lower_s.read(run, ("analysis_s",))
