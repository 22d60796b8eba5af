"""Exclusive device time a step of the vocabulary head and the
cross-entropy, forward and backward, in ms: the ops whose name stack holds
the scope ``lm_head`` (the tied-head matmul) or ``ce``."""

from benchmark import phase_times


def read(run):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    run["notes"].append(
        f"head + ce: lm_head {phase_times.component_ms(times, 'lm_head'):.3f}"
        f" ms, ce {phase_times.component_ms(times, 'ce'):.3f} ms a step")
    return phase_times.component_ms(times, "lm_head", "ce")
