"""Exclusive device time a step of the forward pass, in ms: the ops of the
traced window that the program's phase census puts in ``fwd`` (jax's
``jvp(`` marker on the instruction's name stack, not ``transpose(jvp(``)."""

from benchmark import phase_times

PHASES = ("fwd",)


def read(run, phases=PHASES):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    return phase_times.ms(times, *phases)
