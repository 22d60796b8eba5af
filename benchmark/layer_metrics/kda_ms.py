"""Exclusive device time a step under the scope ``kda`` (the Kimi delta
attention mixers with their norm and residual), forward and backward, in
ms.  The note splits the convolution, the rule and the rest (projections,
norms, gates)."""

from benchmark import phase_times, scope_times


def read(run):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    total = scope_times.scope_ms(times, "kda")
    if total <= 0:
        return None
    run["notes"].append(
        "kda: " + ", ".join(
            f"{part or 'projections and the rest'} "
            f"{scope_times.scope_ms(times, 'kda', part, ('fwd',)):.3f} fwd + "
            f"{scope_times.scope_ms(times, 'kda', part, ('bwd',)):.3f} bwd"
            for part in ("kda_conv", "kda_rule", "")) + " ms a step")
    return total
