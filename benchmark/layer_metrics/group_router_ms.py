"""Exclusive device time a step under ``moe/router`` in ms: a grouped
sigmoid router's float32 matmul and sigmoid over all the experts, its two
selections (the groups by the sum of their two best, the experts among the
groups kept) and the renormalisation; forward only where the router gets
no gradient."""

from benchmark import phase_times, scope_times


def read(run):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    total = scope_times.scope_ms(times, "moe", "router")
    if total <= 0:
        return None
    run["notes"].append(
        f"router: {scope_times.scope_ms(times, 'moe', 'router', ('fwd',)):.3f}"
        f" fwd + {scope_times.scope_ms(times, 'moe', 'router', ('bwd',)):.3f}"
        " bwd ms a step")
    return total
