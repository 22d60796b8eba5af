"""Exclusive device time a step under the scope ``moe`` (the expert part of
every layer with its norm and residual), forward and backward, in ms, with
XLA's ``ragged-dot`` kernels, which carry no scope.  The note splits the
router, the routed experts (sort, gather, grouped products), the shared
expert and what is left (norm, residual, the weighted sum)."""

from benchmark import phase_times, scope_times
from benchmark.layer_metrics import moe_experts_roofline as experts


def read(run):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    scoped = scope_times.scope_ms(times, "moe")
    if scoped <= 0:
        return None
    kernels = scope_times.unscoped_ms(run, times, experts.UNSCOPED)
    run["notes"].append(
        f"moe: router {scope_times.scope_ms(times, 'moe', 'router'):.3f}, "
        f"experts {experts.experts_ms(run, times):.3f} (of it the grouped "
        f"products' own kernels {kernels:.3f}), shared_expert "
        f"{scope_times.scope_ms(times, 'moe', 'shared_expert'):.3f}, the "
        f"rest {scope_times.scope_ms(times, 'moe', ''):.3f} ms a step")
    return scoped + kernels
