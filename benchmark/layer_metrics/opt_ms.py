"""Exclusive device time a step of the clip and the optimizer's update, in
ms: the ops the program's phase census puts under the scopes ``clip`` and
``update``.  The note gives each, and the least time the update could take
by bytes: per parameter it reads the parameter, the gradient and both
moments and writes the parameter and both moments, at the configuration's
dtypes.  A reading under that least means update work is fused into ops
that the census puts elsewhere."""

import jax.numpy as jnp

from benchmark import phase_times


def update_bytes_per_param(training: dict) -> int:
    p = jnp.dtype(training["param_dtype"]).itemsize
    m = jnp.dtype(training["moment_dtype"]).itemsize
    master = 8 if training.get("master_weights") else 0
    return (p + p + 2 * m) + (p + 2 * m) + master


def read(run):
    times = phase_times.phase_times(run)
    if times is None or run["peaks"] is None:
        return None
    cfg = run["config"]
    millions = cfg.get("parameters_millions")
    least = "not stated"
    if millions:
        per = update_bytes_per_param(cfg["training"])
        seconds = per * millions * 1e6 / run["peaks"]["hbm_bytes_per_s"]
        least = (f"{1e3 * seconds:.2f} ms ({per} B x {millions} M "
                 "parameters)")
    run["notes"].append(
        f"opt: clip {phase_times.ms(times, 'clip'):.3f} ms, update "
        f"{phase_times.ms(times, 'update'):.3f} ms a step; least for the "
        f"update by bytes {least}")
    return phase_times.ms(times, "clip", "update")
