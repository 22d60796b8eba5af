"""Exclusive device time a step under the scope ``mla`` (the latent
attention mixer with its norms, rotary, gate and residual), forward and
backward, in ms.  The note gives the three packed flash kernels' time and
the plan they run under."""

from benchmark import phase_times, scope_times, trace_reduce

KERNELS = ("flash_packed_fwd", "flash_packed_bwd_dkdv", "flash_packed_bwd_dq")


def _plan(run):
    try:
        from paddle_hackathon_tpu.incubate.nn.kernels import \
            flash_attention_packed as fap
        cfg, seqlen = run["config"], run["facts"]["seqlen"]
        return fap._plan(seqlen, seqlen, cfg["num_attention_heads"],
                         cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    except Exception:  # noqa: BLE001 -- a note, never the metric
        return None


def read(run):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    total = scope_times.scope_ms(times, "mla")
    if total <= 0:
        return None
    ops = run["facts"]["traced"]["ops"]
    run["notes"].append(
        "latent attention: " + ", ".join(
            f"{k} {trace_reduce.time_of(ops, (k,)) / times['steps'] / 1e6:.3f}"
            for k in KERNELS)
        + " ms a step; plan (block_q, block_kv, heads a cell, strip rows) "
        f"{_plan(run)}")
    return total
