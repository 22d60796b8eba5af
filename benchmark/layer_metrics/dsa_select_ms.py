"""Exclusive device time a step under ``dsa/dsa_select``, in ms: each
block of queries' exact ``top_k`` over its indexer scores, the threshold
and its ties, and the selection's bits; forward only (the selection has
no gradient).  The note gives the whole ``dsa`` scope by part."""

from benchmark import phase_times, scope_times

PARTS = ("dsa_index", "dsa_select", "dsa_attn", "dsa_kl", "")


def read(run):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    total = scope_times.scope_ms(times, "dsa", "dsa_select")
    if total <= 0:
        return None
    run["notes"].append(
        "dsa: " + ", ".join(
            f"{part or 'projections, norms, rotations and the rest'} "
            f"{scope_times.scope_ms(times, 'dsa', part, ('fwd',)):.3f} fwd + "
            f"{scope_times.scope_ms(times, 'dsa', part, ('bwd',)):.3f} bwd"
            for part in PARTS) + " ms a step")
    return total
