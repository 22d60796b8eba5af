"""Of the causal (q tile, kv tile) cells the sparse attention kernels run,
the share that holds at least one selected key, in %, summed over the
layers of the step the counters are from (the last one): the program's own
counters, returned beside the loss (``sparse_attention.DSA_COUNTERS``:
selected pairs, causal cells, live cells).  It bounds what skipping dead
tiles could save: 100 % leaves nothing."""

from benchmark import scope_times

LAYER = "self_attn"


def read(run):
    counters = scope_times.program_counters(run)
    if not counters:
        return None
    mine = {k: v for k, v in counters.items()
            if k.rsplit(".", 1)[-1] == LAYER and len(v) == 3}
    causal = sum(v[1] for v in mine.values())
    if not causal > 0:
        return None
    run["notes"].append(
        "sparse attention's counters of the last step, a layer (selected "
        "pairs, causal cells, live cells): "
        + "; ".join(f"{k} {[round(x, 1) for x in v]}"
                    for k, v in sorted(mine.items())))
    return 100.0 * sum(v[2] for v in mine.values()) / causal
