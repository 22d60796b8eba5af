"""Rows routed to the held experts over the rows the grouped products span,
in %, summed over the layers of the step the counters are from (the last
one): the program's own counters, returned beside the loss
(``parallel/moe.py ROUTER_COUNTERS``: rows routed here, the static row
bound).  Low means the dropless bound is far above the traffic."""

from benchmark import scope_times


def read(run):
    counters = scope_times.program_counters(run)
    if not counters:
        return None
    routed = sum(c[0] for c in counters.values())
    bound = sum(c[1] for c in counters.values())
    if not (routed > 0 and bound > 0):
        return None
    run["notes"].append(
        "experts' counters of the last step, a layer (rows routed here, "
        "row bound, rows of the busiest expert, mean rows an expert): "
        + "; ".join(f"{k} {[round(x, 1) for x in v]}"
                    for k, v in sorted(counters.items())))
    return 100.0 * routed / bound
