"""Share of the traced window's busy time that neither the program's names
nor its placement reaches, in %: ops the phase census holds under ``other``
without a component and the placed census cannot place (no neighbour with
a name in their own computation), or that the census does not hold.  The
note gives the ten largest kinds of such ops, ms a step."""

from benchmark import placed_times


def read(run):
    times = placed_times.placed_times(run)
    if times is None:
        return None
    run["notes"].append(
        "unplaced, ms a step by kind of op: " + (placed_times.by_kind(
            times, [r for r in times["rows"] if r[6] == "unplaced"])
            or "nothing"))
    return 100.0 * times["by_how"]["unplaced"] / times["busy"]
