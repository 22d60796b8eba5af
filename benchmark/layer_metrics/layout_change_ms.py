"""Exclusive device time a step of the instructions that run as an op of
their own and compute nothing, in ms: ``copy``, ``reshape``, ``transpose``
and ``bitcast`` by the instruction's own name (``trace_reduce.op_kind``),
named or placed.  The note gives the time by ``phase/component`` and how
much of it had a name stack."""

from benchmark import placed_times


def read(run):
    times = placed_times.placed_times(run)
    if times is None:
        return None
    rows = [r for r in times["rows"] if r[1] in placed_times.LAYOUT]
    named = [r for r in rows if r[6] == "named"]
    run["notes"].append(
        "layout changes, ms a step: " + placed_times.by_kind(times, rows)
        + f"; with a name stack {placed_times.rows_ms(times, named):.3f}; "
        "by place: " + placed_times.by_place(times, rows))
    return placed_times.rows_ms(times, rows)
