"""Exclusive device time a step of the compiler's own asynchronous
transfers, in ms: every ``*-start`` / ``*-done`` op of the trace
(``copy-start``, ``copy-done``, ``slice-start``, ``slice-done``), the time
the step waits on its memory plan.  The note gives the time by the
``phase/component`` the transfers are placed in (who waits), largest
first, and the count of ``*-done`` ops a step."""

from benchmark import placed_times


def read(run):
    times = placed_times.placed_times(run)
    if times is None:
        return None
    rows = [r for r in times["rows"] if r[1].endswith(placed_times.ASYNC)]
    done = sum(r[3] for r in rows if r[1].endswith("-done"))
    run["notes"].append(
        f"asynchronous transfers: {done / times['steps']:.1f} `*-done` ops "
        "a step; ms a step by who waits: "
        + placed_times.by_place(times, rows) + "; by kind: "
        + placed_times.by_kind(times, rows))
    return placed_times.rows_ms(times, rows)
