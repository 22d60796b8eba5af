"""Share of the traced window's busy time that the program's names do not
reach, in %: ops the phase census does not hold, or holds under ``other``
(no metadata, or under no scope and no transform).  The note gives beside
it the share of the fusions the census marks mixed: they are attributed,
but to one of the phases fused into them."""

from benchmark import phase_times


def read(run):
    times = phase_times.phase_times(run)
    if times is None:
        return None
    run["notes"].append(
        f"mixed fusions: {100.0 * times['mixed'] / times['busy']:.2f} % of "
        "busy; absent from the census: "
        f"{100.0 * times['by_phase']['absent'] / times['busy']:.2f} %")
    return 100.0 * (times["by_phase"]["other"]
                    + times["by_phase"]["absent"]) / times["busy"]
