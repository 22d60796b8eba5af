"""Share of the traced steps in which no operation ran on the device, in %:
1 - (union of the op intervals) / (first op's start to last op's end)."""


def read(run):
    traced = run["facts"]["traced"]
    if traced is None or traced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
