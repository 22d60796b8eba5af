"""Peak device memory after the window over the chip's HBM, in %."""


def read(run):
    peak = run["facts"]["memory_peak_bytes"]
    if run["peaks"] is None or not peak:
        return None
    return 100.0 * peak / run["peaks"]["hbm_bytes"]
