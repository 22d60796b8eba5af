"""Whole step's share of the chip's bf16 peak, in %: the operations a token
costs, as the module that the configuration names counts them from shapes
(``op_count`` -> ``op_counts/<name>.py``; the driver calls it at the
cell's sequence length) x the window's tokens per second, over the peak
of the run's ``device_kind`` (``peaks.json``) x the chips used."""


def read(run):
    if run["peaks"] is None:
        return None
    f = run["facts"]
    return 100.0 * f["train_flops_per_token"] * f["tokens_per_s"] / (
        run["peaks"]["bf16_flops_per_s"] * run["chips"])
