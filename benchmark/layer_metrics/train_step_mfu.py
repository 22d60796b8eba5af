"""Whole step's share of the chip's bf16 peak, in %: operations per token
from shapes (``flops.py``) x the window's tokens per second, over the
peak of the run's ``device_kind`` (``peaks.json``) x the chips used."""

from benchmark import flops


def read(run):
    if run["peaks"] is None:
        return None
    f = run["facts"]
    per_token = flops.train_flops_per_token(run["config"], f["seqlen"])
    return 100.0 * per_token * f["tokens_per_s"] / (
        run["peaks"]["bf16_flops_per_s"] * run["chips"])
