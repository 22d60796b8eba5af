"""Latent attention's backward share of its roofline, in %: as the forward
reader, for the two backward kernels together (one call of each a layer)."""

from benchmark.layer_metrics import mla_flash_fwd_roofline as fwd

EVENTS = ("flash_packed_bwd_dkdv", "flash_packed_bwd_dq")


def read(run):
    return fwd.read(run, events=EVENTS, backward=True)
