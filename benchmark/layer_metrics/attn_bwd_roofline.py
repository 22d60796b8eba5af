"""Causal attention backward's share of its roofline, in %: as the forward
reader, for the two backward kernels together (one call of each a layer)."""

from benchmark.layer_metrics import attn_fwd_roofline

EVENTS = ("flash_packed_bwd_dkdv", "flash_packed_bwd_dq")


def read(run):
    return attn_fwd_roofline.read(run, events=EVENTS, backward=True,
                                  calls_of=EVENTS[:1])
