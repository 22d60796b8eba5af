"""Sparse attention's backward share of its roofline, in %: as the
forward reader, for the ops under ``dsa/dsa_attn`` in the backward phase
(2.5 x the forward's operations)."""

from benchmark.layer_metrics import dsa_attn_fwd_roofline as fwd


def read(run):
    return fwd.read(run, phases=("bwd",), backward=True)
