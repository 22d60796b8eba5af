"""The channel-decay delta rule's backward share of its roofline, in %: as
``kda_rule_fwd_roofline`` for the ops under ``kda_rule`` in the backward
phase (the forward's recomputation is in their time and not in the work)."""

from benchmark.layer_metrics import kda_rule_fwd_roofline as fwd


def read(run):
    return fwd.read(run, phases=("bwd",), backward=True)
