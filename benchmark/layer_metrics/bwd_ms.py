"""Exclusive device time a step of the backward pass, in ms: the ops the
program's phase census puts in ``bwd`` (``transpose(jvp(`` on the name
stack; a weight-gradient matmul that carries the clip's sum of squares
counts here, by its matmul)."""

from benchmark.layer_metrics import fwd_ms


def read(run):
    return fwd_ms.read(run, ("bwd",))
