"""Operations and bytes, computed from shapes, of the pieces of work that a
hybrid linear-attention / mixture-of-experts layer adds to attention's
(``flops.py``): one layer's gated delta rule, and one layer's grouped
expert products.  Nothing here looks at the program, so a PR that swaps a
kernel leaves the work it is measured against unchanged.
"""

from __future__ import annotations


def gated_delta_rule_work(batch: int, seqlen: int, heads: int, key_dim: int,
                          value_dim: int, itemsize: int,
                          backward: bool) -> dict:
    """What one layer's gated delta rule needs, whatever chunking computes
    it.  Forward, a head and token: ``S^T k``, ``k r^T`` and ``S^T q``, 2 x
    dk x dv operations each; reads q, k (dk), v (dv) at ``itemsize`` and g,
    beta as float32, writes o (dv).  Backward: twice the operations (a
    matmul's two gradients), recomputation not counted; reads the same
    inputs and dO, writes a gradient for each input."""
    tokens = batch * seqlen * heads
    flops = 6.0 * key_dim * value_dim * tokens
    inputs = ((2 * key_dim + value_dim) * itemsize + 8) * tokens
    out = value_dim * itemsize * tokens
    if backward:
        return {"flops": 2.0 * flops, "bytes": 2.0 * inputs + out}
    return {"flops": flops, "bytes": inputs + out}


def grouped_expert_work(rows: float, experts: int, hidden: int, width: int,
                        itemsize: int) -> dict:
    """What one layer's routed experts need, forward and backward together,
    for ``rows`` (token, slot) pairs routed to the ``experts`` held here:
    three grouped products (gate, up: hidden x width each; down: width x
    hidden), 2 x rows x hidden x width operations each forward and twice
    that backward; every held expert's weights read once forward and once
    backward and their gradient written once; each row read and written
    once a phase at ``hidden``."""
    flops = 3.0 * 3.0 * 2.0 * rows * hidden * width
    weights = experts * 3 * hidden * width * itemsize
    row_traffic = 3.0 * 2.0 * rows * hidden * itemsize
    return {"flops": flops, "bytes": 3.0 * weights + row_traffic}
