"""Operations and bytes computed from shapes, the part that every
architecture shares: one layer's causal attention, and the least time the
chip could take for a piece of work.  Nothing here looks at the program,
so a PR that swaps a kernel or a fusion leaves the work it is measured
against unchanged.

What a whole training step costs a token is the configuration's to name:
``op_counts/<name>.py``, found through the ``op_count`` key of its file.
"""

from __future__ import annotations


# --------------------------------------------------------------- attention --

def causal_attention_work(batch: int, heads: int, seqlen: int, head_dim: int,
                          itemsize: int, backward: bool) -> dict:
    """What one layer's causal self-attention needs at these shapes.

    Forward: QK^T and PV over the causal half, 2 x 2 x b x h x s^2/2 x d
    operations; reads q, k, v and writes o once (4 arrays of b x s x h x d).
    Backward (flash form, scores recomputed): five matmuls over the causal
    half (S = QK^T again, dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q),
    2.5 x the forward; reads q, k, v, o, dO and writes dq, dk, dv (8 arrays).
    The softmax's exponentials and the row statistics are not counted."""
    half_square = batch * heads * seqlen * seqlen / 2.0
    fwd_flops = 2.0 * 2.0 * half_square * head_dim
    array = batch * seqlen * heads * head_dim * itemsize
    if backward:
        return {"flops": 2.5 * fwd_flops, "bytes": 8.0 * array}
    return {"flops": fwd_flops, "bytes": 4.0 * array}


def roofline_seconds(work: dict, peaks: dict) -> dict:
    """The least time the chip could take for ``work`` and which side
    bounds it."""
    t_compute = work["flops"] / peaks["bf16_flops_per_s"]
    t_memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_compute, t_memory),
            "bound": "compute" if t_compute >= t_memory else "memory"}
