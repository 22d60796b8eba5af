"""Operations and bytes computed from shapes: the numerators of every
utilisation and roofline share.  Nothing here looks at the program, so a
PR that swaps a kernel or a fusion leaves the work it is measured against
unchanged.

Training operations per token are a GPT's: both configurations are
``GPTForCausalLM``.  An architecture that counts otherwise brings a module
of its own, named by its configuration file as ``reference`` is.
"""

from __future__ import annotations


# --------------------------------------------------------------------- gpt --

def gpt_matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matmul of the forward pass: per block
    qkv (3h^2), out (h^2), fc_in and fc_out (2 h f), and the tied head
    (vocab x h).  Embedding lookups, biases and norms are not matmuls."""
    h = cfg["hidden_size"]
    f = cfg.get("intermediate_size") or 4 * h
    return cfg["num_layers"] * (4 * h * h + 2 * h * f) + cfg["vocab_size"] * h


def train_flops_per_token(cfg: dict, seqlen: int) -> float:
    """Forward + backward operations one token requires: 6 per matmul
    parameter (2 forward, 4 backward) plus attention's score and context
    matmuls, 12 x layers x hidden x seqlen (the PaLM appendix-B count: the
    full square, not the causal half, and nothing recomputed)."""
    return 6.0 * gpt_matmul_params(cfg) \
        + 12.0 * cfg["num_layers"] * cfg["hidden_size"] * seqlen


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
