"""Operations and bytes, computed from shapes, of one layer's delta rule
with a decay a key channel (Kimi delta attention) and of one layer's causal
attention whose values are narrower than its keys (latent attention as it
trains).  Nothing here looks at the program, so a PR that swaps a kernel
leaves the work it is measured against unchanged.
"""

from __future__ import annotations


def channel_decay_rule_work(batch: int, seqlen: int, heads: int,
                            key_dim: int, value_dim: int, itemsize: int,
                            backward: bool) -> dict:
    """What one layer's rule needs, whatever chunking computes it: the
    gated delta rule's count (``mixer_work.gated_delta_rule_work``) with
    the decay read as ``key_dim`` float32 a token and head.  Forward, a
    head and token: ``S^T k``, ``k r^T`` and ``S^T q``, 2 x dk x dv
    operations each (the decay's dk x dv multiplications of the state are
    not counted: a chunked form never makes them); reads q, k (dk), v
    (dv) at ``itemsize``, g (dk) and beta as float32, writes o (dv).
    Backward: twice the operations, recomputation not counted; reads the
    same inputs and dO, writes a gradient for each input."""
    tokens = batch * seqlen * heads
    flops = 6.0 * key_dim * value_dim * tokens
    inputs = ((2 * key_dim + value_dim) * itemsize
              + 4 * key_dim + 4) * tokens
    out = value_dim * itemsize * tokens
    if backward:
        return {"flops": 2.0 * flops, "bytes": 2.0 * inputs + out}
    return {"flops": flops, "bytes": inputs + out}


def causal_attention_work(batch: int, heads: int, seqlen: int, qk_dim: int,
                          v_dim: int, itemsize: int, backward: bool) -> dict:
    """What one layer's causal self-attention needs with keys of ``qk_dim``
    and values of ``v_dim``: ``flops.causal_attention_work`` with the two
    widths told apart.  Forward: QK^T (qk_dim) and PV (v_dim) over the
    causal half; reads q, k, v and writes o once.  Backward (flash form,
    scores recomputed): S = QK^T again, dQ = dS K and dK = dS^T Q at
    qk_dim, dV = P^T dO and dP = dO V^T at v_dim (2.5 x the forward where
    the two widths are one); reads q, k, v, o, dO and writes dq, dk, dv."""
    half_square = batch * heads * seqlen * seqlen / 2.0
    row = batch * seqlen * heads * itemsize
    if backward:
        return {"flops": 2.0 * half_square * (3 * qk_dim + 2 * v_dim),
                "bytes": row * (4.0 * qk_dim + 4.0 * v_dim)}
    return {"flops": 2.0 * half_square * (qk_dim + v_dim),
            "bytes": row * (2.0 * qk_dim + 2.0 * v_dim)}
