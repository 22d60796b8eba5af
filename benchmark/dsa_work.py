"""Operations and bytes, computed from shapes, of one layer of DeepSeek
Sparse Attention: attention over each query's selected keys, and the
lightning indexer's scores and KL gradient.  Nothing here looks at the
program, so a PR that swaps a kernel or skips tiles leaves the work it is
measured against unchanged.
"""

from __future__ import annotations


def selected_pairs(seqlen: int, topk: int) -> int:
    """(query, key) pairs a sequence selects: ``min(t + 1, topk)`` keys for
    query ``t`` (31,458,304 at s = 16,384 and top-k 2,048)."""
    full = min(seqlen, topk)
    return full * (full + 1) // 2 + (seqlen - full) * topk


def causal_pairs(seqlen: int) -> int:
    """(query, key) pairs with ``key <= query``."""
    return seqlen * (seqlen + 1) // 2


def sparse_attention_work(batch: int, heads: int, kv_heads: int,
                          seqlen: int, head_dim: int, topk: int,
                          itemsize: int, backward: bool) -> dict:
    """What one layer's attention over the selected pairs needs, whatever
    computes it.  Forward: QK^T and PV over the selected pairs, 2 x 2 x
    heads x d operations a pair; reads q, k, v and writes o once (k and v
    at ``kv_heads``).  Backward (flash form): 2.5 x the forward's
    operations; reads q, k, v, o, dO and writes dq, dk, dv.  The
    softmax's exponentials and the selection are not counted."""
    pairs = batch * selected_pairs(seqlen, topk)
    fwd_flops = 4.0 * pairs * heads * head_dim
    q_bytes = batch * seqlen * heads * head_dim * itemsize
    kv_bytes = batch * seqlen * kv_heads * head_dim * itemsize
    if backward:
        return {"flops": 2.5 * fwd_flops,
                "bytes": 4.0 * q_bytes + 4.0 * kv_bytes}
    return {"flops": fwd_flops, "bytes": 2.0 * q_bytes + 2.0 * kv_bytes}


def indexer_work(batch: int, seqlen: int, index_heads: int, index_dim: int,
                 topk: int, itemsize: int) -> dict:
    """What one layer's indexer needs in a training step, both phases: its
    scores over every causal pair, 2 x heads x d operations a pair, and
    the KL's gradient for its query and its key over the selected pairs
    (the only pairs where the loss has one), 2 x 2 x heads x d a pair;
    reads its query, key and head weights (float32) and writes their
    gradients.  The attention probabilities the loss compares with are
    the attention's work, not the indexer's."""
    width = index_heads * index_dim
    flops = 2.0 * width * batch * causal_pairs(seqlen) \
        + 4.0 * width * batch * selected_pairs(seqlen, topk)
    rows = batch * seqlen * ((width + index_dim) * itemsize + 4 * index_heads)
    return {"flops": flops, "bytes": 2.0 * rows}
