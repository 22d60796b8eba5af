"""Operations a token costs a ``GPTForCausalLM`` in training (pre-LN
blocks of fused qkv, output projection and a two-matmul MLP; tied head):
the count that the GPT configurations name as their ``op_count``."""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
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
    return 6.0 * matmul_params(cfg) \
        + 12.0 * cfg["num_layers"] * cfg["hidden_size"] * seqlen
