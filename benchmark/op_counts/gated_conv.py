"""Operations a token costs the rehearsal's gated-convolution language
model in training (``tests/benchmark_checks/gated_conv_lm.py``): no term
grows with the sequence, and the embedding is a lookup."""

from __future__ import annotations


def train_flops_per_token(cfg: dict, seqlen: int) -> float:
    """6 per matmul parameter (per block the fused input projection h x 2d
    and the output projection d x h; the untied head h x vocab), and 6 per
    tap and channel of each block's depthwise convolution (2 forward, 2
    for the input's gradient, 2 for the taps')."""
    h, d = cfg["hidden_size"], cfg["inner_size"]
    matmul_params = cfg["num_layers"] * 3 * h * d + h * cfg["vocab_size"]
    return 6.0 * matmul_params \
        + 6.0 * cfg["num_layers"] * cfg["conv_kernel"] * d
