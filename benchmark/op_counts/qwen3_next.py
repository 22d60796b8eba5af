"""Operations a token costs ``Qwen3NextForCausalLM`` in training, from the
configuration's numbers: 6 per parameter a token multiplies by, plus what
each kind of mixing layer costs beside its projections, each kind as often
as the stack has it.

A token multiplies by: a DeltaNet layer's two input projections, its
convolution's taps and its output projection; an attention layer's q (with
the gate), k, v and o projections; in every layer the router at its
published width, the shared expert with its gate, and of the routed
experts the share that is both chosen and held here, ``k x held /
published`` experts in expectation (0.625 at the published sizes with 32
of 512 held: the count of a chip's share, not of the whole model's 10);
the untied head over the held slice of the vocabulary.  The embedding is a
lookup; norm scales, ``A_log`` and ``dt_bias`` are elementwise.
"""

from __future__ import annotations


def matmul_params_per_token(cfg: dict) -> float:
    h = cfg["hidden_size"]
    kq = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vz = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    delta = h * (2 * kq + 2 * vz) + h * 2 * cfg["linear_num_value_heads"] \
        + (2 * kq + vz) * cfg["linear_conv_kernel_dim"] + vz * h
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = h * 2 * width + 2 * h * kv_width + width * h
    expert = 3 * h * cfg["moe_intermediate_size"]
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    moe = h * cfg["num_experts_published"] \
        + 3 * h * cfg["shared_expert_intermediate_size"] + h \
        + held_share * expert
    n_attn = attention_layers(cfg)
    n_delta = cfg["num_hidden_layers"] - n_attn
    return n_delta * delta + n_attn * attention \
        + cfg["num_hidden_layers"] * moe + h * cfg["vocab_size"]


def attention_layers(cfg: dict) -> int:
    return sum((i + 1) % cfg["full_attention_interval"] == 0
               for i in range(cfg["num_hidden_layers"]))


def train_flops_per_token(cfg: dict, seqlen: int) -> float:
    """6 x the parameters above; per attention layer 12 x seqlen x (heads x
    head dim), the full square as for every other cell (QK^T and PV,
    forward and twice that backward); per DeltaNet layer 3 x 6 x dk x dv a
    value head: the recurrence reads ``S^T k``, writes ``k r^T`` and reads
    ``S^T q`` (2 dk dv each) forward and costs twice that backward,
    whatever the chunking recomputes."""
    n_attn = attention_layers(cfg)
    n_delta = cfg["num_hidden_layers"] - n_attn
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    recurrence = 6.0 * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] * cfg["linear_num_value_heads"]
    return 6.0 * matmul_params_per_token(cfg) \
        + 12.0 * n_attn * seqlen * width + 3.0 * n_delta * recurrence
