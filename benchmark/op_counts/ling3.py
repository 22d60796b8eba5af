"""Operations a token costs ``BailingHybridForCausalLM`` in training, from
the configuration's numbers: 6 per parameter a token multiplies by, plus
what each kind of mixing layer costs beside its projections, each kind as
often as the stack has it.

A token multiplies by: a KDA layer's projections ([q | k | v], [f | gate],
b), its convolution's taps and its output projection; an MLA layer's q,
latent, up, gate and output projections; a dense layer's MLP; in every
expert layer the router at its published width, the ungated shared expert
and, of the routed experts, the share that is both chosen and held here,
``k x held / published`` experts in expectation (0.125 at the published
sizes with 8 of 512 held: a chip's share, not the whole model's 8); the
untied head over the held slice of the vocabulary.  The embedding is a
lookup; norm scales, ``A_log``, ``dt_bias`` and the selection bias are
elementwise.
"""

from __future__ import annotations


def mla_layers(cfg: dict) -> int:
    return sum((i + 1) % cfg["layer_group_size"] == 0
               for i in range(cfg["num_hidden_layers"]))


def dense_layers(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def matmul_params_per_token(cfg: dict) -> float:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    width = heads * cfg["head_dim"]
    kda = h * 3 * width + h * 2 * width + h * heads \
        + 3 * width * cfg["short_conv_kernel_size"] + width * h
    qk_dim = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = h * heads * qk_dim \
        + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"]) \
        + h * heads + heads * cfg["v_head_dim"] * h
    dense = 3 * h * cfg["intermediate_size"]
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    moe = h * cfg["num_experts_published"] \
        + 3 * h * cfg["moe_shared_expert_intermediate_size"] \
        + held_share * 3 * h * cfg["moe_intermediate_size"]
    layers, n_mla, n_dense = (cfg["num_hidden_layers"], mla_layers(cfg),
                              dense_layers(cfg))
    return (layers - n_mla) * kda + n_mla * mla + n_dense * dense \
        + (layers - n_dense) * moe + h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seqlen: int) -> float:
    """6 x the parameters above; per MLA layer the full square as for every
    other cell, counted at the widths the equations have and not at the
    kernel's padded ones: QK^T over nope + rope and PV over ``v_head_dim``,
    2 x seqlen x heads x (qk + v) forward and twice that backward; per KDA
    layer 3 x 6 x d x d a head: the recurrence reads ``S^T k``, writes ``k
    r^T`` and reads ``S^T q`` (2 d^2 each) forward and costs twice that
    backward, whatever the chunking recomputes."""
    n_mla = mla_layers(cfg)
    n_kda = cfg["num_hidden_layers"] - n_mla
    heads = cfg["num_attention_heads"]
    attention = 6.0 * seqlen * heads * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    recurrence = 6.0 * cfg["head_dim"] * cfg["head_dim"] * heads
    return 6.0 * matmul_params_per_token(cfg) \
        + n_mla * attention + 3.0 * n_kda * recurrence
