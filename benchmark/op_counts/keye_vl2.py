"""Operations a token costs ``KeyeVL2ForCausalLM`` in training, from the
configuration's numbers.

A token multiplies by: the attention's q, k, v and output projections and,
of the routed experts, the share that is both chosen and held here, ``k x
held / published`` experts in expectation (1 at the published sizes with
16 of 128 held: a chip's share), 6 operations a parameter (forward, and the
input's and the weight's gradients); the indexer's projections, whose
input carries no gradient, 4; the router, which gets no gradient on this
chip and passes none, 2; the untied head over the held slice of the
vocabulary, 6.  The embedding is a lookup; norm scales and the indexer's
LayerNorm are elementwise.

Per layer beside its projections (``benchmark/dsa_work.py``): attention
over each query's selected keys, ``min(t + 1, topk)`` of them, 2 x 2 x
heads x d operations a pair forward and twice that backward; the
indexer's scores over the whole causal row, 2 x ih x id a pair, and the
KL's gradient over the selected pairs, 4 x ih x id a pair.  What a kernel
computes beyond that (masked pairs of a live tile, the attention
probabilities the KL recomputes) is not counted.  Nor is what the held
experts compute beyond their routed rows: at the cell's shapes they run
on every token under its gate (``parallel/moe.py _every_token``), 16 x
the rows a router sends a chip its share of, so that the step's time
does not follow the routing; the count holds the routed rows alone.
"""

from __future__ import annotations

from benchmark import dsa_work


def _indexer(cfg):
    sa = cfg["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def train_flops_per_token(cfg: dict, seqlen: int) -> float:
    h, heads, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["head_dim"])
    kv = cfg["num_key_value_heads"]
    ih, idim, topk = _indexer(cfg)
    attention = 2 * h * heads * d + 2 * h * kv * d
    held_share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    experts = held_share * 3 * h * cfg["moe_intermediate_size"]
    indexer = h * ih * idim + h * idim + h * ih
    router = h * cfg["num_experts_published"]
    pairs_per_token = dsa_work.selected_pairs(seqlen, topk) / seqlen
    causal_per_token = dsa_work.causal_pairs(seqlen) / seqlen
    sparse = 3.0 * 4.0 * heads * d * pairs_per_token
    index = 2.0 * ih * idim * causal_per_token \
        + 4.0 * ih * idim * pairs_per_token
    layer = 6.0 * (attention + experts) + 4.0 * indexer + 2.0 * router \
        + sparse + index
    return cfg["num_hidden_layers"] * layer \
        + 6.0 * h * cfg["vocab_size"]
