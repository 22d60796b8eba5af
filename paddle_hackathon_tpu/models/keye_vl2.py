"""Keye-VL-2.0's language model: a Qwen3-MoE-shaped decoder whose every
attention layer is DeepSeek Sparse Attention (``incubate/nn/functional/
sparse_attention.py``).  Source of the shapes and keys: the public
``KeyeVL2`` ``config.json`` (its ``sa_config`` gives the indexer); the
vision tower is not here.

``x`` is (b, s, hidden); ``h = x + attn(rms(x))``, ``y = h + moe(rms(h))``;
a final RMSNorm and an untied head.  Every eps is ``rms_norm_eps``.

*Attention.*  Bias-free ``q = x W_q`` (heads x head_dim), ``k``, ``v`` (KV
heads x head_dim); per-head RMSNorm of q and of k (``q_norm``,
``k_norm``); rotary positions in rotate-half form on all head_dim dims at
``rope_theta`` (``mrope_section`` with one position on all three axes, as
text has, is 1-D rotary); attention over each query's selected keys at
scale head_dim^-1/2, ``heads / kv_heads`` query heads to a KV head, then
``o W_o``.

*Lightning indexer* (on ``x`` detached: only its KL trains it).  ``qi = x
W_qi`` (``index_n_heads`` x ``index_head_dim``), ``ki = LayerNorm(x
W_ki)`` (one head), ``w = x W_w * index_n_heads^-1/2 *
index_head_dim^-1/2`` in float32; rotary positions on the first
``index_rope_dim`` dims of ``qi`` and ``ki``, rotate-half at
``rope_theta``; ``I[t, j] = sum_h w[t, h] relu(qi[t, h] . ki[j])``; the
``index_topk`` keys of largest ``I`` a query are its selection.  The
layer's ``l_aux`` is the mean over the batch's tokens of the KL between
the heads' mean attention probability and ``softmax(I)`` over the
selection, weighted 1 (``aux_weight``, DeepSeek-V3.2-Exp's) in the default
loss; ``layer_counters`` holds ``sparse_attention.DSA_COUNTERS``.

*Experts.*  Softmax over all ``num_experts`` router logits in float32,
top ``num_experts_per_tok``, renormalised (``norm_topk_prob``); SwiGLU
experts of ``moe_intermediate_size``; no shared expert
(``parallel/moe.py DroplessMoELayer`` with ``shared_hidden=0``).

*A chip's share.*  ``experts_held = (first, count)`` tells every expert
layer which experts it holds; ``vocab_size`` is the slice of the
vocabulary held.  A layer that holds a part of the experts does not train
its router (``DroplessMoELayer`` says why).

Scopes: ``embed``, ``dsa`` (the attention half, with the parts
``dsa_index``, ``dsa_select``, ``dsa_attn``, ``dsa_kl``; the projections,
norms and rotations are the scope's own), ``moe`` (``router``,
``experts``), ``ln_f``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.functional.norm import rms_norm_f32 as _rms
from ..nn.layer import Layer
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.norm import LayerNorm, RMSNorm
from ..nn.parameter import ParamAttr
from .qwen3_next import _rotate


@dataclasses.dataclass
class KeyeVL2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10000000.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_rope_dim: int = 32
    initializer_range: float = 0.02
    # (first, count) of the num_experts this chip holds; None = all of them
    experts_held: Optional[Tuple[int, int]] = None


def _init(config):
    return ParamAttr(initializer=I.Normal(0.0, config.initializer_range))


def _dsa_core(x, q, k, v, q_norm_w, k_norm_w, wq, wk, kn_w, kn_b, w_proj, *,
              heads, kv_heads, head_dim, theta, eps, index_heads, index_dim,
              index_rope, topk):
    """The attention half between the q / k / v projections and the
    output projection, on arrays: ``(o, kl, counters)``."""
    from ..incubate.nn.functional.sparse_attention import sparse_attention
    f32, dt = jnp.float32, q.dtype
    b, s, _ = q.shape
    q = _rotate(_rms(q.reshape(b, s, heads, head_dim), q_norm_w, eps),
                head_dim, theta).astype(dt).reshape(b, s, -1)
    k = _rotate(_rms(k.reshape(b, s, kv_heads, head_dim), k_norm_w, eps),
                head_dim, theta).astype(dt).reshape(b, s, -1)
    with jax.named_scope("dsa_index"):
        xd = jax.lax.stop_gradient(x)
        qi = _rotate((xd @ wq).reshape(b, s, index_heads, index_dim)
                     .astype(f32), index_rope, theta).astype(dt)
        ki = (xd @ wk).astype(f32)
        mu = jnp.mean(ki, -1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mu), -1, keepdims=True)
        ki = (ki - mu) * jax.lax.rsqrt(var + eps) * kn_w.astype(f32) \
            + kn_b.astype(f32)
        ki = _rotate(ki[:, :, None, :], index_rope, theta)[:, :, 0] \
            .astype(dt)
        w = jnp.matmul(xd.astype(f32), w_proj.astype(f32)) \
            * (index_heads ** -0.5 * index_dim ** -0.5)
        qi = jnp.swapaxes(qi, 1, 2)
    o, kl, counters = sparse_attention(q, k, v, qi, ki, w, heads=heads,
                                       topk=topk, scale=head_dim ** -0.5)
    return o, jnp.mean(kl), counters


class KeyeIndexer(Layer):
    """The lightning indexer's leaves: ``wq``, ``wk``, ``k_norm`` (a
    LayerNorm with its bias), ``weights_proj``."""

    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        c, init = config, _init(config)
        self.wq = Linear(c.hidden_size, c.index_n_heads * c.index_head_dim,
                         weight_attr=init, bias_attr=False)
        self.wk = Linear(c.hidden_size, c.index_head_dim, weight_attr=init,
                         bias_attr=False)
        self.k_norm = LayerNorm(c.index_head_dim, epsilon=c.rms_norm_eps)
        self.weights_proj = Linear(c.hidden_size, c.index_n_heads,
                                   weight_attr=init, bias_attr=False)


class KeyeVL2Attention(Layer):
    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        c, init = config, _init(config)
        width = c.num_attention_heads * c.head_dim
        kv_width = c.num_key_value_heads * c.head_dim
        self.q_proj = Linear(c.hidden_size, width, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(c.hidden_size, kv_width, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(c.hidden_size, kv_width, weight_attr=init,
                             bias_attr=False)
        self.q_norm = RMSNorm(c.head_dim, c.rms_norm_eps)
        self.k_norm = RMSNorm(c.head_dim, c.rms_norm_eps)
        self.indexer = KeyeIndexer(c)
        self.o_proj = Linear(width, c.hidden_size, weight_attr=init,
                             bias_attr=False)
        self.aux_weight = 1.0
        self._core = functools.partial(
            _dsa_core, heads=c.num_attention_heads,
            kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            theta=float(c.rope_theta), eps=c.rms_norm_eps,
            index_heads=c.index_n_heads, index_dim=c.index_head_dim,
            index_rope=c.index_rope_dim, topk=c.index_topk)
        self.l_aux = None
        self.layer_counters = None

    def forward(self, x):
        ix = self.indexer
        o, self.l_aux, self.layer_counters = apply_op(
            "keye_sparse_attention", self._core, [
                x, self.q_proj(x), self.k_proj(x), self.v_proj(x),
                self.q_norm.weight, self.k_norm.weight, ix.wq.weight,
                ix.wk.weight, ix.k_norm.weight, ix.k_norm.bias,
                ix.weights_proj.weight], n_outputs=3)
        return self.o_proj(o)


class KeyeVL2DecoderLayer(Layer):
    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = KeyeVL2Attention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        from ..parallel.moe import DroplessMoELayer
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok,
            experts_held=c.experts_held or (0, c.num_experts),
            shared_hidden=0, norm_topk_prob=c.norm_topk_prob)

    def forward(self, x):
        with jax.named_scope("dsa"):
            x = x + self.self_attn(self.input_layernorm(x))
        with jax.named_scope("moe"):
            return x + self.mlp(self.post_attention_layernorm(x))


class KeyeVL2ForCausalLM(Layer):
    """``model(ids)`` -> logits (b, s, vocab_size) over the held slice of
    the vocabulary.  ``s`` is a multiple of 128 (the kernels' tiles)."""

    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        c = self.config = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=_init(c))
        self.layers = LayerList([KeyeVL2DecoderLayer(c)
                                 for _ in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.lm_head = Linear(c.hidden_size, c.vocab_size,
                              weight_attr=_init(c), bias_attr=False)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        with jax.named_scope("ln_f"):
            x = self.norm(x)
        with jax.named_scope("lm_head"):
            return self.lm_head(x)


def keye_vl2_sharding_spec(name: str, shape) -> tuple:
    """Every leaf whole on every chip of the mesh: the chips that share a
    layer in an expert-parallel deployment each run this model with their
    own ``experts_held`` and slice of the vocabulary; a mesh here is
    data-parallel replicas of one such share."""
    return (None,) * len(shape)
