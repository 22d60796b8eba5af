"""Bailing-hybrid decoder (the public ``bailing_hybrid`` config: Ling 3.0):
a stack with two kinds of mixer and two kinds of feed-forward part.

Layer ``l`` mixes tokens by latent attention (MLA, DeepSeek-V2) where ``(l
+ 1) % layer_group_size == 0`` and by Kimi delta attention (KDA, a linear
recurrence with a decay a key channel, ``incubate/nn/functional/
kimi_delta_rule.py``) elsewhere; the first ``first_k_dense_replace``
layers feed forward through a dense SwiGLU MLP, the others through a
top-k mixture of SwiGLU experts beside one ungated shared expert
(``parallel/moe.py DroplessMoELayer``, routed as DeepSeek-V3's
``noaux_tc``: sigmoid scores, a selection bias, ``topk_group`` of
``n_group`` groups).  RMSNorm before each half, residuals around both, a
final RMSNorm and an untied head.  ``BailingHybridConfig`` keeps the
config's key names.

``x`` is (b, s, hidden); ``h = x + mixer(norm(x))``, ``y = h + ffn(norm(h))``.

*KDA mixer.*  Heads x ``head_dim`` for q, k and v alike.  Bias-free
projections of ``x``: ``[q | k | v]``, ``[f | gate]`` (full rank, each as
wide as q) and ``b`` (one a head).  ``[q | k | v]`` goes through a causal
depthwise convolution of ``short_conv_kernel_size`` taps and SiLU; ``q``
and ``k`` are L2-normalised over the head dim, ``q`` scaled by head
dim^-1/2; ``beta = sigmoid(b)``; the log decay a head and key channel, in
float32, ``g = kda_lower_bound * sigmoid(exp(A_log_h) (f + dt_bias))``, so
``g`` lies in (-5, 0) whatever the weights are (the bound the chunked rule
needs); the rule; then per head ``w * o / rms(o) * sigmoid(gate)`` and a
bias-free output projection.  (The published weights keep the five
projections apart; fused they are the same function.)  The layer keeps
its input, the rule's triangular inverse and the two wide projections'
outputs for the backward and rebuilds the rest
(``BailingKimiDeltaAttention`` says why).

*MLA mixer.*  ``q_proj`` to heads x ``[nope | rope]``; ``kv_a_proj`` to
``[c | k_rope]`` (``kv_lora_rank`` + rope), ``c`` through an RMSNorm,
``kv_b_proj`` to heads x ``[k_nope | v]``; rotary positions as interleaved
pairs on every head's ``q_rope`` and on the one ``k_rope`` all heads
share; causal softmax attention at scale (nope + rope)^-1/2 through the
packed flash kernel, which takes one head dim for q, k and v: ``v`` is
padded with zeros from ``v_head_dim`` to the keys' width and the output's
padding dropped (exact); a gate a head, ``sigmoid(x W_g)``; a bias-free
output projection.

*A chip's share*: as ``models/qwen3_next.py`` -- ``experts_held = (first,
count)``, ``vocab_size`` the slice held, the mixers whole, no router
gradient where a part of the experts is held.  No MTP module.

The scopes ``embed``, ``kda`` (with ``kda_proj``, ``kda_conv``,
``kda_gates``, ``kda_rule`` inside: siblings, which leave only the layer's
norm and residual outside a part), ``mla``, ``mlp``, ``moe`` (with
``router``, ``experts``, ``shared_expert``), ``ln_f``, ``lm_head`` name
the step's parts for the phase census (``observability/programs.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..core.autograd import apply_op
from ..nn import functional as F
from ..nn.functional.norm import rms_norm_f32 as _rms
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.norm import RMSNorm
from ..nn.parameter import ParamAttr


@dataclasses.dataclass
class BailingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    head_dim: int = 128
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 6000000.0
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    intermediate_size: int = 6144
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    score_function: str = "sigmoid"
    moe_router_enable_expert_bias: bool = True
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # (first, count) of the num_experts this chip holds; None = all of them
    experts_held: Optional[Tuple[int, int]] = None

    def layer_is_mla(self, layer_idx: int) -> bool:
        return (layer_idx + 1) % self.layer_group_size == 0

    def layer_is_dense(self, layer_idx: int) -> bool:
        return layer_idx < self.first_k_dense_replace


# the names of what a KDA layer keeps for its backward beside the rule's
# inverse (``BailingKimiDeltaAttention``): the two wide projections' outputs
KEPT_QKV = "kda_qkv_projection"
KEPT_FG = "kda_fg_projection"


def _init(config):
    return ParamAttr(initializer=I.Normal(0.0, config.initializer_range))


def _rule_inputs(qkv, f, b, conv, a_log, dt_bias, heads, head_dim, floor):
    """``q, k, v, g, beta`` of the rule from the projected [q | k | v],
    ``f`` and ``b``: the convolution and SiLU (scope ``kda_conv``), the L2
    norms and the gates (``kda_gates``)."""
    from ..incubate.nn.functional.gated_delta_rule import \
        causal_depthwise_conv
    f32, dt = jnp.float32, qkv.dtype
    bsz, s, _ = qkv.shape
    width = heads * head_dim
    with jax.named_scope("kda_conv"):
        mixed = jax.nn.silu(causal_depthwise_conv(qkv, conv))
    with jax.named_scope("kda_gates"):
        q = mixed[..., :width].reshape(bsz, s, heads, head_dim).astype(f32)
        k = mixed[..., width:2 * width].reshape(bsz, s, heads, head_dim) \
            .astype(f32)
        v = mixed[..., 2 * width:].reshape(bsz, s, heads, head_dim)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * head_dim ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        beta = jax.nn.sigmoid(b.astype(f32))
        g = floor * jax.nn.sigmoid(
            jnp.exp(a_log.astype(f32))[:, None]
            * (f.reshape(bsz, s, heads, head_dim).astype(f32)
               + dt_bias.astype(f32).reshape(heads, head_dim)))
        return q.astype(dt), k.astype(dt), v, g, beta


def _chunk_inputs_of_one_inverse(q, k, v, g, beta, inv):
    """``kimi_delta_rule._chunk_inputs`` behind a barrier on ``inv``: the
    inverse is ``I - a`` plus five products, and with nothing in the way
    XLA fuses that sum into every op that reads it, so that what a layer
    held for its backward was the five products and ``a``, six float32
    (chunks, b, h, 64, 64) arrays, not the one it names."""
    from ..incubate.nn.functional.kimi_delta_rule import _chunk_inputs
    return _chunk_inputs(q, k, v, g, beta, jax.lax.optimization_barrier(inv))


def _kimi_delta_attention(x, w_qkv, w_fg, w_b, conv, a_log, dt_bias, norm_w,
                          w_o, *, heads, head_dim, floor, eps):
    """The whole KDA mixer on the normed ``x`` (b, s, hidden), on arrays.
    Its parts are sibling scopes: ``kda_proj`` (the four projections),
    ``kda_conv``, ``kda_gates`` (norms, gates, casts and head reshapes),
    ``kda_rule``."""
    from ..incubate.nn.functional.gated_delta_rule import chunked_rule
    from ..incubate.nn.functional.kimi_delta_rule import _chunk_system
    bsz, s, _ = x.shape
    width = heads * head_dim
    with jax.named_scope("kda_proj"):
        qkv = checkpoint_name(x @ w_qkv, KEPT_QKV)
        fg = checkpoint_name(x @ w_fg, KEPT_FG)
        b = x @ w_b
    with jax.named_scope("kda_gates"):
        f = fg[..., :width]
    q, k, v, g, beta = _rule_inputs(qkv, f, b, conv, a_log, dt_bias, heads,
                                    head_dim, floor)
    with jax.named_scope("kda_rule"):
        o = chunked_rule(q, k, v, g, beta, _chunk_system,
                         _chunk_inputs_of_one_inverse)
    with jax.named_scope("kda_gates"):
        gate = fg[..., width:].reshape(bsz, s, heads, head_dim)
        o = (_rms(o, norm_w, eps)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)
        o = o.reshape(bsz, s, width)
    with jax.named_scope("kda_proj"):
        return o @ w_o


class BailingKimiDeltaAttention(Layer):
    """Kept for the backward, a layer, beside its input (5 KB a token):
    the rule's float32 triangular inverse (``gated_delta_rule.
    KEPT_INVERSE``, 8 KB a token, 16 as the chip lays its 64-wide rows
    out) and the outputs of the two wide projections, ``x @ w_qkv`` (24 KB)
    and ``x @ w_fg`` (16 KB): 48 KB a token, 0.20 GB a layer and 1.2 GB
    for six at 4,096 tokens.  They are what is dear to build again: the
    inverse's ten batched float32 products and the system in front of it,
    and 0.43 TFLOP of matmul a layer.  Everything else -- the rule's
    inputs (40 KB a token, the decay a channel in float32 among them),
    what the scan reads, a state a chunk (32 KB), ``o`` -- is rebuilt in
    the backward, one layer at a time: elementwise passes and narrow
    matmuls that would take another 0.5 GB a layer, and six layers of
    them beside 884 M parameters' state do not fit a 16 GB chip."""

    def __init__(self, config: BailingHybridConfig):
        super().__init__()
        from ..incubate.nn.functional.gated_delta_rule import KEPT_INVERSE
        c, init = config, _init(config)
        heads, width = c.num_attention_heads, \
            c.num_attention_heads * c.head_dim
        self.in_proj_qkv = Linear(c.hidden_size, 3 * width, weight_attr=init,
                                  bias_attr=False)
        self.in_proj_fg = Linear(c.hidden_size, 2 * width, weight_attr=init,
                                 bias_attr=False)
        self.in_proj_b = Linear(c.hidden_size, heads, weight_attr=init,
                                bias_attr=False)
        self.conv = self.create_parameter(
            [3 * width, c.short_conv_kernel_size], attr=init)
        # the delta-rule family's initialisation: A spread over (0, 16]
        self.A_log = self.create_parameter(
            [heads], default_initializer=I.Assign(
                jnp.log(jnp.linspace(1.0, 16.0, heads))))
        self.dt_bias = self.create_parameter(
            [width], default_initializer=I.Constant(0.0))
        self.norm = RMSNorm(c.head_dim, c.rms_norm_eps)
        self.o_proj = Linear(width, c.hidden_size, weight_attr=init,
                             bias_attr=False)
        self._core = jax.checkpoint(
            functools.partial(
                _kimi_delta_attention, heads=heads, head_dim=c.head_dim,
                floor=float(c.kda_lower_bound), eps=c.rms_norm_eps),
            policy=jax.checkpoint_policies.save_only_these_names(
                KEPT_INVERSE, KEPT_QKV, KEPT_FG))

    def forward(self, x):
        return apply_op("kimi_delta_attention", self._core, [
            x, self.in_proj_qkv.weight, self.in_proj_fg.weight,
            self.in_proj_b.weight, self.conv, self.A_log, self.dt_bias,
            self.norm.weight, self.o_proj.weight])


def _rotate_pairs(x, theta):
    """Rotary positions on the whole last axis of ``x`` (b, s, heads, r) as
    interleaved pairs, float32: ``(x_2i, x_2i+1)`` turned by ``t
    theta^(-2i / r)``."""
    s, r = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _latent_attention_inputs(q, kv, k_rope, *, heads, nope, rope, v_dim,
                             theta, pad_v):
    """``q, k`` (b, s, heads, nope + rope) and ``v`` from the three
    projections' outputs: rotary on ``q_rope`` and on the one ``k_rope``,
    which every head then reads; ``pad_v`` gives ``v`` the keys' width
    (zeros) and packs ``[q | k | v]`` for the flash kernel."""
    dt = q.dtype
    b, s, _ = q.shape
    q = q.reshape(b, s, heads, nope + rope)
    kv = kv.reshape(b, s, heads, nope + v_dim)
    q = jnp.concatenate(
        [q[..., :nope], _rotate_pairs(q[..., nope:], theta).astype(dt)], -1)
    k_rope = _rotate_pairs(k_rope[:, :, None, :], theta).astype(dt)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], -1)
    v = kv[..., nope:]
    if not pad_v:
        return q, k, v
    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nope + rope - v_dim)))
    return jnp.concatenate([x.reshape(b, s, -1) for x in (q, k, v)], -1)


class BailingLatentAttention(Layer):
    def __init__(self, config: BailingHybridConfig):
        super().__init__()
        c, init = config, _init(config)
        self.heads = c.num_attention_heads
        self.qk_dim = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.v_dim, self.latent = c.v_head_dim, c.kv_lora_rank
        self.q_proj = Linear(c.hidden_size, self.heads * self.qk_dim,
                             weight_attr=init, bias_attr=False)
        self.kv_a_proj = Linear(c.hidden_size,
                                c.kv_lora_rank + c.qk_rope_head_dim,
                                weight_attr=init, bias_attr=False)
        self.kv_a_norm = RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = Linear(
            c.kv_lora_rank, self.heads * (c.qk_nope_head_dim + c.v_head_dim),
            weight_attr=init, bias_attr=False)
        self.g_proj = Linear(c.hidden_size, self.heads, weight_attr=init,
                             bias_attr=False)
        self.o_proj = Linear(self.heads * c.v_head_dim, c.hidden_size,
                             weight_attr=init, bias_attr=False)
        # under jax.checkpoint: the float32 copies the rotation takes, the
        # shared key's 32 copies and the padding are rebuilt in the
        # backward, not kept
        inputs = functools.partial(
            _latent_attention_inputs, heads=self.heads,
            nope=c.qk_nope_head_dim, rope=c.qk_rope_head_dim,
            v_dim=c.v_head_dim, theta=float(c.rope_theta))
        self._packed = jax.checkpoint(functools.partial(inputs, pad_v=True))
        self._apart = functools.partial(inputs, pad_v=False)

    def forward(self, x):
        from .. import ops
        from ..core import flags
        b, s, _ = x.shape
        kv_a = self.kv_a_proj(x)
        args = [self.q_proj(x),
                self.kv_b_proj(self.kv_a_norm(kv_a[..., :self.latent])),
                kv_a[..., self.latent:]]
        # Qwen3NextAttention's rule: the XLA composition for short
        # sequences, from ``flash_attention_min_seqlen`` on the packed
        # kernel, which raises where it has no plan
        if flags.flag("use_fused_kernels") \
                and s >= flags.flag("flash_attention_min_seqlen"):
            from ..incubate.nn.functional import flash_attention_qkv_packed
            out = flash_attention_qkv_packed(
                apply_op("latent_attention_inputs", self._packed, args),
                self.heads, causal=True,
                sm_scale=1.0 / math.sqrt(self.qk_dim))
            out = ops.reshape(out, [b, s, self.heads, self.qk_dim])
            out = out[..., :self.v_dim]
        else:
            q, k, v = apply_op("latent_attention_inputs", self._apart, args,
                               n_outputs=3)
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, use_flash=False)
        gate = ops.unsqueeze(F.sigmoid(self.g_proj(x)), -1)
        return self.o_proj(ops.reshape(out * gate, [b, s, -1]))


class BailingMLP(Layer):
    """``W_down(SiLU(W_gate x) * W_up x)``, gate and up as one projection."""

    def __init__(self, config: BailingHybridConfig):
        super().__init__()
        c, init = config, _init(config)
        self.width = c.intermediate_size
        self.gate_up_proj = Linear(c.hidden_size, 2 * self.width,
                                   weight_attr=init, bias_attr=False)
        self.down_proj = Linear(self.width, c.hidden_size, weight_attr=init,
                                bias_attr=False)

    def forward(self, x):
        h = self.gate_up_proj(x)
        return self.down_proj(
            F.silu(h[..., :self.width]) * h[..., self.width:])


class BailingHybridDecoderLayer(Layer):
    def __init__(self, config: BailingHybridConfig, layer_idx: int):
        super().__init__()
        c = config
        self.mla = c.layer_is_mla(layer_idx)
        self.dense = c.layer_is_dense(layer_idx)
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        if self.mla:
            self.self_attn = BailingLatentAttention(c)
        else:
            self.linear_attn = BailingKimiDeltaAttention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        if self.dense:
            self.mlp = BailingMLP(c)
        else:
            from ..parallel.moe import DroplessMoELayer
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok,
                experts_held=c.experts_held or (0, c.num_experts),
                shared_hidden=c.moe_shared_expert_intermediate_size,
                norm_topk_prob=c.norm_topk_prob,
                score_function=c.score_function, n_group=c.n_group,
                topk_group=c.topk_group,
                routed_scaling_factor=c.routed_scaling_factor,
                selection_bias=c.moe_router_enable_expert_bias,
                shared_gated=False)

    def forward(self, x):
        # each scope holds one half of the layer with its norm and
        # residual, as GPTBlock's do; no index, the layers aggregate
        with jax.named_scope("mla" if self.mla else "kda"):
            mixer = self.self_attn if self.mla else self.linear_attn
            x = x + mixer(self.input_layernorm(x))
        with jax.named_scope("mlp" if self.dense else "moe"):
            return x + self.mlp(self.post_attention_layernorm(x))


class BailingHybridForCausalLM(Layer):
    """``model(ids)`` -> logits (b, s, vocab_size) over the held slice of
    the vocabulary."""

    def __init__(self, config: BailingHybridConfig):
        super().__init__()
        c = self.config = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=_init(c))
        self.layers = LayerList([BailingHybridDecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
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


def bailing_hybrid_sharding_spec(name: str, shape) -> tuple:
    """Every leaf whole on every chip of the mesh: a mesh here is
    data-parallel replicas of one chip's share (``qwen3_next_sharding_spec``
    says the same of its model)."""
    return (None,) * len(shape)
