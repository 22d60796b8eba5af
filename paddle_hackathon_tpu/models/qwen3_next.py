"""Qwen3-Next-style hybrid decoder: a stack of layers of two kinds.

Layer ``l`` mixes tokens by gated softmax attention where ``(l + 1) %
full_attention_interval == 0`` and by a Gated DeltaNet (a linear-attention
recurrence, ``incubate/nn/functional/gated_delta_rule.py``) elsewhere;
every layer's feed-forward part is a top-k mixture of SwiGLU experts
beside one shared expert (``parallel/moe.py DroplessMoELayer``); RMSNorm
before each half, residuals around both, a final RMSNorm and an untied
head.  Source of the shapes and keys: the public ``qwen3_next``
``config.json`` (``Qwen3NextConfig`` keeps its key names).

``x`` is (b, s, hidden); ``h = x + mixer(norm(x))``, ``y = h + moe(norm(h))``.

*Gated DeltaNet mixer.*  Two bias-free projections of ``x``: ``[q | k | v |
z]`` (key heads x key dim, the same, value heads x value dim, the same)
and ``[b | a]`` (one each a value head).  ``[q | k | v]`` goes through a
causal depthwise convolution of ``linear_conv_kernel_dim`` taps and SiLU;
``q`` and ``k`` are L2-normalised over the head dim, each key head
repeated to serve ``value heads / key heads`` value heads, ``q`` scaled by
key dim^-1/2; ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a +
dt_bias)`` in float32; the delta rule; then per head ``w * o / rms(o) *
SiLU(z)`` and a bias-free output projection.  (The published checkpoint
interleaves the fused projection's columns per key head; here they stand
as four blocks, the same function under a permutation of columns.)

*Gated attention mixer.*  Bias-free ``q_proj`` to ``[q | gate]`` (heads x
head dim each), ``k_proj``, ``v_proj`` to the KV heads; per-head RMSNorm
of q and of k; rotary positions (rotate-half) on the first
``partial_rotary_factor`` of each head's dims; causal softmax attention at
scale head dim^-1/2 through the packed flash kernel, the KV heads repeated
to the query heads (the kernel wants equal counts); ``attn *
sigmoid(gate)``; a bias-free output projection.

*A chip's share.*  ``experts_held = (first, count)`` tells every expert
layer which of the ``num_experts`` it holds; ``vocab_size`` is the slice of
the vocabulary held (ids, logits and loss are over the slice).  The mixers
are whole.  A layer that holds a part of the experts does not train its
router (``parallel/moe.py DroplessMoELayer`` says why).  No MTP module.

The scopes ``embed``, ``gdn`` (with ``gdn_proj``, ``gdn_conv``,
``gdn_gates``, ``gdn_rule`` inside: siblings, which leave only the layer's
norm and residual outside a part), ``attn``, ``moe`` (with ``router``,
``experts``, ``shared_expert``), ``ln_f``, ``lm_head`` name the step's
parts for the phase census (``observability/programs.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

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
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # (first, count) of the num_experts this chip holds; None = all of them
    experts_held: Optional[Tuple[int, int]] = None

    def layer_is_full_attention(self, layer_idx: int) -> bool:
        return (layer_idx + 1) % self.full_attention_interval == 0


def _init(config):
    return ParamAttr(initializer=I.Normal(0.0, config.initializer_range))


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7, 8))
def _rule_inputs(qkv, ba, conv, a_log, dt_bias, key_heads, value_heads,
                 key_dim, value_dim):
    """``q, k, v, g, beta`` of the delta rule from the projected [q | k |
    v] and [b | a]: the convolution and SiLU, the L2 norms, the heads'
    repeat, the gates.  Under ``jax.checkpoint``: the convolution's
    shifted copies and the float32 copies the norms take are rebuilt in
    the backward from the projection's output, not kept by every layer."""
    from ..incubate.nn.functional.gated_delta_rule import \
        causal_depthwise_conv
    f32, dt = jnp.float32, qkv.dtype
    b, s, _ = qkv.shape
    kq = key_heads * key_dim
    with jax.named_scope("gdn_conv"):
        mixed = jax.nn.silu(causal_depthwise_conv(qkv, conv))
    with jax.named_scope("gdn_gates"):
        q = mixed[..., :kq].reshape(b, s, key_heads, key_dim).astype(f32)
        k = mixed[..., kq:2 * kq].reshape(b, s, key_heads, key_dim) \
            .astype(f32)
        v = mixed[..., 2 * kq:].reshape(b, s, value_heads, value_dim)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * key_dim ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        rep = value_heads // key_heads
        q = jnp.repeat(q.astype(dt), rep, axis=2)
        k = jnp.repeat(k.astype(dt), rep, axis=2)
        beta = jax.nn.sigmoid(ba[..., :value_heads].astype(f32))
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            ba[..., value_heads:].astype(f32) + dt_bias.astype(f32))
        return q, k, v, g, beta


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _gated_norm(o, z, weight, eps):
    """``w * o / rms(o) * SiLU(z)`` over each head's dims, in float32."""
    with jax.named_scope("gdn_gates"):
        return (_rms(o, weight, eps)
                * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


def _gated_delta_net(qkvz, ba, conv, a_log, dt_bias, norm_w, *, key_heads,
                     value_heads, key_dim, value_dim, eps):
    """Everything of the DeltaNet mixer between its input projections and
    its output projection, on arrays: the sibling scopes ``gdn_conv``,
    ``gdn_gates`` (norms, gates, casts, the heads' repeat and reshapes)
    and ``gdn_rule``."""
    from ..incubate.nn.functional.gated_delta_rule import \
        gated_delta_rule_chunked
    b, s, _ = qkvz.shape
    split = 2 * key_heads * key_dim + value_heads * value_dim
    with jax.named_scope("gdn_gates"):
        qkv = qkvz[..., :split]
    q, k, v, g, beta = _rule_inputs(
        qkv, ba, conv, a_log, dt_bias, key_heads, value_heads, key_dim,
        value_dim)
    with jax.named_scope("gdn_rule"):
        o = gated_delta_rule_chunked(q, k, v, g, beta)
    with jax.named_scope("gdn_gates"):
        z = qkvz[..., split:].reshape(b, s, value_heads, value_dim)
    out = _gated_norm(o, z, norm_w, eps)
    with jax.named_scope("gdn_gates"):
        return out.reshape(b, s, -1)


class Qwen3NextGatedDeltaNet(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c, init = config, _init(config)
        kq = c.linear_num_key_heads * c.linear_key_head_dim
        vz = c.linear_num_value_heads * c.linear_value_head_dim
        self.in_proj_qkvz = Linear(c.hidden_size, 2 * kq + 2 * vz,
                                   weight_attr=init, bias_attr=False)
        self.in_proj_ba = Linear(c.hidden_size, 2 * c.linear_num_value_heads,
                                 weight_attr=init, bias_attr=False)
        self.conv = self.create_parameter(
            [2 * kq + vz, c.linear_conv_kernel_dim], attr=init)
        heads = c.linear_num_value_heads
        # the family's initialisation: A spread over (0, 16], dt_bias 1
        self.A_log = self.create_parameter(
            [heads], default_initializer=I.Assign(
                jnp.log(jnp.linspace(1.0, 16.0, heads))))
        self.dt_bias = self.create_parameter(
            [heads], default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(c.linear_value_head_dim, c.rms_norm_eps)
        self.out_proj = Linear(vz, c.hidden_size, weight_attr=init,
                               bias_attr=False)
        self._core = functools.partial(
            _gated_delta_net, key_heads=c.linear_num_key_heads,
            value_heads=heads, key_dim=c.linear_key_head_dim,
            value_dim=c.linear_value_head_dim, eps=c.rms_norm_eps)

    def forward(self, x):
        with jax.named_scope("gdn_proj"):
            qkvz, ba = self.in_proj_qkvz(x), self.in_proj_ba(x)
        mixed = apply_op("gated_delta_net", self._core, [
            qkvz, ba, self.conv, self.A_log, self.dt_bias,
            self.norm.weight])
        with jax.named_scope("gdn_proj"):
            return self.out_proj(mixed)


def _rotate(x, rotary_dim, theta):
    """Rotate-half rotary positions on the first ``rotary_dim`` of the
    last axis of ``x`` (b, s, heads, head_dim), float32."""
    s, half = x.shape[1], rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / rotary_dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _attention_inputs(q_gate, k, v, q_norm_w, k_norm_w, *, heads, kv_heads,
                      head_dim, rotary_dim, theta, eps):
    """``(packed [q | k | v] with the KV heads repeated to the query
    heads, gate)`` from the three projections' outputs."""
    dt = q_gate.dtype
    b, s, _ = q_gate.shape
    q = q_gate[..., :heads * head_dim].reshape(b, s, heads, head_dim)
    gate = q_gate[..., heads * head_dim:]
    k = k.reshape(b, s, kv_heads, head_dim)
    q = _rotate(_rms(q, q_norm_w, eps), rotary_dim, theta).astype(dt)
    k = _rotate(_rms(k, k_norm_w, eps), rotary_dim, theta).astype(dt)
    rep = heads // kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v.reshape(b, s, kv_heads, head_dim), rep, axis=2)
    packed = jnp.concatenate([x.reshape(b, s, heads * head_dim)
                              for x in (q, k, v)], -1)
    return packed, gate


class Qwen3NextAttention(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c, init = config, _init(config)
        self.num_heads, self.head_dim = c.num_attention_heads, c.head_dim
        width = c.num_attention_heads * c.head_dim
        kv_width = c.num_key_value_heads * c.head_dim
        self.q_proj = Linear(c.hidden_size, 2 * width, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(c.hidden_size, kv_width, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(c.hidden_size, kv_width, weight_attr=init,
                             bias_attr=False)
        self.q_norm = RMSNorm(c.head_dim, c.rms_norm_eps)
        self.k_norm = RMSNorm(c.head_dim, c.rms_norm_eps)
        self.o_proj = Linear(width, c.hidden_size, weight_attr=init,
                             bias_attr=False)
        # under jax.checkpoint: the float32 copies that the norms and the
        # rotation take are rebuilt in the backward, not kept
        self._inputs = jax.checkpoint(functools.partial(
            _attention_inputs, heads=c.num_attention_heads,
            kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            rotary_dim=int(c.head_dim * c.partial_rotary_factor),
            theta=float(c.rope_theta), eps=c.rms_norm_eps))

    def forward(self, x):
        from .. import ops
        from ..core import flags
        packed, gate = apply_op("qwen3_next_attention_inputs", self._inputs, [
            self.q_proj(x), self.k_proj(x), self.v_proj(x),
            self.q_norm.weight, self.k_norm.weight], n_outputs=2)
        b, s, _ = packed.shape
        # GPTAttention's rule for short sequences (the XLA composition
        # under ``flash_attention_min_seqlen`` or with the fused kernels
        # switched off); from there on the packed kernel, which raises
        # where it has no plan for the geometry: the composition's float32
        # scores are 2 GB at s4096, and a step that took it unasked would
        # only show as a slower, larger step
        if flags.flag("use_fused_kernels") \
                and s >= flags.flag("flash_attention_min_seqlen"):
            from ..incubate.nn.functional import flash_attention_qkv_packed
            out = flash_attention_qkv_packed(
                packed, self.num_heads, causal=True,
                sm_scale=1.0 / math.sqrt(self.head_dim))
        else:
            q, k, v = ops.unstack(ops.reshape(
                packed, [b, s, 3, self.num_heads, self.head_dim]), axis=2)
            out = ops.reshape(F.scaled_dot_product_attention(
                q, k, v, is_causal=True, use_flash=False), [b, s, -1])
        return self.o_proj(out * F.sigmoid(gate))


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, config: Qwen3NextConfig, layer_idx: int):
        super().__init__()
        c = config
        self.full_attention = c.layer_is_full_attention(layer_idx)
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        if self.full_attention:
            self.self_attn = Qwen3NextAttention(c)
        else:
            self.linear_attn = Qwen3NextGatedDeltaNet(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        from ..parallel.moe import DroplessMoELayer
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok,
            experts_held=c.experts_held or (0, c.num_experts),
            shared_hidden=c.shared_expert_intermediate_size,
            norm_topk_prob=c.norm_topk_prob)

    def forward(self, x):
        # each scope holds one half of the layer with its norm and
        # residual, as GPTBlock's do; no index, the layers aggregate
        if self.full_attention:
            with jax.named_scope("attn"):
                x = x + self.self_attn(self.input_layernorm(x))
        else:
            with jax.named_scope("gdn"):
                x = x + self.linear_attn(self.input_layernorm(x))
        with jax.named_scope("moe"):
            return x + self.mlp(self.post_attention_layernorm(x))


class Qwen3NextForCausalLM(Layer):
    """``model(ids)`` -> logits (b, s, vocab_size) over the held slice of
    the vocabulary."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c = self.config = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=_init(c))
        self.layers = LayerList([Qwen3NextDecoderLayer(c, i)
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


def qwen3_next_sharding_spec(name: str, shape) -> tuple:
    """Every leaf whole on every chip of the mesh.  The chips that share a
    layer in an expert-parallel deployment each run this model with their
    own ``experts_held`` and their own slice of the vocabulary; a mesh
    here is data-parallel replicas of one such share."""
    return (None,) * len(shape)
