"""Fused functionals (ref ``python/paddle/incubate/nn/functional/``).

The reference backs these with hand-written fused CUDA kernels
(``paddle/fluid/operators/fused/fused_attention_op.cu``,
``fused_feedforward_op.cu``, ``fused_gemm_epilogue_op.cu``,
``fused_layernorm_residual_dropout_bias.h``). Here attention is a Pallas
TPU kernel; the elementwise chains (layernorm+residual+dropout,
gemm+bias+activation) are expressed as single taped ops whose bodies XLA
fuses into one HBM pass — the TPU-correct way to get what the CUDA fusions
buy, without hand-scheduling.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ....core.autograd import apply_op
from ....core.tensor import Tensor
from ..kernels import flash_attention as _fa
from ..kernels import flash_attention_packed as _fap
from ..kernels import mesh as _mesh
from .gated_delta_rule import (causal_depthwise_conv,  # noqa: F401
                               gated_delta_rule, gated_delta_rule_chunked)
from .kimi_delta_rule import (kimi_delta_rule,  # noqa: F401
                              kimi_delta_rule_chunked)


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))


def _dropout(h, rate, key, mode="upscale_in_train"):
    """Shared dropout body for the fused chains. key=None -> identity."""
    if key is None:
        return h
    keep = jax.random.bernoulli(key, 1.0 - rate, h.shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, h / (1.0 - rate), 0.0).astype(h.dtype)
    return jnp.where(keep, h, 0.0).astype(h.dtype)


def flash_attention_bshd(query, key, value, causal=False, sm_scale=None,
                         dropout_p=0.0, seed=None):
    """Flash attention over paddle-layout (batch, seq, heads, head_dim).

    ``dropout_p`` drops attention probabilities inside the kernel (ref
    ``fused_attention_op.cu`` attn_dropout); the mask is regenerated from
    ``seed`` in the backward, never materialised. Falls back to the
    caller's XLA path by raising if shapes don't qualify.
    """
    b, sq, h, d = query.shape
    skv = key.shape[1]
    plan = _mesh.plan(b, h)
    if plan is None or not _fa.supported(sq, skv):
        raise ValueError(f"flash kernel unsupported for seq ({sq},{skv}) "
                         f"on mesh axes {_mesh.partitioned_axes()}")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if dropout_p and seed is None:
        from ....core import random as core_random
        key_arr = core_random.split_key()
        seed = jax.random.randint(key_arr, (1,), -2**31, 2**31 - 1,
                                  dtype=jnp.int32)

    def local(q, k, v, seed):
        bl, hl = q.shape[0], q.shape[2]         # this device's rows/heads

        def to_bhd(x, s):
            # no explicit lane padding: Mosaic pads d<128 in-register, and an
            # explicit pad materialises 2x HBM copies of q/k/v (measured -8%
            # e2e on gpt2-small); odd head dims (80/96/256) verified native
            x = jnp.swapaxes(x, 1, 2)           # b h s d
            return x.reshape(bl * hl, s, d)

        qb, kb, vb = to_bhd(q, sq), to_bhd(k, skv), to_bhd(v, skv)
        _fa.maybe_autotune(qb, kb, vb, causal, scale)
        out = _fa.flash_attention_bhd(qb, kb, vb, causal, scale,
                                      float(dropout_p), seed)
        out = out.reshape(bl, hl, sq, d)
        return jnp.swapaxes(out, 1, 2)          # b s h d

    def fn(q, k, v):
        if not plan.partitioned:
            return local(q, k, v, seed)
        return _mesh.over_batch_and_heads(local, plan, (q, k, v), (2, 2, 2),
                                          4, 2, seed)

    return apply_op("flash_attention", fn, [_t(query), _t(key), _t(value)])


def flash_attention_qkv_packed(qkv, num_heads, causal=True, sm_scale=None,
                               dropout_p=0.0, seed=None):
    """Flash attention directly on the fused qkv projection output
    ``(b, s, 3*num_heads*head_dim)`` — no head split/merge ever touches
    HBM (the (b,s,h,d) reorganization around the bhd kernel costs ~10% of
    a gpt2-class train step in layout copies). Returns ``(b, s, h*d)``
    ready for the output projection. Raises ValueError when shapes don't
    qualify so callers can fall back.
    """
    qkv = _t(qkv)
    b, s, hd3 = qkv.shape
    head_dim = hd3 // 3 // num_heads
    plan = packed_flash_plan(b, s, num_heads, head_dim, qkv.dtype)
    if plan is None:
        raise ValueError(
            f"packed flash kernel unsupported for seq {s}, heads {num_heads}, "
            f"head_dim {head_dim}, dtype {qkv.dtype} on mesh axes "
            f"{_mesh.partitioned_axes()}")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(head_dim)
    if dropout_p and seed is None:
        from ....core import random as core_random
        key_arr = core_random.split_key()
        seed = jax.random.randint(key_arr, (1,), -2**31, 2**31 - 1,
                                  dtype=jnp.int32)

    def local(x5, seed):
        # this device's (rows, s, 3, heads, D) slice, re-packed in place
        bl, _, _, hl, _ = x5.shape
        out = _fap.flash_attention_packed(
            x5.reshape(bl, s, 3 * hl * head_dim), hl, causal, scale,
            float(dropout_p), seed)
        return out.reshape(bl, s, hl, head_dim)

    def fn(qkv_val):
        if not plan.partitioned:
            return _fap.flash_attention_packed(qkv_val, num_heads, causal,
                                               scale, float(dropout_p), seed)
        # heads must be whole per shard: regroup the packed lane dim as
        # (3, H, D) so 'mp' splits H (GSPMD reshards the projection's
        # contiguous column split into it), run per shard, merge back
        x5 = qkv_val.reshape(b, s, 3, num_heads, head_dim)
        out = _mesh.over_batch_and_heads(local, plan, (x5,), (3,), 4, 2, seed)
        return out.reshape(b, s, num_heads * head_dim)

    return apply_op("flash_attention_packed", fn, [qkv])


def packed_flash_plan(batch, seqlen, num_heads, head_dim, dtype):
    """The mesh split (``kernels.mesh.Plan``) under which the packed
    kernel handles this call — each shard's own heads must still be a
    supported geometry — or ``None`` (callers take the XLA path)."""
    plan = _mesh.plan(batch, num_heads)
    if plan is None or not _fap.supported(
            seqlen, seqlen, num_heads // plan.head_shards, head_dim, dtype):
        return None
    return plan


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None):
    """paddle.incubate flash_attention-style API: returns (out, softmax)."""
    assert not return_softmax, "flash kernel never materialises softmax"
    out = flash_attention_bshd(query, key, value, causal=causal,
                               dropout_p=dropout)
    return out, None


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     residual=None, bias=None, dropout_rate=0.0,
                     training=True, rng_key=None):
    """layernorm(residual + dropout(x + bias)) in one taped op.

    Ref ``fused_layernorm_residual_dropout_bias.h`` — one HBM pass; XLA
    fuses this body into a single loop the same way.
    Returns (out, residual_out).
    """
    args = [_t(x)]
    names = ["x"]
    for nm, v in (("norm_weight", norm_weight), ("norm_bias", norm_bias),
                  ("residual", residual), ("bias", bias)):
        if v is not None:
            args.append(_t(v))
            names.append(nm)

    drop_key = None
    if dropout_rate > 0.0 and training:
        if rng_key is None:
            from ....core import random as core_random
            drop_key = core_random.split_key()
        else:
            drop_key = rng_key

    def fn(*vals):
        d = dict(zip(names, vals))
        h = d["x"]
        if "bias" in d:
            h = h + d["bias"]
        h = _dropout(h, dropout_rate, drop_key)
        if "residual" in d:
            h = h + d["residual"]
        res_out = h
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        y = (h - mu) * jax.lax.rsqrt(var + epsilon)
        if "norm_weight" in d:
            y = y * d["norm_weight"]
        if "norm_bias" in d:
            y = y + d["norm_bias"]
        return y.astype(h.dtype), res_out

    return apply_op("fused_layer_norm", fn, args, n_outputs=2)


def fused_dropout_add(x, y, p=0.0, training=True, mode="upscale_in_train"):
    """dropout(x) + y as one op (ref fused_dropout_add in incubate)."""
    drop_key = None
    if p > 0.0 and training:
        from ....core import random as core_random
        drop_key = core_random.split_key()

    def fn(a, b):
        if drop_key is None:
            # upscale_in_train: eval is identity (train already rescaled);
            # downscale_in_infer: eval scales by the keep probability.
            if not training and p > 0.0 and mode != "upscale_in_train":
                a = a * (1.0 - p)
            return a + b
        return _dropout(a, p, drop_key, mode) + b

    return apply_op("fused_dropout_add", fn, [_t(x), _t(y)])


def fused_linear(x, weight, bias=None, transpose_weight=False,
                 activation=None, name=None):
    """matmul + bias + activation epilogue (ref fused_gemm_epilogue_op.cu,
    cublasLt epilogue). XLA fuses the epilogue into the MXU matmul."""
    args = [_t(x), _t(weight)] + ([_t(bias)] if bias is not None else [])

    def fn(xv, wv, *rest):
        if transpose_weight:
            wv = wv.T
        out = jnp.matmul(xv, wv)
        if rest:
            out = out + rest[0]
        if activation in ("gelu",):
            out = jax.nn.gelu(out)
        elif activation in ("relu",):
            out = jax.nn.relu(out)
        return out

    return apply_op("fused_linear", fn, args)


def fused_feedforward(x, linear1_weight, linear1_bias, linear2_weight,
                      linear2_bias, ln1_scale=None, ln1_bias=None,
                      dropout1_rate=0.5, dropout2_rate=0.5,
                      activation="relu", ln1_epsilon=1e-5,
                      pre_layer_norm=False, training=True):
    """Transformer FFN block as one taped op (ref fused_feedforward_op.cu).

    out = residual + dropout2(linear2(dropout1(act(linear1(ln(x))))))
    (post-LN applies layer_norm at the end instead).
    """
    args = [_t(x), _t(linear1_weight), _t(linear2_weight)]
    names = ["x", "w1", "w2"]
    for nm, v in (("b1", linear1_bias), ("b2", linear2_bias)):
        if v is not None:
            args.append(_t(v))
            names.append(nm)
    for nm, v in (("ln_scale", ln1_scale), ("ln_bias", ln1_bias)):
        if v is not None:
            args.append(_t(v))
            names.append(nm)

    keys = [None, None]
    if training:
        from ....core import random as core_random
        if dropout1_rate > 0.0:
            keys[0] = core_random.split_key()
        if dropout2_rate > 0.0:
            keys[1] = core_random.split_key()

    def _ln(h, d, eps):
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        y = (h - mu) * jax.lax.rsqrt(var + eps)
        if "ln_scale" in d:
            y = y * d["ln_scale"]
        if "ln_bias" in d:
            y = y + d["ln_bias"]
        return y.astype(h.dtype)

    def fn(*vals):
        d = dict(zip(names, vals))
        residual = d["x"]
        h = _ln(d["x"], d, ln1_epsilon) if pre_layer_norm else d["x"]
        h = jnp.matmul(h, d["w1"])
        if "b1" in d:
            h = h + d["b1"]
        h = jax.nn.gelu(h) if activation == "gelu" else jax.nn.relu(h)
        h = _dropout(h, dropout1_rate, keys[0])
        h = jnp.matmul(h, d["w2"])
        if "b2" in d:
            h = h + d["b2"]
        h = _dropout(h, dropout2_rate, keys[1])
        out = residual + h
        if not pre_layer_norm:
            out = _ln(out, d, ln1_epsilon)
        return out

    return apply_op("fused_feedforward", fn, args)


__all__ = [
    "flash_attention", "flash_attention_bshd", "fused_layer_norm",
    "fused_dropout_add", "fused_linear", "fused_feedforward",
]


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """matmul + bias epilogue (ref fused_gemm_epilogue via cublasLt)."""
    args = [_t(x), _t(y)] + ([_t(bias)] if bias is not None else [])

    def fn(xv, yv, *rest):
        if transpose_x:
            xv = jnp.swapaxes(xv, -1, -2)
        if transpose_y:
            yv = jnp.swapaxes(yv, -1, -2)
        out = jnp.matmul(xv, yv)
        if rest:
            out = out + rest[0]
        return out
    return apply_op("fused_matmul_bias", fn, args)


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """LN(residual + dropout(x + bias)) in one fused op (ref
    fused_bias_dropout_residual_layer_norm op)."""
    out, _ = fused_layer_norm(x, ln_scale, ln_bias, epsilon=ln_epsilon,
                              residual=residual, bias=bias,
                              dropout_rate=dropout_rate, training=training)
    return out


def fused_multi_head_attention(
        x, qkv_weight, linear_weight, pre_layer_norm=False,
        pre_ln_scale=None, pre_ln_bias=None, ln_scale=None, ln_bias=None,
        pre_ln_epsilon=1e-5, qkv_bias=None, linear_bias=None,
        cache_kv=None, attn_mask=None, dropout_rate=0.5,
        attn_dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", ring_id=-1, add_residual=True,
        time_step=None, name=None):
    """Functional form of the fused attention block (ref
    fused_attention_op.cu): optional pre-LN -> qkv -> MHA -> out proj ->
    bias+dropout+residual(+post-LN).

    With ``cache_kv`` (shape (2, batch, heads, max_seq, head_dim), the
    reference's CacheKV layout) the call runs incremental decoding: this
    step's k/v are written at ``time_step`` (scalar, default 0 = prefill)
    and queries attend over every cached position ≤ their global position.
    Since arrays are immutable here, the updated cache is RETURNED:
    ``(out, cache_kv_out)`` instead of the reference's in-place write.
    """
    import math as _math
    h = _t(x)
    residual = h
    if pre_layer_norm:
        h, _ = fused_layer_norm(h, pre_ln_scale, pre_ln_bias,
                                epsilon=pre_ln_epsilon)
    qkvw = _t(qkv_weight)  # (3, num_heads, head_dim, embed)
    _, n_heads, head_dim, embed = qkvw.shape
    has_bias = qkv_bias is not None
    has_mask = attn_mask is not None

    drop_key = None
    if attn_dropout_rate > 0.0 and training:
        from ....core import random as core_random
        drop_key = core_random.split_key()

    def qkv_fn(hv, wv, *rest):
        it = iter(rest)
        b = next(it) if has_bias else None
        mask = next(it) if has_mask else None
        q, k, v = (jnp.einsum("bsd,hed->bshe", hv, wv[i])
                   for i in range(3))
        if b is not None:
            q = q + b[0][None, None]
            k = k + b[1][None, None]
            v = v + b[2][None, None]
        logits = jnp.einsum("bshe,bthe->bhst", q, k) / _math.sqrt(head_dim)
        if mask is not None:
            logits = logits + mask
        probs = jax.nn.softmax(logits, -1)
        probs = _dropout(probs, attn_dropout_rate, drop_key)
        ctx = jnp.einsum("bhst,bthe->bshe", probs, v)
        return ctx.reshape(ctx.shape[0], ctx.shape[1], -1)

    def qkv_cached_fn(hv, wv, cachev, tstep, *rest):
        """Incremental decoding against a static (2, B, H, Tmax, D) cache
        (ref fused_multi_transformer_op.cu decode phase): write this call's
        k/v at [time_step, time_step+s), attend each query i over key
        positions j <= time_step + i.  Functional: returns the new cache."""
        it = iter(rest)
        b = next(it) if has_bias else None
        mask = next(it) if has_mask else None
        q, k, v = (jnp.einsum("bsd,hed->bshe", hv, wv[i])
                   for i in range(3))
        if b is not None:
            q = q + b[0][None, None]
            k = k + b[1][None, None]
            v = v + b[2][None, None]
        t0 = tstep.astype(jnp.int32)
        kc, vc = cachev[0], cachev[1]                    # (B, H, Tmax, D)
        k_bh = jnp.swapaxes(k, 1, 2).astype(kc.dtype)    # (B, H, s, D)
        v_bh = jnp.swapaxes(v, 1, 2).astype(vc.dtype)
        zero = jnp.zeros((), jnp.int32)
        kc = jax.lax.dynamic_update_slice(kc, k_bh, (zero, zero, t0, zero))
        vc = jax.lax.dynamic_update_slice(vc, v_bh, (zero, zero, t0, zero))
        logits = jnp.einsum("bshe,bhte->bhst", q,
                            kc.astype(q.dtype)) / _math.sqrt(head_dim)
        s, t_max = q.shape[1], kc.shape[2]
        qpos = t0 + jnp.arange(s)[:, None]               # (s, 1) global pos
        kpos = jnp.arange(t_max)[None, :]
        logits = jnp.where((kpos <= qpos)[None, None], logits,
                           jnp.asarray(-1e30, logits.dtype))
        if mask is not None:
            logits = logits + mask
        probs = jax.nn.softmax(logits, -1)
        probs = _dropout(probs, attn_dropout_rate, drop_key)
        ctx = jnp.einsum("bhst,bhte->bshe", probs, vc.astype(probs.dtype))
        return (ctx.reshape(ctx.shape[0], ctx.shape[1], -1),
                jnp.stack([kc, vc]))

    new_cache = None
    args = [h, qkvw]
    if cache_kv is not None:
        ts = time_step if time_step is not None else jnp.asarray(
            0, jnp.int32)
        args += [_t(cache_kv), _t(ts)]
    if has_bias:
        args.append(_t(qkv_bias))
    if has_mask:
        args.append(_t(attn_mask))
    if cache_kv is not None:
        ctx, new_cache = apply_op("fused_mha_core_cached", qkv_cached_fn,
                                  args, n_outputs=2)
    else:
        ctx = apply_op("fused_mha_core", qkv_fn, args)
    out = fused_linear(ctx, linear_weight, linear_bias)
    if add_residual:
        out = fused_dropout_add(out, residual, p=dropout_rate,
                                training=training, mode=mode)
    if not pre_layer_norm:
        out, _ = fused_layer_norm(out, ln_scale, ln_bias, epsilon=ln_epsilon)
    if new_cache is not None:
        return out, new_cache
    return out


def fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, pre_layer_norm=True,
        epsilon=1e-5, cache_kvs=None, time_step=None, attn_mask=None,
        dropout_rate=0.0, activation="gelu", training=False,
        mode="upscale_in_train", trans_qkvw=True, ring_id=-1, name=None):
    """Stacked fused transformer decoder blocks (ref
    fused_multi_transformer_op.cu). Returns (out, cache_kvs)."""
    h = _t(x)
    n_layers = len(qkv_weights)
    if not trans_qkvw:
        # weights arrive (embed, 3, heads, head_dim): move embed last to the
        # (3, heads, head_dim, embed) layout the attention core consumes
        from ....ops import manipulation as _M
        qkv_weights = [_M.transpose(_t(w), [1, 2, 3, 0])
                       for w in qkv_weights]
    new_caches = [] if cache_kvs is not None else None
    for i in range(n_layers):
        att = fused_multi_head_attention(
            h, qkv_weights[i], linear_weights[i],
            pre_layer_norm=pre_layer_norm,
            pre_ln_scale=ln_scales[i] if ln_scales else None,
            pre_ln_bias=ln_biases[i] if ln_biases else None,
            qkv_bias=qkv_biases[i] if qkv_biases else None,
            linear_bias=linear_biases[i] if linear_biases else None,
            cache_kv=cache_kvs[i] if cache_kvs is not None else None,
            time_step=time_step,
            attn_mask=attn_mask, dropout_rate=dropout_rate,
            attn_dropout_rate=dropout_rate, training=training, mode=mode)
        if cache_kvs is not None:
            h, cache_i = att
            new_caches.append(cache_i)
        else:
            h = att
        h = fused_feedforward(
            h, ffn1_weights[i], ffn1_biases[i] if ffn1_biases else None,
            ffn2_weights[i], ffn2_biases[i] if ffn2_biases else None,
            ln1_scale=ffn_ln_scales[i] if ffn_ln_scales else None,
            ln1_bias=ffn_ln_biases[i] if ffn_ln_biases else None,
            dropout1_rate=dropout_rate, dropout2_rate=dropout_rate,
            activation=activation, pre_layer_norm=pre_layer_norm,
            training=training)
    return h, (new_caches if new_caches is not None else cache_kvs)


__all__ += ["fused_matmul_bias", "fused_bias_dropout_residual_layer_norm",
            "fused_multi_head_attention", "fused_multi_transformer"]
