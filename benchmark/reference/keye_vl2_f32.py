"""Plain reference for ``KeyeVL2ForCausalLM`` training: float32
``jax.numpy``, matmul precision "highest", no kernels, written from the
layer equations of the public ``KeyeVL2`` config (Qwen3-MoE's decoder;
DeepSeek-V3.2-Exp's sparse attention with the config's ``sa_config``).  It
imports nothing of the program and takes nothing the program made: weights
and batches come from the seed through ``benchmark/weights.py``; the steps
that follow the gradients are ``benchmark/reference_steps.py``.

``x`` is (b, s, hidden); layer: ``h = x + attn(rms(x))``, ``y = h +
experts(rms(h))``; a final RMSNorm and the untied head; loss: mean softmax
cross-entropy plus every layer's indexer loss (weight 1).

*Attention.*  ``q = x W_q`` (heads x d), ``k``, ``v`` (KV heads x d); q and
k RMS-normed a head; rotate-half rotary positions on all d dims:
``[x1 cos - x2 sin | x2 cos + x1 sin]`` at angle ``t theta^(-2i/d)``; each
KV head repeated to its ``heads / kv_heads`` query heads.

*Indexer* (on ``x`` with its gradient stopped).  ``qi = x W_qi`` (ih x
id), ``ki = LayerNorm(x W_ki)`` (scale and bias), rotate-half rotary on
the first ``index_rope_dim`` dims of both, ``w = x W_w ih^-1/2 id^-1/2``;
``I[t, j] = sum_h w[t, h] relu(qi[t, h] . ki[j])`` over a query's whole
row; the selection ``S_t`` is ``jax.lax.top_k`` of the causal row (``-inf``
after ``t``) intersected with ``j <= t``.

*Sparse attention.*  Softmax of ``q_h . k_j / sqrt(d)`` over ``S_t`` by
masking, ``o = P v``, then ``W_o``.  *Indexer loss*: ``p`` = the heads'
mean of ``P`` (a target, gradient stopped), ``KL_t = sum_{S_t} p (log p -
log_softmax_{S_t}(I))``, its mean over the tokens.  Written out: the
program's gradient of it is the same function's.

*Experts.*  Softmax over all ``num_experts_published`` router logits in
float32, the top ``num_experts_per_tok``, renormalised; expert ``e``:
``W_down(SiLU(W_gate x) * W_up x)``; only ``experts_held`` are computed,
each on every token under the weight of the tokens that chose it; no
shared expert.  Where fewer experts are held than the router chooses
among, the weights are constants to the backward pass (``qwen3_next_f32.py``
says why).

Memory (b1 x s16384 beside 659 M parameters and their float32 moments):
one layer at a time, each layer's backward recomputing its forward;
attention and the indexer ``ATTN_ROWS`` query rows at a time under a
``lax.scan`` with each block recomputed in the backward; the experts one
at a time; the head ``HEAD_ROWS`` rows at a time.  ``quant="fp8"`` is the
control of the correctness check: every matmul the configuration states in
bfloat16 (the projections, attention's two, the indexer's projections and
its products) on float8 operands; the router and the indexer's head
weights, stated float32, stay float32.  ``rows`` plants the "half of the
batch left out" fault; a batch of one sequence loses the second half of
its tokens instead.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_steps

_mm = reference_steps.mm
HEAD_ROWS = 2048     # rows of the vocabulary head computed at once
ATTN_ROWS = 128      # query rows of attention and the indexer at once


def sizes(cfg: dict) -> dict:
    """The numbers the equations need, from a configuration's file: there
    ``num_experts`` counts the experts held here and
    ``num_experts_published`` is the router's width."""
    first, count = cfg["experts_held"]
    assert count == cfg["num_experts"], "num_experts counts the experts held"
    sa = cfg["sa_config"]
    return {
        "layers": cfg["num_hidden_layers"], "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "theta": float(cfg["rope_theta"]),
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "topk": sa["topk"],
        "index_rope": cfg["index_rope_dim"],
        "router": cfg["num_experts_published"], "first": first,
        "count": count, "experts_per_token": cfg["num_experts_per_tok"],
        "width": cfg["moe_intermediate_size"],
        "renorm": bool(cfg["norm_topk_prob"]), "eps": cfg["rms_norm_eps"]}


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted(sizes(cfg).items()))


def param_spec(cfg: dict) -> dict:
    """``{name: shape}`` under the names ``KeyeVL2ForCausalLM`` gives."""
    c = sizes(cfg)
    h, nh, nkv, d = c["hidden"], c["heads"], c["kv_heads"], c["head_dim"]
    ih, idim = c["index_heads"], c["index_dim"]
    spec = {"embed_tokens.weight": (c["vocab"], h)}
    for i in range(c["layers"]):
        p = f"layers.{i}."
        a = p + "self_attn."
        spec.update({
            p + "input_layernorm.weight": (h,),
            a + "q_proj.weight": (h, nh * d),
            a + "k_proj.weight": (h, nkv * d),
            a + "v_proj.weight": (h, nkv * d),
            a + "q_norm.weight": (d,),
            a + "k_norm.weight": (d,),
            a + "indexer.wq.weight": (h, ih * idim),
            a + "indexer.wk.weight": (h, idim),
            a + "indexer.k_norm.weight": (idim,),
            a + "indexer.k_norm.bias": (idim,),
            a + "indexer.weights_proj.weight": (h, ih),
            a + "o_proj.weight": (nh * d, h),
            p + "post_attention_layernorm.weight": (h,),
            p + "mlp.router.weight": (h, c["router"]),
            p + "mlp.experts_gate_up": (c["count"], h, 2 * c["width"]),
            p + "mlp.experts_down": (c["count"], c["width"], h)})
    spec.update({"norm.weight": (h,), "lm_head.weight": (h, c["vocab"])})
    return spec


def leaf_segments(cfg: dict) -> dict:
    """The one fused leaf a layer has: the experts' [gate | up]."""
    return {f"layers.{i}.mlp.experts_gate_up": 2
            for i in range(sizes(cfg)["layers"])}


# ---------------------------------------------------------------------------
# the equations
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rotary(x, rotary_dim, theta):
    """Rotate-half rotary positions on the first ``rotary_dim`` of the last
    axis of ``x`` (b, s, heads, d)."""
    s, half = x.shape[1], rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / rotary_dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _rows(q, k, v, qi, ki, w, row0, c, quant):
    """Attention and the indexer loss of the query rows ``row0 ...``:
    ``q`` (b, h, r, d), ``k``, ``v`` (b, h, s, d) (KV heads repeated),
    ``qi`` (b, ih, r, id), ``ki`` (b, s, id), ``w`` (b, r, ih) ->
    (context (b, h, r, d), KL (b, r))."""
    r, s = q.shape[2], k.shape[2]
    low = reference_steps.fp8 if quant == "fp8" else (lambda a: a)
    dots = jnp.einsum("bhrd,bsd->bhrs", low(qi), low(ki),
                      precision=jax.lax.Precision.HIGHEST)
    index = jnp.einsum("brh,bhrs->brs", w, jax.nn.relu(dots),
                       precision=jax.lax.Precision.HIGHEST)
    rows = row0 + jnp.arange(r)[:, None]
    causal = jnp.arange(s)[None, :] <= rows                  # (r, s)
    index = jnp.where(causal, index, -jnp.inf)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(index), min(c["topk"], s))
    b = q.shape[0]
    sel = jnp.zeros((b, r, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(r)[None, :, None],
        idx].set(True) & causal
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(sel[:, None], scores, -jnp.inf), -1)
    ctx = _mm(probs, v, quant)
    p = jax.lax.stop_gradient(jnp.mean(probs, 1))             # (b, r, s)
    log_q = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), -1)
    live = sel & (p > 0)
    kl = jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                      - jnp.where(sel, log_q, 0.0)), 0.0), -1)
    return ctx, kl


def sparse_attention(p, x, c, quant=None):
    """The attention half on the normed ``x`` (b, s, hidden): (output, the
    indexer's loss, the mean of its per-token KL)."""
    b, s, _ = x.shape
    nh, nkv, d = c["heads"], c["kv_heads"], c["head_dim"]
    ih, idim = c["index_heads"], c["index_dim"]
    q = _mm(x, p["q_proj.weight"], quant).reshape(b, s, nh, d)
    k = _mm(x, p["k_proj.weight"], quant).reshape(b, s, nkv, d)
    v = _mm(x, p["v_proj.weight"], quant).reshape(b, s, nkv, d)
    q = rotary(_rms(q, p["q_norm.weight"], c["eps"]), d, c["theta"])
    k = rotary(_rms(k, p["k_norm.weight"], c["eps"]), d, c["theta"])
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))    # b h s d
    xd = jax.lax.stop_gradient(x)
    qi = rotary(_mm(xd, p["indexer.wq.weight"], quant)
                .reshape(b, s, ih, idim), c["index_rope"], c["theta"])
    ki = _layer_norm(_mm(xd, p["indexer.wk.weight"], quant),
                     p["indexer.k_norm.weight"], p["indexer.k_norm.bias"],
                     c["eps"])
    ki = rotary(ki[:, :, None, :], c["index_rope"], c["theta"])[:, :, 0]
    w = _mm(xd, p["indexer.weights_proj.weight"], None) \
        * (ih ** -0.5 * idim ** -0.5)
    qi = jnp.swapaxes(qi, 1, 2)                              # b ih s id
    block = min(ATTN_ROWS, s)

    @jax.checkpoint
    def one(_, r0):
        ctx, kl = _rows(
            jax.lax.dynamic_slice_in_dim(q, r0, block, 2), k, v,
            jax.lax.dynamic_slice_in_dim(qi, r0, block, 2), ki,
            jax.lax.dynamic_slice_in_dim(w, r0, block, 1), r0, c, quant)
        return None, (ctx, kl)

    _, (ctx, kl) = jax.lax.scan(one, None, jnp.arange(0, s, block))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, nh, s, d)       # b h s d
    ctx = jnp.swapaxes(ctx, 1, 2).reshape(b, s, nh * d)
    return _mm(ctx, p["o_proj.weight"], quant), jnp.mean(kl)


def router_choice(p, x, c):
    """``(weights (n, k), experts (n, k))``: float32 matmul and softmax over
    all experts, the k largest, renormalised; constants to the backward
    pass where only a part of the experts is held."""
    probs = jax.nn.softmax(_mm(x, p["router.weight"], None), -1)
    vals, idx = jax.lax.top_k(probs, c["experts_per_token"])
    if c["renorm"]:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    if c["count"] < p["router.weight"].shape[1]:
        vals = jax.lax.stop_gradient(vals)
    return vals, idx


def swiglu(x, w_gate_up, w_down, quant):
    h = _mm(x, w_gate_up, quant)
    half = h.shape[-1] // 2
    return _mm(jax.nn.silu(h[..., :half]) * h[..., half:], w_down, quant)


def experts(p, x, c, quant=None):
    """The held experts' share of the routed sum on the normed ``x``."""
    tokens = x.reshape(-1, x.shape[-1])
    vals, idx = router_choice(p, tokens, c)

    @jax.checkpoint
    def one(acc, e):
        w_gate_up, w_down, number = e
        weight = jnp.sum(jnp.where(idx == number, vals, 0.0), -1)
        return acc + weight[:, None] * swiglu(tokens, w_gate_up, w_down,
                                              quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(tokens), (
        p["experts_gate_up"], p["experts_down"],
        c["first"] + jnp.arange(c["count"])))
    return out.reshape(x.shape)


def layer(p, x, c, quant=None):
    """One layer on ``x`` (b, s, hidden) -> (output, its indexer loss);
    ``p`` holds the layer's leaves under their short names."""
    attn, kl = sparse_attention(
        _sub(p, "self_attn."), _rms(x, p["input_layernorm.weight"], c["eps"]),
        c, quant)
    x = x + attn
    y = _rms(x, p["post_attention_layernorm.weight"], c["eps"])
    return x + experts(_sub(p, "mlp."), y, c, quant), kl


def head_loss_sum(norm_w, head_w, x, labels, eps, quant=None):
    """Sum over rows of the cross-entropy of the untied head on ``x``."""
    logits = _mm(_rms(x, norm_w, eps), head_w, quant)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def logits_fn(params, ids, cfg, quant=None):
    """Whole forward to ``(logits (b, s, vocab), the indexer losses'
    sum)``: for tests at small sizes."""
    c = sizes(cfg)
    p32 = _f32(params)
    x = p32["embed_tokens.weight"][ids]
    kl = 0.0
    for i in range(c["layers"]):
        x, kl_i = layer(_sub(p32, f"layers.{i}."), x, c, quant)
        kl = kl + kl_i
    return _mm(_rms(x, p32["norm.weight"], c["eps"]), p32["lm_head.weight"],
               quant), kl


# ---------------------------------------------------------------------------
# jitted pieces
# ---------------------------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("frozen", "quant"))
def _layer_fwd(p, x, frozen, quant):
    return layer(_f32(p), x, dict(frozen), quant)


@functools.partial(jax.jit, static_argnames=("frozen", "quant"))
def _layer_bwd(p, x, dy, frozen, quant):
    _, vjp = jax.vjp(lambda p_, x_: layer(p_, x_, dict(frozen), quant),
                     _f32(p), x)
    return vjp((dy, jnp.float32(1.0)))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_bwd(norm_w, head_w, x, labels, eps, quant):
    return jax.value_and_grad(head_loss_sum, argnums=(0, 1, 2))(
        norm_w.astype(jnp.float32), head_w.astype(jnp.float32), x, labels,
        eps, quant)


@jax.jit
def _embed(table, ids):
    return table.astype(jnp.float32)[ids]


@functools.partial(jax.jit, static_argnames=("rows",))
def _embed_bwd(ids, dx, rows):
    return jnp.zeros((rows, dx.shape[-1]), jnp.float32).at[ids].add(dx)


def grads_pass(params, ids, labels, cfg, consume, quant=None):
    """Loss of one batch (mean cross-entropy plus the layers' indexer
    losses), and every leaf's float32 gradient handed to ``consume(name,
    grad)`` as soon as it is complete, last layer first."""
    c, frozen = sizes(cfg), _frozen(cfg)
    b, s = ids.shape
    x = _embed(params["embed_tokens.weight"], ids)
    xs, kl = [], 0.0
    for i in range(c["layers"]):
        xs.append(x)
        x, kl_i = _layer_fwd(_sub(params, f"layers.{i}."), x, frozen, quant)
        kl = kl + kl_i
    n = b * s
    xf, lf = x.reshape(n, -1), labels.reshape(n)
    loss, d_norm, d_head, dxs = 0.0, None, None, []
    for lo in range(0, n, HEAD_ROWS):
        val, (gn, gh, gx) = _head_bwd(
            params["norm.weight"], params["lm_head.weight"],
            xf[lo:lo + HEAD_ROWS], lf[lo:lo + HEAD_ROWS], c["eps"], quant)
        loss = loss + val
        d_norm = gn if d_norm is None else d_norm + gn
        d_head = gh if d_head is None else d_head + gh
        dxs.append(gx)
    consume("norm.weight", d_norm / n)
    consume("lm_head.weight", d_head / n)
    dx = (jnp.concatenate(dxs) / n).reshape(b, s, -1)
    del dxs, x, xf, d_head
    for i in reversed(range(c["layers"])):
        gp, dx = _layer_bwd(_sub(params, f"layers.{i}."), xs.pop(), dx,
                            frozen, quant)
        for k, g in gp.items():
            consume(f"layers.{i}.{k}", g)
        del gp
    consume("embed_tokens.weight", _embed_bwd(ids, dx, c["vocab"]))
    return loss / n + kl


def train_readings(cfg, train, make_params, batches, quant=None, rows=None):
    """The check's readings over ``batches`` (``reference_steps.
    train_readings`` has the contract), by this file's gradients.  Where
    ``rows`` leaves no sequence of a batch (half of a batch of one), the
    second half of every sequence's tokens is left out instead."""
    if rows is not None and not len(range(*rows.indices(
            batches[0][0].shape[0]))):
        batches = [(ids[:, :ids.shape[1] // 2],
                    labels[:, :labels.shape[1] // 2])
                   for ids, labels in batches]
        rows = None
    return reference_steps.train_readings(
        lambda params, ids, labels, consume: grads_pass(
            params, ids, labels, cfg, consume, quant),
        leaf_segments(cfg), train, make_params, batches, rows)
