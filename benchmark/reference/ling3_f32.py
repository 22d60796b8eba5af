"""Plain reference for ``BailingHybridForCausalLM`` training: float32
``jax.numpy``, matmul precision "highest", no kernels, no chunks, written
from the layer equations that the public ``bailing_hybrid`` config's keys
name (Kimi Linear, arXiv:2510.26692, for the Kimi delta attention;
DeepSeek-V2, arXiv:2405.04434, for the latent attention; DeepSeek-V3,
arXiv:2412.19437, for the ``noaux_tc`` router).  It imports nothing of the
program and takes nothing the program made: weights and batches come from
the seed through ``benchmark/weights.py``; the steps that follow the
gradients are ``benchmark/reference_steps.py``.

``x`` is (b, s, hidden); layer: ``h = x + mixer(rms(x))``, ``y = h +
ffn(rms(h))``; layer ``l`` is latent attention (MLA) where ``(l + 1) %
layer_group_size == 0``, else Kimi delta attention (KDA); the first
``first_k_dense_replace`` layers have a dense SwiGLU MLP, the others the
experts; after the last layer a final RMSNorm and the untied head; mean
softmax cross-entropy.

*KDA.*  ``[q | k | v] = x W_qkv`` (heads x head_dim each), ``[f | gate] =
x W_fg`` (the same widths), ``b = x W_b`` (one a head).  ``[q | k | v]``
through a causal depthwise convolution (``y[t] = sum_j conv[:, j] x[t - (K
- 1) + j]``), then SiLU; q, k L2-normalised over the head dim (eps 1e-6),
q scaled by d^-1/2; ``beta = sigmoid(b)``; the log decay a head and key
channel ``g = kda_lower_bound * sigmoid(exp(A_log_h) (f + dt_bias))``, in
(-5, 0); per head, token by token, ``S' = diag(exp(g_t)) S``, ``r = v_t -
S'^T k_t``, ``S = S' + k_t (beta_t r)^T``, ``o_t = S^T q_t``; output ``w *
o / rms(o) * sigmoid(gate)`` per head, then ``W_o``.

*MLA.*  ``q = x W_q`` -> heads x (nope + rope); ``[c | k_rope] = x W_kva``
(``kv_lora_rank`` + rope); ``c = rms(c)``; ``[k_nope | v]`` a head ``= c
W_kvb``; rotary positions on ``q_rope`` of every head and on the one
``k_rope`` that all heads share, as interleaved pairs: ``(x_2i, x_2i+1)``
turned by ``t theta^(-2i / rope)``; ``k = [k_nope | k_rope]``; the full
causal softmax at scale (nope + rope)^-1/2 over explicit keys of that
width and values of ``v_head_dim`` (nothing padded); ``o_h * sigmoid(x
W_g)_h``, one gate a head; then ``W_o``.

*Experts.*  ``s = sigmoid(x W_r)`` over all ``num_experts_published``; for
the selection only ``s' = s + bias``; the experts stand as ``n_group``
groups, a group's score is the sum of its two largest ``s'``, the
``topk_group`` best groups stay, and of their experts the
``num_experts_per_tok`` with the largest ``s'``; weights ``s_e / sum of
the chosen s`` times ``routed_scaling_factor``.  All of it by sorting.
Expert ``e``: ``W_down(SiLU(W_gate x) * W_up x)``; only the experts
``experts_held = (first, count)`` are computed, each on every token under a
0/1 mask of the tokens that chose it (what the absent experts would add is
left out, as in the program); plus the shared expert, ungated.  Where
fewer experts are held than the router chooses among, the weights are
constants to the backward pass (``qwen3_next_f32.py`` says why); the bias
never has a gradient.

Departures from the published checkpoint, none of which changes the
function class: KDA's projections stand fused as blocks of columns ([q | k
| v], [f | gate]); every norm's stored scale is the whole scale; no MTP
module (its loss weight is published as 0).

Memory (b1 x s4096 beside 884 M parameters and their float32 moments): one
layer at a time, each layer's backward recomputing its forward; attention
in blocks of ``ATTN_ROWS`` query rows; the recurrence's backward keeps one
state per ``SCAN_SEGMENT`` tokens; the experts one at a time; the head
``HEAD_ROWS`` rows at a time.  ``quant="fp8"`` is the control of the
correctness check: what the configuration states as bfloat16 a precision
below it -- every matmul with a bfloat16 weight, attention's two and the
recurrence's reads and writes of its state on float8 operands -- and what
it states as float32 left float32: the router's matmul, sigmoid and
selection, the recurrence's state and decays.  ``rows`` plants the "half of
the batch left out" fault; a batch of one sequence loses the second half of
its tokens instead.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_steps

_mm = reference_steps.mm
_HI = jax.lax.Precision.HIGHEST
HEAD_ROWS = 2048     # rows of the vocabulary head computed at once
ATTN_ROWS = 512      # query rows of the softmax computed at once
SCAN_SEGMENT = 64    # tokens between two remembered states of the recurrence
L2_EPS = 1e-6


def sizes(cfg: dict) -> dict:
    """The numbers the equations need, from a configuration's file: there
    ``num_experts`` counts the experts held here and
    ``num_experts_published`` is the router's width."""
    first, count = cfg["experts_held"]
    assert count == cfg["num_experts"], "num_experts counts the experts held"
    return {
        "layers": cfg["num_hidden_layers"], "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "period": cfg["layer_group_size"],
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"], "head_dim": cfg["head_dim"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "latent": cfg["kv_lora_rank"],
        "theta": float(cfg["rope_theta"]),
        "taps": cfg["short_conv_kernel_size"],
        "floor": float(cfg["kda_lower_bound"]),
        "dense_width": cfg["intermediate_size"],
        "router": cfg["num_experts_published"], "first": first,
        "count": count, "topk": cfg["num_experts_per_tok"],
        "groups": cfg["n_group"], "groups_kept": cfg["topk_group"],
        "scaling": float(cfg["routed_scaling_factor"]),
        "width": cfg["moe_intermediate_size"],
        "shared": cfg["moe_shared_expert_intermediate_size"],
        "renorm": bool(cfg["norm_topk_prob"]), "eps": cfg["rms_norm_eps"]}


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted(sizes(cfg).items()))


def is_mla(c: dict, i: int) -> bool:
    return (i + 1) % c["period"] == 0


def is_dense(c: dict, i: int) -> bool:
    return i < c["dense_layers"]


def param_spec(cfg: dict) -> dict:
    """``{name: shape}`` under the names ``BailingHybridForCausalLM``
    gives."""
    c = sizes(cfg)
    h, nh, d = c["hidden"], c["heads"], c["head_dim"]
    spec = {"embed_tokens.weight": (c["vocab"], h)}
    for i in range(c["layers"]):
        p = f"layers.{i}."
        spec[p + "input_layernorm.weight"] = (h,)
        if is_mla(c, i):
            a = p + "self_attn."
            spec.update({
                a + "q_proj.weight": (h, nh * (c["nope"] + c["rope"])),
                a + "kv_a_proj.weight": (h, c["latent"] + c["rope"]),
                a + "kv_a_norm.weight": (c["latent"],),
                a + "kv_b_proj.weight":
                    (c["latent"], nh * (c["nope"] + c["v_dim"])),
                a + "g_proj.weight": (h, nh),
                a + "o_proj.weight": (nh * c["v_dim"], h)})
        else:
            a = p + "linear_attn."
            spec.update({
                a + "in_proj_qkv.weight": (h, 3 * nh * d),
                a + "in_proj_fg.weight": (h, 2 * nh * d),
                a + "in_proj_b.weight": (h, nh),
                a + "conv": (3 * nh * d, c["taps"]),
                a + "A_log": (nh,),
                a + "dt_bias": (nh * d,),
                a + "norm.weight": (d,),
                a + "o_proj.weight": (nh * d, h)})
        spec[p + "post_attention_layernorm.weight"] = (h,)
        m = p + "mlp."
        if is_dense(c, i):
            spec.update({
                m + "gate_up_proj.weight": (h, 2 * c["dense_width"]),
                m + "down_proj.weight": (c["dense_width"], h)})
        else:
            spec.update({
                m + "router.weight": (h, c["router"]),
                m + "router_bias": (c["router"],),
                m + "experts_gate_up": (c["count"], h, 2 * c["width"]),
                m + "experts_down": (c["count"], c["width"], h),
                m + "shared_gate_up.weight": (h, 2 * c["shared"]),
                m + "shared_down.weight": (c["shared"], h)})
    spec.update({"norm.weight": (h,), "lm_head.weight": (h, c["vocab"])})
    return spec


def leaf_segments(cfg: dict) -> dict:
    """The fused projections, by their blocks: [q | k | v], [f | gate],
    [gate | up]; the latent projection [c | k_rope] in parts of the rotary
    width (the last one is ``k_rope``)."""
    c = sizes(cfg)
    out = {}
    for i in range(c["layers"]):
        p = f"layers.{i}."
        if is_mla(c, i):
            out[p + "self_attn.kv_a_proj.weight"] = \
                (c["latent"] + c["rope"]) // math.gcd(c["latent"], c["rope"])
        else:
            out[p + "linear_attn.in_proj_qkv.weight"] = 3
            out[p + "linear_attn.in_proj_fg.weight"] = 2
        if is_dense(c, i):
            out[p + "mlp.gate_up_proj.weight"] = 2
        else:
            out[p + "mlp.experts_gate_up"] = 2
            out[p + "mlp.shared_gate_up.weight"] = 2
    return out


# ---------------------------------------------------------------------------
# the equations
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def causal_conv(x, taps):
    """``y[t] = sum_j taps[:, j] x[t - (K - 1) + j]``, ``x[t < 0] = 0``."""
    k, s = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[:, j] for j in range(k))


def _token(state, x, quant=None):
    """One token of the recurrence, all heads: ``state`` (b, h, dk, dv),
    ``g`` (b, h, dk).  The control (``quant="fp8"``) rounds what a chunked
    form feeds its matmuls -- the state read, the key, the query, the
    written value -- to float8; the state and the decay stay float32."""
    low = reference_steps.fp8 if quant == "fp8" else (lambda a: a)
    q, k, v, g, beta = x
    k, q = low(k), low(q)
    state = state * jnp.exp(g)[..., None]
    r = v - jnp.einsum("bhkv,bhk->bhv", low(state), k, precision=_HI)
    state = state + k[..., :, None] * low(beta[..., None] * r)[..., None, :]
    return state, jnp.einsum("bhkv,bhk->bhv", low(state), q, precision=_HI)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _segment(state, xs, quant):
    return jax.lax.scan(functools.partial(_token, quant=quant), state, xs)


def delta_rule(q, k, v, g, beta, quant=None):
    """The delta rule with a decay a key channel, token by token: ``q``,
    ``k``, ``g`` (b, s, h, dk), ``v`` (b, s, h, dv), ``beta`` (b, s, h) ->
    ``o`` (b, s, h, dv).  A ``lax.scan`` over t, the decay applied as
    ``exp(g_t)`` a step; its backward remembers the state every
    ``SCAN_SEGMENT`` tokens (the tail padded with tokens that neither
    decay nor write)."""
    b, s, h, dk = q.shape
    pad = -s % SCAN_SEGMENT
    n = (s + pad) // SCAN_SEGMENT

    def by_segment(x):   # (b, s, h, ...) -> (n, segment, b, h, ...)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((n, SCAN_SEGMENT) + x.shape[1:])

    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(lambda st, xs: _segment(st, xs, quant), state,
                        tuple(map(by_segment, (q, k, v, g, beta))))
    o = o.reshape((n * SCAN_SEGMENT,) + o.shape[2:])
    return jnp.moveaxis(o, 0, 1)[:, :s]


def log_decay(f, a_log, dt_bias, floor):
    """``floor * sigmoid(exp(A_log_h) (f + dt_bias))``: ``f`` (b, s, h, d),
    ``a_log`` (h,), ``dt_bias`` (h * d,)."""
    h, d = f.shape[-2:]
    return floor * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (f + dt_bias.reshape(h, d)))


def kda(p, x, c, quant=None):
    """The Kimi delta attention mixer on the normed ``x`` (b, s, hidden)."""
    b, s, _ = x.shape
    nh, d = c["heads"], c["head_dim"]
    w = nh * d
    qkv = _mm(x, p["in_proj_qkv.weight"], quant)
    fg = _mm(x, p["in_proj_fg.weight"], quant)
    mixed = jax.nn.silu(causal_conv(qkv, p["conv"]))
    q = _l2(mixed[..., :w].reshape(b, s, nh, d)) * d ** -0.5
    k = _l2(mixed[..., w:2 * w].reshape(b, s, nh, d))
    v = mixed[..., 2 * w:].reshape(b, s, nh, d)
    beta = jax.nn.sigmoid(_mm(x, p["in_proj_b.weight"], quant))
    g = log_decay(fg[..., :w].reshape(b, s, nh, d), p["A_log"],
                  p["dt_bias"], c["floor"])
    o = delta_rule(q, k, v, g, beta, quant)
    gate = fg[..., w:].reshape(b, s, nh, d)
    o = _rms(o, p["norm.weight"], c["eps"]) * jax.nn.sigmoid(gate)
    return _mm(o.reshape(b, s, w), p["o_proj.weight"], quant)


def rotary_pairs(x, theta):
    """Rotary positions on the whole last axis of ``x`` (b, s, heads, r) as
    interleaved pairs: ``(x_2i, x_2i+1)`` turned by ``t theta^(-2i/r)``."""
    s, r = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _softmax_rows(q, k, v, row0, quant):
    """Causal softmax attention of the query rows ``row0 ...`` against all
    keys: ``q`` (b, h, r, dq), ``k`` (b, h, s, dq), ``v`` (b, h, s, dv)."""
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) / math.sqrt(q.shape[-1])
    rows = row0 + jnp.arange(q.shape[2])[:, None]
    cols = jnp.arange(k.shape[2])[None, :]
    probs = jax.nn.softmax(jnp.where(cols <= rows, scores, -jnp.inf), -1)
    return _mm(probs, v, quant)


def mla(p, x, c, quant=None):
    """The latent attention mixer on the normed ``x`` (b, s, hidden)."""
    b, s, _ = x.shape
    nh, nope, rope, dv = c["heads"], c["nope"], c["rope"], c["v_dim"]
    q = _mm(x, p["q_proj.weight"], quant).reshape(b, s, nh, nope + rope)
    kva = _mm(x, p["kv_a_proj.weight"], quant)
    latent = _rms(kva[..., :c["latent"]], p["kv_a_norm.weight"], c["eps"])
    kvb = _mm(latent, p["kv_b_proj.weight"], quant).reshape(
        b, s, nh, nope + dv)
    k_rope = rotary_pairs(kva[..., c["latent"]:][:, :, None, :], c["theta"])
    q = jnp.concatenate(
        [q[..., :nope], rotary_pairs(q[..., nope:], c["theta"])], -1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope, (b, s, nh, rope))], -1)
    v = kvb[..., nope:]
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))    # b h s d
    rows = jax.checkpoint(_softmax_rows, static_argnums=(3, 4))
    ctx = jnp.concatenate([
        rows(q[:, :, r0:r0 + ATTN_ROWS], k, v, r0, quant)
        for r0 in range(0, s, ATTN_ROWS)], 2)
    ctx = jnp.swapaxes(ctx, 1, 2)                            # b s h dv
    gate = jax.nn.sigmoid(_mm(x, p["g_proj.weight"], quant))
    return _mm((ctx * gate[..., None]).reshape(b, s, nh * dv),
               p["o_proj.weight"], quant)


def grouped_choice(scores, bias, c):
    """``(weights (n, k), experts (n, k))`` from the unbiased ``scores`` (n,
    experts): the selection by ``scores + bias``, every step a sort."""
    n, experts = scores.shape
    biased = scores + bias
    per_group = experts // c["groups"]
    grouped = biased.reshape(n, c["groups"], per_group)
    group_score = jnp.sum(jnp.sort(grouped, -1)[..., -2:], -1)
    kept = jnp.argsort(-group_score, -1)[:, :c["groups_kept"]]
    group_open = jnp.any(
        kept[:, :, None] == jnp.arange(c["groups"])[None, None, :], 1)
    masked = jnp.where(jnp.repeat(group_open, per_group, -1), biased,
                       -jnp.inf)
    idx = jnp.argsort(-masked, -1)[:, :c["topk"]]
    vals = jnp.take_along_axis(scores, idx, -1)
    if c["renorm"]:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    return vals * c["scaling"], idx


def router_choice(p, x, c):
    """The tokens' ``x`` (n, hidden) choice: float32 matmul and sigmoid
    over all experts (under the control too: the configuration states them
    float32); constants to the backward pass where only a part of the
    experts is held."""
    scores = jax.nn.sigmoid(_mm(x, p["router.weight"], None))
    vals, idx = grouped_choice(scores, p["router_bias"], c)
    if c["count"] < p["router.weight"].shape[1]:
        vals = jax.lax.stop_gradient(vals)
    return vals, idx


def swiglu(x, w_gate_up, w_down, quant):
    h = _mm(x, w_gate_up, quant)
    half = h.shape[-1] // 2
    return _mm(jax.nn.silu(h[..., :half]) * h[..., half:], w_down, quant)


def experts(p, x, c, quant=None, shared=True):
    """The expert part on the normed ``x`` (b, s, hidden): the held
    experts' share of the routed sum, plus (``shared``) the shared
    expert."""
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
    if shared:
        out = out + swiglu(tokens, p["shared_gate_up.weight"],
                           p["shared_down.weight"], quant)
    return out.reshape(x.shape)


def layer(p, x, c, quant=None):
    """One layer on ``x`` (b, s, hidden); ``p`` holds the layer's leaves
    under their short names; its kinds are told by the leaves it has."""
    y = _rms(x, p["input_layernorm.weight"], c["eps"])
    if "self_attn.q_proj.weight" in p:
        x = x + mla(_sub(p, "self_attn."), y, c, quant)
    else:
        x = x + kda(_sub(p, "linear_attn."), y, c, quant)
    y = _rms(x, p["post_attention_layernorm.weight"], c["eps"])
    if "mlp.router.weight" in p:
        return x + experts(_sub(p, "mlp."), y, c, quant)
    return x + swiglu(y, p["mlp.gate_up_proj.weight"],
                      p["mlp.down_proj.weight"], quant)


def head_loss_sum(norm_w, head_w, x, labels, eps, quant=None):
    """Sum over rows of the cross-entropy of the untied head on ``x``."""
    logits = _mm(_rms(x, norm_w, eps), head_w, quant)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def logits_fn(params, ids, cfg, quant=None):
    """Whole forward to the logits (b, s, vocab): for tests at small sizes."""
    c = sizes(cfg)
    p32 = _f32(params)
    x = p32["embed_tokens.weight"][ids]
    for i in range(c["layers"]):
        x = layer(_sub(p32, f"layers.{i}."), x, c, quant)
    return _mm(_rms(x, p32["norm.weight"], c["eps"]), p32["lm_head.weight"],
               quant)


# ---------------------------------------------------------------------------
# jitted pieces (one program per kind of layer)
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
    return vjp(dy)


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
    """Loss of one batch, and every leaf's float32 gradient handed to
    ``consume(name, grad)`` as soon as it is complete, last layer first."""
    c, frozen = sizes(cfg), _frozen(cfg)
    b, s = ids.shape
    x = _embed(params["embed_tokens.weight"], ids)
    xs = []
    for i in range(c["layers"]):
        xs.append(x)
        x = _layer_fwd(_sub(params, f"layers.{i}."), x, frozen, quant)
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
    return loss / n


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
