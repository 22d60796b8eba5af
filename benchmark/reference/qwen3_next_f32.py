"""Plain reference for ``Qwen3NextForCausalLM`` training: float32
``jax.numpy``, matmul precision "highest", no kernels, no chunks, written
from the layer equations of the public ``qwen3_next`` config and the
family's published description.  It imports nothing of the program and
takes nothing the program made: weights and batches come from the seed
through ``benchmark/weights.py``; the steps that follow the gradients are
``benchmark/reference_steps.py``.

``x`` is (b, s, hidden); layer: ``h = x + mixer(rms(x))``, ``y = h +
moe(rms(h))``; layer ``l`` is gated attention where ``(l + 1) %
full_attention_interval == 0``, else a Gated DeltaNet; after the last
layer a final RMSNorm and the untied head; mean softmax cross-entropy.

*Gated DeltaNet.*  ``[q | k | v | z] = x W_qkvz``, ``[b | a] = x W_ba``;
``[q | k | v]`` through a causal depthwise convolution (``y[t] = sum_j
conv[:, j] x[t - (K - 1) + j]``), then SiLU; q, k L2-normalised over the
head dim (eps 1e-6), each key head repeated for its value heads, q scaled
by dk^-1/2; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)``; per head, token by token, ``S' = exp(g_t) S``, ``r = v_t -
S'^T k_t``, ``S = S' + k_t (beta_t r)^T``, ``o_t = S^T q_t``; output ``w *
o / rms(o) * SiLU(z)`` per head, then ``W_out``.

*Gated attention.*  ``[q | gate] = x W_q``, ``k = x W_k``, ``v = x W_v``;
RMSNorm of q and of k per head; rotate-half rotary positions (theta
``rope_theta``) on the first ``partial_rotary_factor`` of each head's
dims; the full causal softmax at scale d^-1/2, query head ``h`` on KV head
``h // (heads / kv heads)``; ``attn * sigmoid(gate)``, then ``W_o``.

*Experts.*  ``p = softmax(x W_r)`` over all ``num_experts_published``; the
``num_experts_per_tok`` largest, renormalised to sum 1; expert ``e``:
``W_down(SiLU(W_gate x) * W_up x)``; only the experts ``experts_held =
(first, count)`` are computed, each on every token under a 0/1 mask of the
tokens that chose it (what the absent experts would add is left out, as in
the program); plus the shared expert times ``sigmoid(w_s . x)``.  Where
fewer experts are held than the router chooses among, the k weights are
constants to the backward pass (``stop_gradient``): a router's gradient is
made of all k returns of a token, the held part of it alone is a pull
towards the held experts, and the router's weight gets none.

Departures from the published checkpoint, none of which changes the
function class: the fused projections' columns stand as blocks ([q | k | v |
z], [b | a], [q | gate], [gate | up]) where the checkpoint interleaves them
per head; every norm's stored scale is the whole scale ``w`` (the
checkpoint stores ``w - 1`` for the layer norms); no MTP module.

Memory (b2 x s4096 beside 625 M parameters and their float32 moments): one
layer at a time, each layer's backward recomputing its forward; attention
in blocks of ``ATTN_ROWS`` query rows; the recurrence's backward keeps one
state per ``SCAN_SEGMENT`` tokens and recomputes inside a segment (the
same token-by-token recurrence, remembered more sparsely); the experts one
at a time, each recomputed in the backward; the head ``HEAD_ROWS`` rows at
a time.  ``quant="fp8"`` is the control of the correctness check: what
the configuration states as bfloat16 a precision below it -- every matmul
with a bfloat16 weight, attention's two and the recurrence's reads and
writes of its state on float8 operands -- and what it states as float32
left float32: the router's matmul and softmax, the shared expert's
one-column gate, the recurrence's state and decays.  ``rows`` plants the
"half of the batch left out" fault.
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
        "vocab": cfg["vocab_size"], "interval": cfg["full_attention_interval"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "rotary_dim": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "theta": float(cfg["rope_theta"]),
        "taps": cfg["linear_conv_kernel_dim"],
        "key_heads": cfg["linear_num_key_heads"],
        "value_heads": cfg["linear_num_value_heads"],
        "key_dim": cfg["linear_key_head_dim"],
        "value_dim": cfg["linear_value_head_dim"],
        "router": cfg["num_experts_published"], "first": first,
        "count": count, "topk": cfg["num_experts_per_tok"],
        "width": cfg["moe_intermediate_size"],
        "shared": cfg["shared_expert_intermediate_size"],
        "renorm": bool(cfg["norm_topk_prob"]), "eps": cfg["rms_norm_eps"]}


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted(sizes(cfg).items()))


def is_attention(c: dict, i: int) -> bool:
    return (i + 1) % c["interval"] == 0


def param_spec(cfg: dict) -> dict:
    """``{name: shape}`` under the names ``Qwen3NextForCausalLM`` gives."""
    c = sizes(cfg)
    h = c["hidden"]
    kq, vz = c["key_heads"] * c["key_dim"], c["value_heads"] * c["value_dim"]
    width = c["heads"] * c["head_dim"]
    spec = {"embed_tokens.weight": (c["vocab"], h)}
    for i in range(c["layers"]):
        p = f"layers.{i}."
        spec[p + "input_layernorm.weight"] = (h,)
        if is_attention(c, i):
            a = p + "self_attn."
            spec.update({
                a + "q_proj.weight": (h, 2 * width),
                a + "k_proj.weight": (h, c["kv_heads"] * c["head_dim"]),
                a + "v_proj.weight": (h, c["kv_heads"] * c["head_dim"]),
                a + "q_norm.weight": (c["head_dim"],),
                a + "k_norm.weight": (c["head_dim"],),
                a + "o_proj.weight": (width, h)})
        else:
            a = p + "linear_attn."
            spec.update({
                a + "in_proj_qkvz.weight": (h, 2 * kq + 2 * vz),
                a + "in_proj_ba.weight": (h, 2 * c["value_heads"]),
                a + "conv": (2 * kq + vz, c["taps"]),
                a + "A_log": (c["value_heads"],),
                a + "dt_bias": (c["value_heads"],),
                a + "norm.weight": (c["value_dim"],),
                a + "out_proj.weight": (vz, h)})
        m = p + "mlp."
        spec.update({
            p + "post_attention_layernorm.weight": (h,),
            m + "router.weight": (h, c["router"]),
            m + "experts_gate_up": (c["count"], h, 2 * c["width"]),
            m + "experts_down": (c["count"], c["width"], h),
            m + "shared_gate_up.weight": (h, 2 * c["shared"]),
            m + "shared_down.weight": (c["shared"], h),
            m + "shared_gate.weight": (h, 1)})
    spec.update({"norm.weight": (h,), "lm_head.weight": (h, c["vocab"])})
    return spec


def leaf_segments(cfg: dict) -> dict:
    """The fused projections, by their blocks: [q | k | v | z] in equal
    parts of the widths' common measure (q, k one part each and v, z two
    at the published sizes), [b | a], [q | gate], [gate | up]."""
    c = sizes(cfg)
    kq, vz = c["key_heads"] * c["key_dim"], c["value_heads"] * c["value_dim"]
    out = {}
    for i in range(c["layers"]):
        p = f"layers.{i}."
        if is_attention(c, i):
            out[p + "self_attn.q_proj.weight"] = 2
        else:
            out[p + "linear_attn.in_proj_qkvz.weight"] = \
                (2 * kq + 2 * vz) // math.gcd(kq, vz)
            out[p + "linear_attn.in_proj_ba.weight"] = 2
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
    """One token of the recurrence, all heads: ``state`` (b, h, dk, dv).
    The control (``quant="fp8"``) rounds what a chunked form feeds its
    matmuls -- the state read, the key, the query, the written value --
    to float8; the state itself stays float32."""
    low = reference_steps.fp8 if quant == "fp8" else (lambda a: a)
    q, k, v, g, beta = x
    k, q = low(k), low(q)
    state = state * jnp.exp(g)[..., None, None]
    r = v - jnp.einsum("bhkv,bhk->bhv", low(state), k, precision=_HI)
    state = state + k[..., :, None] * low(beta[..., None] * r)[..., None, :]
    return state, jnp.einsum("bhkv,bhk->bhv", low(state), q, precision=_HI)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _segment(state, xs, quant):
    return jax.lax.scan(functools.partial(_token, quant=quant), state, xs)


def delta_rule(q, k, v, g, beta, quant=None):
    """The gated delta rule token by token: ``q``, ``k`` (b, s, h, dk),
    ``v`` (b, s, h, dv), ``g``, ``beta`` (b, s, h) -> ``o`` (b, s, h, dv).
    A ``lax.scan`` over t; its backward remembers the state every
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


def gated_delta_net(p, x, c, quant=None):
    """The DeltaNet mixer on the normed ``x`` (b, s, hidden)."""
    b, s, _ = x.shape
    hk, hv, dk, dv = (c["key_heads"], c["value_heads"], c["key_dim"],
                      c["value_dim"])
    kq, vz = hk * dk, hv * dv
    qkvz = _mm(x, p["in_proj_qkvz.weight"], quant)
    ba = _mm(x, p["in_proj_ba.weight"], quant)
    mixed = jax.nn.silu(causal_conv(qkvz[..., :2 * kq + vz], p["conv"]))
    q = _l2(mixed[..., :kq].reshape(b, s, hk, dk)) * dk ** -0.5
    k = _l2(mixed[..., kq:2 * kq].reshape(b, s, hk, dk))
    v = mixed[..., 2 * kq:].reshape(b, s, hv, dv)
    z = qkvz[..., 2 * kq + vz:].reshape(b, s, hv, dv)
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta, quant)
    o = _rms(o, p["norm.weight"], c["eps"]) * jax.nn.silu(z)
    return _mm(o.reshape(b, s, vz), p["out_proj.weight"], quant)


def rotary(x, rotary_dim, theta):
    """Rotate-half rotary positions on the first ``rotary_dim`` of the last
    axis of ``x`` (b, s, heads, d): with ``x = [x1 | x2 | rest]``, halves of
    the rotated part, ``[x1 cos - x2 sin | x2 cos + x1 sin | rest]``."""
    s, half = x.shape[1], rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / rotary_dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _softmax_rows(q, k, v, row0, quant):
    """Causal softmax attention of the query rows ``row0 ...`` against all
    keys: ``q`` (b, h, r, d), ``k``, ``v`` (b, h, s, d)."""
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) / math.sqrt(q.shape[-1])
    rows = row0 + jnp.arange(q.shape[2])[:, None]
    cols = jnp.arange(k.shape[2])[None, :]
    probs = jax.nn.softmax(jnp.where(cols <= rows, scores, -jnp.inf), -1)
    return _mm(probs, v, quant)


def gated_attention(p, x, c, quant=None):
    """The gated attention mixer on the normed ``x`` (b, s, hidden)."""
    b, s, _ = x.shape
    nh, nkv, d = c["heads"], c["kv_heads"], c["head_dim"]
    q_gate = _mm(x, p["q_proj.weight"], quant)
    q = q_gate[..., :nh * d].reshape(b, s, nh, d)
    gate = q_gate[..., nh * d:]
    k = _mm(x, p["k_proj.weight"], quant).reshape(b, s, nkv, d)
    v = _mm(x, p["v_proj.weight"], quant).reshape(b, s, nkv, d)
    q = rotary(_rms(q, p["q_norm.weight"], c["eps"]), c["rotary_dim"],
               c["theta"])
    k = rotary(_rms(k, p["k_norm.weight"], c["eps"]), c["rotary_dim"],
               c["theta"])
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))    # b h s d
    rows = jax.checkpoint(_softmax_rows, static_argnums=(3, 4))
    ctx = jnp.concatenate([
        rows(q[:, :, r0:r0 + ATTN_ROWS], k, v, r0, quant)
        for r0 in range(0, s, ATTN_ROWS)], 2)
    ctx = jnp.swapaxes(ctx, 1, 2).reshape(b, s, nh * d)
    return _mm(ctx * jax.nn.sigmoid(gate), p["o_proj.weight"], quant)


def router_choice(p, x, c):
    """``(weights (n, k), experts (n, k))`` of the tokens ``x`` (n, hidden):
    float32 matmul and softmax over all experts (under the control too:
    the configuration states them float32), the k largest, renormalised;
    constants to the backward pass where only a part of the experts is
    held."""
    probs = jax.nn.softmax(_mm(x, p["router.weight"], None), -1)
    vals, idx = jax.lax.top_k(probs, c["topk"])
    if c["renorm"]:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
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
                           p["shared_down.weight"], quant) \
            * jax.nn.sigmoid(_mm(tokens, p["shared_gate.weight"], None))
    return out.reshape(x.shape)


def layer(p, x, c, quant=None):
    """One layer on ``x`` (b, s, hidden); ``p`` holds the layer's leaves
    under their short names; its kind is told by the leaves it has."""
    y = _rms(x, p["input_layernorm.weight"], c["eps"])
    if "self_attn.q_proj.weight" in p:
        x = x + gated_attention(_sub(p, "self_attn."), y, c, quant)
    else:
        x = x + gated_delta_net(_sub(p, "linear_attn."), y, c, quant)
    y = _rms(x, p["post_attention_layernorm.weight"], c["eps"])
    return x + experts(_sub(p, "mlp."), y, c, quant)


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


def routing_choices(params, ids, cfg):
    """Per layer the experts (b * s, k) each token chooses: for the
    reading of how many choices flip between program and reference."""
    c = sizes(cfg)
    p32 = _f32(params)
    x = p32["embed_tokens.weight"][ids]
    out = []
    for i in range(c["layers"]):
        p = _sub(p32, f"layers.{i}.")
        y = _rms(x, p["input_layernorm.weight"], c["eps"])
        mixer = gated_attention if is_attention(c, i) else gated_delta_net
        h = x + mixer(_sub(p, "self_attn." if is_attention(c, i)
                           else "linear_attn."), y, c)
        y = _rms(h, p["post_attention_layernorm.weight"], c["eps"])
        out.append(router_choice(_sub(p, "mlp."),
                                 y.reshape(-1, y.shape[-1]), c)[1])
        x = h + experts(_sub(p, "mlp."), y, c)
    return out


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
    train_readings`` has the contract), by this file's gradients."""
    return reference_steps.train_readings(
        lambda params, ids, labels, consume: grads_pass(
            params, ids, labels, cfg, consume, quant),
        leaf_segments(cfg), train, make_params, batches, rows)
