"""Plain reference for ``GPTForCausalLM`` training: float32 ``jax.numpy``,
matmul precision "highest", no kernels, written from the GPT-2/3 equations
(pre-LN blocks, learned positions, fused qkv in [q | k | v] column order,
tanh GELU, tied head, mean softmax cross-entropy).  It imports nothing of
the program and takes nothing the program made: weights and batches come
from the seed through ``benchmark/weights.py``.  The steps that follow the
gradients (global grad-norm clip, bias-corrected Adam at the
configuration's stated storage) and the control's float8 matmul are
``benchmark/reference_steps.py``, shared with every other architecture's
reference.

Memory: one block at a time, and per step two passes over the gradients
(``reference_steps.train_readings``).  ``quant="fp8"`` is the control of
the correctness check; ``rows`` (a slice of the batch) plants the "half of
the batch left out" fault in the reference put in the program's place.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_steps

_mm = reference_steps.mm
HEAD_ROWS = 2048  # rows of the vocabulary head computed at once


def param_spec(cfg: dict) -> dict:
    """``{name: shape}`` of the parameter tree, under the names the
    program's ``GPTForCausalLM`` gives them (the driver checks that the
    program's state has exactly these)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    f = cfg.get("intermediate_size") or 4 * h
    spec = {"gpt.wte.weight": (v, h),
            "gpt.wpe.weight": (cfg["max_position_embeddings"], h)}
    for i in range(cfg["num_layers"]):
        p = f"gpt.blocks.{i}."
        spec.update({
            p + "ln_1.weight": (h,), p + "ln_1.bias": (h,),
            p + "attn.qkv_proj.weight": (h, 3 * h),
            p + "attn.qkv_proj.bias": (3 * h,),
            p + "attn.out_proj.weight": (h, h),
            p + "attn.out_proj.bias": (h,),
            p + "ln_2.weight": (h,), p + "ln_2.bias": (h,),
            p + "mlp.fc_in.weight": (h, f), p + "mlp.fc_in.bias": (f,),
            p + "mlp.fc_out.weight": (f, h), p + "mlp.fc_out.bias": (h,)})
    spec.update({"gpt.ln_f.weight": (h,), "gpt.ln_f.bias": (h,)})
    return spec


def leaf_segments(cfg: dict) -> dict:
    """Leaves that store several logical parameters side by side along
    their last axis, and how many: the fused qkv projection is [q | k | v].
    The check takes norms per segment, because the key's bias has no
    gradient under softmax and Adam moves it by round-off alone, while the
    query's and the value's, in the same stored leaf, move in earnest."""
    return {f"gpt.blocks.{i}.attn.qkv_proj.{w}": 3
            for i in range(cfg["num_layers"]) for w in ("weight", "bias")}


# ---------------------------------------------------------------------------
# the equations
# ---------------------------------------------------------------------------

def _ln(x, w, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, heads, quant=None):
    """One pre-LN block on ``x`` (B, S, H); ``p`` holds the block's leaves
    under their short names (``ln_1.weight`` ...)."""
    b, s, h = x.shape
    d = h // heads
    y = _ln(x, p["ln_1.weight"], p["ln_1.bias"])
    qkv = _mm(y, p["attn.qkv_proj.weight"], quant) + p["attn.qkv_proj.bias"]
    q, k, v = (qkv[..., i * h:(i + 1) * h].reshape(b, s, heads, d)
               .transpose(0, 2, 1, 3) for i in range(3))
    scores = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    ctx = _mm(probs, v, quant).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + _mm(ctx, p["attn.out_proj.weight"], quant) \
        + p["attn.out_proj.bias"]
    y = _ln(x, p["ln_2.weight"], p["ln_2.bias"])
    y = _gelu_tanh(_mm(y, p["mlp.fc_in.weight"], quant)
                   + p["mlp.fc_in.bias"])
    return x + _mm(y, p["mlp.fc_out.weight"], quant) + p["mlp.fc_out.bias"]


def head_loss_sum(lnw, lnb, wte, x, labels, quant=None):
    """Sum over rows of the cross-entropy of the tied head on ``x`` (N, H)."""
    logits = _mm(_ln(x, lnw, lnb), wte.T, quant)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - picked)


def logits_fn(params, ids, cfg, quant=None):
    """Whole forward to the logits (B, S, V): for tests at small sizes."""
    p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    x = p32["gpt.wte.weight"][ids] + p32["gpt.wpe.weight"][:ids.shape[1]]
    for i in range(cfg["num_layers"]):
        x = block(_block_leaves(p32, i), x, cfg["num_heads"], quant)
    return _mm(_ln(x, p32["gpt.ln_f.weight"], p32["gpt.ln_f.bias"]),
               p32["gpt.wte.weight"].T, quant)


def _block_leaves(params, i):
    pre = f"gpt.blocks.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


# ---------------------------------------------------------------------------
# jitted pieces (one program per distinct shape; the blocks share one)
# ---------------------------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def _block_fwd(p, x, heads, quant):
    return block(_f32(p), x, heads, quant)


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def _block_bwd(p, x, dy, heads, quant):
    _, vjp = jax.vjp(lambda p_, x_: block(p_, x_, heads, quant), _f32(p), x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head_bwd(lnw, lnb, wte, x, labels, quant):
    return jax.value_and_grad(head_loss_sum, argnums=(0, 1, 2, 3))(
        lnw.astype(jnp.float32), lnb.astype(jnp.float32),
        wte.astype(jnp.float32), x, labels, quant)


@jax.jit
def _embed(wte, wpe, ids):
    return wte.astype(jnp.float32)[ids] \
        + wpe.astype(jnp.float32)[:ids.shape[1]]


@jax.jit
def _embed_bwd(dwte, ids, dx):
    """Adds the embedding's share to the head's ``dwte``; returns it with
    the positions' gradient (rows past the sequence get none)."""
    return dwte.at[ids].add(dx), jnp.sum(dx, 0)


def grads_pass(params, ids, labels, cfg, consume, quant=None):
    """Loss of one batch, and every leaf's float32 gradient handed to
    ``consume(name, grad)`` as soon as it is complete, last block first."""
    heads, n_layers = cfg["num_heads"], cfg["num_layers"]
    b, s = ids.shape
    x = _embed(params["gpt.wte.weight"], params["gpt.wpe.weight"], ids)
    xs = []
    for i in range(n_layers):
        xs.append(x)
        x = _block_fwd(_block_leaves(params, i), x, heads, quant)
    # tied head and loss, HEAD_ROWS rows at a time
    n = b * s
    xf, lf = x.reshape(n, -1), labels.reshape(n)
    loss = 0.0
    dlnw = dlnb = dwte = None
    dxs = []
    for lo in range(0, n, HEAD_ROWS):
        val, (gw, gb, ge, gx) = _head_bwd(
            params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
            params["gpt.wte.weight"], xf[lo:lo + HEAD_ROWS],
            lf[lo:lo + HEAD_ROWS], quant)
        loss = loss + val
        dlnw = gw if dlnw is None else dlnw + gw
        dlnb = gb if dlnb is None else dlnb + gb
        dwte = ge if dwte is None else dwte + ge
        dxs.append(gx)
    consume("gpt.ln_f.weight", dlnw / n)
    consume("gpt.ln_f.bias", dlnb / n)
    dx = (jnp.concatenate(dxs) / n).reshape(b, s, -1)
    del dxs, x, xf
    for i in reversed(range(n_layers)):
        gp, dx = _block_bwd(_block_leaves(params, i), xs.pop(), dx,
                            heads, quant)
        for k, g in gp.items():
            consume(f"gpt.blocks.{i}.{k}", g)
        del gp
    dwte, dpos = _embed_bwd(dwte / n, ids, dx)
    consume("gpt.wte.weight", dwte)
    wpe_rows = params["gpt.wpe.weight"].shape[0]
    consume("gpt.wpe.weight", jnp.pad(dpos, ((0, wpe_rows - s), (0, 0))))
    return loss / n


def train_readings(cfg, train, make_params, batches, quant=None, rows=None):
    """The check's readings over ``batches`` (``reference_steps.
    train_readings`` has the contract), by this file's gradients."""
    return reference_steps.train_readings(
        lambda params, ids, labels, consume: grads_pass(
            params, ids, labels, cfg, consume, quant),
        leaf_segments(cfg), train, make_params, batches, rows)
