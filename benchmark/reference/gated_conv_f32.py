"""Plain reference for the rehearsal's gated-convolution language model
(``tests/benchmark_checks/gated_conv_lm.py``): float32 ``jax.numpy``,
matmul precision "highest", written from its equations.  Per block

    u = rmsnorm(x) * w_norm;   [a | g] = u W_in
    c[t] = sum_j conv[j] * a[t - j],  j < conv_kernel, a[t < 0] = 0
    x = x + (c * silu(g)) W_out

then ``logits = (rmsnorm(x) * w_f) W_head`` (an untied head, no positions)
and the mean softmax cross-entropy.  A toy needs no block-by-block pass:
the gradient is ``jax.grad`` of the whole loss.  The steps that follow it
are ``benchmark/reference_steps.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import reference_steps

_mm = reference_steps.mm
RMS_EPS = 1e-6


def param_spec(cfg: dict) -> dict:
    """``{name: shape}`` under the names the program's model gives."""
    h, d = cfg["hidden_size"], cfg["inner_size"]
    spec = {"embed.weight": (cfg["vocab_size"], h)}
    for i in range(cfg["num_layers"]):
        p = f"blocks.{i}."
        spec.update({p + "norm.weight": (h,),
                     p + "in_proj.weight": (h, 2 * d),
                     p + "conv": (cfg["conv_kernel"], d),
                     p + "out_proj.weight": (d, h)})
    spec.update({"norm_f.weight": (h,), "head.weight": (h, cfg["vocab_size"])})
    return spec


def leaf_segments(cfg: dict) -> dict:
    """The fused input projection stores [a | g] side by side."""
    return {f"blocks.{i}.in_proj.weight": 2 for i in range(cfg["num_layers"])}


def _rms(x, w):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + RMS_EPS) * w


def logits_fn(params, ids, cfg, quant=None):
    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    d, taps, s = cfg["inner_size"], cfg["conv_kernel"], ids.shape[1]
    x = p["embed.weight"][ids]
    for i in range(cfg["num_layers"]):
        b = f"blocks.{i}."
        ag = _mm(_rms(x, p[b + "norm.weight"]), p[b + "in_proj.weight"], quant)
        a = jnp.pad(ag[..., :d], ((0, 0), (taps - 1, 0), (0, 0)))
        c = sum(a[:, taps - 1 - j:taps - 1 - j + s] * p[b + "conv"][j]
                for j in range(taps))
        x = x + _mm(c * jax.nn.silu(ag[..., d:]), p[b + "out_proj.weight"],
                    quant)
    return _mm(_rms(x, p["norm_f.weight"]), p["head.weight"], quant)


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def _loss_and_grads(params, ids, labels, sizes, quant):
    def loss(p):
        logits = logits_fn(p, ids, dict(sizes), quant)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)
    return jax.value_and_grad(loss)(
        {k: v.astype(jnp.float32) for k, v in params.items()})


def grads_pass(params, ids, labels, cfg, consume, quant=None):
    """Loss of one batch; every leaf's float32 gradient to ``consume``."""
    sizes = tuple((k, cfg[k]) for k in ("num_layers", "inner_size",
                                        "conv_kernel"))
    loss, grads = _loss_and_grads(params, ids, labels, sizes, quant)
    for k, g in grads.items():
        consume(k, g)
    return loss


def train_readings(cfg, train, make_params, batches, quant=None, rows=None):
    return reference_steps.train_readings(
        lambda params, ids, labels, consume: grads_pass(
            params, ids, labels, cfg, consume, quant),
        leaf_segments(cfg), train, make_params, batches, rows)
