"""What the plain references of all architectures share: the control's
float8 matmul, and the train steps that follow a gradient (global
grad-norm clip, bias-corrected Adam, the configuration's stated storage).
An architecture's own file under ``reference/`` writes its equations and
hands its ``grads_pass`` to ``train_readings`` here.

Stated storage: parameters and Adam moments are rounded to the dtypes the
configuration file states at the places a train step stores them (after
the update), and every piece of arithmetic between two stores is float32.
A float32 master copy would be a different configuration
(``master_weights``), not a more exact one.

Memory: per step the gradients are computed twice (pass 1: per-leaf norms
for the global clip; pass 2: the same gradients, consumed leaf by leaf by
the Adam update) so that no float32 gradient tree (5.25 GB at 1.3 G
parameters) is ever held beside parameters and moments.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights

_HI = jax.lax.Precision.HIGHEST


def fp8(x):
    """Round to float8_e4m3 under a per-tensor scale; gradient passes
    straight through."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    r = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(r - x)


def mm(a, b, quant):
    """The references' matmul.  ``quant="fp8"`` is the control of the
    correctness check, not a feature: both operands are rounded to
    float8_e4m3, the nearest precision below bfloat16."""
    if quant == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.matmul(a, b, precision=_HI)


@functools.partial(jax.jit, static_argnames=("n",))
def _sumsq(g, n):
    return weights.segment_sumsq(g, n)


@functools.partial(jax.jit, static_argnames=("mdt",), donate_argnums=(0, 2, 3))
def _adam(p, g, m, v, scale, t, lr, b1, b2, eps, mdt):
    g = g * scale
    m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
    v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
    new = p.astype(jnp.float32) - lr * (m32 / (1 - b1 ** t)) / (
        jnp.sqrt(v32 / (1 - b2 ** t)) + eps)
    return new.astype(p.dtype), m32.astype(mdt), v32.astype(mdt)


@functools.partial(jax.jit, static_argnames=("n",))
def _diff_norm(a, b, n):
    return jnp.sqrt(weights.segment_sumsq(
        a.astype(jnp.float32) - b.astype(jnp.float32), n))


def train_readings(grads_pass, segs, train, make_params, batches, rows=None):
    """Follow ``len(batches)`` train steps from ``make_params()`` and
    return what the check compares: each step's loss, per leaf the norm of
    the first gradient as Adam gets it (after the clip), and per leaf the
    norm of the parameters' change over the steps.  A leaf of ``segs``
    (the reference's ``leaf_segments``) gives one norm per segment, under
    ``name#i``.

    ``grads_pass(params, ids, labels, consume)``: the architecture's loss
    of one batch, every leaf's float32 gradient handed to ``consume(name,
    grad)``.  ``train``: learning_rate, beta1, beta2, epsilon,
    grad_clip_norm, moment_dtype.  ``make_params``: the seeded generator,
    called once at the start and once more at the end (to measure the
    change without holding a second copy through the steps).  ``batches``:
    ``[(ids, labels), ...]``.  ``rows``: a slice of every batch to keep
    (plants the "half of the batch left out" fault in the reference put in
    the program's place)."""
    mdt = jnp.dtype(train["moment_dtype"])
    clip = train["grad_clip_norm"]
    params = make_params()

    m = {k: jnp.zeros(v.shape, mdt) for k, v in params.items()}
    v_ = {k: jnp.zeros(v.shape, mdt) for k, v in params.items()}
    losses, first_grad = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        sq = {}
        loss = grads_pass(params, ids, labels,
                          lambda k, g: sq.__setitem__(
                              k, _sumsq(g, segs.get(k, 1))))
        losses.append(float(loss))
        sq = {name: x for k, v in sq.items()
              for name, x in weights.by_segment(k, v).items()}
        gnorm = math.sqrt(sum(sq.values()))
        scale = clip / max(gnorm, clip) if clip is not None else 1.0
        if first_grad is None:
            first_grad = {k: math.sqrt(x) * scale for k, x in sq.items()}
        new = {}

        def update(k, g):
            new[k], m[k], v_[k] = _adam(
                params[k], g, m[k], v_[k], jnp.float32(scale),
                jnp.float32(t), jnp.float32(train["learning_rate"]),
                jnp.float32(train["beta1"]), jnp.float32(train["beta2"]),
                jnp.float32(train["epsilon"]), mdt)

        # pass 2: the same gradients again, now consumed by the update;
        # ``params`` stays whole until the pass is over (an embedding is
        # read first and updated last)
        grads_pass(params, ids, labels, update)
        params = new
    del m, v_
    start = make_params()
    change = {}
    for k in sorted(params):
        change.update(weights.by_segment(
            k, _diff_norm(params[k], start[k], segs.get(k, 1))))
    return {"losses": losses, "grad_norm": first_grad, "change_norm": change}
