"""The chunked gated delta rule (``incubate/nn/functional/
gated_delta_rule.py``) against the token-by-token recurrence of the
benchmark's plain reference, forward and gradients, at sequence lengths on
both sides of a chunk's edge and with decays near 0 and near -0.7 a token
(44 nats across a chunk: ``exp(-cumsum)`` would overflow)."""

import importlib
import os
import re
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import qwen3_next_f32 as ref  # noqa: E402
from paddle_hackathon_tpu.incubate.nn.functional import (  # noqa: E402
    causal_depthwise_conv, gated_delta_rule, gated_delta_rule_chunked)

# the package's name is bound to the taped op; the module's parts by path
rule = importlib.import_module(
    "paddle_hackathon_tpu.incubate.nn.functional.gated_delta_rule")


def _inputs(s, g_mean, seed=0, b=2, h=3, dk=16, dv=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = g_mean * (1.0 + 0.3 * jax.random.uniform(ks[3], (b, s, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


@pytest.mark.parametrize("g_mean", [-0.01, -0.7])
@pytest.mark.parametrize("s", [1, 63, 64, 65, 256])
def test_chunked_rule_equals_the_recurrence(s, g_mean):
    args = _inputs(s, g_mean)
    with jax.default_matmul_precision("highest"):
        want = ref.delta_rule(*args)
        got = gated_delta_rule_chunked(*args)
        assert got.shape == want.shape
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) < 2e-5 * scale

        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)))
        want_g = jax.grad(loss(ref.delta_rule), argnums=(0, 1, 2, 3, 4))(*args)
        got_g = jax.grad(loss(gated_delta_rule_chunked),
                         argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max()) + 1e-9
        assert float(jnp.abs(a - b).max()) < 1e-4 * scale, name


def test_bfloat16_operands_stay_close_and_finite_under_strong_decay():
    args = _inputs(192, -0.7, dtype=jnp.bfloat16)
    want = ref.delta_rule(*(x.astype(jnp.float32) for x in args))
    got = gated_delta_rule_chunked(*args)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(err.max()) < 0.05 * float(jnp.abs(want).max())


def _largest_array(fn, args):
    """Elements of the largest array any equation of ``fn``'s jaxpr makes."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
               for v in eqn.outvars if hasattr(v.aval, "shape"))


def test_the_scan_keeps_one_state_a_chunk():
    """The backward's residuals grow with the chunks, not the tokens: no
    array of the jaxpr holds a (dk, dv) state for every token."""
    args = _inputs(256, -0.1)
    s, dk, dv = 256, 16, 8
    grad = jax.grad(lambda *a: jnp.sum(gated_delta_rule_chunked(*a)))
    # the largest things are (chunks, b, h, ...) stacks; a state a token
    # would be b * h * s * dk * dv
    assert _largest_array(grad, args) < 2 * 3 * s * dk * dv


def _inverse_products(fn, args):
    """``dot_general``s of the lowered ``fn`` whose two operands are both
    float32 (.., CHUNK, CHUNK): the two products of the inverse's rule and
    any product that built the inverse outside its kernel (the parent's
    log-doubling had ten), nothing else in the file."""
    square = f"{rule.CHUNK}x{rule.CHUNK}xf32"
    count = 0
    for line in jax.jit(fn).lower(*args).as_text().splitlines():
        if "dot_general" not in line:
            continue
        operands = re.findall(r"tensor<([^>]*)>",
                              line.rsplit(" : ", 1)[-1].split("->")[0])
        count += len(operands) == 2 and all(
            o.endswith(square) for o in operands)
    return count


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _kernel_calls(fn, args, name="delta_rule_inverse"):
    """Calls of the Pallas kernel ``name`` in ``fn``'s jaxpr, through every
    nested jaxpr (jit, checkpoint, scan, custom rules; a rebuild in the
    backward is a second call): on the CPU the kernel runs interpreted and
    leaves no custom call in the lowered text to count."""
    def calls(jaxpr):
        return sum(e.params["name"] == name
                   if e.primitive.name == "pallas_call"
                   else sum(calls(j) for j in _sub_jaxprs(e))
                   for e in jaxpr.eqns)
    return calls(jax.make_jaxpr(fn)(*args).jaxpr)


def test_the_backward_reads_the_inverse_and_does_not_rebuild_it():
    """The inverse stands outside every checkpoint: the forward is one
    call of its kernel and no float32 product of its size; the gradient
    the same call and the two products of its own rule, where a second
    call would mean it is rebuilt in the backward."""
    args = _inputs(2 * rule.CHUNK, -0.1, dtype=jnp.bfloat16)
    assert _kernel_calls(gated_delta_rule_chunked, args) == 1
    assert _inverse_products(gated_delta_rule_chunked, args) == 0

    def loss(*a):
        return jnp.sum(gated_delta_rule_chunked(*a).astype(jnp.float32))
    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    assert _kernel_calls(grad, args) == 1
    assert _inverse_products(grad, args) == 2


def test_a_caller_that_rebuilds_its_mixer_keeps_the_inverse_by_name():
    """Under a caller's ``jax.checkpoint`` whose policy saves
    ``KEPT_INVERSE`` alone the gradient still calls the kernel once: the
    name sits on the value the inverse's own rule reads, so the policy
    keeps it.  (On the rule's output alone it kept a copy for
    ``_chunk_inputs`` and the backward built the inverse again for its
    rule.)"""
    args = _inputs(2 * rule.CHUNK, -0.1, dtype=jnp.bfloat16)
    wrapped = jax.checkpoint(
        gated_delta_rule_chunked,
        policy=jax.checkpoint_policies.save_only_these_names(
            rule.KEPT_INVERSE))

    def loss(*a):
        return jnp.sum(wrapped(*a).astype(jnp.float32))
    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    assert _kernel_calls(grad, args) == 1
    assert _inverse_products(grad, args) == 2


def _rule_with_the_inverse_rebuilt(q, k, v, g, beta):
    """``gated_delta_rule_chunked`` from the module's own parts, with the
    system, its inverse and what the scan reads inside one checkpoint:
    the backward keeps the five inputs and rebuilds the inverse."""
    b, s, h, dk = q.shape
    c = rule.CHUNK
    n = s // c

    def chunks(x):
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    @jax.checkpoint
    def inputs(q, k, v, g, beta):
        inv = rule._unit_lower_inverse(rule._chunk_system(k, g, beta))
        return rule._chunk_inputs(q, k, v, g, beta, inv)

    xs = inputs(chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(jax.checkpoint(rule._chunk_body), state, xs)
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, s, h, -1)


def test_the_kept_inverse_gives_the_gradient_of_the_rebuilt_one():
    """What is kept is the float32 inverse, one (CHUNK, CHUNK) a chunk and
    head: the gradient equals, within a unit in the last place, that of
    the same parts with the inverse rebuilt in the backward, and still no
    array of its jaxpr holds a (dk, dv) state for every token."""
    s, dk, dv = 256, 16, 8
    args = _inputs(s, -0.1, dtype=jnp.bfloat16)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))
    with jax.default_matmul_precision("highest"):
        assert bool((gated_delta_rule_chunked(*args)
                     == _rule_with_the_inverse_rebuilt(*args)).all())
        grad = jax.grad(loss(gated_delta_rule_chunked),
                        argnums=(0, 1, 2, 3, 4))
        got = grad(*args)
        want = jax.grad(loss(_rule_with_the_inverse_rebuilt),
                        argnums=(0, 1, 2, 3, 4))(*args)
        largest = _largest_array(grad, args)
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert a.dtype == w.dtype, name
        a, w = np.asarray(a), np.asarray(w)
        # k, g, beta take cotangents from two functions where the rebuilt
        # form sums them inside one: another order, a rounding apart
        ulp = np.spacing(np.abs(w).max())                # of w's own dtype
        gap = np.abs(a.astype(np.float32) - w.astype(np.float32)).max()
        assert gap <= float(ulp), name
    assert largest < 2 * 3 * s * dk * dv


def test_tensor_op_is_taped():
    import paddle_hackathon_tpu as paddle
    q, k, v, g, beta = (paddle.to_tensor(np.asarray(x), stop_gradient=False)
                        for x in _inputs(70, -0.2))
    out = gated_delta_rule(q, k, v, g, beta)
    out.sum().backward()
    assert v.grad is not None and np.isfinite(np.asarray(v.grad)).all()


def test_causal_depthwise_conv_equals_the_reference_and_is_causal():
    x = jax.random.normal(jax.random.key(3), (2, 9, 5))
    taps = jax.random.normal(jax.random.key(4), (5, 4))
    got = causal_depthwise_conv(x, taps)
    assert float(jnp.abs(got - ref.causal_conv(x, taps)).max()) < 1e-6
    # the newest token stands under the last tap; the first token sees
    # nothing before it
    assert np.allclose(got[:, 0], x[:, 0] * taps[:, 3], atol=1e-6)
    later = x.at[:, 5:].set(0.0)
    assert np.allclose(causal_depthwise_conv(later, taps)[:, :5], got[:, :5])
