"""The chunked gated delta rule (``incubate/nn/functional/
gated_delta_rule.py``) against the token-by-token recurrence of the
benchmark's plain reference, forward and gradients, at sequence lengths on
both sides of a chunk's edge and with decays near 0 and near -0.7 a token
(44 nats across a chunk: ``exp(-cumsum)`` would overflow)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import qwen3_next_f32 as ref  # noqa: E402
from paddle_hackathon_tpu.incubate.nn.functional import (  # noqa: E402
    causal_depthwise_conv, gated_delta_rule, gated_delta_rule_chunked)


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


def test_the_scan_keeps_one_state_a_chunk():
    """The backward's residuals grow with the chunks, not the tokens: no
    array of the jaxpr holds a (dk, dv) state for every token."""
    args = _inputs(256, -0.1)
    s, dk, dv = 256, 16, 8
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule_chunked(*a))))(*args)
    per_token = s * dk * dv
    sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    # the largest things are (chunks, b, h, ...) stacks; a state a token
    # would be b * h * s * dk * dv
    assert max(sizes) < 2 * 3 * per_token


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
