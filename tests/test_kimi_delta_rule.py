"""The chunked delta rule with a decay a key channel (``incubate/nn/
functional/kimi_delta_rule.py``) against the token-by-token recurrence of
the benchmark's plain reference, forward and the gradients of all its
inputs in float32: with slow decays (-0.02 a token: the state that enters
a chunk carries, and a build that dropped the carry fails), at the
harness's draw (-2.5: 160 nats across a chunk, where a factoring over the
whole chunk overflows) and at the family's bound (-4.99 on every channel:
75 nats across a 16-token sub-block, under float32's 88); and, with one
decay for all channels, against the scalar rule."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ling3_f32 as ref  # noqa: E402
from paddle_hackathon_tpu.incubate.nn.functional import (  # noqa: E402
    gated_delta_rule_chunked, kimi_delta_rule, kimi_delta_rule_chunked)

scalar_rule = importlib.import_module(
    "paddle_hackathon_tpu.incubate.nn.functional.gated_delta_rule")
NAMES = "q k v g beta".split()


def _inputs(s, g_mean, spread=0.3, seed=0, b=2, h=3, dk=16, dv=8,
            dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = g_mean * (1.0 + spread * jax.random.uniform(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def _loss(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a)))


def _compare(args):
    """Values to 1e-4 of the largest; each input's gradient to 1e-4 of its
    own largest entry, plus 1e-5: at -4.99 a token ``dg`` is a sum of O(1)
    terms that cancel to e^-5 of them, and float32 leaves 3e-6 there."""
    with jax.default_matmul_precision("highest"):
        want = ref.delta_rule(*args)
        got = kimi_delta_rule_chunked(*args)
        want_g = jax.grad(_loss(ref.delta_rule), argnums=range(5))(*args)
        got_g = jax.grad(_loss(kimi_delta_rule_chunked),
                         argnums=range(5))(*args)
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    worst = {"o": float(jnp.abs(got - want).max())
             / float(jnp.abs(want).max())}
    for name, a, b in zip(NAMES, got_g, want_g):
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max())
        worst[name] = max(0.0, float(jnp.abs(a - b).max()) - 1e-5) / scale
    return worst


@pytest.mark.parametrize("g_mean, spread", [(-0.02, 0.3), (-2.5, 0.3),
                                            (-4.99, 0.0)])
@pytest.mark.parametrize("s", [64, 128, 192])
def test_chunked_rule_equals_the_recurrence(s, g_mean, spread):
    worst = _compare(_inputs(s, g_mean, spread))
    assert max(worst.values()) < 1e-4, worst


def test_a_ragged_tail_is_padded_with_tokens_that_do_nothing():
    worst = _compare(_inputs(70, -0.5))
    assert max(worst.values()) < 1e-4, worst


def test_dropping_the_carry_between_chunks_is_seen(monkeypatch):
    """At -0.02 a token every token of a chunk reads the state that
    entered it: with the scan's carry zeroed the comparison above fails by
    three orders.  (At the harness's -2.5 a token only a chunk's first
    token or two still see it, at e^-2.5 and e^-5: the chip's check sees
    the carry faintly, this test plainly.)"""
    body = scalar_rule._chunk_body

    def forgetful(state, xs):
        return body(jnp.zeros_like(state), xs)

    monkeypatch.setattr(scalar_rule, "_chunk_body", forgetful)
    worst = _compare(_inputs(192, -0.02))
    assert min(worst.values()) > 0.1, worst


def test_one_decay_for_all_channels_is_the_scalar_rule():
    q, k, v, g, beta = _inputs(130, -0.5)
    g_head = g[..., 0]
    with jax.default_matmul_precision("highest"):
        got = kimi_delta_rule_chunked(
            q, k, v, jnp.broadcast_to(g_head[..., None], g.shape), beta)
        want = gated_delta_rule_chunked(q, k, v, g_head, beta)
    assert float(jnp.abs(got - want).max()) \
        < 2e-6 * float(jnp.abs(want).max())


def test_no_exp_overflows_where_a_chunks_cumulated_decay_would():
    """bfloat16 operands at the family's bound on every channel (320 nats
    across a chunk): finite values and gradients, close to the float32
    recurrence."""
    args = _inputs(192, -4.99, 0.0, dtype=jnp.bfloat16)
    want = ref.delta_rule(*(x.astype(jnp.float32) for x in args))
    got = kimi_delta_rule_chunked(*args)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(err.max()) < 0.05 * float(jnp.abs(want).max())
    grads = jax.grad(_loss(lambda *a: kimi_delta_rule_chunked(*a).astype(
        jnp.float32)), argnums=range(5))(*args)
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in grads)


def test_tensor_op_is_taped():
    import paddle_hackathon_tpu as paddle
    q, k, v, g, beta = (paddle.to_tensor(x, stop_gradient=False)
                        for x in _inputs(64, -0.5))
    out = kimi_delta_rule(q, k, v, g, beta)
    out.sum().backward()
    assert g.grad is not None and tuple(g.grad.shape) == tuple(g.shape)
