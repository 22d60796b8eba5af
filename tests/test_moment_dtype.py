"""bf16 Adam-moment storage (optax mu_dtype-style TPU option; +26% on the
GPT-3 1.3B row in round 3, old toolchain).  Default stays f32 =
reference-parity; these tests pin the option's convergence parity.
"""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import nn, optimizer, parallel
from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                         param_sharding_spec)


def _train_eager(moment_dtype, steps=30):
    paddle.seed(3)
    m = nn.Sequential(nn.Linear(16, 64), nn.Tanh(), nn.Linear(64, 1))
    opt = optimizer.Adam(learning_rate=0.01, parameters=m.parameters(),
                         moment_dtype=moment_dtype)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 16).astype("float32"))
    y = paddle.to_tensor((rng.randn(64, 1) * 0.1).astype("float32"))
    losses = []
    for _ in range(steps):
        loss = paddle.mean((m(x) - y) ** 2)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    return losses


def test_eager_adam_bf16_moments_track_f32():
    f32 = _train_eager(None)
    bf16 = _train_eager("bfloat16")
    assert f32[-1] < f32[0] * 0.2
    assert bf16[-1] < bf16[0] * 0.2
    # trajectories stay close — bf16 moments must not change optimization
    # behavior beyond rounding noise
    np.testing.assert_allclose(bf16[-1], f32[-1], rtol=0.25, atol=1e-3)


def test_sharded_step_moment_dtype():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=16,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    mesh = parallel.create_mesh({"dp": 2, "mp": 2},
                                devices=jax.devices()[:4])
    try:
        def run(mdt):
            paddle.seed(0)
            model = GPTForCausalLM(cfg)
            step, state = parallel.make_sharded_train_step(
                model, mesh, rule=param_sharding_spec, learning_rate=1e-2,
                moment_dtype=mdt)
            if mdt is not None:
                for s in state["opt_state"].values():
                    assert s["m"].dtype == jnp.bfloat16
                    assert s["v"].dtype == jnp.bfloat16
            rng = np.random.RandomState(0)
            ids = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)
            lab = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)
            losses = []
            for _ in range(10):
                state, loss = step(state, ids, lab, jax.random.key(1))
                losses.append(float(loss))
            return losses

        f32 = run(None)
        bf16 = run(jnp.bfloat16)
    finally:
        parallel.set_mesh(None)
    assert f32[-1] < f32[0]
    assert bf16[-1] < bf16[0]
    np.testing.assert_allclose(bf16[-1], f32[-1], rtol=0.05)
