"""Decompose the GPT train-step time on the real chip.

Times (a) forward loss only, (b) forward+backward, (c) the full train step
(fwd+bwd+clip+Adam), plus a pure-matmul MXU calibration at the model's
dominant shapes, so the MFU gap can be attributed to a phase instead of
guessed at.  Not a test — a tuning tool (ref tools/ci_op_benchmark.sh
gathers per-op numbers the same way).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, steps=10, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def main():
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTForCausalLM, gpt_config,
                                             param_sharding_spec)
    from paddle_hackathon_tpu.nn.layer import functional_call
    from paddle_hackathon_tpu.core.tensor import Tensor

    paddle.seed(0)
    cfg = gpt_config("gpt2-small-en", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    batch, seqlen = 24, 1024
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seqlen)

    model = GPTForCausalLM(cfg)
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-4,
        zero_stage=0, param_dtype=jnp.bfloat16)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)),
                         jnp.int32)
    key = jax.random.key(0)

    params = state["params"]
    _, buffers = model.functional_state()

    from paddle_hackathon_tpu.nn.functional.loss import fused_softmax_ce_rows
    from paddle_hackathon_tpu.core import random as core_random

    def loss_fn(p):
        with core_random.rng_scope(key):
            logits = functional_call(model, p, (Tensor(ids),),
                                     buffers=dict(buffers))
        lg = logits._value if isinstance(logits, Tensor) else logits
        return jnp.mean(fused_softmax_ce_rows(lg, labels))

    fwd = jax.jit(loss_fn)
    fwdbwd = jax.jit(lambda p: jax.value_and_grad(loss_fn)(p)[0])

    t_fwd = timeit(fwd, params)
    t_fwdbwd = timeit(fwdbwd, params)

    # step() mutates python-side state dict; time it directly
    for _ in range(3):
        state, loss = step(state, ids, labels, key)
    float(loss)
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        state, loss = step(state, ids, labels, key)
    float(loss)
    t_step = (time.perf_counter() - t0) / n

    # MXU calibration: model-shaped matmul chain in bf16
    h, ffn, v = cfg.hidden_size, 4 * cfg.hidden_size, cfg.vocab_size
    tok = batch * seqlen
    a = jnp.zeros((tok, h), jnp.bfloat16)
    w1 = jnp.zeros((h, ffn), jnp.bfloat16)
    w2 = jnp.zeros((ffn, h), jnp.bfloat16)
    wv = jnp.zeros((h, v), jnp.bfloat16)

    @jax.jit
    def mm(a):
        x = a @ w1
        y = x @ w2
        z = y @ wv
        return jnp.sum(z.astype(jnp.float32))

    t_mm = timeit(mm, a)
    fl_mm = 2 * tok * (h * ffn + ffn * h + h * v)

    # model flops (fwd): 6*N per token approx via params; use 2*N_matmul
    n_params = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    fl_fwd = 2 * n_params * tok + 2 * 2 * batch * cfg.num_layers * \
        cfg.num_heads * seqlen * seqlen * (cfg.hidden_size // cfg.num_heads)
    fl_step = 3 * fl_fwd  # fwd + 2x bwd

    from paddle_hackathon_tpu.cost_model.cost_model import device_peak_flops
    peak = device_peak_flops()   # bf16 peak by device_kind
    if peak is None:
        raise RuntimeError(
            f"no peak FLOP/s known for device kind "
            f"{jax.devices()[0].device_kind!r}: the MFU columns would be "
            "ratios against a made-up denominator (add the kind to "
            "cost_model._PEAK_FLOPS_BY_KIND or set PHT_PEAK_FLOPS)")
    tok_s = tok / t_step
    print(f"fwd      {t_fwd*1e3:8.2f} ms  ({fl_fwd/t_fwd/1e12:6.1f} TF/s, "
          f"{fl_fwd/t_fwd/peak*100:5.1f}% MFU)")
    print(f"fwd+bwd  {t_fwdbwd*1e3:8.2f} ms  ({fl_step/t_fwdbwd/1e12:6.1f} TF/s, "
          f"{fl_step/t_fwdbwd/peak*100:5.1f}% MFU)")
    print(f"step     {t_step*1e3:8.2f} ms  ({fl_step/t_step/1e12:6.1f} TF/s, "
          f"{fl_step/t_step/peak*100:5.1f}% MFU)  {tok_s:,.0f} tok/s")
    print(f"opt+clip {(t_step-t_fwdbwd)*1e3:8.2f} ms  (step - fwdbwd)")
    print(f"bwd      {(t_fwdbwd-t_fwd)*1e3:8.2f} ms  (fwdbwd - fwd)")
    print(f"mxu cal  {t_mm*1e3:8.2f} ms  ({fl_mm/t_mm/1e12:6.1f} TF/s, "
          f"{fl_mm/t_mm/peak*100:5.1f}% of peak) at model shapes")


if __name__ == "__main__":
    main()
