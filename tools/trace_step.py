"""Trace the gpt2 train step and aggregate per-op durations from the
profiler's trace (per-op device time; a wall-clock microbench times the
host's dispatch along with the op)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np


def main(batch=32, seqlen=1024, outdir="/tmp/trace_step"):
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTForCausalLM, gpt_config,
                                             param_sharding_spec)
    paddle.seed(0)
    cfg = gpt_config("gpt2-small-en", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-4,
        zero_stage=0, param_dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)), jnp.int32)
    key = jax.random.key(0)
    for _ in range(3):
        state, loss = step(state, ids, labels, key)
    float(loss)
    import shutil
    shutil.rmtree(outdir, ignore_errors=True)
    jax.profiler.start_trace(outdir)
    for _ in range(3):
        state, loss = step(state, ids, labels, key)
    float(loss)
    jax.profiler.stop_trace()

    from trace_util import bucket_by_mnemonic, xla_op_durations_ms
    ind = xla_op_durations_ms(outdir)
    agg = bucket_by_mnemonic(ind)
    total = sum(ind.values())
    print(f"total device op time: {total/3:.2f} ms/step  "
          f"({batch*seqlen*3/ (total/1e3):,.0f} tok/s-equivalent)")
    for name, dur in agg.most_common(30):
        print(f"  {name:40s} {dur/3:8.2f} ms")
    print("top individual ops:")
    for name, dur in ind.most_common(25):
        print(f"  {name:60s} {dur/3:8.2f} ms")


if __name__ == "__main__":
    main()
