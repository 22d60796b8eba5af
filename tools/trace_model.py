"""Trace the conv-model train/infer steps (ResNet-50 / PP-YOLOE) and
aggregate per-op device durations from the profiler trace — the same
methodology that found the ERNIE MLM-head relayout win (round 3; the
notes are in git history).

Usage: python tools/trace_model.py [resnet|resnet-infer] [batch]
"""
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from trace_util import bucket_by_mnemonic, xla_op_durations_ms

REPS = 3


def _aggregate(outdir, reps, norm_label):
    ind = xla_op_durations_ms(outdir)
    agg = bucket_by_mnemonic(ind)
    total = sum(ind.values())
    print(f"total device op time: {total / reps:.2f} ms/step ({norm_label})")
    for name, dur in agg.most_common(25):
        print(f"  {name:40s} {dur / reps:8.2f} ms")
    print("top individual ops:")
    for name, dur in ind.most_common(30):
        print(f"  {name:70s} {dur / reps:8.2f} ms")


def build_resnet_train(batch):
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.core import random as core_random
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.nn.functional.loss import fused_softmax_ce_rows
    from paddle_hackathon_tpu.nn.layer import functional_call
    from paddle_hackathon_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50()

    def loss_fn(model, params, buffers, batch_, rng):
        images, labels = batch_
        with core_random.rng_scope(rng):
            logits = functional_call(model, params, (Tensor(images),),
                                     buffers=dict(buffers))
        lg = logits._value if isinstance(logits, Tensor) else logits
        return jnp.mean(fused_softmax_ce_rows(lg, labels))

    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=None, learning_rate=1e-4, zero_stage=0,
        param_dtype=jnp.bfloat16, loss_fn=loss_fn)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, 3, 224, 224), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    key = jax.random.key(0)

    def run():
        nonlocal state
        for _ in range(REPS):
            state, loss = step(state, images, labels, key)
        float(loss)

    return run


def build_resnet_infer(batch):
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.nn.layer import functional_call
    from paddle_hackathon_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50()
    model.eval()
    params, buffers = model.functional_state()

    def _bf16(d):
        return {k: v.astype(jnp.bfloat16) if jnp.issubdtype(
            v.dtype, jnp.floating) else v for k, v in d.items()}

    params, buffers = _bf16(params), _bf16(buffers)

    @jax.jit
    def fwd(params, x):
        return functional_call(model, params, (Tensor(x),), buffers=buffers,
                               training=False)

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, 3, 224, 224), jnp.bfloat16)

    def run():
        out = None
        for _ in range(REPS):
            out = fwd(params, images)
        jax.block_until_ready(out)

    return run


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else (
        512 if which == "resnet-infer" else 256)
    outdir = "/tmp/trace_model"
    run = {"resnet": build_resnet_train,
           "resnet-infer": build_resnet_infer}[which](batch)
    run()  # warm/compile
    run()
    shutil.rmtree(outdir, ignore_errors=True)
    jax.profiler.start_trace(outdir)
    run()
    jax.profiler.stop_trace()
    _aggregate(outdir, REPS, f"{which} bs={batch}")


if __name__ == "__main__":
    main()
