"""Generate the committed op-time snapshot
(``paddle_hackathon_tpu/cost_model/static_op_benchmark.json``) by timing
~55 hot ops on the local accelerator.

Schema mirrors the reference's ``static_op_benchmark.json`` (the CI op gate
input, ``tools/ci_op_benchmark.sh:117``) with ``paddle_gpu_time`` holding
this framework's measured device ms.

Timing method (default): the N-queued-reps + one float() sync wall
pattern — fair for the multi-ms shapes used here, where dispatch
pipelines fully under the op.  ``GEN_OPS_TRACE=1`` switches to exact
per-op profiler traces (sums on the "XLA Ops" thread).
"""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np


def _trace_device_ms(run, outdir):
    from trace_util import xla_op_durations_ms
    shutil.rmtree(outdir, ignore_errors=True)
    jax.profiler.start_trace(outdir)
    run()
    jax.profiler.stop_trace()
    durs = xla_op_durations_ms(outdir)
    return sum(durs.values()) if durs else None


def device_time(fn, *args, reps=20):
    """Device ms per execution.

    Default: the N-queued-reps + one float() sync wall pattern — fair
    for the multi-ms shapes used here (dispatch pipelines under the op).
    ``GEN_OPS_TRACE=1`` switches to per-op profiler traces (exact device
    ms, one trace per op)."""
    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)  # accept pre-jitted
    out = jfn(*args)
    float(jnp.sum(jnp.ravel(jax.tree.leaves(out)[0])[:1]).astype(jnp.float32))

    def run():
        o = out
        for _ in range(reps):
            o = jfn(*args)
        float(jnp.sum(jnp.ravel(jax.tree.leaves(o)[0])[:1])
              .astype(jnp.float32))

    if os.environ.get("GEN_OPS_TRACE") == "1":
        with tempfile.TemporaryDirectory() as d:
            ms = _trace_device_ms(run, d)
        if ms is not None:
            return ms / reps
    t0 = time.perf_counter()
    run()
    return (time.perf_counter() - t0) / reps * 1e3


# Module level with static shape/dtype args: a stable jit identity, so
# repeated shapes hit the cache instead of retracing a fresh lambda per
# operand (pht-lint PHT002).
def _rnd_impl(k, shape, dtype):
    return jax.random.normal(k, shape, jnp.float32).astype(dtype)


def _rint_impl(k, shape, hi):
    return jax.random.randint(k, shape, 0, hi, jnp.int32)


_rnd_impl = jax.jit(_rnd_impl, static_argnums=(1, 2))
_rint_impl = jax.jit(_rint_impl, static_argnums=(1, 2))


def build_ops():
    # ALL inputs are generated ON DEVICE (jax.random): no ~3 GB of
    # operands built host-side and copied over before the first op runs
    _key_iter = iter(jax.random.split(jax.random.key(0), 40))

    def _rnd(shape, dtype=jnp.float32):
        return _rnd_impl(next(_key_iter), tuple(shape), dtype)

    def _rint(shape, hi):
        return _rint_impl(next(_key_iter), tuple(shape), int(hi))
    # elementwise workhorse shape: big enough that per-call dispatch noise
    # vanishes under the op (~6 ms/pass f32)
    x4 = _rnd((16, 128, 257, 257), jnp.float32)
    x4b = _rnd((16, 128, 257, 257), jnp.bfloat16)
    m1 = _rnd((1024, 1024), jnp.float32)
    m2 = _rnd((1024, 1024), jnp.float32)
    # model-shaped matmuls (gpt2 ffn / vocab head, bf16 MXU path)
    a_tok = _rnd((8192, 768), jnp.bfloat16)
    w_ffn = _rnd((768, 3072), jnp.bfloat16)
    w_voc = _rnd((768, 50304), jnp.bfloat16)
    img = _rnd((32, 64, 56, 56), jnp.float32)
    ker = _rnd((64, 64, 3, 3), jnp.float32)
    ker1 = _rnd((256, 64, 1, 1), jnp.float32)
    imgb = _rnd((64, 256, 56, 56), jnp.bfloat16)
    kerb = _rnd((64, 256, 1, 1), jnp.bfloat16)
    seq = _rnd((32, 1024, 768), jnp.float32)
    logits = _rnd((8192, 50304), jnp.float32)
    lab = _rint((8192,), 50304)
    emb = _rnd((50304, 768), jnp.float32)
    ids = _rint((32, 1024), 50304)
    key = jax.random.key(0)

    def conv(x, k, stride=1):
        return jax.lax.conv_general_dilated(x, k, (stride, stride), "SAME")

    def adam(p, g, m, v):
        m2_ = 0.9 * m + 0.1 * g
        v2_ = 0.95 * v + 0.05 * g * g
        return p - 1e-3 * m2_ / (jnp.sqrt(v2_) + 1e-8), m2_, v2_

    big = "x (Variable) - dtype: float32, shape: [16, 128, 257, 257]\n"
    bigb = "x (Variable) - dtype: bfloat16, shape: [16, 128, 257, 257]\n"
    tokc = "x bf16 [8192, 768]"
    seqc = "x f32 [32, 1024, 768]"

    ew = {  # elementwise family on the workhorse shape (fwd + bwd)
        "abs": jnp.abs, "relu": jax.nn.relu, "exp": jnp.exp,
        "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "silu": jax.nn.silu, "erf": jax.lax.erf,
        "log": lambda x: jnp.log(jnp.abs(x) + 1e-6),
        "sqrt": lambda x: jnp.sqrt(jnp.abs(x)),
        "rsqrt": lambda x: jax.lax.rsqrt(jnp.abs(x) + 1e-6),
        "square": jnp.square, "floor": jnp.floor, "sign": jnp.sign,
        "clip": lambda x: jnp.clip(x, -1.0, 1.0),
    }
    binw = {
        "elementwise_add": jnp.add, "elementwise_mul": jnp.multiply,
        "elementwise_sub": jnp.subtract,
        "elementwise_div": lambda a, b: a / (jnp.abs(b) + 1.0),
        "elementwise_max": jnp.maximum, "elementwise_min": jnp.minimum,
        "elementwise_pow": lambda a, b: jnp.power(jnp.abs(a) + 1e-3, 2.0),
        "where": lambda a, b: jnp.where(a > 0, a, b),
    }
    ops = {}
    for name, fn in ew.items():
        ops[name] = (fn, (x4,), big, True)
    for name, fn in binw.items():
        ops[name] = (fn, (x4, x4), big, True)
    ops.update({
        "softmax": (lambda x: jax.nn.softmax(x, axis=-1), (x4,), big, True),
        "log_softmax": (lambda x: jax.nn.log_softmax(x, axis=-1), (x4,),
                        big, True),
        "mean": (jnp.mean, (x4,), big, True),
        "sum": (jnp.sum, (x4,), big, True),
        "reduce_max": (jnp.max, (x4,), big, True),
        "cumsum": (lambda x: jnp.cumsum(x, axis=-1), (x4,), big, True),
        "cast_bf16": (lambda x: x.astype(jnp.bfloat16), (x4,), big, False),
        "transpose": (lambda x: jnp.swapaxes(x, -1, -2), (x4,), big, False),
        "concat": (lambda a, b: jnp.concatenate([a, b], -1), (x4b, x4b),
                   bigb, False),
        "split": (lambda x: jnp.split(x, 2, axis=1)[0], (x4,), big, False),
        "pad": (lambda x: jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1))),
                (x4b,), bigb, False),
        "slice": (lambda x: x[:, :, 1:-1, 1:-1], (x4,), big, False),
        "matmul": (jnp.matmul, (m1, m2), "x f32 [1024,1024] @ [1024,1024]",
                   True),
        "matmul_ffn_bf16": (jnp.matmul, (a_tok, w_ffn),
                            tokc + " @ [768, 3072]", True),
        "matmul_vocab_bf16": (jnp.matmul, (a_tok, w_voc),
                              tokc + " @ [768, 50304]", True),
        "conv2d": (conv, (img, ker), "x f32 [32,64,56,56]; w [64,64,3,3]",
                   True),
        "conv2d_1x1": (lambda x, k: conv(x, k), (img, ker1),
                       "x f32 [32,64,56,56]; w [256,64,1,1]", True),
        "conv2d_1x1_bf16": (lambda x, k: conv(x, k), (imgb, kerb),
                            "x bf16 [64,256,56,56]; w [64,256,1,1]", True),
        "layer_norm": (lambda x: jax.nn.standardize(x, axis=-1), (seq,),
                       seqc, True),
        "batch_norm_infer": (
            lambda x: (x - jnp.mean(x, (0, 2, 3), keepdims=True))
            * jax.lax.rsqrt(jnp.var(x, (0, 2, 3), keepdims=True) + 1e-5),
            (img,), "x f32 [32,64,56,56]", True),
        "max_pool2d": (
            lambda x: jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2),
                "VALID"), (img,), "x f32 [32,64,56,56] k2s2", True),
        "avg_pool2d": (
            lambda x: jax.lax.reduce_window(
                x, 0.0, jax.lax.add, (1, 1, 2, 2), (1, 1, 2, 2),
                "VALID") / 4.0, (img,), "x f32 [32,64,56,56] k2s2", True),
        "embedding_lookup": (lambda w, i: jnp.take(w, i, axis=0),
                             (emb, ids), "w f32 [50304,768]; ids [32,1024]",
                             True),
        "one_hot": (lambda i: jax.nn.one_hot(i, 50304, dtype=jnp.bfloat16),
                    (lab,), "ids [8192] -> [8192, 50304]", False),
        "gather_rows": (
            lambda lg, i: jnp.take_along_axis(lg, i[:, None], axis=1),
            (logits, lab), "logits f32 [8192, 50304]", True),
        "argmax": (lambda x: jnp.argmax(x, axis=-1), (logits,),
                   "logits f32 [8192, 50304]", False),
        "top_k": (lambda x: jax.lax.top_k(x, 8)[0], (logits,),
                  "logits f32 [8192, 50304] k=8", False),
        "softmax_ce_fused": (
            lambda lg, i: jnp.mean(
                jax.nn.logsumexp(lg, axis=-1)
                - jnp.take_along_axis(lg, i[:, None], axis=1)[:, 0]),
            (logits, lab), "fused lse-gather CE rows [8192, 50304]", True),
        "dropout": (
            lambda x: x * (jax.random.bernoulli(key, 0.9, x.shape)
                           / 0.9).astype(x.dtype),
            (seq,), seqc, True),
        "adam_update": (adam, (m1, m2, m1 * 0.1, jnp.abs(m2) * 0.1),
                        "p/g/m/v f32 [1024, 1024] fused update", False),
        "global_norm": (
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a))
                                  + jnp.sum(jnp.square(b))),
            (m1, m2), "grad-norm over two [1024,1024] leaves", False),
        "flip": (lambda x: jnp.flip(x, axis=-1), (x4b,), bigb, False),
        "tril_mask": (
            lambda x: jnp.where(
                jnp.arange(x.shape[-1])[None, :]
                <= jnp.arange(x.shape[-2])[:, None], x, -1e30),
            (_rnd((1024, 1024), jnp.float32),),
            "causal mask [1024, 1024]", False),
    })

    # the perf-critical Pallas kernel itself
    from paddle_hackathon_tpu.incubate.nn.kernels import (
        flash_attention_packed as fap)
    qkv = _rnd((8, 1024, 3 * 768), jnp.bfloat16) * 0.1
    ops["flash_attention_packed"] = (
        lambda x: fap.flash_attention_packed(x, 12, True, 0.125), (qkv,),
        "packed qkv bf16 [8, 1024, 2304] causal", True)
    return ops


def main():
    ops = build_ops()
    rows = []
    stamp = time.strftime("%Y.%m%d.%H%M%S") + ".tpu-v5e"

    # compiles happen serially on first call inside device_time — threaded
    # pre-compilation deadlocks the remote compile helper
    for name, (fn, args, cfg, diff) in ops.items():
        fwd = device_time(fn, *args)
        bwd = 0.0
        if diff:
            def loss(*a, _fn=fn):
                out = _fn(*a)
                return jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32))
            darg = tuple(i for i, a in enumerate(args)
                         if jnp.issubdtype(a.dtype, jnp.floating))
            if darg:
                bwd = device_time(jax.grad(loss, argnums=darg), *args)
        rows.append({
            "name": f"{name}_0",
            "op": name,
            "op_count": 0,
            "config": cfg,
            "timestamp": stamp,
            "paddle_gpu_time": round(fwd, 4),
            "paddle_gpu_time_backward": round(bwd, 4),
            "device": ("tpu-v5e (trace-measured device ms)"
                       if os.environ.get("GEN_OPS_TRACE") == "1" else
                       "tpu-v5e (queued-reps wall ms; see module doc)"),
        })
        print(f"{name:24s} fwd {fwd:8.3f}  bwd {bwd:8.3f} ms")
    out = os.path.join(os.path.dirname(__file__), "..",
                       "paddle_hackathon_tpu", "cost_model",
                       "static_op_benchmark.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {len(rows)} ops to", out)


if __name__ == "__main__":
    main()
