"""Adjudicate the attention kernel: ours vs jax's pallas kernels, by
trace-measured op time inside the REAL train step (VERDICT r4 #4 — the
round-3 note "jax's flash_attention is within ~25% per call" left
unresolved whether the headline has attention fat).  Per-kernel time comes
from the device trace, not from wall-clock microbenches, which time the
host's dispatch along with the kernel.

Candidates, each spliced into GPTAttention's fast path for a full traced
train step:
  packed    — this repo's packed-heads family (consumes the qkv projection
              output directly; in-kernel transposes; the round-2+ default)
  jax_flash — jax.experimental.pallas.ops.tpu.flash_attention (needs
              (b, h, s, d): head split/merge transposes around the call)
  splash    — jax.experimental.pallas.ops.tpu.splash_attention (same
              layout; its vjp recomputes per its own schedule)

Usage: python tools/adjudicate_attention.py [--batch 32] [--seq 1024]
"""
import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np


def _jax_flash_from_packed(qkv_t, num_heads, causal):
    """(b, s, 3hd) -> jax flash kernel -> (b, s, hd)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    from paddle_hackathon_tpu.core.autograd import apply_op

    def fn(qkv):
        b, s, hd3 = qkv.shape
        d = hd3 // 3 // num_heads
        x = qkv.reshape(b, s, 3, num_heads, d)
        q, k, v = [jnp.transpose(x[:, :, i], (0, 2, 1, 3))
                   for i in range(3)]          # (b, h, s, d)
        # bf16 operands at DEFAULT precision (the framework's global
        # 'highest' would make the jax kernel request an fp32 contract
        # Mosaic rejects — same choice our kernels' _prec() makes)
        blocks = None
        if os.environ.get("ADJ_TUNED_BLOCKS"):
            bq = min(1024, s)
            blocks = jfa.BlockSizes(
                block_q=bq, block_k_major=bq, block_k=bq, block_b=1,
                block_q_major_dkv=bq, block_k_major_dkv=bq,
                block_k_dkv=bq, block_q_dkv=bq,
                block_k_major_dq=bq, block_k_dq=bq, block_q_dq=bq)
        with jax.default_matmul_precision("default"):
            o = jfa.flash_attention(q, k, v, causal=causal,
                                    sm_scale=1.0 / d ** 0.5,
                                    block_sizes=blocks)
        return jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, -1)

    return apply_op("jax_flash_attention", fn, [qkv_t])


def _splash_from_packed(qkv_t, num_heads, causal):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    from paddle_hackathon_tpu.core.autograd import apply_op

    def fn(qkv):
        b, s, hd3 = qkv.shape
        d = hd3 // 3 // num_heads
        x = qkv.reshape(b, s, 3, num_heads, d)
        q, k, v = [jnp.transpose(x[:, :, i], (0, 2, 1, 3))
                   for i in range(3)]
        mask = (sm.CausalMask((s, s)) if causal
                else sm.FullMask((s, s)))
        kernel = sk.make_splash_mha(
            mask=sm.MultiHeadMask([mask] * num_heads),
            head_shards=1, q_seq_shards=1)
        with jax.default_matmul_precision("default"):
            o = jax.vmap(kernel)(q * (1.0 / d ** 0.5), k, v)
        return jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, -1)

    return apply_op("splash_attention", fn, [qkv_t])


def run_one(impl, batch, seqlen, outdir):
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTForCausalLM, gpt_config,
                                             param_sharding_spec)

    if impl != "packed":
        # the framework's global 'highest' default would make the jax
        # kernels' BACKWARD (traced during grad, outside any local
        # context) request fp32 contracts on bf16 that Mosaic rejects;
        # our kernels pin per-dot precision instead (_prec()).  The model
        # matmuls run bf16 either way, so the step compare stays fair.
        jax.config.update("jax_default_matmul_precision", "default")
        import paddle_hackathon_tpu.incubate.nn.functional as IF
        fn = (_jax_flash_from_packed if impl == "jax_flash"
              else _splash_from_packed)
        orig = IF.flash_attention_qkv_packed

        def patched(qkv, num_heads, causal=True, sm_scale=None,
                    dropout_p=0.0, seed=None):
            assert dropout_p == 0.0
            return fn(qkv, num_heads, causal)
        # GPTAttention imports the symbol at call time from the package
        IF.flash_attention_qkv_packed = patched

    paddle.seed(0)
    cfg = gpt_config("gpt2-small-en", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0, use_flash_attention=True)
    model = GPTForCausalLM(cfg)
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-4,
        zero_stage=0, param_dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)),
                      jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)),
                         jnp.int32)
    key = jax.random.key(0)
    for _ in range(3):
        state, loss = step(state, ids, labels, key)
    float(loss)
    shutil.rmtree(outdir, ignore_errors=True)
    jax.profiler.start_trace(outdir)
    for _ in range(3):
        state, loss = step(state, ids, labels, key)
    float(loss)
    jax.profiler.stop_trace()

    from trace_util import toplevel_device_ms
    total = toplevel_device_ms(outdir) / 3
    # per-impl kernel names differ (ours: jvp__.N pallas calls; jax's:
    # their own fusion names) — the step total is the decisive number
    tok_s = batch * seqlen / (total / 1e3)
    print(f"{impl:10s} step {total:7.2f} ms  {tok_s:,.0f} tok/s-equivalent")
    return {"impl": impl, "step_ms": total, "tok_s": tok_s}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--impls", default="packed,jax_flash,splash")
    args = ap.parse_args()
    results = []
    for impl in args.impls.split(","):
        # fresh subprocess per impl: the monkeypatch and compile caches
        # must not leak across candidates.  One process per chip: this
        # parent imports jax but never initialises a backend, so each
        # child can take the chip (tests/test_chip_smoke.py pins it)
        import subprocess
        code = (f"import sys; sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r}); "
                f"from adjudicate_attention import run_one; "
                f"run_one({impl!r}, {args.batch}, {args.seq}, "
                f"'/tmp/adjudicate_{impl}')")
        proc = subprocess.run([sys.executable, "-c", code], timeout=1200)
        if proc.returncode != 0:
            print(f"{impl}: FAILED (rc {proc.returncode})")
    print("(per-impl rows printed above by subprocesses)")


if __name__ == "__main__":
    main()
