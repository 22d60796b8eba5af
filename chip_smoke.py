#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of gpt2-small (12 layers, hidden 768, 12 heads, vocab
50304; seeded random weights), in ONE process:

  kernels   every Pallas kernel family compiled by Mosaic at the shapes
            the main path dispatches (gpt2-small, plus GPT-3 1.3B's and
            ERNIE's attention shapes) and compared with the repo's jnp
            references on the same chip;
  trainer   ``parallel.make_sharded_train_step`` on a one-chip mesh,
            batch 32 x seq 1024, bf16 params: several steps on one
            repeated batch, every loss finite and the last below the
            first;
  trace     three more steps under ``profiler.Profiler``: one
            ``.xplane.pb`` holds the device's ops, the ``train_step``
            annotations and the program's ``train.dispatch`` /
            ``train.rebind`` spans, and no idle gap of the device over
            1 ms is left without a span of the program's;
  server    ``inference.ServingEngine(cache_mode="paged")`` answering
            requests of different prompt lengths submitted while others
            are in flight — bf16, then the same requests through the
            weight-only int8 artifact — judged against the dense-cache
            engine and a teacher-forced reference forward;
  multichip (only when >= 4 devices are visible) the same train step on
            {"dp": 2, "mp": 2} with ZeRO-1 and the TP-sharded
            ``generate()``.

``main()`` always demands the chip: it exits non-zero, printing no result
line, unless ``jax.default_backend() == "tpu"``.  Any phase that raises
ends the run non-zero.  The phases are importable functions taking sizes,
so ``tests/test_chip_smoke.py`` drives them tiny on the CPU.

Proof the kernels ran COMPILED comes from the executables themselves: the
program observatory's AOT pass (``observability/programs.py``) counts the
Mosaic custom calls in each compiled program's HLO, and the trainer /
server phases require the packed-flash fwd + both bwd kernels, the paged
decode kernel and the quant GEMM there.  An interpreted or bypassed
kernel leaves no custom call and fails the smoke.

The last stdout line is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Per-phase compile / step seconds are printed as set-up facts (also
written to ``chiprun_out/chip_smoke.json``); they are not benchmark
metrics.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time
import warnings

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Kernel-vs-reference tolerance: two bf16 units-in-the-last-place of the
# largest reference magnitude.  The kernels keep bf16 operands on the MXU
# with f32 accumulation; against an f32 reference they differ by the
# bf16 rounding of the probabilities and of the output — measured up to
# 0.8 ulp at the top of the range on the v5e (PR 21), never structure.
KERNEL_TOL = 2.0 ** -6

# Teacher-forced token check: every token an engine emitted must be
# within this many logit units of the reference forward's top logit at
# that position.  Random-weight gpt2-small logits have std ~0.5 and the
# bf16 forward carries ~1e-2 of rounding noise, so a greedy argmax may
# flip between near-ties (exact token equality is the wrong contract on
# random weights: 97.5% of paged-bf16 tokens equal the reference argmax
# on the v5e, the rest sit <= 0.019 below it — PR 21) but a token read
# through a wrong cache row or page lands ~2 logit units down.
LOGIT_GAP_TOL = 0.05
# ... and at least this share must BE the reference's argmax (measured
# 0.975–0.992 on the v5e; near-ties account for the rest).
ARGMAX_FLOOR = 0.9

# Serving requests: different prompt lengths, the first three submitted
# up front, the rest while those are in flight.
PROMPT_LENS = (17, 64, 33, 5, 90, 48)
NEW_TOKENS = 40


def _say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# small shared pieces
# ---------------------------------------------------------------------------

def device_report():
    """Platform, kind, count and toolchain versions, as JAX reports them."""
    import jax
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — not installed / no metadata
        libtpu = None
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "python": sys.version.split()[0]}


def bytes_in_use(device):
    """Device memory in use, asserted — a backend that reports no stats
    (or zeros after a model was placed) is not the chip."""
    stats = device.memory_stats()
    if not stats or "bytes_in_use" not in stats:
        raise RuntimeError(f"{device} reports no memory_stats()")
    n = int(stats["bytes_in_use"])
    if n <= 0:
        raise RuntimeError(f"{device} reports bytes_in_use={n} after the "
                           "model was placed")
    return n


def _programs_mark():
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    snap = get_program_registry().snapshot()["sites"]
    return {k: v["builds"] for k, v in snap.items()}


def _mosaic_since(mark, site):
    """The Mosaic kernel census of the programs built at jit site
    ``site`` since ``mark``: per kernel, the largest count any one of
    those programs holds."""
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    rec = get_program_registry().snapshot()["sites"].get(site)
    new = [h for h in (rec["history"] if rec else ())
           if h["build"] > mark.get(site, 0)]
    if not new:
        raise RuntimeError(f"no program was built at {site} — the phase "
                           "did not go through its jit site")
    out = {}
    for h in new:
        census = (h.get("analysis") or {}).get("mosaic_kernels")
        if census is None:
            raise RuntimeError(
                f"program build {h['build']} at {site} carries no Mosaic "
                "census: its AOT re-lower/compile failed "
                "(observability/programs.py _harvest_analysis)")
        for k, n in census.items():
            out[k] = max(out.get(k, 0), n)
    return out


def _require_kernels(census, wanted, where):
    missing = {k: n for k, n in wanted.items() if census.get(k, 0) < n}
    if missing:
        raise RuntimeError(
            f"{where}: compiled HLO lacks Mosaic custom calls {missing} "
            f"(found {census}) — a kernel was bypassed or interpreted")


def _gpt(cfg_kw, seed=0, dtype=None, eval_mode=False):
    import jax.numpy as jnp

    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu.models import GPTForCausalLM, gpt_config
    paddle.seed(seed)
    cfg = gpt_config("gpt2-small-en", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0, **(cfg_kw or {}))
    model = GPTForCausalLM(cfg)
    if eval_mode:
        model.eval()
    if dtype is not None:
        for _, p in model.named_parameters():
            if jnp.issubdtype(p._value.dtype, jnp.floating):
                p._set_value(p._value.astype(dtype))
    return model, cfg


def reference_gaps(model, seqs, n_new):
    """Teacher-forced judgement of generated tokens: ONE plain jitted
    forward of ``model`` over the finished sequences (no KV cache, no
    engine; XLA attention; quantized layers on ``quant_matmul_ref``), and
    for every generated token the distance between the reference's top
    logit at that position and the logit of the token that was emitted.
    Returns the per-token gaps (0 = the reference's own argmax)."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.incubate.nn.kernels import quant_matmul as qm
    from paddle_hackathon_tpu.nn.layer import functional_call

    lmax = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), lmax), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s          # causal: tail padding changes nothing
    params, buffers = model.functional_state()

    def fwd(p, x):
        out = functional_call(model, p, (Tensor(x),), buffers=buffers,
                              training=False)
        return (out._value if isinstance(out, Tensor) else out
                ).astype(jnp.float32)

    prev, qm.FORCE_KERNEL = qm.FORCE_KERNEL, False   # read at trace time
    try:
        # the whole logits come to the host (~150 MB at full width): a
        # max and a gather fused into the program on device read the
        # head matmul at two different roundings and no gap is ever 0
        logits = np.asarray(jax.jit(fwd)(params, jnp.asarray(ids)))
    finally:
        qm.FORCE_KERNEL = prev
    gaps = []
    for i, s in enumerate(seqs):
        for pos in range(len(s) - n_new[i], len(s)):
            row = logits[i, pos - 1]
            gaps.append(float(row.max() - row[s[pos]]))
    return np.asarray(gaps)


# ---------------------------------------------------------------------------
# kernels: Mosaic compile + agreement with the jnp references
# ---------------------------------------------------------------------------

def _ref_attention(q, k, v, causal):
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") \
        / math.sqrt(q.shape[-1])
    if causal:
        keep = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(keep, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _check(name, got, ref, facts):
    import jax.numpy as jnp
    ref = ref.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
    top = float(jnp.max(jnp.abs(ref)))
    facts[name] = {"max_err": round(err, 6), "ref_max": round(top, 4)}
    if not (np.isfinite(err) and err <= KERNEL_TOL * top):
        raise RuntimeError(
            f"kernel {name}: max |kernel - reference| = {err:.4g} exceeds "
            f"two bf16 ulps of the reference's range "
            f"({KERNEL_TOL * top:.4g})")


def packed_flash_case(name, b, s, heads, d, causal, facts, dropout=0.0):
    """Packed-heads flash fwd + both bwd kernels vs f32 XLA attention."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu.incubate.nn.kernels import \
        flash_attention_packed as fap
    qkv = jax.random.normal(jax.random.key(0), (b, s, 3 * heads * d),
                            jnp.float32).astype(jnp.bfloat16)
    scale = 1.0 / math.sqrt(d)
    seed = jnp.asarray([1234], jnp.int32) if dropout else None

    def loss(x):
        o = fap.flash_attention_packed(x, heads, causal, scale, dropout,
                                       seed)
        return jnp.sum(o.astype(jnp.float32) ** 2), o
    (val, out), grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(qkv)
    # what the plan runs of the score square (0.5 is the causal least)
    plan = {"plan": fap._plan(s, s, heads, d, qkv.dtype),
            "executed_score_share": fap.executed_score_share(
                s, s, heads, d, qkv.dtype, causal)}
    if dropout:
        if not bool(jnp.isfinite(val)) or not bool(
                jnp.all(jnp.isfinite(grad.astype(jnp.float32)))):
            raise RuntimeError(f"kernel {name}: non-finite with dropout")
        facts[name] = {"finite": True}
        return plan

    def ref_loss(x):
        x5 = x.astype(jnp.float32).reshape(b, s, 3, heads, d)
        o = _ref_attention(x5[:, :, 0], x5[:, :, 1], x5[:, :, 2],
                           causal).reshape(b, s, heads * d)
        return jnp.sum(o ** 2), o
    (_, rout), rgrad = jax.jit(
        jax.value_and_grad(ref_loss, has_aux=True))(qkv)
    _check(name + ".fwd", out, rout, facts)
    _check(name + ".bwd", grad, rgrad, facts)
    return plan


def bhd_flash_case(name, bh, s, d, causal, facts):
    """Head-major flash fwd + bwd kernels vs f32 XLA attention."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu.incubate.nn.kernels import \
        flash_attention as fa
    q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.float32
                                 ).astype(jnp.bfloat16)
               for kk in jax.random.split(jax.random.key(1), 3))
    scale = 1.0 / math.sqrt(d)

    def loss(q, k, v):
        o = fa.flash_attention_bhd(q, k, v, causal, scale)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def ref_loss(q, k, v):
        o = _ref_attention(*(a.astype(jnp.float32)[:, :, None]
                             for a in (q, k, v)), causal)[:, :, 0]
        return jnp.sum(o ** 2), o
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, rout), rgrads = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    _check(name + ".fwd", out, rout, facts)
    for tag, g, r in zip("qkv", grads, rgrads):
        _check(f"{name}.d{tag}", g, r, facts)


def paged_decode_case(name, slots, page, heads, d, pages_per_slot, facts):
    """The paged decode kernel vs ``paged_attention_ref`` on a random
    pool, shuffled page tables, lengths from empty to full."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu.incubate.nn.kernels import \
        paged_attention as pa
    n = slots * pages_per_slot + 1
    k1, k2, k3 = jax.random.split(jax.random.key(2), 3)
    kp = jax.random.normal(k1, (n, page, heads, d), jnp.float32
                           ).astype(jnp.bfloat16)
    vp = jax.random.normal(k2, (n, page, heads, d), jnp.float32
                           ).astype(jnp.bfloat16)
    q = jax.random.normal(k3, (slots, 1, heads, d), jnp.float32
                          ).astype(jnp.bfloat16)
    rng = np.random.RandomState(0)
    table = jnp.asarray(
        rng.permutation(n - 1).reshape(slots, pages_per_slot) + 1, jnp.int32)
    lengths = rng.randint(0, pages_per_slot * page, (slots,))
    lengths[0], lengths[-1] = 0, pages_per_slot * page - 1
    lengths = jnp.asarray(lengths, jnp.int32)
    out = jax.jit(pa.paged_attention_decode)(q, kp, vp, table, lengths)
    ref = jax.jit(pa.paged_attention_ref)(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), table, lengths)
    _check(name, out, ref, facts)


def quant_matmul_case(name, m, k, n, w_dtype, facts):
    """The fused dequant GEMM vs ``quant_matmul_ref``."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu.incubate.nn.kernels import quant_matmul as qm
    k1, k2 = jax.random.split(jax.random.key(3))
    x = jax.random.normal(k1, (m, k), jnp.float32).astype(jnp.bfloat16)
    if w_dtype == jnp.int8:
        w = jax.random.randint(k2, (k, n), -127, 128, jnp.int32
                               ).astype(jnp.int8)
    else:
        w = jax.random.normal(k2, (k, n), jnp.float32).astype(w_dtype)
    scale = jnp.full((n,), 0.01, jnp.float32)
    out = jax.jit(qm.quant_matmul_kernel)(x, w, scale)
    ref = jax.jit(qm.quant_matmul_ref)(x, w, scale)
    _check(name, out, ref, facts)


def phase_kernels(hidden=768, heads=12, seqlen=1024, batch=2, slots=8,
                  chunk=32, page=16, pages_per_slot=14, extra_shapes=True):
    """Compile every kernel family at the main path's shapes and compare
    with the references.  ``extra_shapes`` adds GPT-3 1.3B's attention
    (H16/D128), gpt2-medium's (H16/D64, eight heads a cell), a four-block
    row (s2048), ERNIE's (s512, non-causal, both layouts), a dropout
    variant and 1.3B's deepest GEMM."""
    import jax.numpy as jnp
    t_all = time.perf_counter()
    facts, seconds, plans = {}, {}, {}

    def run(case, name, *args, **kw):
        t0 = time.perf_counter()
        plan = case(name, *args, facts, **kw)
        seconds[name] = round(time.perf_counter() - t0, 2)
        if plan:
            plans[name] = plan

    d = hidden // heads
    run(packed_flash_case, "flash_packed", batch, seqlen, heads, d, True)
    run(bhd_flash_case, "flash_bhd", 2 * heads, seqlen, d, True)
    run(paged_decode_case, "paged_decode", slots, page, heads, d,
        pages_per_slot)
    # the four projections of a block, at decode (M = slots) and prefill
    # (M = slots * chunk) widths
    for m in (slots, slots * chunk):
        for k, n in ((hidden, 3 * hidden), (hidden, hidden),
                     (hidden, 4 * hidden), (4 * hidden, hidden)):
            run(quant_matmul_case, f"quant_matmul_int8.m{m}.k{k}.n{n}",
                m, k, n, jnp.int8)
    run(quant_matmul_case, f"quant_matmul_fp8.m{slots}", slots, hidden,
        3 * hidden, jnp.float8_e4m3fn)
    if extra_shapes:
        run(packed_flash_case, "flash_packed_dropout", batch, seqlen, heads,
            d, True, dropout=0.1)
        run(packed_flash_case, "flash_packed_1p3b", 1, 1024, 16, 128, True)
        run(packed_flash_case, "flash_packed_medium", 2, 1024, 16, 64, True)
        # four kv blocks a row: strips on the diagonal, whole tiles under
        run(packed_flash_case, "flash_packed_s2048", 1, 2048, 12, 64, True)
        run(packed_flash_case, "flash_packed_ernie", batch, 512, 12, 64,
            False)
        run(bhd_flash_case, "flash_bhd_ernie", 24, 512, 64, False)
        run(bhd_flash_case, "flash_bhd_1p3b", 16, 1024, 128, True)
        run(paged_decode_case, "paged_decode_1p3b", slots, page, 16, 128,
            pages_per_slot)
        # 1.3B's deepest contraction, full-K blocks (decode-width M
        # takes Mosaic ~30 s to compile: ~25k unrolled ops)
        for m in (slots, slots * chunk):
            run(quant_matmul_case, f"quant_matmul_int8.m{m}.k8192.n2048",
                m, 8192, 2048, jnp.int8)
    return {"seconds": round(time.perf_counter() - t_all, 2),
            "cases": facts, "case_seconds": seconds,
            "packed_flash_plans": plans}


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def phase_trainer(cfg_kw=None, batch=32, seqlen=1024, steps=6,
                  mesh_dims=None, zero_stage=0, devices=None,
                  on_chip=False):
    """A few train steps through ``make_sharded_train_step`` (the call
    ``bench.py bench_gpt2`` makes) on one repeated batch: every loss
    finite, the last below the first, one host sync per step."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import param_sharding_spec

    model, cfg = _gpt(cfg_kw)
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seqlen)
    mesh_dims = dict(mesh_dims or {"dp": 1})
    n_dev = int(np.prod(list(mesh_dims.values())))
    devices = list(devices or jax.devices()[:n_dev])
    mesh = parallel.create_mesh(mesh_dims, devices=devices)
    mark = _programs_mark()
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-4,
        zero_stage=zero_stage, param_dtype=jnp.bfloat16)
    facts = {"mesh": mesh_dims, "zero_stage": zero_stage,
             "layers": cfg.num_layers, "hidden": cfg.hidden_size,
             "batch": batch, "seqlen": seqlen}
    if on_chip:
        facts["bytes_in_use_after_place"] = [bytes_in_use(d)
                                             for d in devices]
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)),
                      jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)),
                         jnp.int32)
    key = jax.random.key(0)
    losses, walls = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        state, loss = step(state, ids, labels, jax.random.fold_in(key, i))
        loss.block_until_ready()         # the one host sync of the step
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss))
    facts["losses"] = [round(x, 4) for x in losses]
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"trainer: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"trainer: loss did not fall: {losses}")
    steady = sorted(walls[2:])[len(walls[2:]) // 2]
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    site = get_program_registry().snapshot()["sites"][
        "parallel.sharded_train_step"]
    facts.update(
        first_step_seconds=round(walls[0], 2),
        second_step_seconds=round(walls[1], 3),
        steady_step_seconds=round(steady, 4),
        program_builds=site["builds"]
        - mark.get("parallel.sharded_train_step", 0))
    if facts["program_builds"] != 1:
        raise RuntimeError(
            f"trainer: the step compiled {facts['program_builds']} times "
            f"over {steps} identical calls (retrace: see the program "
            "observatory's causes)")
    if on_chip:
        census = _mosaic_since(mark, "parallel.sharded_train_step")
        _require_kernels(
            census, {"flash_packed_fwd": cfg.num_layers,
                     "flash_packed_bwd_dkdv": cfg.num_layers,
                     "flash_packed_bwd_dq": cfg.num_layers},
            "train step")
        facts["mosaic_kernels"] = census
    # what the packed flash kernels said of themselves as the step was
    # traced (0.5 is the causal least)
    facts["executed_score_share"] = site["history"][-1].get(
        "kernel_facts", {}).get("executed_score_share")
    parallel.set_mesh(None)
    return facts, state, mesh


# ---------------------------------------------------------------------------
# trace: the program's spans and the device's ops on one clock
# ---------------------------------------------------------------------------

TRACE_SPANS = ("train_step", "train.dispatch", "train.rebind")
SMOKE_WAIT = "smoke.wait"       # this script's own wait for a step's loss
UNNAMED_GAP_NS = 1e6


def phase_trace(cfg_kw=None, batch=32, seqlen=1024, steps=3, on_chip=False):
    """A few trainer steps recorded by ``profiler.Profiler`` with the TPU
    target: ONE ``.xplane.pb`` holds the device's ops, one ``train_step``
    annotation a step and the program's ``train.dispatch`` /
    ``train.rebind`` spans, so every idle gap of the device over 1 ms
    falls under a span of the program's (or under this loop's own wait
    for the loss, annotated as the benchmark annotates its own).  The
    recipe an operator follows (docs/OBSERVABILITY.md, "The train step on
    the device trace")."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from paddle_hackathon_tpu import parallel, profiler
    from paddle_hackathon_tpu.models import param_sharding_spec

    model, cfg = _gpt(cfg_kw)
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seqlen)
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-4,
        param_dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seqlen)),
                      jnp.int32)
    # the keys ahead of the recording: fold_in is a device program of this
    # loop's, and its dispatch between two steps would be a gap of its own
    keys = [jax.random.fold_in(jax.random.key(0), i)
            for i in range(steps + 1)]
    state, loss = step(state, ids, ids, keys[0])      # builds, untraced
    jax.block_until_ready((loss, keys))
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.TPU])
    walls = []
    try:
        with prof:
            for key in keys[1:]:
                t0 = time.perf_counter()
                state, loss = step(state, ids, ids, key)
                with jax.profiler.TraceAnnotation(SMOKE_WAIT):
                    loss.block_until_ready()   # a gap a step, to be named
                walls.append(time.perf_counter() - t0)
        planes = trace_reduce.load_planes(
            trace_reduce.find_xplane(prof.device_trace_dir))
    finally:
        shutil.rmtree(prof.device_trace_dir, ignore_errors=True)
        parallel.set_mesh(None)
    host = [ev for pname, lines in planes.items()
            if pname.startswith(trace_reduce.HOST_PLANE_PREFIX)
            for evs in lines.values() for ev in evs
            if ev[0] in TRACE_SPANS + (SMOKE_WAIT,)]
    seen = {n: sum(ev[0] == n for ev in host) for n in TRACE_SPANS}
    # beside the trainer phase's steady_step_seconds: what recording costs
    facts = {"steps": steps, "host_spans": seen,
             "recorded_step_seconds": round(sorted(walls)[len(walls) // 2],
                                            4)}
    if any(n != steps for n in seen.values()):
        raise RuntimeError(f"trace: {steps} steps left {seen} in the "
                           "profiler's file, one of each a step expected")
    if not on_chip:
        return facts
    traced = trace_reduce.reduce(planes, TRACE_SPANS)
    if traced is None:
        raise RuntimeError(f"trace: no device op in {sorted(planes)}")
    gap_list = trace_reduce.gaps(traced["ops"])
    named = trace_reduce.name_gaps(
        [g for g in gap_list if g[1] > UNNAMED_GAP_NS], host)
    facts.update(device_ops=len(traced["ops"]), idle_gaps=len(gap_list),
                 gaps_over_1ms=[[k, round(v / 1e6, 3)] for k, v in named],
                 idle_share=round(
                     1.0 - traced["busy_s"] / traced["window_s"], 5))
    if any(k == "(no host span)" for k, _ in named):
        raise RuntimeError(f"trace: an idle gap over 1 ms falls under no "
                           f"span of the program's or of this loop's: "
                           f"{facts}")
    return facts


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def _serve(model, prompts, new_tokens, cache_mode, slots, chunk, page):
    """Answer ``prompts`` to completion: the first half submitted up
    front, the rest while those are in flight (ServingEngine arguments of
    ``bench.py bench_serving``)."""
    from paddle_hackathon_tpu.inference import ServingEngine
    max_len = max(len(p) for p in prompts) + new_tokens + 2 * chunk
    eng = ServingEngine(model, max_slots=slots, max_len=max_len, spec_k=0,
                        auto_run=False, decode_window=chunk, chunk=chunk,
                        cache_mode=cache_mode, page_size=page)
    t0 = time.perf_counter()
    half = len(prompts) // 2
    reqs = [eng.submit(p, new_tokens) for p in prompts[:half]]
    for _ in range(3):
        eng.step()
    if not any(not r.done for r in reqs):
        raise RuntimeError("server: nothing in flight at the late submit")
    reqs += [eng.submit(p, new_tokens) for p in prompts[half:]]
    eng.run_until_idle()
    cold = time.perf_counter() - t0
    for r in reqs:
        if not r.done or r.error is not None:
            raise RuntimeError(f"server: request {r.rid} failed: {r.error}")
    seqs = [np.asarray(r.result()) for r in reqs]
    for p, s in zip(prompts, seqs):
        if len(s) != len(p) + new_tokens or not (s[:len(p)] == p).all():
            raise RuntimeError("server: a result is not prompt + "
                               f"{new_tokens} new tokens")
    # the same requests again on the warm programs: steady tick time
    ticks0 = eng.stats["ticks"]
    t0 = time.perf_counter()
    again = [eng.submit(p, new_tokens) for p in prompts]
    eng.run_until_idle()
    warm = time.perf_counter() - t0
    if not all(r.done and r.error is None for r in again):
        raise RuntimeError("server: warm pass failed")
    ticks = eng.stats["ticks"] - ticks0
    eng.shutdown()
    return seqs, {"cold_pass_seconds": round(cold, 2),
                  "warm_pass_seconds": round(warm, 3),
                  "warm_ticks": ticks,
                  "warm_seconds_per_tick": round(warm / max(ticks, 1), 4)}


def phase_server(cfg_kw=None, quant=None, prompt_lens=PROMPT_LENS,
                 new_tokens=NEW_TOKENS, slots=8, chunk=32, page=16,
                 on_chip=False):
    """Paged serving judged against the dense-cache engine and the
    teacher-forced reference forward.  ``quant="int8"`` serves the
    ``save_for_serving(quant=)`` -> ``load_for_serving`` artifact."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu.inference.serving import (load_for_serving,
                                                        save_for_serving)
    model, cfg = _gpt(cfg_kw, dtype=jnp.bfloat16, eval_mode=True)
    facts = {"quant": quant, "layers": cfg.num_layers,
             "hidden": cfg.hidden_size}
    if quant is not None:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_art") as d:
            art = os.path.join(d, "artifact")
            save_for_serving(model, art, quant=quant)
            model = load_for_serving(art)
    if on_chip:
        facts["bytes_in_use_after_place"] = bytes_in_use(jax.devices()[0])
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prompt_lens]
    n_new = [new_tokens] * len(prompts)

    mark = _programs_mark()
    paged, facts["paged"] = _serve(model, prompts, new_tokens, "paged",
                                   slots, chunk, page)
    if on_chip:
        census = _mosaic_since(mark, "serving.tick_multi")
        wanted = {"paged_decode": cfg.num_layers}
        if quant is not None:
            wanted["quant_matmul"] = 4 * cfg.num_layers
        _require_kernels(census, wanted, f"decode tick (quant={quant})")
        facts["decode_tick_mosaic_kernels"] = census
        if quant is not None:
            prefill = _mosaic_since(mark, "serving.tick")
            _require_kernels(prefill,
                             {"quant_matmul": 4 * cfg.num_layers},
                             f"prefill tick (quant={quant})")
    dense, facts["dense"] = _serve(model, prompts, new_tokens, "dense",
                                   slots, chunk, page)

    for tag, seqs in (("paged", paged), ("dense", dense)):
        gaps = reference_gaps(model, seqs, n_new)
        facts[tag]["max_logit_gap_vs_reference"] = round(float(gaps.max()),
                                                         4)
        facts[tag]["tokens_equal_reference_argmax"] = round(
            float((gaps == 0).mean()), 4)
        if not gaps.max() <= LOGIT_GAP_TOL:
            raise RuntimeError(
                f"server ({tag}, quant={quant}): an emitted token sits "
                f"{gaps.max():.3f} logit units below the reference "
                f"forward's top logit (tolerance {LOGIT_GAP_TOL})")
        if (gaps == 0).mean() < ARGMAX_FLOOR:
            raise RuntimeError(
                f"server ({tag}, quant={quant}): only "
                f"{(gaps == 0).mean():.3f} of the emitted tokens are the "
                f"reference forward's argmax (floor {ARGMAX_FLOOR})")
    facts["paged_vs_dense_token_agreement"] = round(float(np.mean(
        [np.mean(a[-new_tokens:] == b[-new_tokens:])
         for a, b in zip(paged, dense)])), 4)
    return facts


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _shard_facts(name, arr, mesh):
    """Devices and shard shape of one array; the shard shape must be the
    global shape divided by the mesh axes its spec names."""
    spec = tuple(arr.sharding.spec) + (None,) * (
        arr.ndim - len(arr.sharding.spec))
    want = []
    for dim, ax in zip(arr.shape, spec):
        axes = () if ax is None else (ax if isinstance(ax, tuple) else (ax,))
        want.append(dim // int(np.prod([mesh.shape[a] for a in axes] or [1])))
    shapes = {tuple(s.data.shape) for s in arr.addressable_shards}
    devs = {s.device for s in arr.addressable_shards}
    if shapes != {tuple(want)}:
        raise RuntimeError(f"{name}: shard shapes {shapes}, expected "
                           f"{tuple(want)} for spec {spec}")
    return {"spec": [str(a) for a in spec], "shard_shape": list(want),
            "devices": len(devs)}


def phase_multichip(cfg_kw=None, batch=32, seqlen=1024, steps=4,
                    one_chip_first_loss=None, loss_tol=0.05, on_chip=False):
    """The same train step on {"dp": 2, "mp": 2} with ZeRO-1, then the
    TP-sharded ``generate()`` on the same mesh.  Asserts where the shards
    live, that all four devices hold memory, and that the first-step loss
    agrees with the one-chip run."""
    import jax
    import jax.numpy as jnp

    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.models import param_sharding_spec

    devices = jax.devices()[:4]
    facts, state, mesh = phase_trainer(
        cfg_kw, batch=batch, seqlen=seqlen, steps=steps,
        mesh_dims={"dp": 2, "mp": 2}, zero_stage=1, devices=devices,
        on_chip=on_chip)
    name = next(k for k in state["params"] if k.endswith(
        "attn.qkv_proj.weight"))
    p = _shard_facts(name, state["params"][name], mesh)
    m = _shard_facts(name + ".m", state["opt_state"][name]["m"], mesh)
    facts["param_shards"], facts["moment_shards"] = p, m
    if p["devices"] != 4 or m["devices"] != 4:
        raise RuntimeError(f"shards not on four devices: {p} {m}")
    if "mp" not in p["spec"] or not {"mp", "dp"} <= set(m["spec"]):
        raise RuntimeError(f"{name}: param spec {p['spec']} / moment spec "
                           f"{m['spec']} — expected TP on 'mp' and ZeRO-1 "
                           "moments additionally on 'dp'")
    if on_chip:
        facts["bytes_in_use"] = [bytes_in_use(d) for d in devices]
    if one_chip_first_loss is not None:
        delta = abs(facts["losses"][0] - one_chip_first_loss)
        facts["first_loss_delta_vs_one_chip"] = round(delta, 5)
        if not delta <= loss_tol:
            raise RuntimeError(
                f"first-step loss {facts['losses'][0]} on dp2 x mp2 vs "
                f"{one_chip_first_loss} on one chip (tolerance {loss_tol})")
    del state

    # TP-sharded one-program decode on the same mesh
    model, cfg = _gpt(cfg_kw, dtype=jnp.bfloat16, eval_mode=True)
    mesh = parallel.create_mesh({"dp": 2, "mp": 2}, devices=devices)
    parallel.shard_params(model, mesh, rule=param_sharding_spec)
    rng = np.random.RandomState(0)
    prompt, new = 16, 16
    ids = rng.randint(0, cfg.vocab_size, (4, prompt)).astype(np.int32)
    t0 = time.perf_counter()
    out = np.asarray(model.generate(Tensor(jnp.asarray(ids)),
                                    max_new_tokens=new,
                                    temperature=0.0).numpy())
    facts["generate_seconds"] = round(time.perf_counter() - t0, 2)
    if out.shape != (4, prompt + new) or not (out[:, :prompt] == ids).all():
        raise RuntimeError(f"TP generate(): bad output shape {out.shape}")
    gaps = reference_gaps(model, list(out), [new] * len(out))
    facts["generate_max_logit_gap_vs_reference"] = round(float(gaps.max()),
                                                         4)
    if not gaps.max() <= LOGIT_GAP_TOL:
        raise RuntimeError(
            f"TP generate(): an emitted token sits {gaps.max():.3f} logit "
            f"units below the reference's top logit")
    parallel.set_mesh(None)
    return facts


# ---------------------------------------------------------------------------
# main: always on the chip
# ---------------------------------------------------------------------------

def _compile_meter():
    """Totals of what jax itself reports about compilation in this
    process (``jax.monitoring``): seconds inside the backend compile call
    (on a persistent-cache hit that is the load, not a compile), and the
    persistent cache's hits and misses.  The two-run cache proof reads
    these: run 2's backend seconds are a small fraction of run 1's."""
    import jax.monitoring as mon
    totals = {"backend_compile_seconds": 0.0, "trace_seconds": 0.0,
              "lowering_seconds": 0.0, "cache_hits": 0, "cache_misses": 0}
    durations = {
        "/jax/core/compile/backend_compile_duration":
            "backend_compile_seconds",
        "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
        "/jax/core/compile/jaxpr_to_mlir_module_duration":
            "lowering_seconds"}
    counts = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def on_duration(event, seconds, **kw):
        if event in durations:
            totals[durations[event]] += seconds

    def on_event(event, **kw):
        if event in counts:
            totals[counts[event]] += 1
    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    return totals


def main():
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, jax.default_backend() is "
              f"{backend!r} — no result", file=sys.stderr)
        return 2
    t_all = time.perf_counter()
    # a Pallas lowering error inside a trainer that warns-and-falls-back
    # (hapi Model.fit) must not pass as a run on the chip
    warnings.simplefilter("error", RuntimeWarning)

    from paddle_hackathon_tpu.core import native
    from paddle_hackathon_tpu.core.compile_cache import enable_compile_cache
    from paddle_hackathon_tpu.observability.programs import program_analysis
    cache_dir = enable_compile_cache()
    compile_totals = _compile_meter()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    dev = device_report()
    facts = {"device": dev, "compile_cache": {"dir": cache_dir,
                                              "entries_at_start": entries},
             "native_runtime_built": native.load() is not None}
    _say(f"device {dev}")
    _say(f"compile cache {cache_dir} ({entries} entries at start); native "
         f"runtime built: {facts['native_runtime_built']}")

    with program_analysis():
        facts["kernels"] = phase_kernels()
        _say(f"kernels ok in {facts['kernels']['seconds']} s "
             f"({len(facts['kernels']['cases'])} comparisons; seconds per "
             f"case {facts['kernels']['case_seconds']}; packed flash "
             f"plans {facts['kernels']['packed_flash_plans']})")
        facts["trainer"], state, _ = phase_trainer(on_chip=True)
        del state
        _say(f"trainer ok {facts['trainer']}")
        facts["trace"] = phase_trace(on_chip=True)
        _say(f"trace ok {facts['trace']}")
        facts["server_bf16"] = phase_server(on_chip=True)
        _say(f"server bf16 ok {facts['server_bf16']}")
        facts["server_int8"] = phase_server(quant="int8", on_chip=True)
        _say(f"server int8 ok {facts['server_int8']}")
        if len(jax.devices()) >= 4:
            facts["multichip"] = phase_multichip(
                one_chip_first_loss=facts["trainer"]["losses"][0],
                on_chip=True)
            _say(f"multichip ok {facts['multichip']}")
        else:
            _say(f"multichip skipped: {len(jax.devices())} device(s)")

    facts["compile"] = {k: round(v, 2) for k, v in compile_totals.items()}
    _say(f"compile totals {facts['compile']}")
    facts["total_seconds"] = round(time.perf_counter() - t_all, 1)
    outdir = os.path.join(HERE, "chiprun_out")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, "chip_smoke.json"), "a") as fh:
        fh.write(json.dumps(facts) + "\n")
    _say(f"all phases ok in {facts['total_seconds']} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
