"""Pallas TPU flash attention — forward and backward kernels.

The reference has no flash attention; its fused attention CUDA ops
(``paddle/fluid/operators/fused/fused_attention_op.cu``, ``fmha_ref.h``)
materialise the full (s, s) probability matrix. On TPU the memory-bound
classic attention wastes HBM bandwidth and caps sequence length, so the
framework's fused-attention slot is filled with an online-softmax tiled
kernel instead: O(s) memory, MXU-shaped (block_q x d) @ (d x block_kv)
tiles, f32 accumulators in VMEM scratch.

Layout contract: (batch*heads, seq, head_dim) arrays; head_dim needs no
explicit lane padding (Mosaic pads sub-128 lanes in VMEM; explicit padding
would cost real HBM copies). Gradients follow the standard two-kernel
split (dk/dv accumulate over q blocks; dq accumulates over kv blocks) with
the log-sum-exp saved from the forward pass and ``delta = rowsum(dO * O)``
precomputed in XLA.

All operands arrive in natural (s, d) block layout; where a contraction
needs a (d, s) operand it is transposed *in VMEM* inside the kernel (a
register shuffle) rather than pre-transposed by XLA — the XLA transposes
cost a full HBM read+write per tensor per pass and doubled the kernels'
input DMA streams (measured ~10% of the gpt2 train step as pure `copy`
ops).

Causal masking skips work at the *grid* level: the kv-block index map
clamps to the diagonal, so cells entirely above it re-request the previous
block index — Pallas elides the DMA — and a ``pl.when`` skips the compute.
This makes causal attention cost ~(n+1)/2n of full instead of always-full
(the old kernels only skipped compute, and only between whole blocks).

On non-TPU backends the same kernels run under the Pallas interpreter so
numerics are testable on the virtual CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30  # large-but-finite: keeps exp()=0 without inf-inf NaNs
_LANES = 128
_SUB = 8  # sublane count of the (8, seq) stats (lse/delta) layout


def _prec(dtype):
    # f32 operands: keep full precision (DEFAULT would run them at bf16
    # MXU rate and lose bits). bf16 operands: DEFAULT — the global
    # 'highest' default would request an fp32 contract Mosaic rejects.
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dropout_keep(seed, b, q_pos, k_pos, keep_prob):
    """Layout-independent dropout mask: a murmur-style integer hash of
    (seed, batch*head, q position, k position) so the forward kernel and
    both backward kernels — which see the score matrix in different
    layouts — regenerate the identical mask without storing it (the
    reference's fused attention stores the O(s^2) mask; fmha_ref.h).
    int32 ops wrap, which is fine for mixing."""
    # avalanche the (seed, b) word BEFORE mixing positions, with distinct
    # odd constants per coordinate — otherwise masks are shifted copies
    # across batch*head (h would depend on b + q_pos only)
    h = (seed ^ (b * jnp.int32(-2048144789))).astype(jnp.int32)  # 0x85EBCA6B
    h = (h ^ (h >> 16)) * jnp.int32(-1640531527)    # 0x9E3779B9
    h = h + q_pos * jnp.int32(-1028477387)          # 0xC2B2AE35
    h = (h ^ (h >> 13)) * jnp.int32(668265261)      # 0x27D4EB2F
    h = h + k_pos * jnp.int32(461845907)            # 0x1B873593
    h = (h ^ (h >> 16)) * jnp.int32(-2048144789)
    h = h ^ (h >> 13)
    bits23 = h & jnp.int32(0x7FFFFF)
    thresh = jnp.int32(int(keep_prob * float(0x800000)))
    return bits23 < thresh


def _smem_spec():
    """(1,) int32 scalar input block (seed) — SMEM on TPU, plain block
    under the CPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu
    if _interpret():
        return pl.BlockSpec((1,), lambda *_: (0,))
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _block_sizes(sq: int, skv: int, dtype=jnp.bfloat16):
    """Pick (block_q, block_kv). Swept on v5e in round 2 (jax 0.4.37;
    fwd+bwd, bf16, d=64, B*H=288): square 1024x1024 blocks won at every
    seq length that admits them — 12.9 ms vs 19.5 for (1024,512) at
    S=1024, 23.7 vs 25.8 at S=4096. Wider blocks blow the 16 MB
    scoped-VMEM budget (the s/p temporaries are f32 (bq, bkv): 4 MB at
    1024^2); with f32 *operands* the backward's doubled input blocks push
    a 1024^2 grid cell past the budget too, so f32 caps at 512.  Mosaic
    under jax 0.9.0 / libtpu 0.0.34 accepts the same footprints (no
    ``vmem_limit_bytes`` needed; chip_smoke.py, PR 21); the sweep itself
    has not been repeated there.

    ``paddle.incubate.autotune`` overrides this default per shape: a
    measured winner in the autotune cache (keyed like _tune_key) wins."""
    from ....core import autotune as _at
    cached = _at.kernel_cache.get(_tune_key(sq, skv, dtype))         if _at.enabled() else None
    if cached is not None:
        return cached
    cap = _vmem_cap(dtype)
    bq = next((b for b in _BLOCK_CANDIDATES
               if b <= min(sq, cap) and sq % b == 0), None)
    bkv = next((b for b in _BLOCK_CANDIDATES
                if b <= min(skv, cap) and skv % b == 0), None)
    if bq is None or bkv is None:
        return None
    return bq, bkv


_BLOCK_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)


def _vmem_cap(dtype):
    """Largest admissible block edge under the 16 MB scoped-VMEM budget
    (single source for the default chooser AND the autotuner's candidate
    set — they must agree on what is safe)."""
    return 1024 if jnp.dtype(dtype).itemsize <= 2 else 512


def _tune_key(sq, skv, dtype):
    return ("flash_attention_blocks", sq, skv, jnp.dtype(dtype).itemsize)


def _candidate_blocks(sq, skv, dtype):
    cap = _vmem_cap(dtype)
    cands = []
    for bq in (1024, 512, 256, 128):
        for bkv in (1024, 512, 256, 128):
            if bq <= min(sq, cap) and bkv <= min(skv, cap)                     and sq % bq == 0 and skv % bkv == 0:
                cands.append((bq, bkv))
    return cands


def maybe_autotune(q, k, v, causal, sm_scale):
    """Eager-mode block-shape autotune (ref ``auto_tune_base.h``): when
    ``incubate.autotune`` enabled kernel tuning and we are inside the
    tuning step window, measure the fwd kernel across candidate block
    shapes for this (sq, skv, dtype) signature and cache the winner.
    No-op under a jit trace (nothing can be measured) — the cache filled
    during eager warmup steps then serves compiled calls too. Measurement
    covers the forward kernel only (the backward shares the cached block
    choice); the static default remains the bwd-swept optimum when tuning
    is off."""
    from ....core import autotune as _at
    if not (_at.enabled() and _at.in_tuning_window()):
        return
    if isinstance(q, jax.core.Tracer) or _interpret():
        return
    sq, skv = q.shape[1], k.shape[1]
    key = _tune_key(sq, skv, q.dtype)
    if _at.kernel_cache.get(key) is not None:
        return
    default = _block_sizes(sq, skv, q.dtype)
    cands = _candidate_blocks(sq, skv, q.dtype)

    def measure(blocks):
        def run():
            out, _ = _fwd(q, k, v, causal, sm_scale, _blocks=blocks)
            jax.block_until_ready(out)
            float(jnp.sum(out[..., :1].astype(jnp.float32)))  # hard sync
        run()  # compile outside the timed reps
        return _at.measure_wall(run)

    _at.tune(key, cands, measure, default=default)


def supported(sq: int, skv: int) -> bool:
    """Whether the kernel handles these sequence lengths (else XLA path)."""
    return _block_sizes(sq, skv) is not None


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, block_q,
                block_kv, n_kv, dropout_p):
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0]
        kt = jnp.swapaxes(k_ref[0], 0, 1)        # (d, block_kv) in-VMEM
        v = v_ref[0]
        # standard (1),(0) contraction — the only dot shape Mosaic's bf16
        # matmul supports; the k transpose is a VMEM register shuffle
        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype))
        s = s * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_ref[...]                      # (block_q, LANES)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # (block_q, 1)
        m_next = jnp.maximum(m_prev, m_cur)              # (block_q, LANES)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])                   # (block_q, block_kv)
        l_ref[...] = l_prev * alpha + jnp.sum(
            p, axis=1, keepdims=True) * jnp.ones_like(l_prev)
        if dropout_p > 0.0:
            # drop the unnormalised p only in the PV accumulation: the
            # final /l then equals dropout(softmax(s)) @ v, and lse stays
            # the exact (undropped) statistic the backward needs
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            keep = _dropout_keep(seed_ref[0], bi, q_pos, k_pos,
                                 1.0 - dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(v.dtype))
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_next

    if causal:
        @pl.when(ki * block_kv <= qi * block_q + block_q - 1)
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked row -> zeros out
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # store lse transposed as (8, block_q) sublane-broadcast rows: a
        # (bh, 8, sq) stats array costs 8 f32 lanes per token in HBM where
        # the old lane-broadcast (bh, sq, 128) layout cost 128 — at
        # B*H=288, S=1024 that is 9.4 MB vs 151 MB of residual per layer
        lse2d = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[0] = jnp.swapaxes(lse2d[:, :_SUB], 0, 1)


def _kv_index(causal, bq, bkv, n_kv):
    """kv-block index map: clamp to the causal diagonal so fully-masked
    cells repeat the previous block index (Pallas elides the DMA).  The
    diagonal position is additionally clamped into [0, n_kv) — with
    sq != skv it can land past the last kv block."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def idx(b, i, j):
        diag = jnp.minimum((i * bq + bq - 1) // bkv, n_kv - 1)
        return (b, jnp.minimum(j, diag), 0)
    return idx


def _fwd(q, k, v, causal, sm_scale, dropout_p=0.0, seed=None,
         _blocks=None):
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq, bkv = _blocks or _block_sizes(sq, skv, q.dtype)
    n_q, n_kv = sq // bq, skv // bkv

    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, n_kv=n_kv, dropout_p=dropout_p)
    kv_idx = _kv_index(causal, bq, bkv, n_kv)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), kv_idx),
            pl.BlockSpec((1, bkv, d), kv_idx),
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, _SUB, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, _SUB, sq), jnp.float32),
        ],
        scratch_shapes=_fwd_scratch(bq, d),
        interpret=_interpret(),
        name="flash_bhd_fwd",
    )(q, k, v, seed)
    return out, lse


def _fwd_scratch(bq, d):
    from jax.experimental.pallas import tpu as pltpu
    return [
        pltpu.VMEM((bq, d), jnp.float32),       # acc
        pltpu.VMEM((bq, _LANES), jnp.float32),  # m
        pltpu.VMEM((bq, _LANES), jnp.float32),  # l
    ]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref,
                     lse_ref, delta_ref, seed_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                     block_q, block_kv, n_q, dropout_p):
    """dk/dv in transposed (kv, q) layout.

    Every contraction is a standard (1),(0) dot — the only shape Mosaic's
    native bf16 matmul supports — by computing s^T = k @ q^T with the
    (d, block_q) operands produced by in-VMEM transposes (register
    shuffles; the old XLA pre-transposes cost an HBM pass per tensor and
    doubled the kernel's input DMA streams).
    lse/delta arrive as (8, block_q) sublane-broadcast rows. bf16 operands
    stay bf16 on the MXU (f32 accumulate); only softmax/elementwise math
    is f32.
    """
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0]                            # (block_q, d)
        qt = jnp.swapaxes(q, 0, 1)              # (d, block_q) in-VMEM
        k = k_ref[0]                            # (block_kv, d)
        v = v_ref[0]
        do = do_ref[0]                          # (block_q, d)
        dot_ = jnp.swapaxes(do, 0, 1)           # (d, block_q) = dO^T
        lse = lse_ref[0][:1, :]                 # (1, block_q)
        delta = delta_ref[0][:1, :]
        # s^T = (k @ q^T) * scale                 (block_kv, block_q)
        st = jax.lax.dot_general(k, qt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(k.dtype))
        st = st * sm_scale
        if causal:
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, block_q), 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp(st - lse)                  # (block_kv, block_q)
        pt_v = pt
        if dropout_p > 0.0:
            # same positional-hash mask as the forward (transposed layout:
            # k along rows, q along columns)
            k_pos_t = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, block_q), 0)
            q_pos_t = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, block_q), 1)
            keep = _dropout_keep(seed_ref[0], bi, q_pos_t, k_pos_t,
                                 1.0 - dropout_p)
            pt_v = jnp.where(keep, pt / (1.0 - dropout_p), 0.0)
        # dv += dropout(p)^T @ dO                 (block_kv, d)
        dv_acc[...] += jax.lax.dot_general(
            pt_v.astype(v.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(v.dtype))
        # dp^T = v @ dO^T                         (block_kv, block_q)
        dpt = jax.lax.dot_general(v, dot_, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32,
                                  precision=_prec(v.dtype))
        if dropout_p > 0.0:
            dpt = jnp.where(keep, dpt / (1.0 - dropout_p), 0.0)
        dst = pt * (dpt - delta) * sm_scale
        # dk += ds^T @ q                          (block_kv, d)
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(k.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(k.dtype))

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_kv)
        def _run():
            _body()
    else:
        _body()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   seed_ref,
                   dq_ref, dq_acc, *, sm_scale, causal, block_q, block_kv,
                   n_kv, dropout_p):
    """dq in natural (q, kv) layout; the (d, block_kv) operands are in-VMEM
    transposes of the natural k/v blocks so every dot is a standard (1),(0)
    bf16 contraction (see dkdv kernel).
    lse/delta arrive in the (8, block_q) stats layout and are transposed to
    a (block_q, 1) column in-VMEM (a cheap sublane/lane swap)."""
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        q = q_ref[0]                            # (block_q, d)
        k = k_ref[0]                            # (block_kv, d)
        kt = jnp.swapaxes(k, 0, 1)              # (d, block_kv) in-VMEM
        vt = jnp.swapaxes(v_ref[0], 0, 1)       # (d, block_kv)
        do = do_ref[0]                          # (block_q, d)
        lse = jnp.swapaxes(lse_ref[0], 0, 1)[:, :1]     # (block_q, 1)
        delta = jnp.swapaxes(delta_ref[0], 0, 1)[:, :1]
        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(q.dtype))
        s = s * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        # dp = dO @ v^T                           (block_q, block_kv)
        dp = jax.lax.dot_general(do, vt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_prec(do.dtype))
        if dropout_p > 0.0:
            q_pos2 = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos2 = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            keep = _dropout_keep(seed_ref[0], bi, q_pos2, k_pos2,
                                 1.0 - dropout_p)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta) * sm_scale
        # dq += ds @ k                            (block_q, d)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(k.dtype))

    if causal:
        @pl.when(ki * block_kv <= qi * block_q + block_q - 1)
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd(causal, sm_scale, dropout_p, res, do):
    q, k, v, out, lse, seed = res
    delta_row = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                          # (bh, sq)
    dq, dk, dv = _bwd_pair(q, k, v, do, lse, delta_row, causal, sm_scale,
                           dropout_p, seed)
    return dq, dk, dv, None


def _bwd_pair(q, k, v, do, lse, delta_row, causal, sm_scale,
              dropout_p=0.0, seed=None):
    """(dq, dk, dv) for one q-chunk x kv-chunk pair, given the *global*
    softmax statistics of the q rows: ``lse`` in the (bh, 8, sq) stats
    layout and ``delta_row = rowsum(dO * O_final)`` as (bh, sq).

    This is the whole-sequence backward when the pair covers the full
    sequence — and the per-step building block of ring attention, where
    the same q rows pair with a rotating kv chunk (Liu et al. 2023): with
    global lse/delta the per-pair grads sum exactly to the full-attention
    gradient."""
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq, bkv = _block_sizes(sq, skv, q.dtype)
    n_q, n_kv = sq // bq, skv // bkv
    from jax.experimental.pallas import tpu as pltpu

    delta_t = jnp.broadcast_to(delta_row[:, None, :], (bh, _SUB, sq))
    lse_t = lse                                           # (bh, 8, sq)

    # causal: q-block index map clamped to the diagonal from the other side
    # (the first q block that attends to kv block j) — skipped cells repeat
    # the previous q index so their DMA is elided.  Clamped into [0, n_q)
    # for the skv > sq case where the diagonal falls past the last q block.
    if causal:
        def q_idx(b, j, i):
            first = jnp.minimum((j * bkv) // bq, n_q - 1)
            return (b, jnp.maximum(i, first), 0)

        def stat_idx(b, j, i):
            first = jnp.minimum((j * bkv) // bq, n_q - 1)
            return (b, 0, jnp.maximum(i, first))
    else:
        def q_idx(b, j, i):
            return (b, i, 0)

        def stat_idx(b, j, i):
            return (b, 0, i)

    dkdv = functools.partial(
        _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, n_q=n_q, dropout_p=dropout_p)
    dk, dv = pl.pallas_call(
        dkdv,
        grid=(bh, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_idx),                        # q
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, d), q_idx),                        # do
            pl.BlockSpec((1, _SUB, bq), stat_idx),                  # lse^T
            pl.BlockSpec((1, _SUB, bq), stat_idx),                  # delta^T
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bhd_bwd_dkdv",
    )(q, k, v, do, lse_t, delta_t, seed)

    kv_idx = _kv_index(causal, bq, bkv, n_kv)
    dqk = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, n_kv=n_kv, dropout_p=dropout_p)
    dq = pl.pallas_call(
        dqk,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),    # q
            pl.BlockSpec((1, bkv, d), kv_idx),                      # k
            pl.BlockSpec((1, bkv, d), kv_idx),                      # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),    # do
            pl.BlockSpec((1, _SUB, bq), lambda b, i, j: (b, 0, i)),  # lse
            pl.BlockSpec((1, _SUB, bq), lambda b, i, j: (b, 0, i)),  # delta
            _smem_spec(),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bhd_bwd_dq",
    )(q, k, v, do, lse_t, delta_t, seed)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_bhd(q, k, v, causal, sm_scale, dropout_p=0.0,
                        seed=None):
    """Flash attention over (batch*heads, seq, head_dim) arrays.

    ``dropout_p`` drops attention probabilities inside the kernel (the
    mask is a positional hash of ``seed``, regenerated — never stored —
    in the backward kernels). ``seed`` is a (1,) int32 array; required
    when ``dropout_p > 0``.
    """
    out, _ = _fwd(q, k, v, causal, sm_scale, dropout_p, seed)
    return out


def _vjp_fwd(q, k, v, causal, sm_scale, dropout_p=0.0, seed=None):
    out, lse = _fwd(q, k, v, causal, sm_scale, dropout_p, seed)
    return out, (q, k, v, out, lse, seed)


flash_attention_bhd.defvjp(_vjp_fwd, _bwd)
