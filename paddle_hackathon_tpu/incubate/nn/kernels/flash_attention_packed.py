"""Packed-heads Pallas flash attention: consumes the qkv projection output
directly.

The (bh, s, d) kernels in ``flash_attention.py`` require the model to
reorganize activations (b, s, H*D) -> (b, H, s, d) around every attention
call; XLA materializes those as layout-change copies (measured ~10% of the
gpt2-small train step, plus the (3,b,s,H,d) gradient re-assembly fusions).
The reference pays the same cost on GPU inside
``fused_attention_op.cu``'s transpose stage (``fmha_ref.h``).

This kernel family keeps everything in the projection-native layout:

- input is the fused qkv projection output ``(b, s, 3*H*D)`` — q/k/v are
  *lane-offset BlockSpecs into the same array*, so no split, reshape, or
  transpose ever exists in HBM;
- heads are processed in *groups* of G per grid cell (one extra grid
  dimension indexes the group): per head the kernel lane-slices
  (block, D) tiles out of its (block, G*D) VMEM blocks, runs the online
  softmax recurrence, and writes packed (b, s, H*D) outputs that feed
  out_proj directly.  Grouping keeps VMEM per cell bounded for any H, so
  gpt2-small (H*D=768) runs whole rows per cell while a 2048-hidden model
  splits into G=4-head groups without shrinking the 512-edge blocks;
- backward mirrors it (dq kernel + dkdv kernel); the only XLA-side work
  left is one lane concat of (dq, dk, dv) into the qkv cotangent.

Stats (lse) live transposed as (b, H, 8, s) sublane-broadcast rows — the
running max/sum also live transposed in VMEM ((G, 8, block) instead of
(G, block, 128)), which is what lets 512-edge blocks fit.  Causal masking
uses diagonal-clamped index maps (masked cells skip compute AND their
DMA).  Dropout reuses the positional-hash mask, keyed by the global head
index so each head draws an independent mask.

``supported()`` gates callers: bf16/f16 only (f32 blocks blow the VMEM
budget — those callers take the bhd path), D a sublane multiple, G*D a
lane multiple.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import (_NEG_INF, _SUB, _dropout_keep, _interpret,
                              _prec, _smem_spec)

_LANES = 128
# The estimator under-counts the compiler's score/prob temporaries; 13 MB
# keeps the worst (dkdv) kernel clear of the 16 MB scoped-vmem limit
# (G=12 at 512^2 blocks estimated 14.6 MB but compiled to 16.56 MB on
# the round-2 toolchain).  The plans this picks — (512, 512, G=6) for
# gpt2-small, G=4 for H16/D128 — compile under jax 0.9.0 / libtpu 0.0.34
# on the v5e (chip_smoke.py, PR 21).
_VMEM_BUDGET = 13 * 2**20


def _tune_key(sq, skv, heads, dtype):
    return ("flash_packed_blocks", sq, skv, heads, jnp.dtype(dtype).itemsize)


def _plan(sq, skv, heads, head_dim, dtype=jnp.bfloat16):
    """Pick (block_q, block_kv, group) — block edges and heads-per-cell.

    Largest block edge wins (512 beat 256 by ~12% e2e on gpt2-small), then
    the largest head group that keeps the worst-case (dkdv) cell inside
    the scoped-VMEM budget: 4 double-buffered (b, G*D) input streams, two
    (b, G*D) outputs, two (G, b, D) f32 accumulators, ~2 (b, b) f32
    score/prob temporaries.  The autotune cache can override per shape."""
    from ....core import autotune as _at
    cached = (_at.kernel_cache.get(_tune_key(sq, skv, heads, dtype))
              if _at.enabled() else None)
    if cached is not None:
        return cached
    isz = jnp.dtype(dtype).itemsize

    def est(b, g):
        gd = g * head_dim
        return (2 * 4 * b * gd * isz + 2 * 2 * b * gd * isz
                + 2 * g * b * head_dim * 4 + 2 * b * b * 4)

    groups = [g for g in range(heads, 0, -1) if heads % g == 0
              and (g * head_dim) % _LANES == 0]
    for b in (512, 256, 128, 64, 32, 16, 8):
        if sq % b or skv % b or b > sq or b > skv:
            continue
        for g in groups:
            if est(b, g) <= _VMEM_BUDGET:
                return (b, b, g)
    return None


def _block_sizes(sq, skv, heads, head_dim, dtype=jnp.bfloat16):
    plan = _plan(sq, skv, heads, head_dim, dtype)
    return None if plan is None else (plan[0], plan[1])


def supported(sq, skv, heads, head_dim, dtype) -> bool:
    if head_dim % 8 != 0:
        return False
    if jnp.dtype(dtype).itemsize > 2:
        return False  # f32 blocks blow the VMEM budget; use the bhd path
    return _plan(sq, skv, heads, head_dim, dtype) is not None


def _causal_positions(qi, ki, bq, bkv, transposed=False):
    if transposed:  # (block_kv, block_q) layouts (the dkdv kernel)
        k_pos = ki * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (bkv, bq), 0)
        q_pos = qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bkv, bq), 1)
    else:
        q_pos = qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 0)
        k_pos = ki * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 1)
    return q_pos, k_pos


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, block_q,
                block_kv, n_kv, group, heads, head_dim, dropout_p):
    bi = pl.program_id(0)
    gi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    D = head_dim

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body(masked):
        # VPU passes over the (block_q, block_kv) tile are the kernel's
        # critical path (the d=64 dots leave the MXU mostly idle), so the
        # softmax touches the full tile as few times as possible:
        # sm_scale is folded into the small (block, D) q slice (exact for
        # power-of-two 1/sqrt(D)), and the causal mask + iotas exist only
        # on diagonal-crossing cells (``masked``) — strictly-lower cells
        # skip them entirely.  Diag cells mask BEFORE the running max (a
        # raw-block max could be inflated by a masked outlier logit,
        # underflowing every valid probability in the row).
        qb = q_ref[0]                            # (block_q, G*D)
        kb = k_ref[0]                            # (block_kv, G*D)
        vb = v_ref[0]
        if masked or dropout_p > 0.0:
            q_pos, k_pos = _causal_positions(qi, ki, block_q, block_kv)
        if masked:
            causal_keep = q_pos >= k_pos         # bool; the i32 iotas die here
        for h in range(group):
            q = (qb[:, h * D:(h + 1) * D] *
                 jnp.asarray(sm_scale, qb.dtype))
            k = kb[:, h * D:(h + 1) * D]
            v = vb[:, h * D:(h + 1) * D]
            # contract over d of BOTH operands directly — current Mosaic
            # takes (1,1) bf16 contractions natively, no register transpose
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=_prec(q.dtype))
            if masked:
                s = jnp.where(causal_keep, s, _NEG_INF)
            # stats live transposed (8, block_q); work in (block_q, 1)
            m_prev = jnp.swapaxes(m_ref[h], 0, 1)[:, :1]
            l_prev = jnp.swapaxes(l_ref[h], 0, 1)[:, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)          # (block_q, 1)
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_next = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            if dropout_p > 0.0:
                keep = _dropout_keep(seed_ref[0],
                                     bi * heads + gi * group + h,
                                     q_pos, k_pos, 1.0 - dropout_p)
                p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=_prec(v.dtype))
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.swapaxes(
                jnp.broadcast_to(m_next, (block_q, _SUB)), 0, 1)
            l_ref[h] = jnp.swapaxes(
                jnp.broadcast_to(l_next, (block_q, _SUB)), 0, 1)

    if causal:
        last_q = qi * block_q + block_q - 1
        diag = (ki * block_kv <= last_q) & \
            (ki * block_kv + block_kv - 1 > last_q - block_q)

        @pl.when(diag)
        def _run_diag():
            _body(True)

        @pl.when(ki * block_kv + block_kv - 1 <= last_q - block_q)
        def _run_full():
            _body(False)
    else:
        _body(False)

    @pl.when(ki == n_kv - 1)
    def _finish():
        for h in range(group):
            lt = l_ref[h]                        # (8, block_q)
            lt = jnp.where(lt == 0.0, 1.0, lt)
            l_col = jnp.swapaxes(lt, 0, 1)[:, :1]
            o_ref[0, :, h * D:(h + 1) * D] = (
                acc_ref[h] / l_col).astype(o_ref.dtype)
            lse_ref[0, h] = m_ref[h] + jnp.log(jnp.maximum(lt, 1e-30))


def _kv_idx_packed(causal, bq, bkv, n_kv, part, n_groups):
    """kv index map into the packed (b, s, 3*H*D) qkv array, in G*D-lane
    block units: ``part`` selects q (0), k (1) or v (2); the group grid
    index picks the lane block within the part; causal clamps to the
    diagonal so masked cells elide their DMA."""
    if not causal:
        return lambda b, g, i, j: (b, j, part * n_groups + g)

    def idx(b, g, i, j):
        diag = jnp.minimum((i * bq + bq - 1) // bkv, n_kv - 1)
        return (b, jnp.minimum(j, diag), part * n_groups + g)
    return idx


def _fwd(qkv, heads, causal, sm_scale, dropout_p=0.0, seed=None,
         _blocks=None):
    from jax.experimental.pallas import tpu as pltpu
    b, sq, hd3 = qkv.shape
    hd = hd3 // 3
    D = hd // heads
    skv = sq
    bq, bkv, G = _blocks or _plan(sq, skv, heads, D, qkv.dtype)
    n_q, n_kv = sq // bq, skv // bkv
    n_g = heads // G
    gd = G * D

    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, n_kv=n_kv, group=G, heads=heads, head_dim=D,
        dropout_p=dropout_p)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, n_g, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, gd), lambda bb, g, i, j: (bb, i, g)),
            pl.BlockSpec((1, bkv, gd),
                         _kv_idx_packed(causal, bq, bkv, n_kv, 1, n_g)),
            pl.BlockSpec((1, bkv, gd),
                         _kv_idx_packed(causal, bq, bkv, n_kv, 2, n_g)),
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, gd), lambda bb, g, i, j: (bb, i, g)),
            pl.BlockSpec((1, G, _SUB, bq),
                         lambda bb, g, i, j: (bb, g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), qkv.dtype),
            jax.ShapeDtypeStruct((b, heads, _SUB, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, bq, D), jnp.float32),       # acc
            pltpu.VMEM((G, _SUB, bq), jnp.float32),    # m (transposed)
            pltpu.VMEM((G, _SUB, bq), jnp.float32),    # l (transposed)
        ],
        interpret=_interpret(),
        name="flash_packed_fwd",
    )(qkv, qkv, qkv, seed)
    return out, lse


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, seed_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                     *, sm_scale, causal, block_q, block_kv, n_q, group,
                     heads, head_dim, dropout_p):
    bi = pl.program_id(0)
    gi = pl.program_id(1)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    D = head_dim

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body(masked):
        # VPU economy (see _fwd_kernel): sm_scale folded into the q slice
        # (st lands in lse space; the same scaled q also serves the dk dot,
        # since dk = pt*(dpt-delta) . q*scale), causal select after the
        # exp, diagonal-crossing cells only
        qb = q_ref[0]                            # (block_q, G*D)
        kb = k_ref[0]                            # (block_kv, G*D)
        vb = v_ref[0]
        dob = do_ref[0]
        if masked or dropout_p > 0.0:
            q_pos_t, k_pos_t = _causal_positions(
                qi, ki, block_q, block_kv, transposed=True)
        if masked:
            causal_keep = q_pos_t >= k_pos_t
        for h in range(group):
            q = (qb[:, h * D:(h + 1) * D] *
                 jnp.asarray(sm_scale, qb.dtype))
            k = kb[:, h * D:(h + 1) * D]
            v = vb[:, h * D:(h + 1) * D]
            do = dob[:, h * D:(h + 1) * D]
            lse = lse_ref[0, h][:1, :]           # (1, block_q)
            delta = delta_ref[0, h][:1, :]
            st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=_prec(k.dtype))
            pt = jnp.exp(st - lse)
            if masked:
                pt = jnp.where(causal_keep, pt, 0.0)
            pt_v = pt
            if dropout_p > 0.0:
                keep = _dropout_keep(seed_ref[0],
                                     bi * heads + gi * group + h,
                                     q_pos_t, k_pos_t, 1.0 - dropout_p)
                pt_v = jnp.where(keep, pt / (1.0 - dropout_p), 0.0)
            dv_acc[h] += jax.lax.dot_general(
                pt_v.astype(v.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(v.dtype))
            dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=_prec(v.dtype))
            if dropout_p > 0.0:
                dpt = jnp.where(keep, dpt / (1.0 - dropout_p), 0.0)
            dst = pt * (dpt - delta)
            dk_acc[h] += jax.lax.dot_general(
                dst.astype(k.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(k.dtype))

    if causal:
        first_k = ki * block_kv
        diag = (qi * block_q + block_q - 1 >= first_k) & \
            (qi * block_q < first_k + block_kv)

        @pl.when(diag)
        def _run_diag():
            _body(True)

        @pl.when(qi * block_q >= first_k + block_kv)
        def _run_full():
            _body(False)
    else:
        _body(False)

    @pl.when(qi == n_q - 1)
    def _finish():
        for h in range(group):
            dk_ref[0, :, h * D:(h + 1) * D] = dk_acc[h].astype(dk_ref.dtype)
            dv_ref[0, :, h * D:(h + 1) * D] = dv_acc[h].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   seed_ref, dq_ref, dq_acc, *, sm_scale, causal, block_q,
                   block_kv, n_kv, group, heads, head_dim, dropout_p):
    bi = pl.program_id(0)
    gi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    D = head_dim

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body(masked):
        # same VPU economy as the forward: sm_scale folded into the small
        # q slice (s lands in lse space directly) and into the k slice of
        # the final dot (dq = p*(dp-delta) . k*scale); the causal select
        # runs on p AFTER the exp and only on diagonal-crossing cells
        qb = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        dob = do_ref[0]
        if masked or dropout_p > 0.0:
            q_pos, k_pos = _causal_positions(qi, ki, block_q, block_kv)
        if masked:
            causal_keep = q_pos >= k_pos
        for h in range(group):
            scale = jnp.asarray(sm_scale, qb.dtype)
            q = qb[:, h * D:(h + 1) * D] * scale
            k = kb[:, h * D:(h + 1) * D]
            v = vb[:, h * D:(h + 1) * D]
            do = dob[:, h * D:(h + 1) * D]
            lse = jnp.swapaxes(lse_ref[0, h], 0, 1)[:, :1]   # (block_q, 1)
            delta = jnp.swapaxes(delta_ref[0, h], 0, 1)[:, :1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=_prec(q.dtype))
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(causal_keep, p, 0.0)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=_prec(do.dtype))
            if dropout_p > 0.0:
                keep = _dropout_keep(seed_ref[0],
                                     bi * heads + gi * group + h,
                                     q_pos, k_pos, 1.0 - dropout_p)
                dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
            ds = p * (dp - delta)
            dq_acc[h] += jax.lax.dot_general(
                ds.astype(k.dtype), k * scale, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(k.dtype))

    if causal:
        last_q = qi * block_q + block_q - 1
        diag = (ki * block_kv <= last_q) & \
            (ki * block_kv + block_kv - 1 > last_q - block_q)

        @pl.when(diag)
        def _run_diag():
            _body(True)

        @pl.when(ki * block_kv + block_kv - 1 <= last_q - block_q)
        def _run_full():
            _body(False)
    else:
        _body(False)

    @pl.when(ki == n_kv - 1)
    def _finish():
        for h in range(group):
            dq_ref[0, :, h * D:(h + 1) * D] = dq_acc[h].astype(dq_ref.dtype)


def _bwd(heads, causal, sm_scale, dropout_p, res, do):
    from jax.experimental.pallas import tpu as pltpu
    qkv, out, lse, seed = res
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    b, sq, hd3 = qkv.shape
    hd = hd3 // 3
    D = hd // heads
    skv = sq
    bq, bkv, G = _plan(sq, skv, heads, D, qkv.dtype)
    n_q, n_kv = sq // bq, skv // bkv
    n_g = heads // G
    gd = G * D

    # delta = rowsum(dO * O) per head, in the (b, H, 8, s) stats layout
    do_h = do.reshape(b, sq, heads, D).astype(jnp.float32)
    out_h = out.reshape(b, sq, heads, D).astype(jnp.float32)
    delta_row = jnp.sum(do_h * out_h, axis=-1)            # (b, sq, H)
    delta_t = jnp.broadcast_to(
        jnp.swapaxes(delta_row, 1, 2)[:, :, None, :], (b, heads, _SUB, sq))

    if causal:
        def q_idx(bb, g, j, i):
            first = jnp.minimum((j * bkv) // bq, n_q - 1)
            return (bb, jnp.maximum(i, first), g)

        def stat_idx(bb, g, j, i):
            first = jnp.minimum((j * bkv) // bq, n_q - 1)
            return (bb, g, 0, jnp.maximum(i, first))
    else:
        def q_idx(bb, g, j, i):
            return (bb, i, g)

        def stat_idx(bb, g, j, i):
            return (bb, g, 0, i)

    dkdv = functools.partial(
        _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, n_q=n_q, group=G, heads=heads, head_dim=D,
        dropout_p=dropout_p)
    dk, dv = pl.pallas_call(
        dkdv,
        grid=(b, n_g, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, gd), q_idx),                       # q rows
            pl.BlockSpec((1, bkv, gd),
                         lambda bb, g, j, i: (bb, j, n_g + g)),     # k
            pl.BlockSpec((1, bkv, gd),
                         lambda bb, g, j, i: (bb, j, 2 * n_g + g)),  # v
            pl.BlockSpec((1, bq, gd), q_idx),                       # dO rows
            pl.BlockSpec((1, G, _SUB, bq), stat_idx),               # lse
            pl.BlockSpec((1, G, _SUB, bq), stat_idx),               # delta
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, bkv, gd), lambda bb, g, j, i: (bb, j, g)),
            pl.BlockSpec((1, bkv, gd), lambda bb, g, j, i: (bb, j, g)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, skv, hd), qkv.dtype),
            jax.ShapeDtypeStruct((b, skv, hd), qkv.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, bkv, D), jnp.float32),
            pltpu.VMEM((G, bkv, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_packed_bwd_dkdv",
    )(qkv, qkv, qkv, do, lse, delta_t, seed)

    dqk = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, n_kv=n_kv, group=G, heads=heads, head_dim=D,
        dropout_p=dropout_p)
    dq = pl.pallas_call(
        dqk,
        grid=(b, n_g, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, gd), lambda bb, g, i, j: (bb, i, g)),
            pl.BlockSpec((1, bkv, gd),
                         _kv_idx_packed(causal, bq, bkv, n_kv, 1, n_g)),
            pl.BlockSpec((1, bkv, gd),
                         _kv_idx_packed(causal, bq, bkv, n_kv, 2, n_g)),
            pl.BlockSpec((1, bq, gd), lambda bb, g, i, j: (bb, i, g)),
            pl.BlockSpec((1, G, _SUB, bq),
                         lambda bb, g, i, j: (bb, g, 0, i)),
            pl.BlockSpec((1, G, _SUB, bq),
                         lambda bb, g, i, j: (bb, g, 0, i)),
            _smem_spec(),
        ],
        out_specs=pl.BlockSpec((1, bq, gd), lambda bb, g, i, j: (bb, i, g)),
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((G, bq, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_packed_bwd_dq",
    )(qkv, qkv, qkv, do, lse, delta_t, seed)

    dqkv = jnp.concatenate([dq, dk, dv], axis=-1)   # (b, s, 3*H*D)
    return (dqkv, None)                             # None: the int seed array


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def flash_attention_packed(qkv, heads, causal, sm_scale, dropout_p=0.0,
                           seed=None):
    """Flash attention over a packed ``(b, s, 3*H*D)`` qkv projection.

    Returns the packed attention output ``(b, s, H*D)`` ready for the
    output projection. ``seed`` is a (1,) int32 array, required when
    ``dropout_p > 0``.
    """
    out, _ = _fwd(qkv, heads, causal, sm_scale, dropout_p, seed)
    return out


def _vjp_fwd(qkv, heads, causal, sm_scale, dropout_p=0.0, seed=None):
    out, lse = _fwd(qkv, heads, causal, sm_scale, dropout_p, seed)
    return out, (qkv, out, lse, seed)


flash_attention_packed.defvjp(_vjp_fwd, _bwd)
