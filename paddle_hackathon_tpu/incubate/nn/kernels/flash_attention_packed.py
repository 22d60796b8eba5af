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
- backward mirrors it (dq kernel, then dkdv kernel).  delta =
  rowsum(dO * O) is computed inside the dq kernel, from the dO block it
  streams anyway and the O block beside it, in the first step of each q
  row, and leaves it as a second result in the statistics' layout, which
  the dkdv kernel reads: XLA computes nothing of the backward.  What it
  is left with is the lane concat of (dq, dk, dv) into the qkv
  cotangent, and that it fuses into the cotangent's three consumers
  (``_bwd`` says in which form it does): no packed array is built.

Stats (lse) live transposed as (b, H, 8, s) sublane-broadcast rows — the
running max/sum also live transposed in VMEM ((G, 8, block) instead of
(G, block, 128)), which is what lets 512-edge blocks fit.  The forward
computes in that layout: its score tile is kv-major, (kv, q) as the dkdv
kernel's, so a q row's maximum and sum are reductions down sublanes
(elementwise over the sublane tiles, one 8-to-1 step; no cross-lane
pass) that are born as the (1, block) rows they are stored in, and its
accumulator is (G, D, block), turned once a head and q block when the
output is written.

Causal masking works at two grains.  Cells wholly above the diagonal
skip compute AND their DMA (diagonal-clamped index maps).  A cell the
diagonal crosses computes, in all three kernels, only the trapezoid its
rows can see: c-row strips with static extents (``_diag_cell``), the
``where`` on each strip's one (c, c) diagonal sub-tile — (n + 1) / 2n of
the cell's score area with n = block / c strips, where the whole-tile
masked body (kept for plans with unequal blocks) computes all of it and
masks half.  ``executed_score_share`` is that arithmetic for a whole
call.  Dropout reuses the positional-hash mask, keyed by the global head
index so each head draws an independent mask.

Each kernel body is Python-unrolled over the heads of a cell and the
strips of a diagonal cell, so ``_fwd`` and ``_bwd`` are jitted with
everything but the arrays static: the layers of a model share one trace
and one lowered function of each kernel.

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
# keeps the worst (dkdv) kernel clear of the 16 MB scoped-vmem limit.
# The plans this picks — (512, 512, G=6) for gpt2-small, G=8 for H16/D64,
# G=4 for H16/D128 — compile under jax 0.9.0 / libtpu 0.0.34 on the v5e
# (chip_smoke.py; PR 21, and PR 27 with the strips).
_VMEM_BUDGET = 13 * 2**20


def _tune_key(sq, skv, heads, dtype):
    return ("flash_packed_blocks", sq, skv, heads, jnp.dtype(dtype).itemsize)


def _strip_rows(block_q, block_kv):
    """Rows of a diagonal cell's trapezoid strips, 0 for the whole tile.

    Strips need a square cell (then every diagonal-crossing cell is the
    aligned ``qi == ki`` one) and are one lane tile tall: a strip's kv
    extent is a lane dimension of its score tile, and 128 beat 256 in all
    three kernels at both benchmark cells' shapes (v5e, jax 0.9.0,
    PR 27; ms a call at b16 H16 D64 / b6 H16 D128, s1024, whole tile ->
    256 -> 128: forward 1.565 -> 1.470 -> 1.413 / 0.499 -> 0.486 ->
    0.479, dkdv 1.211 -> 1.034 -> 0.946 / 0.466 -> 0.400 -> 0.366, dq
    0.934 -> 0.809 -> 0.753 / 0.392 -> 0.342 -> 0.319).  A 256-edge
    cell would run two: its backward kernels gained as well, its
    forward lost 9 % at s768 (b16 H16 D64) and the three together 3 %,
    so under four strips a cell keeps its whole tile."""
    if block_q == block_kv and block_q % _LANES == 0 \
            and block_q >= 4 * _LANES:
        return _LANES
    return 0


def _plan(sq, skv, heads, head_dim, dtype=jnp.bfloat16):
    """Pick (block_q, block_kv, group, strip) — block edges, heads per
    cell, and the rows of a diagonal cell's strips (``_strip_rows``).

    Largest block edge wins, then the largest head group that keeps the
    worst-case (dkdv) cell inside the scoped-VMEM budget: 4
    double-buffered (b, G*D) input streams, two (b, G*D) outputs, two
    (G, b, D) f32 accumulators, ~2 (b, b) f32 score/prob temporaries.
    The strips' temporaries are (c, b) at most, but a cell under the
    diagonal (and every cell of a non-causal call) still runs the whole
    (b, b) tile, so the worst case — and with it every group this has
    picked so far — stands.  The dq kernel is not the worst case: it
    holds as many blocks (five inputs with O, one output), one
    accumulator where dkdv has two, and its delta temporaries (the
    (b, G*D) float32 product and its bf16 pieces) live in the first step
    of a row alone, not beside a score tile.  The autotune cache can
    override (block_q, block_kv, group) per shape; the strip always
    follows from the blocks."""
    from ....core import autotune as _at
    cached = (_at.kernel_cache.get(_tune_key(sq, skv, heads, dtype))
              if _at.enabled() else None)
    if cached is not None:
        return (*cached[:3], _strip_rows(*cached[:2]))
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
                return (b, b, g, _strip_rows(b, b))
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


def executed_score_share(sq, skv, heads, head_dim, dtype, causal) -> float:
    """Score elements each of the three kernels computes over
    ``sq * skv``, from the plan: 1.0 non-causal; causal, every cell under
    the diagonal whole, every diagonal-crossing cell whole or — with
    strips of c rows in a b-edge cell — (b/c + 1) / (2 b/c) of it, cells
    above the diagonal nothing.  0.5 is the causal least (what the
    benchmark's roofline counts).  A static fact of a build, not a rate:
    ``_statics`` tells it to the program observatory while a program that
    holds the kernels is traced, and that build's record carries it
    (``observability/programs.py note_kernel_fact``).  ValueError for a
    shape no plan covers (``supported`` is False)."""
    plan = _plan(sq, skv, heads, head_dim, dtype)
    if plan is None:
        raise ValueError(
            f"no packed flash plan for sq={sq} skv={skv} heads={heads} "
            f"head_dim={head_dim}: the kernels do not run this shape")
    return _score_share(sq, skv, plan, causal)


def _score_share(sq, skv, plan, causal):
    if not causal:
        return 1.0
    bq, bkv, _, strip = plan
    n = bq // strip if strip else 1
    diag_share = (n + 1) / (2 * n)
    done = 0.0
    for qi in range(sq // bq):
        last_q = qi * bq + bq - 1
        for ki in range(skv // bkv):
            if ki * bkv > last_q:
                continue                    # above the diagonal: skipped
            crosses = ki * bkv + bkv - 1 > last_q - bq
            done += bq * bkv * (diag_share if crosses else 1.0)
    return done / (sq * skv)


def _positions(q0, k0, nq, nk, transposed=False):
    """Global (q, k) positions of the (nq, nk) score tile whose first row
    is q position ``q0`` and first column k position ``k0`` — or of its
    (nk, nq) transpose (the forward's and the dkdv kernel's layout)."""
    shape = (nk, nq) if transposed else (nq, nk)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                          1 if transposed else 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                          0 if transposed else 1)
    return q_pos, k_pos


def _diag_cell(qi, ki, block_q, block_kv, strip, transposed=False):
    """``(tiles, keeps)`` of a diagonal-crossing cell: the score tiles it
    computes, static, and each tile's causal keep-mask.

    A tile is ``(r0, rn, kn)`` — q rows ``[r0, r0 + rn)`` of the block
    against its first ``kn`` kv columns (dq) — or, transposed (the
    forward and dkdv), ``(k0, kc, q0)`` — kv rows ``[k0, k0 + kc)``
    against the q columns from ``q0`` to the block's end.  With
    ``strip`` = c (a square, aligned cell: ``qi == ki``) the tiles are
    the block's c-row trapezoid strips, each reaching exactly as far as
    its rows can see, and the mask is the one (c, c) triangle every
    strip carries on the sub-tile at its diagonal end; without, the one
    whole tile and its mask from global positions."""
    if not strip:
        q_pos, k_pos = _positions(qi * block_q, ki * block_kv, block_q,
                                  block_kv, transposed)
        return [(0, block_kv if transposed else block_q,
                 0 if transposed else block_kv)], [q_pos >= k_pos]
    q_loc, k_loc = _positions(0, 0, strip, strip, transposed)
    tri = q_loc >= k_loc
    tiles = [(r * strip, strip, r * strip if transposed else (r + 1) * strip)
             for r in range(block_q // strip)]
    return tiles, [tri] * len(tiles)


def _dot(a, b, b_dim, a_dim=1):
    """``a`` (rows, K) times ``b`` contracted over its dim ``b_dim``
    (1: b is (cols, K), no transpose exists — current Mosaic takes (1,1)
    bf16 contractions natively), accumulated in float32.  ``a_dim`` = 0
    contracts ``a`` (K, rows) on its axis 0: the forward's PV product
    into its transposed accumulator, where the small (kv, D) operand is
    the one that turns and the (kv, q) probability tile never does."""
    return jax.lax.dot_general(a, b, (((a_dim,), (b_dim,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_prec(a.dtype))


def _head_row_sums(a, b, group, head_dim):
    """``sum(a * b)`` over each head's lanes of two (rows, G*D) blocks, as
    (G*8, rows) float32: head h's sums fill sublanes 8h to 8h + 7, the
    rows along lanes.  The products are float32 and so is their sum, to
    rounding: a product of two bf16 values carries 16 significant bits
    and splits exactly into two bf16 pieces (three cover float16's 22),
    and the MXU sums each piece's lanes in float32 against a 0 / 1
    selector of the heads.  That is also the cheapest way found to turn a
    lane reduction into rows (v5e, kernel alone, PERF.md §6, PR 31: a
    float32 ones matmul a head, a lane reduce and a turn a head, and the
    diagonal of dO . O^T all cost more)."""
    prod = a.astype(jnp.float32) * b.astype(jnp.float32)
    shape = (group * _SUB, group * head_dim)
    sel = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) // _SUB ==
           jax.lax.broadcasted_iota(jnp.int32, shape, 1) // head_dim
           ).astype(jnp.bfloat16)
    piece = prod.astype(jnp.bfloat16)
    rows = _dot(sel, piece, 1)
    for _ in range(1 if a.dtype == jnp.bfloat16 else 2):
        prod = prod - piece.astype(jnp.float32)
        piece = prod.astype(jnp.bfloat16)
        rows = rows + _dot(sel, piece, 1)
    return rows


def _join_trailing(whole, part, combine):
    """``combine`` ``part`` into the trailing columns of ``whole`` (the
    first part given is the whole).  The kv-major strips of a diagonal
    cell reach from their own q position to the block's end, so their
    per-column partials nest at the trailing edge; a split falls on a
    lane-tile boundary."""
    if whole is None:
        return part
    w = part.shape[1]
    if w == whole.shape[1]:
        return combine(whole, part)
    return jnp.concatenate(
        [whole[:, :-w], combine(whole[:, -w:], part)], axis=1)


def _select_edge(keep, x, fill, leading=False):
    """``where(keep, x, fill)`` on the ``keep.shape[1]`` columns at the
    trailing (or leading) edge of ``x``; the rest of ``x`` passes
    untouched.  A strip's split falls on a lane-tile boundary (strips are
    128-multiples), so the select pass runs on the diagonal sub-tile
    alone."""
    w = keep.shape[1]
    if w == x.shape[1]:
        return jnp.where(keep, x, fill)
    if leading:
        return jnp.concatenate(
            [jnp.where(keep, x[:, :w], fill), x[:, w:]], axis=1)
    return jnp.concatenate(
        [x[:, :-w], jnp.where(keep, x[:, -w:], fill)], axis=1)


def _run_cells(causal, diag, full, body, diag_cell, whole):
    """Dispatch a grid cell to its body: diagonal-crossing cells run
    ``diag_cell()``'s tiles under their masks, cells under the diagonal
    (and every cell of a non-causal call) the one whole tile unmasked,
    cells above it nothing."""
    if not causal:
        body([whole], [None])
        return

    @pl.when(diag)
    def _run_diag():
        body(*diag_cell())

    @pl.when(full)
    def _run_full():
        body([whole], [None])


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, block_q,
                block_kv, strip, n_kv, group, heads, head_dim, dropout_p):
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

    def _body(tiles, keeps):
        # VPU passes over the float32 score tile are most of the kernel's
        # time (PERF.md §5), so the softmax touches as few score elements
        # as few times as
        # possible: sm_scale is folded into the small (rows, D) q slice
        # (exact for power-of-two 1/sqrt(D)); a diagonal cell computes
        # only the strips' trapezoid (``_diag_cell``); the causal select
        # runs on diagonal sub-tiles only — cells under the diagonal skip
        # it entirely.  The score tile is kv-major, (kv, q) as the dkdv
        # kernel has it: a q row's maximum and sum are reductions down
        # axis 0 — elementwise over the sublane tiles, one 8-to-1
        # sublane step at the end, nothing across lanes — and are born
        # as the (1, block_q) rows the statistics are stored in.
        # Masking comes BEFORE the running max (a raw-block max could be
        # inflated by a masked outlier logit, underflowing every valid
        # probability in the row).
        qb = q_ref[0]                            # (block_q, G*D)
        kb = k_ref[0]                            # (block_kv, G*D)
        vb = v_ref[0]
        pos = [_positions(qi * block_q + q0, ki * block_kv + k0,
                          block_q - q0, kc, transposed=True)
               if dropout_p > 0.0 else None for k0, kc, q0 in tiles]
        for h in range(group):
            q_h = qb[:, h * D:(h + 1) * D] * jnp.asarray(sm_scale, qb.dtype)
            k_h = kb[:, h * D:(h + 1) * D]
            v_h = vb[:, h * D:(h + 1) * D]
            # two phases a head, each over all the tiles: every strip's
            # score matmul and column maximum, then every strip's
            # exponential, column sum and PV matmul.  A strip run end to
            # end by itself pays the whole matmul -> reduce -> exp ->
            # matmul latency chain (0.3-0.4 us a strip, PERF.md §6).  A
            # strip's q columns run from its own position to the block's
            # end, so a q column's statistics span the strips up to its
            # own: the strips' partial rows are joined per column before
            # the exponentials
            scores, m_cur = [], None
            for (k0, kc, q0), keep in zip(tiles, keeps):
                st = _dot(k_h[k0:k0 + kc], q_h[q0:], 1)  # (kc, q columns)
                if keep is not None:
                    st = _select_edge(keep, st, _NEG_INF, leading=True)
                scores.append(st)
                m_cur = _join_trailing(
                    m_cur, jnp.max(st, axis=0, keepdims=True), jnp.maximum)
            # read AFTER the score matmuls: read before them, a 256-edge
            # cell's forward was 12 % slower at s768 (PERF.md §6, PR 27)
            m_old = m_ref[h, :1]                         # (1, block_q)
            l_old = l_ref[h, :1]
            m_next = jnp.maximum(m_old, m_cur)
            alpha = jnp.exp(m_old - m_next)
            # a strip reads its columns of the new maximum back from the
            # scratch, as the dkdv kernel reads lse: Mosaic cannot
            # lane-slice the (1, block_q) value itself ("Invalid input
            # layout" on the broadcast of a replicated row's slice)
            m_ref[h] = jnp.broadcast_to(m_next, (_SUB, block_q))
            l_cur, pv = None, None
            for (k0, kc, q0), st, qk_pos in zip(tiles, scores, pos):
                pt = jnp.exp(st - m_ref[h, :1, q0:])
                l_cur = _join_trailing(
                    l_cur, jnp.sum(pt, axis=0, keepdims=True), jnp.add)
                if dropout_p > 0.0:
                    drop_keep = _dropout_keep(seed_ref[0],
                                              bi * heads + gi * group + h,
                                              *qk_pos, 1.0 - dropout_p)
                    pt = jnp.where(drop_keep, pt / (1.0 - dropout_p), 0.0)
                # the accumulator is transposed too, (D, block_q): the
                # small (kc, D) operand is the one contracted on axis 0
                pv = _join_trailing(
                    pv, _dot(v_h[k0:k0 + kc], pt.astype(v_h.dtype), 0,
                             a_dim=0), jnp.add)
            acc_ref[h] = acc_ref[h] * alpha + pv
            l_ref[h] = jnp.broadcast_to(l_old * alpha + l_cur,
                                        (_SUB, block_q))

    first_k = ki * block_kv
    last_q = qi * block_q + block_q - 1
    _run_cells(
        causal,
        diag=(first_k <= last_q) & (first_k + block_kv - 1 > last_q - block_q),
        full=first_k + block_kv - 1 <= last_q - block_q,
        body=_body,
        diag_cell=lambda: _diag_cell(qi, ki, block_q, block_kv, strip,
                                     transposed=True),
        whole=(0, block_kv, 0))

    @pl.when(ki == n_kv - 1)
    def _finish():
        for h in range(group):
            lt = l_ref[h]                        # (8, block_q)
            lt = jnp.where(lt == 0.0, 1.0, lt)
            # the one transpose a head and q block: the normalised
            # accumulator, (D, block_q) -> (block_q, D)
            o_ref[0, :, h * D:(h + 1) * D] = jnp.swapaxes(
                acc_ref[h] / lt[:1], 0, 1).astype(o_ref.dtype)
            lse_ref[0, h] = m_ref[h] + jnp.log(jnp.maximum(lt, 1e-30))


def _kv_idx_packed(causal, bq, bkv, n_kv, part, n_groups,
                   descending=False):
    """kv index map into the packed (b, s, 3*H*D) qkv array, in G*D-lane
    block units: ``part`` selects q (0), k (1) or v (2); the group grid
    index picks the lane block within the part; causal clamps to the
    diagonal so cells above it elide their DMA.  ``descending``: grid
    step j holds kv block ``n_kv - 1 - j`` (the dq kernel's sweep)."""
    if not causal:
        return lambda b, g, i, j: (b, n_kv - 1 - j if descending else j,
                                   part * n_groups + g)

    def idx(b, g, i, j):
        if descending:
            j = n_kv - 1 - j
        diag = jnp.minimum((i * bq + bq - 1) // bkv, n_kv - 1)
        return (b, jnp.minimum(j, diag), part * n_groups + g)
    return idx


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "sm_scale", "dropout_p", "plan", "interpret"))
def _fwd(qkv, seed, *, heads, causal, sm_scale, dropout_p, plan, interpret):
    """``(out, lse)``.  Jitted with everything but the arrays static, so
    a model's layers — same shapes, same statics — share ONE trace of
    the kernel body and one lowered function (the Python-unrolled body
    is the dearest thing a step's trace holds: PERF.md §6, PR 27)."""
    from jax.experimental.pallas import tpu as pltpu
    b, sq, hd3 = qkv.shape
    hd = hd3 // 3
    D = hd // heads
    skv = sq
    bq, bkv, G, strip = plan
    n_q, n_kv = sq // bq, skv // bkv
    n_g = heads // G
    gd = G * D

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, strip=strip, n_kv=n_kv, group=G, heads=heads,
        head_dim=D, dropout_p=dropout_p)
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
            pltpu.VMEM((G, D, bq), jnp.float32),       # acc (transposed)
            pltpu.VMEM((G, _SUB, bq), jnp.float32),    # m (transposed)
            pltpu.VMEM((G, _SUB, bq), jnp.float32),    # l (transposed)
        ],
        interpret=interpret,
        name="flash_packed_fwd",
    )(qkv, qkv, qkv, seed)
    return out, lse


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, seed_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                     *, sm_scale, causal, block_q, block_kv, strip, n_q,
                     group, heads, head_dim, dropout_p):
    bi = pl.program_id(0)
    gi = pl.program_id(1)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    D = head_dim

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body(tiles, keeps):
        # VPU economy (see _fwd_kernel): sm_scale folded into the q slice
        # (st lands in lse space; the same scaled q also serves the dk dot,
        # since dk = pt*(dpt-delta) . q*scale), causal select after the
        # exp and on diagonal sub-tiles only; scores are transposed, so a
        # diagonal cell's strips are kv rows against the q columns from
        # their own position to the block's end
        qb = q_ref[0]                            # (block_q, G*D)
        kb = k_ref[0]                            # (block_kv, G*D)
        vb = v_ref[0]
        dob = do_ref[0]
        pos = [_positions(qi * block_q + q0, ki * block_kv + k0,
                          block_q - q0, kc, transposed=True)
               if dropout_p > 0.0 else None for k0, kc, q0 in tiles]
        for h in range(group):
            q_h = (qb[:, h * D:(h + 1) * D] *
                   jnp.asarray(sm_scale, qb.dtype))
            do_h = dob[:, h * D:(h + 1) * D]
            k_h = kb[:, h * D:(h + 1) * D]
            v_h = vb[:, h * D:(h + 1) * D]

            def score(tile):
                k0, kc, q0 = tile
                return _dot(k_h[k0:k0 + kc], q_h[q0:], 1)

            def dprob(tile):
                k0, kc, q0 = tile
                return _dot(v_h[k0:k0 + kc], do_h[q0:], 1)

            def accumulate(tile, keep, qk_pos, st, dpt=None):
                k0, kc, q0 = tile
                rows = slice(k0, k0 + kc)
                q, do = q_h[q0:], do_h[q0:]
                lse = lse_ref[0, h, :1, q0:]     # (1, q columns)
                delta = delta_ref[0, h, :1, q0:]
                pt = jnp.exp(st - lse)
                if keep is not None:
                    pt = _select_edge(keep, pt, 0.0, leading=True)
                pt_v = pt
                if dropout_p > 0.0:
                    drop_keep = _dropout_keep(seed_ref[0],
                                              bi * heads + gi * group + h,
                                              *qk_pos, 1.0 - dropout_p)
                    pt_v = jnp.where(drop_keep, pt / (1.0 - dropout_p), 0.0)
                dv_acc[h, rows] += _dot(pt_v.astype(v_h.dtype), do, 0)
                if dpt is None:
                    dpt = dprob(tile)
                if dropout_p > 0.0:
                    dpt = jnp.where(drop_keep, dpt / (1.0 - dropout_p), 0.0)
                dst = pt * (dpt - delta)
                dk_acc[h, rows] += _dot(dst.astype(k_h.dtype), q, 0)

            if len(tiles) == 1:
                accumulate(tiles[0], keeps[0], pos[0], score(tiles[0]))
                continue
            # strips: every strip's two score-shaped products first, then
            # every strip's exponential and accumulating matmuls (the
            # forward's two phases; a whole tile is fastest in the order
            # above, its dprob matmul between its two accumulations)
            done = [(score(t), dprob(t)) for t in tiles]
            for t, keep, qk_pos, sd in zip(tiles, keeps, pos, done):
                accumulate(t, keep, qk_pos, *sd)

    first_k = ki * block_kv
    _run_cells(
        causal,
        diag=(qi * block_q + block_q - 1 >= first_k) &
        (qi * block_q < first_k + block_kv),
        full=qi * block_q >= first_k + block_kv,
        body=_body,
        diag_cell=lambda: _diag_cell(qi, ki, block_q, block_kv, strip,
                                     transposed=True),
        whole=(0, block_kv, 0))

    @pl.when(qi == n_q - 1)
    def _finish():
        for h in range(group):
            dk_ref[0, :, h * D:(h + 1) * D] = dk_acc[h].astype(dk_ref.dtype)
            dv_ref[0, :, h * D:(h + 1) * D] = dv_acc[h].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, seed_ref,
                   dq_ref, delta_ref, dq_acc, *, sm_scale, causal, block_q,
                   block_kv, strip, n_kv, group, heads, head_dim,
                   dropout_p):
    bi = pl.program_id(0)
    gi = pl.program_id(1)
    qi = pl.program_id(2)
    # the kv sweep runs from the last block down to the first: a causal
    # row's cells above the diagonal, which compute nothing, then come
    # first, and the next row's q, dO, O and statistics blocks (1.5 MiB
    # and more at a 512-edge plan) are fetched under this row's last
    # cell, a computing one.  Ascending, that fetch stood under an empty
    # cell, where nothing hides it (PERF.md §6, PR 31)
    step = pl.program_id(3)
    ki = n_kv - 1 - step
    D = head_dim

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        # delta = rowsum(dO * O) a head, once a q block, born as the
        # (8, block_q) rows it is stored in.  ``delta_ref`` is an output
        # whose block index ignores the kv step: it stays in VMEM for the
        # whole sweep, every cell of the sweep (this one too) reads it
        # back, and the dkdv kernel takes the array as its input
        rows = _head_row_sums(do_ref[0], o_ref[0], group, D)
        for h in range(group):
            delta_ref[0, h] = rows[h * _SUB:(h + 1) * _SUB]

    def _body(tiles, keeps):
        # same VPU economy as the forward: sm_scale folded into the small
        # q slice (s lands in lse space directly) and into the k slice of
        # the final dot (dq = p*(dp-delta) . k*scale); the causal select
        # runs on p AFTER the exp and on diagonal sub-tiles only; the
        # one kernel whose scores are row-major, so a diagonal cell's
        # strips are q rows against the kv columns they can see
        qb = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        dob = do_ref[0]
        pos = [_positions(qi * block_q + r0, ki * block_kv, rn, kn)
               if dropout_p > 0.0 else None for r0, rn, kn in tiles]
        for h in range(group):
            scale = jnp.asarray(sm_scale, qb.dtype)
            k_h = kb[:, h * D:(h + 1) * D]
            # one transpose a head, sliced by the tiles: a narrow
            # transpose costs as much as a wide one (PERF.md §6, PR 27)
            lse_h = jnp.swapaxes(lse_ref[0, h], 0, 1)[:, :1]  # (block_q, 1)
            delta_h = jnp.swapaxes(delta_ref[0, h], 0, 1)[:, :1]
            q_h = qb[:, h * D:(h + 1) * D] * scale
            v_h = vb[:, h * D:(h + 1) * D]
            do_h = dob[:, h * D:(h + 1) * D]

            def score(tile):
                r0, rn, kn = tile
                return _dot(q_h[r0:r0 + rn], k_h[:kn], 1)

            def dprob(tile):
                r0, rn, kn = tile
                return _dot(do_h[r0:r0 + rn], v_h[:kn], 1)

            def accumulate(tile, keep, qk_pos, s, dp=None, ks_h=None):
                r0, rn, kn = tile
                rows = slice(r0, r0 + rn)
                p = jnp.exp(s - lse_h[rows])
                if keep is not None:
                    p = _select_edge(keep, p, 0.0)
                if dp is None:
                    dp = dprob(tile)
                if dropout_p > 0.0:
                    drop_keep = _dropout_keep(seed_ref[0],
                                              bi * heads + gi * group + h,
                                              *qk_pos, 1.0 - dropout_p)
                    dp = jnp.where(drop_keep, dp / (1.0 - dropout_p), 0.0)
                ds = p * (dp - delta_h[rows])
                if ks_h is None:
                    ks_h = k_h * scale
                dq_acc[h, rows] += _dot(ds.astype(k_h.dtype), ks_h[:kn], 0)

            if len(tiles) == 1:
                accumulate(tiles[0], keeps[0], pos[0], score(tiles[0]))
                continue
            # strips in two phases, as in the dkdv kernel
            done = [(score(t), dprob(t)) for t in tiles]
            ks_h = k_h * scale
            for t, keep, qk_pos, sd in zip(tiles, keeps, pos, done):
                accumulate(t, keep, qk_pos, *sd, ks_h)

    last_q = qi * block_q + block_q - 1
    _run_cells(
        causal,
        diag=(ki * block_kv <= last_q) &
        (ki * block_kv + block_kv - 1 > last_q - block_q),
        full=ki * block_kv + block_kv - 1 <= last_q - block_q,
        body=_body,
        diag_cell=lambda: _diag_cell(qi, ki, block_q, block_kv, strip),
        whole=(0, block_q, block_kv))

    @pl.when(step == n_kv - 1)
    def _finish():
        for h in range(group):
            dq_ref[0, :, h * D:(h + 1) * D] = dq_acc[h].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "sm_scale", "dropout_p", "plan", "interpret"))
def _bwd(qkv, out, lse, seed, do, *, heads, causal, sm_scale, dropout_p,
         plan, interpret):
    """``(dqkv, delta)``: the packed qkv cotangent ``(b, s, 3*H*D)`` and
    the rows ``delta = rowsum(dO * O)`` both kernels used, (b, H, 8, s)
    float32 like lse; jitted like ``_fwd``."""
    from jax.experimental.pallas import tpu as pltpu
    b, sq, hd3 = qkv.shape
    hd = hd3 // 3
    D = hd // heads
    skv = sq
    bq, bkv, G, strip = plan
    n_q, n_kv = sq // bq, skv // bkv
    n_g = heads // G
    gd = G * D

    q_rows = pl.BlockSpec((1, bq, gd), lambda bb, g, i, j: (bb, i, g))
    stat_rows = pl.BlockSpec((1, G, _SUB, bq),
                             lambda bb, g, i, j: (bb, g, 0, i))
    dqk = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_kv=bkv, strip=strip, n_kv=n_kv, group=G, heads=heads,
        head_dim=D, dropout_p=dropout_p)
    # dq first: it also writes delta = rowsum(dO * O), (b, H, 8, s) in the
    # statistics' layout, from the dO and O blocks of its row's first step
    dq, delta_t = pl.pallas_call(
        dqk,
        grid=(b, n_g, n_q, n_kv),
        in_specs=[
            q_rows,                                                 # q
            pl.BlockSpec((1, bkv, gd), _kv_idx_packed(
                causal, bq, bkv, n_kv, 1, n_g, descending=True)),
            pl.BlockSpec((1, bkv, gd), _kv_idx_packed(
                causal, bq, bkv, n_kv, 2, n_g, descending=True)),
            q_rows,                                                 # dO
            q_rows,                                                 # O
            stat_rows,                                              # lse
            _smem_spec(),
        ],
        out_specs=[q_rows, stat_rows],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), qkv.dtype),
            jax.ShapeDtypeStruct((b, heads, _SUB, sq), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((G, bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_packed_bwd_dq",
    )(qkv, qkv, qkv, do, out, lse, seed)

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
        block_kv=bkv, strip=strip, n_q=n_q, group=G, heads=heads,
        head_dim=D, dropout_p=dropout_p)
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
        interpret=interpret,
        name="flash_packed_bwd_dkdv",
    )(qkv, qkv, qkv, do, lse, delta_t, seed)

    # (b, s, 3*H*D): the lane concat of (dq, dk, dv), written as the sum of
    # the three parts padded with zeros (exact: x + 0).  XLA fuses that
    # into each of its consumers (the qkv projection's two backward
    # matmuls and its bias gradient) and no packed array exists; a
    # ``concatenate`` of three kernel results that are all tuple elements
    # (dq's call has two results now, as dkdv's always had) it builds in
    # HBM instead, one dynamic-update-slice a part: 72 fusions, 4.4 ms a
    # step of gpt2-medium (PERF.md §6, PR 31)
    zero = jnp.zeros((), dq.dtype)
    return sum(jax.lax.pad(part, zero,
                           ((0, 0, 0), (0, 0, 0), (i * hd, (2 - i) * hd, 0)))
               for i, part in enumerate((dq, dk, dv))), delta_t


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

def _statics(qkv, heads, causal, sm_scale, dropout_p):
    """The static arguments of ``_fwd`` / ``_bwd``: all of their trace's
    key that is not a shape — the plan, and whether the kernels lower
    interpreted (``_interpret()`` asks the backend, which a test or an
    AOT-for-TPU lowering in a CPU process stands in for).  Runs whenever
    a program that holds the kernels is traced, so it is also where the
    build hears what share of the score square they compute."""
    from ....observability.programs import note_kernel_fact
    _, sq, hd3 = qkv.shape
    plan = _plan(sq, sq, heads, hd3 // 3 // heads, qkv.dtype)
    note_kernel_fact("executed_score_share",
                     _score_share(sq, sq, plan, causal))
    return dict(heads=heads, causal=causal, sm_scale=sm_scale,
                dropout_p=dropout_p, plan=plan, interpret=_interpret())


def _seed_array(seed):
    """The kernels always take a (1,) int32 seed; without dropout they
    never read it."""
    return jnp.zeros((1,), jnp.int32) if seed is None else seed


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def flash_attention_packed(qkv, heads, causal, sm_scale, dropout_p=0.0,
                           seed=None):
    """Flash attention over a packed ``(b, s, 3*H*D)`` qkv projection.

    Returns the packed attention output ``(b, s, H*D)`` ready for the
    output projection. ``seed`` is a (1,) int32 array, required when
    ``dropout_p > 0``.
    """
    out, _ = _fwd(qkv, _seed_array(seed),
                  **_statics(qkv, heads, causal, sm_scale, dropout_p))
    return out


def _vjp_fwd(qkv, heads, causal, sm_scale, dropout_p=0.0, seed=None):
    out, lse = _fwd(qkv, _seed_array(seed),
                    **_statics(qkv, heads, causal, sm_scale, dropout_p))
    return out, (qkv, out, lse, seed)


def _vjp_bwd(heads, causal, sm_scale, dropout_p, res, do):
    qkv, out, lse, seed = res
    dqkv, _ = _bwd(qkv, out, lse, _seed_array(seed), do,
                   **_statics(qkv, heads, causal, sm_scale, dropout_p))
    return (dqkv, None)                             # None: the int seed array


flash_attention_packed.defvjp(_vjp_fwd, _vjp_bwd)
