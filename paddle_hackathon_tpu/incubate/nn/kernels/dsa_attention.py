"""Attention over a learned selection of keys, and what trains the
selector, as Pallas TPU kernels (DeepSeek Sparse Attention's parts: the
lightning indexer's scores, attention masked by the selection, and the
indexer's KL loss with its gradient).

Shapes: batch ``b``, sequence ``s``, ``heads`` query heads and ``kv_heads``
key / value heads of ``d`` dims (``heads / kv_heads`` query heads read one
KV head: no repeat); the indexer has ``ih`` query heads of ``id_`` dims and
one key head.  ``q`` (b, s, heads * d), ``k``, ``v`` (b, s, kv_heads * d)
are the projections' own layout; the indexer's ``qi`` is head-major (b,
ih, s, id_), its key ``ki`` (b, s, id_), its head weights ``w`` (b, s, ih)
and, transposed, ``wt`` (b, ih, 1, s).

**The selection as bits.**  Which keys a query attends to is a bit a
(query, key) pair, packed into int32 words along the queries: ``words``
(b, s, s // 4096 * 128), where query ``t`` and key ``j`` are bit ``(t %
4096) // 128`` of word ``[j, (t // 4096) * 128 + t % 128]``.  A q tile of
``bq`` queries (a multiple of 128 inside one 4096-query group) against a kv
tile of ``bk`` keys is then one (bk, 128) block of words, unpacked by
``bq / 128`` shifts into the (bk, bq) keep-mask of a kv-major score tile.
At s = 16,384 the words are 33.5 MB a layer where a byte a pair would be
268 MB.  The bits hold the causal limit too: a selected key is never later
than its query.

Every score tile is kv-major, (keys, queries), as in
``flash_attention_packed.py``: a query's statistics (maximum, sum,
log-sum-exp) are reductions down the sublanes and are born as the (1, bq)
rows they are stored in, (b, heads, 1, s).  Tiles wholly above the
diagonal are skipped and their blocks not fetched (clamped index maps);
a tile under it runs whole, masked by its bits: with seeded weights the
selections scatter and nearly every causal tile holds a selected key
(``sparse_attention.dsa_counters`` counts them).

Kernels, by ``name`` (what the device trace and the executable's Mosaic
census call them):

- ``dsa_index``: the indexer's scores ``I[t, j] = sum_h w[t, h]
  relu(qi[t, h] . ki[j])`` in float32 for a block of queries against every
  key, ``-inf`` where ``j > t``; q-major (queries, keys), what the
  selection reduces.
- ``dsa_select``: each row's ``k``-th largest score of such a block, found
  bit by bit in VMEM (a count a bit, no sort), the column of its last
  kept tie, and the log-sum-exp of what the row keeps.  Told the block's
  first query, each group of ``SELECT_ROWS`` rows reads only the columns
  up to its last row's position (whole ``SELECT_CHUNK``-column chunks):
  past a row's position its scores are ``-inf``, as ``dsa_index`` writes
  them, so the kept set and the log-sum-exp are those of every column.
- ``dsa_attn_fwd``: softmax attention of each query head over its query's
  selected keys; the output and each row's log-sum-exp.
- ``dsa_attn_bwd_dkdv``, ``dsa_attn_bwd_dq``: its backward, flash style,
  from the log-sum-exp and ``delta = rowsum(dO * O)``.
- ``dsa_kl_fwd``: per query ``KL(p || softmax_S(I))`` over the selected
  set ``S``, with ``p`` the attention's probabilities averaged over all
  heads (read from ``q``, ``k`` and the forward's log-sum-exp), and the
  gradient of the sum of those rows for ``qi`` and ``w``.
- ``dsa_kl_bwd``: the same gradient for ``ki``, whose rows gather over
  queries, scaled by the cotangent of each query's row.

Off the chip every kernel runs interpreted, as the flash kernels do.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _prec

LANES = 128
WORD_BITS = 32
GROUP_QUERIES = WORD_BITS * LANES    # queries a column of 128 words holds
_NEG = -1e30
_VMEM_LIMIT = 96 * 2**20             # of the v5e's 128 MiB


def word_columns(s: int) -> int:
    """Columns of ``words`` for ``s`` queries."""
    return -(-s // GROUP_QUERIES) * LANES


def blocks(s: int, cap: int = 512) -> int:
    """The tile edge for ``s``: ``cap`` or ``s``, whichever is smaller; it
    has to divide ``s`` and be a multiple of 128."""
    b = min(cap, s)
    if s % b or b % LANES:
        raise ValueError(f"sequence {s} is not a multiple of a tile edge "
                         f"(a multiple of {LANES}, at most {cap})")
    return b


def _dot(a, b, b_dim, a_dim=1):
    """``flash_attention_packed._dot``: ``a`` contracted on ``a_dim``
    with ``b`` on ``b_dim``, accumulated in float32."""
    return jax.lax.dot_general(a, b, (((a_dim,), (b_dim,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_prec(a.dtype))


def _keep(words, qi, bq):
    """(bk, bq) keep-mask of a kv-major tile from its (bk, 128) words."""
    shift = (qi * bq) % GROUP_QUERIES // LANES
    parts = [((words >> (shift + i)) & 1) != 0 for i in range(bq // LANES)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# the indexer's scores
# ---------------------------------------------------------------------------

def _index_kernel(row0_ref, qi_ref, w_ref, k_ref, o_ref, *, bq, bk, ih):
    qb, kb = pl.program_id(1), pl.program_id(2)
    first = row0_ref[0] + qb * bq
    live = kb * bk <= first + bq - 1

    @pl.when(live)
    def _scores():
        keys = k_ref[0]                                   # (bk, id)
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(ih):
            s_h = _dot(qi_ref[0, h], keys, 1)             # (bq, bk)
            acc = acc + w_ref[0, :, h:h + 1] * jnp.maximum(s_h, 0.0)
        rows = first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        o_ref[0] = jnp.where(cols <= rows, acc, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _above():
        o_ref[0] = jnp.full((bq, bk), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_call(row0, qi, w, ki, *, interpret):
    b, ih, rows, id_ = qi.shape
    s = ki.shape[1]
    bq, bk = blocks(rows), blocks(s)
    smem = (pl.BlockSpec((1,), lambda *_: (0,)) if interpret
            else pl.BlockSpec(memory_space=pltpu.SMEM))
    return pl.pallas_call(
        functools.partial(_index_kernel, bq=bq, bk=bk, ih=ih),
        out_shape=jax.ShapeDtypeStruct((b, rows, s), jnp.float32),
        grid=(b, rows // bq, s // bk),
        in_specs=[smem,
                  pl.BlockSpec((1, ih, bq, id_), lambda i, q, k: (i, 0, q, 0)),
                  pl.BlockSpec((1, bq, ih), lambda i, q, k: (i, q, 0)),
                  pl.BlockSpec((1, bk, id_), lambda i, q, k: (i, k, 0))],
        out_specs=pl.BlockSpec((1, bq, bk), lambda i, q, k: (i, q, k)),
        compiler_params=_params("parallel", "parallel", "parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * ih * id_ * rows * s, transcendentals=0,
            bytes_accessed=4 * b * rows * s),
        interpret=interpret, name="dsa_index",
    )(row0.reshape(1).astype(jnp.int32), qi, w, ki)


def index_scores(row0, qi, w, ki):
    """The indexer's float32 scores of the queries ``row0 ... row0 +
    rows - 1`` (``qi`` (b, ih, rows, id), ``w`` (b, rows, ih)) against
    every key of ``ki`` (b, s, id): (b, rows, s), ``-inf`` after each
    query's own position."""
    return _index_call(jnp.asarray(row0, jnp.int32), qi, w, ki,
                       interpret=_interpret())


# ---------------------------------------------------------------------------
# the selection: each row's top-k threshold
# ---------------------------------------------------------------------------

_SIGN = -2**31


def order_keys(x):
    """int32 keys of float32 ``x`` in the floats' total order (-0.0 before
    +0.0), the order ``jax.lax.top_k`` sorts by."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


# the selection's grid step and loop widths: the fastest of those tried on
# a v5e at s = 16,384 (PERF.md §6)
SELECT_ROWS = 64       # rows a grid step takes
SELECT_CHUNK = 2048    # columns a step of a counting loop reads
_COUNT_LANES = 256     # lanes of a count's accumulator


def _select_kernel(row0_ref, x_ref, thr_ref, cut_ref, lse_ref, key_s, *, k,
                   chunk, index_bits):
    """Rows of scores in VMEM: the ``k``-th largest key a row, bit by bit
    from the top (a candidate stays where ``k`` keys or more are at or
    above it); of the keys equal to it, the column of the last one the
    row keeps (ties to the earlier key), by halving the columns; and the
    log-sum-exp over what the row keeps.

    The rows are the queries ``row0 + rows * i ...`` (``i`` the grid
    step), and every pass reads the columns before ``row0 + rows * (i +
    1)`` alone, in whole chunks: the contract is that a row's scores past
    its own position are ``-inf`` (``dsa_index`` writes them so).  Then
    the kept columns up to each row's position, and the log-sum-exp, are
    what reading every column gives; ``thr`` and ``cut`` may differ only
    in a row with fewer than ``k`` columns up to its position, all of
    which it keeps either way.

    The keys go once into ``key_s``; once the threshold is known each is
    replaced by its place against it (-1 above, its column where equal,
    ``s`` below), so that the ties before a column and the kept set are
    one comparison each.  A count accumulates lane by lane over the chunks
    and crosses the lanes once a pass; the exponentials are summed a
    128-lane slice at a time in column order, as a sum over the whole row
    adds them."""
    rows, s = x_ref.shape[1:]
    end = jnp.minimum(row0_ref[0] + rows * (pl.program_id(1) + 1), s)
    chunks = (end + chunk - 1) // chunk
    width = _COUNT_LANES if chunk % _COUNT_LANES == 0 else chunk
    sum_w = LANES if chunk % LANES == 0 else chunk
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

    def spans(c, w=width):      # (first column, slice) of chunk c
        c0 = pl.multiple_of(c * chunk, chunk)
        return [(c0 + j, pl.ds(pl.multiple_of(c0 + j, w), w))
                for j in range(0, chunk, w)]

    def count(pred, edge):
        edge = jnp.broadcast_to(edge, (rows, width))

        def step(c, acc):
            for _, at in spans(c):
                acc = acc + pred(key_s[:, at], edge).astype(jnp.int32)
            return acc
        acc = jax.lax.fori_loop(0, chunks, step,
                                jnp.zeros((rows, width), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def keys(c, top):
        for _, at in spans(c):
            x = x_ref[0, :, at]
            key_s[:, at] = order_keys(x)
            top = jnp.maximum(top, x)
        return top

    top = jnp.max(jax.lax.fori_loop(
        0, chunks, keys, jnp.full((rows, width), -jnp.inf, jnp.float32)),
        axis=1, keepdims=True)

    def bit(i, t):      # t in the unsigned order: t ^ _SIGN is a key
        cand = t | jnp.left_shift(jnp.int32(1), 31 - i)
        above = count(lambda key, least: key >= least, cand ^ _SIGN) >= k
        return jnp.where(above, cand, t)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32)) \
        ^ _SIGN
    thr_w = jnp.broadcast_to(thr, (rows, width))

    def place(c, _):
        for j, at in spans(c):
            key = key_s[:, at]
            key_s[:, at] = jnp.where(
                key > thr_w, -1, jnp.where(key == thr_w, j + lane, s))
        return 0

    jax.lax.fori_loop(0, chunks, place, 0)

    def half(i, p):     # the last column p with fewer than k kept before it
        cand = p + jnp.left_shift(jnp.int32(1), index_bits - 1 - i)
        few = count(lambda placed, edge: placed < edge, cand) < k
        return jnp.where((cand <= chunks * chunk) & few, cand, p)

    cut = jax.lax.fori_loop(0, index_bits, half,
                            jnp.zeros((rows, 1), jnp.int32))
    cut_l = jnp.broadcast_to(cut, (rows, sum_w))
    top_l = jnp.broadcast_to(top, (rows, sum_w))

    def exps(c, acc):
        for _, at in spans(c, sum_w):
            acc = acc + jnp.where(key_s[:, at] <= cut_l,
                                  jnp.exp(x_ref[0, :, at] - top_l), 0.0)
        return acc

    total = jnp.sum(jax.lax.fori_loop(
        0, chunks, exps, jnp.zeros((rows, sum_w), jnp.float32)),
        axis=1, keepdims=True)
    thr_ref[0] = jnp.broadcast_to(thr, (rows, LANES))
    cut_ref[0] = jnp.broadcast_to(cut, (rows, LANES))
    lse_ref[0] = jnp.broadcast_to(top + jnp.log(total), (rows, LANES))


def _read_columns(s, rb, chunk):
    """Columns a row reads, on the mean over the rows of a causal sequence
    of ``s`` queries: a group of ``rb`` rows reads up to its last row's
    position, in whole chunks."""
    groups = max(1, s // rb)
    return sum(min(-(-rb * (g + 1) // chunk) * chunk, s)
               for g in range(groups)) / groups


@functools.partial(jax.jit, static_argnames=("k", "chunk", "causal",
                                             "interpret"))
def _select_call(row0, scores, *, k, chunk, causal, interpret):
    b, rows, s = scores.shape
    rb = SELECT_ROWS if rows % SELECT_ROWS == 0 else rows
    chunk = math.gcd(chunk, s) if s % LANES == 0 else s
    lanes = pl.BlockSpec((1, rb, LANES), lambda i, j: (i, j, 0))
    out = (jax.ShapeDtypeStruct((b, rows, LANES), jnp.int32),) * 2 \
        + (jax.ShapeDtypeStruct((b, rows, LANES), jnp.float32),)
    smem = (pl.BlockSpec((1,), lambda *_: (0,)) if interpret
            else pl.BlockSpec(memory_space=pltpu.SMEM))
    index_bits = s.bit_length()
    # passes over each column read: the keys, 32 bits, the places, the
    # halving's index_bits and the exponentials
    pairs = int(b * rows * (_read_columns(s, rb, chunk) if causal else s))
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, chunk=chunk,
                          index_bits=index_bits),
        out_shape=out,
        grid=(b, rows // rb),
        in_specs=[smem, pl.BlockSpec((1, rb, s), lambda i, j: (i, j, 0))],
        out_specs=(lanes,) * 3,
        scratch_shapes=[pltpu.VMEM((rb, s), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * (35 + index_bits) * pairs, transcendentals=pairs,
            bytes_accessed=4 * b * rows * s),
        interpret=interpret, name="dsa_select",
    )(row0.reshape(1).astype(jnp.int32), scores)


def select_threshold(scores, k: int, row0=None):
    """Of every row of ``scores`` (b, rows, s) float32 what its ``k``
    largest (``jax.lax.top_k``'s choice: the floats' total order, ties to
    the earlier column) are: ``(thr, cut, lse)``, each (b, rows, 1).  A
    column is kept where its ``order_keys`` is above ``thr``, or equal to
    it at or before column ``cut``; ``lse`` is the log-sum-exp of the kept
    scores.

    ``row0`` (an int, traced or not) says the rows are the queries
    ``row0 ... row0 + rows - 1`` of a causal sequence, and promises that
    a row's scores past its own position are ``-inf``: each group of rows
    then reads only the columns up to its last row's position.  The kept
    columns up to each row's position and ``lse`` are those of reading
    every column; ``thr`` and ``cut`` may differ in a row with fewer than
    ``k`` such columns.  Without ``row0`` every column is read."""
    s = scores.shape[2]
    thr, cut, lse = _select_call(
        jnp.asarray(s if row0 is None else row0, jnp.int32), scores, k=k,
        chunk=SELECT_CHUNK, causal=row0 is not None,
        interpret=_interpret())
    return thr[..., :1], cut[..., :1], lse[..., :1]


# ---------------------------------------------------------------------------
# attention masked by the selection
# ---------------------------------------------------------------------------

def _last_kv(qi, bq, bk):
    return (qi * bq + bq - 1) // bk


def _fwd_kernel(q_ref, k_ref, v_ref, words_ref, o_ref, lse_ref, acc_s, m_s,
                l_s, *, scale, bq, bk, group, d, nk):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(ki <= _last_kv(qi, bq, bk))
    def _tile():
        keep = _keep(words_ref[0], qi, bq)                      # (bk, bq)
        kb, vb = k_ref[0], v_ref[0]                         # (bk, d)
        for h in range(group):
            st = jnp.where(keep, _dot(kb, q_ref[0, :, h * d:(h + 1) * d], 1)
                           * scale, _NEG)                   # (bk, bq)
            m_old = m_s[h]
            m_new = jnp.maximum(m_old, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            pt = jnp.where(keep, jnp.exp(st - m_new), 0.0)
            l_s[h] = l_s[h] * alpha + jnp.sum(pt, axis=0, keepdims=True)
            acc_s[h] = acc_s[h] * alpha + _dot(vb, pt.astype(vb.dtype), 0,
                                               a_dim=0)     # (d, bq)
            m_s[h] = m_new

    @pl.when(ki == nk - 1)
    def _write():
        for h in range(group):
            o_ref[0, :, h * d:(h + 1) * d] = \
                (acc_s[h] / l_s[h]).T.astype(o_ref.dtype)
            lse_ref[0, h] = m_s[h] + jnp.log(l_s[h])


def _attn_specs(heads, kv_heads, d, bq, bk, s, q_major):
    """Block specs of q / do / o (query blocks of a head group), k / v
    (one KV head), the statistics (b, heads, 1, s) and the words, for a
    grid (b, kv head, outer, inner): q-major has the query tile outer,
    kv-major the key tile."""
    group = heads // kv_heads

    def tiles(i, g, o, n):
        if q_major:      # clamp the skipped kv tiles onto the last one run
            return o, jnp.minimum(n, _last_kv(o, bq, bk))
        return jnp.maximum(n, (o * bk) // bq), o

    def qspec(width):
        return pl.BlockSpec((1, bq, width),
                            lambda i, g, o, n: (i, tiles(i, g, o, n)[0], g))

    kspec = pl.BlockSpec((1, bk, d),
                         lambda i, g, o, n: (i, tiles(i, g, o, n)[1], g))
    stat = pl.BlockSpec((1, group, 1, bq),
                        lambda i, g, o, n: (i, g, 0, tiles(i, g, o, n)[0]))
    words = pl.BlockSpec(
        (1, bk, LANES), lambda i, g, o, n: (
            i, tiles(i, g, o, n)[1], tiles(i, g, o, n)[0] * bq
            // GROUP_QUERIES))
    return qspec(group * d), kspec, stat, words


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def _attn_fwd(q, k, v, words, *, heads, scale, interpret):
    b, s, _ = q.shape
    d = q.shape[2] // heads
    kv_heads = k.shape[2] // d
    group = heads // kv_heads
    bq = bk = blocks(s)
    nq, nk = s // bq, s // bk
    qspec, kspec, stat, wspec = _attn_specs(heads, kv_heads, d, bq, bk, s,
                                            True)
    pairs = nq * (nq + 1) // 2 * bq * bk
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          group=group, d=d, nk=nk),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32)),
        grid=(b, kv_heads, nq, nk),
        in_specs=[qspec, kspec, kspec, wspec],
        out_specs=(qspec, stat),
        scratch_shapes=[pltpu.VMEM((group, d, bq), jnp.float32),
                        pltpu.VMEM((group, 1, bq), jnp.float32),
                        pltpu.VMEM((group, 1, bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * heads * d * pairs,
            transcendentals=b * heads * pairs,
            bytes_accessed=2 * (2 * q.size + 2 * nq * k.size)),
        interpret=interpret, name="dsa_attn_fwd",
    )(q, k, v, words)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, words_ref,
                 dk_ref, dv_ref, dk_s, dv_s, *, scale, bq, bk, group, d, nq):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when(qi * bq + bq - 1 >= ki * bk)
    def _tile():
        keep = _keep(words_ref[0], qi, bq)
        kb, vb = k_ref[0], v_ref[0]
        dk = jnp.zeros((bk, d), jnp.float32)
        dv = jnp.zeros((bk, d), jnp.float32)
        for h in range(group):
            qh = q_ref[0, :, h * d:(h + 1) * d]
            doh = do_ref[0, :, h * d:(h + 1) * d]
            pt = jnp.where(keep, jnp.exp(_dot(kb, qh, 1) * scale
                                         - lse_ref[0, h]), 0.0)
            dv = dv + _dot(pt.astype(doh.dtype), doh, 0)
            dst = pt * (_dot(vb, doh, 1) - dl_ref[0, h])
            dk = dk + _dot(dst.astype(qh.dtype), qh, 0)
        dk_s[...] += dk * scale
        dv_s[...] += dv

    @pl.when(qi == nq - 1)
    def _write():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, words_ref, dq_ref,
               dq_s, *, scale, bq, bk, group, d, nk):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(ki <= _last_kv(qi, bq, bk))
    def _tile():
        keep = _keep(words_ref[0], qi, bq)
        kb, vb = k_ref[0], v_ref[0]
        for h in range(group):
            qh = q_ref[0, :, h * d:(h + 1) * d]
            doh = do_ref[0, :, h * d:(h + 1) * d]
            pt = jnp.where(keep, jnp.exp(_dot(kb, qh, 1) * scale
                                         - lse_ref[0, h]), 0.0)
            dst = pt * (_dot(vb, doh, 1) - dl_ref[0, h])
            dq_s[h] += _dot(kb, dst.astype(kb.dtype), 0, a_dim=0)  # (d, bq)

    @pl.when(ki == nk - 1)
    def _write():
        for h in range(group):
            dq_ref[0, :, h * d:(h + 1) * d] = \
                (dq_s[h] * scale).T.astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def _attn_bwd(q, k, v, words, o, lse, do, *, heads, scale, interpret):
    b, s, _ = q.shape
    d = q.shape[2] // heads
    kv_heads = k.shape[2] // d
    group = heads // kv_heads
    bq = bk = blocks(s)
    nq, nk = s // bq, s // bk
    # delta = rowsum(dO * O) a query and head, in the statistics' layout
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, s, heads, d), -1)
    delta = jnp.swapaxes(delta, 1, 2)[:, :, None, :]
    pairs = nq * (nq + 1) // 2 * bq * bk
    args = (q, k, v, do, lse, delta, words)
    qspec, kspec, stat, wspec = _attn_specs(heads, kv_heads, d, bq, bk, s,
                                            False)
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, bq=bq, bk=bk,
                          group=group, d=d, nq=nq),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid=(b, kv_heads, nk, nq),
        in_specs=[qspec, kspec, kspec, qspec, stat, stat, wspec],
        out_specs=(kspec, kspec),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * heads * d * pairs,
            transcendentals=b * heads * pairs,
            bytes_accessed=2 * (2 * nk * q.size + 4 * k.size)),
        interpret=interpret, name="dsa_attn_bwd_dkdv",
    )(*args)
    qspec, kspec, stat, wspec = _attn_specs(heads, kv_heads, d, bq, bk, s,
                                            True)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk,
                          group=group, d=d, nk=nk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, kv_heads, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, stat, stat, wspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((group, d, bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * heads * d * pairs,
            transcendentals=b * heads * pairs,
            bytes_accessed=2 * (3 * q.size + 2 * nq * k.size)),
        interpret=interpret, name="dsa_attn_bwd_dq",
    )(*args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def masked_attention(q, k, v, words, heads, scale):
    """Softmax attention of every query head over its query's selected
    keys (the bits of ``words``): ``q`` (b, s, heads * d), ``k``, ``v``
    (b, s, kv_heads * d) -> ``(o, lse)``, ``o`` like ``q``, ``lse`` (b,
    heads, 1, s) float32, the log-sum-exp of each row's scaled scores.
    ``lse`` carries no gradient."""
    return _attn_fwd(q, k, v, words, heads=heads, scale=scale,
                     interpret=_interpret())


def _masked_fwd(q, k, v, words, heads, scale):
    o, lse = masked_attention(q, k, v, words, heads, scale)
    return (o, lse), (q, k, v, words, o, lse)


def _masked_bwd(heads, scale, res, cts):
    q, k, v, words, o, lse = res
    dq, dk, dv = _attn_bwd(q, k, v, words, o, lse, cts[0], heads=heads,
                           scale=scale, interpret=_interpret())
    return dq, dk, dv, None


masked_attention.defvjp(_masked_fwd, _masked_bwd)


# ---------------------------------------------------------------------------
# the indexer's KL loss and its gradient
# ---------------------------------------------------------------------------

def _kl_tile(q_ref, k_ref, lse_ref, qi_ref, ki_ref, wt_ref, words_ref, li_ref,
             *, qb, bq, scale, heads, kv_heads, d, ih):
    """What both KL kernels compute of a tile, each (bk, bq) float32 and
    zero off the selection: ``p`` (the heads' mean attention
    probability), the indexer's scores ``I``, and the KL's gradient for
    them, ``softmax_S(I) - p``."""
    keep = _keep(words_ref[0], qb, bq)
    group = heads // kv_heads
    p = None
    for g in range(kv_heads):
        kg = k_ref[0, :, g * d:(g + 1) * d]
        for h in range(g * group, (g + 1) * group):
            e = jnp.exp(_dot(kg, q_ref[0, :, h * d:(h + 1) * d], 1) * scale
                        - lse_ref[0, h])
            p = e if p is None else p + e
    p = jnp.where(keep, p * (1.0 / heads), 0.0)
    keys = ki_ref[0]
    index = None
    for h in range(ih):
        term = wt_ref[0, h] * jnp.maximum(_dot(keys, qi_ref[0, h], 1), 0.0)
        index = term if index is None else index + term
    d_index = jnp.where(keep, jnp.exp(index - li_ref[0]), 0.0) - p
    return p, index, d_index


def _kl_fwd_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, wt_ref, words_ref,
                   li_ref, kl_ref, dqi_ref, dwt_ref, a_s, ps_s, dq_s, dw_s,
                   *, bq, bk, nk, **tile):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        a_s[...] = jnp.zeros_like(a_s)
        ps_s[...] = jnp.zeros_like(ps_s)
        dq_s[...] = jnp.zeros_like(dq_s)
        dw_s[...] = jnp.zeros_like(dw_s)

    @pl.when(kb <= _last_kv(qb, bq, bk))
    def _tile():
        p, index, d_index = _kl_tile(q_ref, k_ref, lse_ref, qi_ref, ki_ref,
                                     wt_ref, words_ref, li_ref, qb=qb, bq=bq,
                                     **tile)
        plogp = jnp.where(p > 0.0, p * (jnp.log(jnp.where(p > 0.0, p, 1.0))
                                        - index), 0.0)
        a_s[...] += jnp.sum(plogp, axis=0, keepdims=True)
        ps_s[...] += jnp.sum(p, axis=0, keepdims=True)
        keys = ki_ref[0]
        for h in range(tile["ih"]):
            s_h = _dot(keys, qi_ref[0, h], 1)
            g_h = jnp.where(s_h > 0.0, d_index, 0.0)
            dw_s[h] += jnp.sum(g_h * s_h, axis=0, keepdims=True)
            dq_s[h] += _dot(keys, g_h.astype(keys.dtype), 0, a_dim=0)

    @pl.when(kb == nk - 1)
    def _write():
        kl_ref[0] = a_s[...] + li_ref[0] * ps_s[...]
        for h in range(tile["ih"]):
            dqi_ref[0, h] = (dq_s[h] * wt_ref[0, h]).T
            dwt_ref[0, h] = dw_s[h]


def _kl_bwd_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, wt_ref, words_ref,
                   li_ref, ct_ref, dki_ref, dk_s, *, bq, bk, nq, **tile):
    kb, qb = pl.program_id(1), pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)

    @pl.when(qb * bq + bq - 1 >= kb * bk)
    def _tile():
        _, _, d_index = _kl_tile(q_ref, k_ref, lse_ref, qi_ref, ki_ref,
                                 wt_ref, words_ref, li_ref, qb=qb, bq=bq,
                                 **tile)
        d_index = d_index * ct_ref[0]
        keys = ki_ref[0]
        acc = None
        for h in range(tile["ih"]):
            qh = qi_ref[0, h]
            g_h = jnp.where(_dot(keys, qh, 1) > 0.0, d_index * wt_ref[0, h],
                            0.0)
            term = _dot(g_h.astype(qh.dtype), qh, 0)       # (bk, id)
            acc = term if acc is None else acc + term
        dk_s[...] += acc

    @pl.when(qb == nq - 1)
    def _write():
        dki_ref[0] = dk_s[...]


def _kl_specs(heads, kv_heads, d, ih, id_, bq, bk, q_major):
    """Block specs for a grid (b, outer, inner) over all heads."""
    def tiles(o, n):
        if q_major:
            return o, jnp.minimum(n, _last_kv(o, bq, bk))
        return jnp.maximum(n, (o * bk) // bq), o

    def at(shape, index):
        return pl.BlockSpec(shape, lambda i, o, n: index(i, *tiles(o, n)))

    return [
        at((1, bq, heads * d), lambda i, t, j: (i, t, 0)),          # q
        at((1, bk, kv_heads * d), lambda i, t, j: (i, j, 0)),       # k
        at((1, heads, 1, bq), lambda i, t, j: (i, 0, 0, t)),        # lse
        at((1, ih, bq, id_), lambda i, t, j: (i, 0, t, 0)),         # qi
        at((1, bk, id_), lambda i, t, j: (i, j, 0)),                # ki
        at((1, ih, 1, bq), lambda i, t, j: (i, 0, 0, t)),           # wt
        at((1, bk, LANES),
           lambda i, t, j: (i, j, t * bq // GROUP_QUERIES)),        # words
        at((1, 1, bq), lambda i, t, j: (i, 0, t)),                  # lse_i
        at((1, 1, bq), lambda i, t, j: (i, 0, t)),                  # rows
    ]


def _kl_statics(q, k, qi, heads):
    b, s, _ = q.shape
    d = q.shape[2] // heads
    kv_heads = k.shape[2] // d
    ih, id_ = qi.shape[1], qi.shape[3]
    return b, s, d, kv_heads, ih, id_


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def _kl_fwd(q, k, lse, qi, ki, wt, words, lse_i, *, heads, scale,
            interpret):
    b, s, d, kv_heads, ih, id_ = _kl_statics(q, k, qi, heads)
    bq, bk = blocks(s, 256), blocks(s)
    nq, nk = s // bq, s // bk
    specs = _kl_specs(heads, kv_heads, d, ih, id_, bq, bk, True)
    pairs = s * (s + 1) // 2
    tile = dict(scale=scale, heads=heads, kv_heads=kv_heads, d=d, ih=ih)
    return pl.pallas_call(
        functools.partial(_kl_fwd_kernel, bq=bq, bk=bk, nk=nk, **tile),
        out_shape=(jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
                   jax.ShapeDtypeStruct(qi.shape, jnp.float32),
                   jax.ShapeDtypeStruct(wt.shape, jnp.float32)),
        grid=(b, nq, nk),
        in_specs=specs[:8],
        out_specs=(specs[8], specs[3], specs[5]),
        scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32),
                        pltpu.VMEM((1, bq), jnp.float32),
                        pltpu.VMEM((ih, id_, bq), jnp.float32),
                        pltpu.VMEM((ih, 1, bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * pairs * (heads * d + 3 * ih * id_),
            transcendentals=b * pairs * (heads + 2),
            bytes_accessed=2 * (q.size + nq * k.size)),
        interpret=interpret, name="dsa_kl_fwd",
    )(q, k, lse, qi, ki, wt, words, lse_i)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def _kl_bwd(q, k, lse, qi, ki, wt, words, lse_i, ct, *, heads, scale,
            interpret):
    b, s, d, kv_heads, ih, id_ = _kl_statics(q, k, qi, heads)
    bq, bk = blocks(s, 256), blocks(s)
    nq, nk = s // bq, s // bk
    specs = _kl_specs(heads, kv_heads, d, ih, id_, bq, bk, False)
    pairs = s * (s + 1) // 2
    tile = dict(scale=scale, heads=heads, kv_heads=kv_heads, d=d, ih=ih)
    return pl.pallas_call(
        functools.partial(_kl_bwd_kernel, bq=bq, bk=bk, nq=nq, **tile),
        out_shape=jax.ShapeDtypeStruct(ki.shape, jnp.float32),
        grid=(b, nk, nq),
        in_specs=specs,
        out_specs=specs[4],
        scratch_shapes=[pltpu.VMEM((bk, id_), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * pairs * (heads * d + 3 * ih * id_),
            transcendentals=b * pairs * (heads + 1),
            bytes_accessed=2 * (nk * q.size + k.size)),
        interpret=interpret, name="dsa_kl_bwd",
    )(q, k, lse, qi, ki, wt, words, lse_i, ct)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def indexer_kl(q, k, lse, qi, ki, wt, words, lse_i, heads, scale):
    """Per query ``KL(p || softmax_S(I))`` over its selected keys ``S``
    (the bits of ``words``): ``p`` the attention probabilities of ``q``,
    ``k`` under ``lse`` (``masked_attention``'s) averaged over the heads,
    ``I`` the indexer's scores from ``qi`` (b, ih, s, id), ``ki`` (b, s,
    id), ``wt`` (b, ih, 1, s), ``lse_i`` (b, 1, s) the log-sum-exp of
    ``I`` over ``S``.  -> (b, 1, s) float32.

    Differentiable in ``qi``, ``ki`` and ``wt`` only: ``p`` is a target
    (no gradient reaches ``q``, ``k``, ``lse``), and ``lse_i`` is a
    function of ``I`` whose part of the gradient is in ``q - p``."""
    return _kl_fwd(q, k, lse, qi, ki, wt, words, lse_i, heads=heads,
                   scale=scale, interpret=_interpret())[0]


def _kl_vjp_fwd(q, k, lse, qi, ki, wt, words, lse_i, heads, scale):
    kl, dqi, dwt = _kl_fwd(q, k, lse, qi, ki, wt, words, lse_i, heads=heads,
                           scale=scale, interpret=_interpret())
    return kl, (q, k, lse, qi, ki, wt, words, lse_i, dqi, dwt)


def _kl_vjp_bwd(heads, scale, res, ct):
    q, k, lse, qi, ki, wt, words, lse_i, dqi, dwt = res
    ct = ct.astype(jnp.float32)
    dki = _kl_bwd(q, k, lse, qi, ki, wt, words, lse_i, ct, heads=heads,
                  scale=scale, interpret=_interpret())
    rows = ct[:, None]                                   # (b, 1, 1, s)
    dqi = dqi * jnp.swapaxes(rows, 2, 3)
    return (None, None, None, dqi.astype(qi.dtype), dki.astype(ki.dtype),
            (dwt * rows).astype(wt.dtype), None, None)


indexer_kl.defvjp(_kl_vjp_fwd, _kl_vjp_bwd)


def causal_tiles(s: int) -> int:
    """Causal (q tile, kv tile) cells the attention kernels run at ``s``."""
    bq = bk = blocks(s)
    return sum(_last_kv(i, bq, bk) + 1 for i in range(s // bq))


def kernel_names():
    """The Mosaic kernels of this file, by the name the device trace and
    the executable's census give them."""
    return ("dsa_index", "dsa_select", "dsa_attn_fwd", "dsa_attn_bwd_dkdv",
            "dsa_attn_bwd_dq", "dsa_kl_fwd", "dsa_kl_bwd")


__all__ = ["index_scores", "select_threshold", "order_keys",
           "masked_attention", "indexer_kl", "word_columns",
           "blocks", "causal_tiles", "kernel_names"]
