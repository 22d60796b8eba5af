"""Pallas TPU paged-attention decode kernel + pure-jnp reference path.

The serving engine's paged KV layout stores each layer's cache as a
global page pool ``(num_pages, page_size, heads, head_dim)`` plus a
per-slot page table ``(B, pages_per_slot)`` of physical page ids
(``inference/paged.py`` owns the host-side allocator).  Attention then
needs a gather through the table.  Two implementations share this
module:

- :func:`paged_attention_ref` — pure jnp, any query width: gather the
  slot's pages into a contiguous ``(B, T, H, D)`` view and run exactly
  the dense static-cache composition from ``models/gpt.py`` (same einsum
  strings, same ``-1e30`` mask, same softmax), so paged greedy decode is
  token-exact against the dense engine.  This is the CPU/tier-1 path and
  the chunk-prefill path.
- :func:`paged_attention_decode` — the Pallas kernel for width-1 decode
  (the steady-state hot path).  The page gather happens at the GRID
  level: the kv block index map reads the scalar-prefetched page table,
  so each grid cell DMAs exactly one physical page from the pool —
  no materialized ``(B, T, H, D)`` gather in HBM.  Pages past a slot's
  length clamp to the previous index (Pallas elides the repeat DMA) and
  a ``pl.when`` skips their compute, mirroring the causal-grid trick in
  the in-tree ``flash_attention.py``.  Softmax is online (f32 VMEM
  scratch); the per-page score/context products are VPU element-wise
  contractions — at decode shapes (one query row) kernel time is
  DMA-bound, which is the point: the kernel reads ``length`` rows where
  the dense tick reads ``max_len``.

On non-TPU backends the kernel runs under the Pallas interpreter for
numerics tests; the engine dispatches the reference path there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret
from .mesh import partitioned_axes

_NEG_INF = -1e30  # large-but-finite, matching the dense composition
_LANES = 128

# test hook: None = auto (kernel on TPU, reference elsewhere);
# True/False force the choice (CPU tests force True to run the kernel
# under the Pallas interpreter)
FORCE_KERNEL = None


def supported(page_size: int, head_dim: int) -> bool:
    """Whether the decode kernel handles this pool geometry (else the
    reference path runs).  Sub-128 lanes are padded by Mosaic in VMEM
    (same contract as flash_attention.py's head_dim handling)."""
    return page_size % 8 == 0 and head_dim % 8 == 0


def use_kernel(page_size: int, head_dim: int) -> bool:
    """Kernel on TPU for supported geometry — on ONE device.  In a
    program partitioned over a multi-device mesh (TP-sharded serving) a
    Mosaic call cannot be auto-partitioned (``kernels/mesh.py``) and this
    kernel is not wrapped per shard yet, so dispatch says reference."""
    if FORCE_KERNEL is not None:
        return bool(FORCE_KERNEL)
    return ((not _interpret()) and supported(page_size, head_dim)
            and not partitioned_axes())


def paged_write(pool, vals, page_table, pos):
    """Write ``vals`` (B, s, H, D) at logical rows ``[pos, pos+s)`` of
    each slot through the page table: row ``r`` of slot ``b`` lives at
    physical row ``page_table[b, r // P] * P + r % P`` of the flattened
    pool.  One scatter covers every slot (page-boundary straddles just
    split a slot's rows across two physical pages).  Inactive slots'
    table rows are NULL (page 0), so their garbage writes land in the
    reserved scratch page instead of live KV."""
    N, P, H, D = pool.shape
    B, s = vals.shape[:2]
    positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    page_idx = positions // P
    # take_along_axis clips out-of-range page indices; active slots are
    # guarded by the engine's page-granular capacity check, inactive
    # slots only ever index page_idx < pages_per_slot (reserve <= max_len)
    phys = jnp.take_along_axis(page_table, page_idx, axis=1) * P \
        + positions % P
    flat = pool.reshape(N * P, H, D)
    flat = flat.at[phys.reshape(-1)].set(
        vals.astype(pool.dtype).reshape(B * s, H, D))
    return flat.reshape(N, P, H, D)


def paged_attention_ref(q, k_pool, v_pool, page_table, lengths):
    """Reference paged attention, any query width: gather + the exact
    dense static-cache composition (``models/gpt.py``).  ``lengths`` is
    each slot's write offset this call (the query at width index ``i``
    sits at global position ``lengths[b] + i`` and attends
    ``kpos <= qpos``); the current tokens' K/V are already in the pool
    (write-before-read, like the dense path)."""
    N, P, H, D = k_pool.shape
    B, s = q.shape[:2]
    rows = (page_table[:, :, None] * P
            + jnp.arange(P, dtype=jnp.int32)[None, None, :]).reshape(B, -1)
    kb = k_pool.reshape(N * P, H, D)[rows]        # (B, T, H, D)
    vb = v_pool.reshape(N * P, H, D)[rows]
    qpos = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    kpos = jnp.arange(rows.shape[1], dtype=jnp.int32)
    mask = (kpos[None, None, :] <= qpos[..., None])[:, None]   # (B,1,s,T)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bshe,bthe->bhst", q, kb.astype(q.dtype)) * scale
    logits = jnp.where(mask, logits, jnp.asarray(_NEG_INF, logits.dtype))
    probs = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhst,bthe->bshe", probs, vb.astype(probs.dtype))


def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, page_size, n_pages, sm_scale):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    # pages past the one holding row `length` are clamped by the index
    # map (DMA elided) and skipped here
    @pl.when(j <= length // page_size)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (H, D)
        kt = jnp.swapaxes(k_ref[0], 0, 1)              # (H, P, D) in-VMEM
        s = jnp.sum(kt.astype(jnp.float32) * q[:, None, :], axis=-1)
        s = s * sm_scale                               # (H, P)
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= length, s, _NEG_INF)
        m_prev = m_ref[...]                            # (H, LANES)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)      # (H, 1)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])                 # (H, P)
        l_ref[...] = l_prev * alpha + jnp.sum(
            p, axis=1, keepdims=True) * jnp.ones_like(l_prev)
        vt = jnp.swapaxes(v_ref[0], 0, 1)              # (H, P, D)
        pv = jnp.sum(vt.astype(jnp.float32) * p[:, :, None], axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_next

    @pl.when(j == n_pages - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_decode(q, k_pool, v_pool, page_table, lengths):
    """Width-1 paged decode attention via the Pallas kernel.  ``q`` is
    (B, 1, H, D); returns (B, 1, H, D)."""
    from jax.experimental.pallas import tpu as pltpu

    N, P, H, D = k_pool.shape
    B, s = q.shape[:2]
    assert s == 1, "the decode kernel is width-1; wider goes via ref"
    maxp = page_table.shape[1]
    sm_scale = 1.0 / math.sqrt(D)

    def kv_idx(b, j, pt_ref, len_ref):
        jj = jnp.minimum(j, len_ref[b] // P)
        return (pt_ref[b * maxp + jj], 0, 0, 0)

    kernel = functools.partial(_decode_kernel, page_size=P, n_pages=maxp,
                               sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, maxp),
            in_specs=[
                pl.BlockSpec((1, 1, H, D), lambda b, j, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, P, H, D), kv_idx),
                pl.BlockSpec((1, P, H, D), kv_idx),
            ],
            out_specs=pl.BlockSpec((1, 1, H, D),
                                   lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, D), jnp.float32),       # acc
                pltpu.VMEM((H, _LANES), jnp.float32),  # m
                pltpu.VMEM((H, _LANES), jnp.float32),  # l
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, H, D), q.dtype),
        interpret=_interpret(),
        name="paged_decode",
    )(page_table.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), q, k_pool, v_pool)
    return out


def paged_attention(q, k_pool, v_pool, page_table, lengths):
    """Dispatch: the Pallas kernel on TPU for width-1 decode, the jnp
    reference otherwise (CPU/tier-1, chunk prefill, spec verify widths).
    ``FORCE_KERNEL`` overrides for interpreter-mode kernel tests."""
    P, D = k_pool.shape[1], k_pool.shape[3]
    if q.shape[1] == 1 and use_kernel(P, D):
        return paged_attention_decode(q, k_pool, v_pool, page_table,
                                      lengths)
    return paged_attention_ref(q, k_pool, v_pool, page_table, lengths)
