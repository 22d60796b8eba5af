"""Pallas TPU weight-only quantized matmul + pure-jnp reference path.

Decode throughput is HBM-bandwidth-bound: every tick re-streams the full
weight matrices, and at decode batch sizes the MXU is idle waiting on
those loads.  Weight-only quantization (LLM.int8 / AWQ lineage) stores
each Linear weight as int8 (or fp8-e4m3) with one f32 scale per OUTPUT
channel and keeps activations bf16 — halving weight HBM traffic roughly
doubles effective GEMM bandwidth while the bf16 activation path
preserves quality.  Two implementations share this module:

- :func:`quant_matmul_ref` — pure jnp, any backend: widen the quantized
  weight to the activation dtype, one f32-accumulated dot, scale the
  columns.  Because the per-output-channel scale is constant over the
  contraction, ``(x @ (w_q * s)) == (x @ w_q) * s`` — dequant commutes
  out of the GEMM, so the reference IS the fused kernel's math.  This is
  the CPU/tier-1 path and the numerics oracle.
- :func:`quant_matmul_kernel` — the Pallas kernel: int8 tiles stream
  HBM→VMEM at half the bf16 bytes, widen to the activation dtype in
  VMEM registers (no dequantized copy ever exists in HBM), MXU dot with
  f32 accumulation, and the per-channel scale applied once on the f32
  accumulator in the epilogue.  The grid is (M tiles, N tiles) with the
  FULL contraction per cell — N innermost, so the activation tile stays
  resident in VMEM while weight tiles stream past it (the weight is the
  array whose bandwidth the quantization bought back).  Blocking only M
  and N keeps every output element's full contraction inside one dot, so
  kernel-vs-ref agreement is at the dot level: interpreter-mode runs
  match the reference to within dot reassociation (CPU XLA picks a
  K-tiling per output shape — observed <= 1 output-ulp on bf16
  activations, the serving dtype) — tests pin the tolerance.

Dispatch mirrors ``paged_attention``: the kernel on TPU for supported
geometry, the reference elsewhere; ``FORCE_KERNEL`` runs the kernel
under the Pallas interpreter for numerics tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret, _prec
from .mesh import partitioned_axes

_LANES = 128

# test hook: None = auto (kernel on TPU, reference elsewhere);
# True/False force the choice (CPU tests force True to run the kernel
# under the Pallas interpreter)
FORCE_KERNEL = None


def _is_quant_dtype(dtype) -> bool:
    return dtype == jnp.int8 or dtype == jnp.float8_e4m3fn


def supported(k: int, n: int, w_dtype) -> bool:
    """Whether the kernel handles this GEMM geometry (else the reference
    runs).  Lane-aligned K and N keep the int8 tiles on the (32, 128)
    native tiling; M is padded by the wrapper."""
    return k % _LANES == 0 and n % _LANES == 0 and _is_quant_dtype(w_dtype)


def use_kernel(k: int, n: int, w_dtype) -> bool:
    """Kernel on TPU for supported geometry — on ONE device.  In a
    program partitioned over a multi-device mesh a Mosaic call cannot be
    auto-partitioned (``kernels/mesh.py``) and this GEMM is not wrapped
    per weight shard yet, so dispatch says reference."""
    if FORCE_KERNEL is not None:
        return bool(FORCE_KERNEL)
    return ((not _interpret()) and supported(k, n, w_dtype)
            and not partitioned_axes())


def quant_matmul_ref(x, w_q, scale):
    """Reference weight-only matmul: ``(x @ widen(w_q)) * scale`` with
    f32 accumulation, result in ``x.dtype``.  ``x`` (..., K) activation,
    ``w_q`` (K, N) int8/fp8, ``scale`` (N,) f32 per-output-channel."""
    acc = jnp.dot(x, w_q.astype(x.dtype),
                  preferred_element_type=jnp.float32)
    return (acc * scale).astype(x.dtype)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref):
    # widen int8→activation dtype in VMEM (the only dequantized form of
    # the weight anywhere), f32-accumulated MXU dot, scale the columns
    # of the f32 accumulator once in the epilogue.  fp8 widens through
    # f32: Mosaic has no direct float8_e4m3fn→bfloat16 cast (jax 0.9.0).
    # Precision is explicit — the package-global "highest" default would
    # request an fp32 contraction on bf16 operands, which Mosaic rejects
    # ("Bad lhs type").
    x = x_ref[...]
    w = w_ref[...]
    if w.dtype != jnp.int8:
        w = w.astype(jnp.float32)
    acc = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32,
                  precision=_prec(x.dtype))
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def quant_matmul_kernel(x2d, w_q, scale, block_m=128, block_n=256):
    """Fused dequant GEMM via the Pallas kernel.  ``x2d`` is (M, K);
    returns (M, N) in ``x2d.dtype``.  M is padded to the block size (the
    zero rows fall out of the slice); K and N must be lane-aligned
    (:func:`supported`)."""
    m, k = x2d.shape
    n = w_q.shape[1]
    if not supported(k, n, w_q.dtype):
        # a non-dividing N would leave tail output columns unwritten by
        # any grid cell (silent garbage); fail loudly — dispatch sends
        # unsupported geometry to the reference, and FORCE_KERNEL tests
        # must use supported shapes
        raise ValueError(
            f"quant_matmul_kernel requires lane-aligned K/N and an "
            f"int8/fp8 weight; got K={k}, N={n}, dtype={w_q.dtype}")
    # rows pad to the activation dtype's native sublane tile (8 rows of
    # f32, 16 of bf16) so a decode-sized M is one whole tile
    sub = 8 * max(1, 4 // x2d.dtype.itemsize)
    bm = block_m if m >= block_m else -(-m // sub) * sub
    m_pad = -(-m // bm) * bm
    if m_pad != m:
        x2d = jnp.pad(x2d, ((0, m_pad - m), (0, 0)))
    bn = block_n if n % block_n == 0 else _LANES  # must divide lane-aligned N
    out = pl.pallas_call(
        _qmm_kernel,
        grid=(m_pad // bm, n // bn),
        in_specs=[
            # N innermost: the x tile's index map is constant over j, so
            # it stays in VMEM while the weight tiles stream
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x2d.dtype),
        interpret=_interpret(),
        name="quant_matmul",
    )(x2d, w_q, scale.astype(jnp.float32).reshape(1, n))
    return out[:m] if m_pad != m else out


# pht-lint: hot-root (decode-path GEMM entry)
def quant_matmul(x, w_q, scale, bias=None):
    """Dispatch: the Pallas fused-dequant kernel on TPU for supported
    geometry, the jnp reference otherwise (CPU/tier-1).  ``x`` (..., K)
    activations in bf16/f32, ``w_q`` (K, N) int8 or fp8-e4m3, ``scale``
    (N,) f32; optional ``bias`` (N,) added in the activation dtype on
    both paths (outside the kernel — XLA fuses it into the epilogue)."""
    k, n = w_q.shape
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    if use_kernel(k, n, w_q.dtype):
        out = quant_matmul_kernel(x2d, w_q, scale)
    else:
        out = quant_matmul_ref(x2d, w_q, scale)
    out = out.reshape(*lead, n)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out
