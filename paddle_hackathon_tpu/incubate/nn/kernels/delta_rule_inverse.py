"""The delta rule's triangular inverse as one Pallas TPU kernel.

``inverse(a)`` = ``inv(I + a)`` for every strictly lower-triangular float32
``(c, c)`` matrix of ``a`` (n, b, h, c, c): what
``gated_delta_rule.chunked_rule`` builds once a step from each chunk's
system (n, b, h, 64, 64), for the scalar and the channel-decay rule alike.

The matrices are cut into blocks of ``LANES`` = 128 and each block is
solved in VMEM, *one matrix a lane*, by forward substitution::

    t_i = e_i - sum_{j < i} a_ij t_j          (t_j: row j of the inverse)

Row i of all 128 inverses is a (c, 128) slab, ``c / 8`` vector registers,
and ``a_ij`` of all 128 a (1, 128) row: every step is an elementwise
float32 multiply-add across lanes, with no product on the MXU and nothing
exchanged between lanes.  A row's sum only touches what can be non-zero:
``t_j`` vanishes past column j, so register k of ``t_j`` (columns 8k ..
8k + 7) is read only for ``j >= 8k``: under c^3 / 4 multiply-adds a matrix,
where the log-doubling took ten c^3 products.  Two rows go together, so
that each register of an earlier row is loaded once for both: on the chip
0.63 ms against 0.84 for one row a step, and 6.67 for XLA's doubling, at
(64, 2, 32, 64, 64) (PERF.md §6, PR 38).  The block comes in with its
rows as the array lays them out, ``(matrix, i)`` down the sublanes and
``j`` along the lanes; a sublane-strided load picks row i of the 128
matrices and one transpose turns it into ``a_ij`` by lane, and the row of
inverses goes back the same way.  One read of ``a`` and one write of the
inverse go through HBM, in ``a``'s own shape and layout.

Float32 throughout: the sums are those of the substitution, in another
order than the doubling's, with no operand rounded below float32.
Off the chip the kernel runs interpreted, as the flash kernels do.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mesh
from .flash_attention import _interpret

LANES = 128     # matrices a block: one a lane
_SUB = 8        # float32 rows a vector register


def _kernel(a_ref, o_ref, row_s, t_s):
    """One block: ``a_ref`` / ``o_ref`` (LANES * c, c), row ``m * c + i``
    holding row i of matrix m; ``row_s`` (2, c, LANES) two rows of ``a``
    by lane; ``t_s`` (c * c, LANES) the inverses' rows, ``t_s[i * c + k,
    m]`` = entry (i, k) of matrix m's inverse.  Two rows, i and i + 1, a
    step: each load of an earlier row ``t_j`` serves both sums."""
    c, f32 = a_ref.shape[1], jnp.float32
    for ib in range(c // _SUB):
        # the rows of this register's band start at zero: the sums below
        # read a band's later rows (with a zero coefficient) before they
        # are written
        t_s[pl.ds(ib * _SUB * c, _SUB * c), :] = jnp.zeros(
            (_SUB * c, LANES), f32)

        def rows(r, carry, ib=ib):
            i = ib * _SUB + 2 * r
            for d in range(2):
                row_s[d] = a_ref[pl.ds(i + d, LANES, stride=c), :].T
            acc = [[jnp.zeros((_SUB, LANES), f32) for _ in range(ib + 1)]
                   for _ in range(2)]
            for j in range((ib + 1) * _SUB):    # a_ij = 0 for j >= i
                a_j = [row_s[d, pl.ds(j, 1), :] for d in range(2)]
                for k in range(j // _SUB + 1):
                    t_j = t_s[pl.ds(j * c + k * _SUB, _SUB), :]
                    for d in range(2):
                        acc[d][k] = acc[d][k] + a_j[d] * t_j
            col = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANES), 0)
            # row i + 1 read t_i while it was still 0: its term is added
            # here, from the t_i just made (its diagonal 1 included)
            a_next = row_s[1, pl.ds(i, 1), :]
            for k in range(ib + 1):
                t_i = -acc[0][k]
                if k == ib:
                    t_i = jnp.where(col + k * _SUB == i, 1.0, t_i)
                t_next = -(acc[1][k] + a_next * t_i)
                if k == ib:
                    t_next = jnp.where(col + k * _SUB == i + 1, 1.0, t_next)
                t_s[pl.ds(i * c + k * _SUB, _SUB), :] = t_i
                t_s[pl.ds((i + 1) * c + k * _SUB, _SUB), :] = t_next
            for d in range(2):
                o_ref[pl.ds(i + d, LANES, stride=c), :] = \
                    t_s[pl.ds((i + d) * c, c), :].T
            return carry

        jax.lax.fori_loop(0, _SUB // 2, rows, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _inverse(a, *, interpret):
    c = a.shape[-1]
    n = math.prod(a.shape[:-2])
    blocks = -(-n // LANES)
    rows = a.reshape(n * c, c)
    if blocks * LANES != n:     # whole blocks: the padding inverts to I
        rows = jnp.pad(rows, ((0, (blocks * LANES - n) * c), (0, 0)))
    block = pl.BlockSpec((LANES * c, c), lambda m: (m, 0))
    lane_block = LANES * c * 128 * 4        # as VMEM lays a block out
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.float32),
        grid=(blocks,),
        in_specs=[block],
        out_specs=block,
        scratch_shapes=[pltpu.VMEM((2, c, LANES), jnp.float32),
                        pltpu.VMEM((c * c, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # two buffers of each block, the inverses' rows, and room
            vmem_limit_bytes=4 * lane_block + c * c * LANES * 4 + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=blocks * LANES * c ** 3 // 3, transcendentals=0,
            bytes_accessed=2 * rows.size * 4),
        interpret=interpret,
        name="delta_rule_inverse",
    )(rows)
    return out[:n * c].reshape(a.shape)


def inverse(a):
    """``inv(I + a)`` for strictly lower-triangular float32 ``a`` (n, b,
    h, c, c) -- chunks, batch, heads -- c a multiple of 8, as the kernel
    computes it; same shape and dtype.  What lies on or above the diagonal
    of ``a`` must be 0.

    Under a mesh that shards the batch or the heads the kernel runs on
    each device's own matrices (``kernels/mesh.py``: jax will not
    partition a Mosaic call); a mesh that module does not cover gets XLA's
    triangular solve, the same inverse in float32."""
    if a.ndim != 5 or a.dtype != jnp.float32 \
            or a.shape[-1] != a.shape[-2] or a.shape[-1] % _SUB:
        raise ValueError(f"float32 (n, b, h, c, c) with c a multiple of "
                         f"{_SUB} expected, not {a.dtype} {a.shape}")
    p = mesh.plan(a.shape[1], a.shape[2])
    if p is None:
        eye = jnp.eye(a.shape[-1], dtype=a.dtype)
        return jax.lax.linalg.triangular_solve(
            eye + a, jnp.broadcast_to(eye, a.shape), left_side=True,
            lower=True, unit_diagonal=True)
    local = functools.partial(_inverse, interpret=_interpret())
    if not p.partitioned:
        return local(a)
    return mesh.over_batch_and_heads(lambda x, _: local(x), p, (a,), (2,),
                                     5, 2, batch_dim=1)
