"""The delta rule with a decay a key channel (Kimi delta attention, Kimi
Linear, arXiv:2510.26692) in chunked form.

Per head, with a state ``S`` in R^(dk x dv), ``S_0 = 0``, and a log decay
``g_t`` in R^dk (<= 0)::

    S' = diag(exp(g_t)) S_{t-1};  r_t = v_t - S'^T k_t
    S_t = S' + k_t (beta_t r_t)^T;  o_t = S_t^T q_t

The chunk scan, the triangular inverse (built once a step and kept for the
backward) and the sequence's cut into ``CHUNK``-token chunks are
``gated_delta_rule.py``'s; what differs is inside a chunk.  With ``G`` the
decays cumulated from the chunk's start, the system matrix and the chunk's
own ``q k^T`` hold ``sum_d a_id k_jd exp(G_id - G_jd)`` (``a`` = ``beta
k`` or ``q``): a decay a channel, so no elementwise product of one matmul
with a (c, c) matrix of ratios gives it.  Factored at a reference row,
``(a_i e^(G_i - G_ref)) . (k_j e^(G_ref - G_j))``, it is a matmul again,
and the second factor is ``exp`` of a positive number for every ``j``
after the reference: across a chunk ``G`` runs to 64 x 5 = 320 nats where
the family bounds ``g >= -5`` a token, and float32 ends at ``exp(88.7)``.
So the rows go ``SUB`` = 16 at a time, each sub-block of rows with its own
first row as the reference: the row factor is ``exp`` of something in
[-75, 0]; the column factor of an earlier sub-block's ``j`` is ``exp`` of
something <= 0, of the same sub-block's ``j`` of something in [0, 75],
and every later ``j`` is masked *before* the ``exp``.  No ``exp`` of a
number above 75, none of an unmasked difference; what a sub-block's own
columns after the row contribute (finite, to ``e^75``) is cut by the
triangle's ``where`` behind the matmul.  Every other decay of the chunk
(``exp(G)``, ``exp(G_end - G)``) is of a number <= 0.

State, decays and the inverse are float32; the matmuls take operands of
the inputs' dtype (bfloat16 in a bf16 model, whose exponent range is
float32's) and accumulate in float32.  Backward: autodiff, as the scalar
rule's -- the two within-chunk functions under ``jax.checkpoint``, the
inverse outside them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ....core.autograd import apply_op
from ....core.tensor import Tensor
from .gated_delta_rule import CHUNK, chunked_rule

SUB = 16


def _sub_block_products(rows, k, gc):
    """``sum_d rows_id k_jd exp(gc_id - gc_jd)`` for every ``j`` up to the
    end of ``i``'s sub-block (0 beyond it), float32 (..., c, c): ``rows``,
    ``k`` (..., c, dk), ``gc`` the float32 cumulated decays (..., c, dk).
    Entries with ``j > i`` inside a sub-block are finite and meaningless:
    the caller's triangle cuts them."""
    f32, dt = jnp.float32, k.dtype
    c, dk = gc.shape[-2:]
    blocks = c // SUB
    lead = gc.shape[:-2]
    by_block = gc.reshape(lead + (blocks, SUB, dk))
    first = by_block[..., :1, :]                      # (.., blocks, 1, dk)
    scaled = (rows.astype(f32).reshape(by_block.shape)
              * jnp.exp(by_block - first)).astype(dt)  # exponent in [-75, 0]
    # column j as block I's rows see it: exp(G_ref(I) - G_j), masked
    # before the exp where j lies behind I's last row
    col = jnp.arange(c)[None, :, None]
    last_row = (SUB * jnp.arange(blocks) + SUB - 1)[:, None, None]
    exponent = jnp.where(col <= last_row,
                         first - gc[..., None, :, :], -jnp.inf)
    cols = (k.astype(f32)[..., None, :, :] * jnp.exp(exponent)).astype(dt)
    return jnp.einsum("...Iik,...Ijk->...Iij", scaled, cols,
                      preferred_element_type=f32).reshape(lead + (c, c))


@jax.checkpoint
def _chunk_system(k, g, beta):
    """The strictly lower-triangular ``a`` of every chunk's system
    ``(I + a) U = diag(beta) (V - (K e^G) S_0)``, float32 (n, b, h, c, c):
    ``a_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)``."""
    k_beta = (k.astype(jnp.float32) * beta[..., None]).astype(k.dtype)
    return jnp.tril(
        _sub_block_products(k_beta, k, jnp.cumsum(g, axis=-2)), -1)


@jax.checkpoint
def _chunk_inputs(q, k, v, g, beta, inv):
    """What the scan reads (``gated_delta_rule._chunk_body``), from the
    chunked inputs and the kept float32 inverse; ``decay_end`` has a key
    axis."""
    f32, dt = jnp.float32, v.dtype
    gc = jnp.cumsum(g, axis=-2)                          # gc <= 0
    k_beta = (k.astype(f32) * beta[..., None]).astype(dt)
    t = inv.astype(dt)
    v_beta = (v.astype(f32) * beta[..., None]).astype(dt)
    u = jnp.einsum("nbhij,nbhjv->nbhiv", t, v_beta,
                   preferred_element_type=f32).astype(dt)
    from_start = jnp.exp(gc)
    w = jnp.einsum("nbhij,nbhjk->nbhik", t,
                   (k_beta.astype(f32) * from_start).astype(dt),
                   preferred_element_type=f32).astype(dt)
    # a token's own q . k carries no decay: taken apart from the factored
    # products, where it reaches ``g``'s gradient as two roundings of one
    # number that should cancel (with bfloat16 operands an error of 5 % of
    # ``dg`` at g = -2.5 a token, where 1 % is left without it)
    attn = _sub_block_products(q, k, gc)
    own = jnp.sum(q.astype(f32) * k.astype(f32), -1)
    attn = (jnp.tril(attn, -1)
            + own[..., None] * jnp.eye(gc.shape[-2], dtype=f32)).astype(dt)
    qd = (q.astype(f32) * from_start).astype(dt)
    g_end = gc[..., -1:, :]
    kd = (k.astype(f32) * jnp.exp(g_end - gc)).astype(dt)
    return qd, kd, w, u, attn, jnp.exp(g_end[..., 0, :])


def kimi_delta_rule_chunked(q, k, v, g, beta):
    """The rule over a whole sequence.  ``q``, ``k`` (b, s, h, dk), already
    normalised and scaled as the model wants them; ``v`` (b, s, h, dv);
    ``g`` (b, s, h, dk), the log decay a key channel, in [-5, 0] (a
    16-token sub-block's cumulated decay has to stay under float32's 88
    nats), and ``beta`` (b, s, h), both taken to float32.  Returns ``o``
    (b, s, h, dv) in ``v``'s dtype."""
    return chunked_rule(q, k, v, g, beta, _chunk_system, _chunk_inputs)


def kimi_delta_rule(q, k, v, g, beta):
    """:func:`kimi_delta_rule_chunked` on Tensors, as one taped op."""
    args = [x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
            for x in (q, k, v, g, beta)]
    return apply_op("kimi_delta_rule", kimi_delta_rule_chunked, args)
