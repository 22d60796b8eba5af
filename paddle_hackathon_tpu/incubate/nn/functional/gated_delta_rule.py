"""The gated delta rule (Gated DeltaNet's recurrent mixer) in chunked form,
and the short causal depthwise convolution that stands in front of it.

Per head, with a state ``S`` in R^(dk x dv), ``S_0 = 0``::

    S' = exp(g_t) S_{t-1};  r_t = v_t - S'^T k_t
    S_t = S' + k_t (beta_t r_t)^T;  o_t = S_t^T q_t

Computed ``CHUNK`` tokens at a time (Yang et al., "Gated Delta Networks",
the WY form): inside a chunk the rule is one unit lower-triangular system
``(I + tril(diag(beta) K K^T . D, -1)) U = diag(beta) V`` with ``D`` the
decay ratios between positions, solved in matrix form; across chunks a
``lax.scan`` carries the state, so trace and compile time do not grow with
the sequence.  State, decays and the triangular inverse are float32; the
matmuls that touch ``q``, ``k``, ``v`` take operands of the inputs' dtype
(bfloat16 in a bf16 model) and accumulate in float32.

Decay ratios are always ``exp`` of a *difference* of the cumulated ``g``,
masked before the ``exp`` (a position sees only earlier ones, so every
difference taken is <= 0); ``exp(-cumsum)`` is never formed: ``g`` may
run to tens of nats across a chunk and its inverse overflows.

Backward: autodiff, with the chunk body under ``jax.checkpoint``: the scan
keeps its carry -- one (dk, dv) float32 state a chunk and head, not one a
token -- and recomputes a chunk's four matmuls in the backward sweep.
Kept beside the five inputs: the float32 triangular inverse, one
(CHUNK, CHUNK) a chunk and head = 4 * CHUNK bytes a token and head (67 MB a
layer at 8,192 tokens x 32 heads, a quarter of the scan's states there).
It is built once a step, outside this module's checkpoints, and is the
residual of its own rule (``d inv(M) = -inv(M) dM inv(M)``, so the kernel
that builds it, ``kernels/delta_rule_inverse.py``, is never differentiated
and keeps nothing).  Rebuilt in the backward, under
``jax.checkpoint``, from ``q, k, v, g, beta`` and that inverse: the system
matrix in front of it (decays, ``k_beta k^T``) and everything the scan reads
behind it (``qd, kd, w, u, attn``: bfloat16 matmuls and elementwise passes).
Rebuilding the inverse instead would run its kernel again and the system
in front of it, for the sake of those cheap arrays.

A caller that puts a whole mixer under a ``jax.checkpoint`` of its own keeps
the inverse by adding ``KEPT_INVERSE`` to its policy's names.  The name sits
on the value the inverse's forward rule returns, which is its output and
its residual at once: a name on the output alone (in ``chunked_rule``)
kept the copy that ``_chunk_inputs`` reads and left the residual unnamed,
so such a caller's backward built the system and the inverse a second time
for the inverse's own rule.  With no checkpoint around the caller the name
is an identity and the residual is kept like any other.

``chunked_rule`` -- the cut into chunks, the inverse and the scan -- also
serves the rule with a decay a key channel (``kimi_delta_rule.py``), which
brings its own two within-chunk functions; ``_chunk_body`` takes its
``decay_end`` a head or a head and key channel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ....core.autograd import apply_op
from ....core.tensor import Tensor
from ..kernels import delta_rule_inverse

CHUNK = 64
# the name the kept inverse carries (``jax.ad_checkpoint.checkpoint_name``,
# given in ``_unit_lower_inverse_fwd``): a caller that rebuilds a whole mixer
# in its backward keeps it by this name
KEPT_INVERSE = "delta_rule_inverse"


def causal_depthwise_conv(x, taps):
    """``y[t, c] = sum_j taps[c, j] * x[t - (K - 1) + j, c]`` with
    ``x[t < 0] = 0``: each channel filtered over its own last ``K``
    tokens, the newest under the last tap.  ``x`` (b, s, c), ``taps``
    (c, K).  K shifted slices of one padded array; XLA fuses the sum."""
    k, s = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[:, j] for j in range(k))


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``inv(I + a)`` for strictly lower-triangular ``a`` (n, b, h, c, c),
    float32: one Pallas kernel (``kernels/delta_rule_inverse.py``), every
    matrix solved in VMEM by forward substitution."""
    return delta_rule_inverse.inverse(a)


def _unit_lower_inverse_fwd(a):
    # named here, where output and residual are still one value: a policy
    # that saves KEPT_INVERSE then saves what ``_unit_lower_inverse_bwd``
    # reads, not only what the caller reads
    inv = checkpoint_name(delta_rule_inverse.inverse(a), KEPT_INVERSE)
    return inv, inv


def _unit_lower_inverse_bwd(inv, d_inv):
    t = jnp.swapaxes(inv, -1, -2)
    c = inv.shape[-1]
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    return (jnp.where(strict, -(t @ d_inv @ t), 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunk_body(state, xs):
    """One chunk, all heads: what of the chunk depends on the state that
    enters it.  ``w`` and ``u`` are the chunk's triangular solve applied
    to the decayed keys and to the values; ``qd`` / ``kd`` the queries
    and keys decayed from the chunk's start / to its end; ``attn`` the
    chunk's own masked, decayed ``q k^T``."""
    qd, kd, w, u, attn, decay_end = xs
    f32, dt = jnp.float32, qd.dtype
    s_in = state.astype(dt)
    v_new = u.astype(f32) - jnp.einsum(
        "bhck,bhkv->bhcv", w, s_in, preferred_element_type=f32)
    v_new_dt = v_new.astype(dt)
    out = jnp.einsum("bhck,bhkv->bhcv", qd, s_in,
                     preferred_element_type=f32) \
        + jnp.einsum("bhcj,bhjv->bhcv", attn, v_new_dt,
                     preferred_element_type=f32)
    # one decay a head (b, h), or one a head and key channel (b, h, dk)
    over = (None,) * (state.ndim - decay_end.ndim)
    state = state * decay_end[(...,) + over] + jnp.einsum(
        "bhck,bhcv->bhkv", kd, v_new_dt, preferred_element_type=f32)
    return state, out.astype(dt)


def _decays(g):
    """The log decay ``g`` (n, b, h, c) cumulated from each chunk's start,
    and the decay from position j to position i >= j of the same chunk
    (0 above the diagonal): exp of a difference <= 0."""
    chunk = g.shape[-1]
    gc = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]
    diff = gc[..., :, None] - gc[..., None, :]
    return gc, jnp.exp(jnp.where(rows >= cols, diff, -jnp.inf))


@jax.checkpoint
def _chunk_system(k, g, beta):
    """The strictly lower-triangular ``a`` of every chunk's system
    ``(I + a) U = diag(beta) V``, float32 (n, b, h, c, c), from the
    chunked inputs (n, b, h, c, ...).  Under ``jax.checkpoint``: nothing
    of that shape is kept from here, the backward rebuilds the decays
    and ``k_beta k^T`` from ``k, g, beta``."""
    f32 = jnp.float32
    _, decay = _decays(g)
    k_beta = (k.astype(f32) * beta[..., None]).astype(k.dtype)
    a = jnp.einsum("nbhik,nbhjk->nbhij", k_beta, k,
                   preferred_element_type=f32)
    return jnp.tril(a * decay, -1)


@jax.checkpoint
def _chunk_inputs(q, k, v, g, beta, inv):
    """What the scan reads, from the chunked inputs (n, b, h, c, ...) and
    the float32 inverse ``inv`` of every chunk's system: the triangular
    solve and the decays of every chunk at once.  Under
    ``jax.checkpoint``: the backward rebuilds these (a dozen arrays the
    size of ``q``, several of them float32) from the six arguments
    instead of keeping them for every layer.  ``inv`` is an argument and
    not built here, so that rebuilding them does not rebuild it."""
    f32, dt = jnp.float32, v.dtype
    gc, decay = _decays(g)
    k_beta = (k.astype(f32) * beta[..., None]).astype(dt)
    t = inv.astype(dt)
    v_beta = (v.astype(f32) * beta[..., None]).astype(dt)
    u = jnp.einsum("nbhij,nbhjv->nbhiv", t, v_beta,
                   preferred_element_type=f32).astype(dt)
    from_start = jnp.exp(gc)[..., None]                  # gc <= 0
    w = jnp.einsum("nbhij,nbhjk->nbhik", t,
                   (k_beta.astype(f32) * from_start).astype(dt),
                   preferred_element_type=f32).astype(dt)
    attn = (jnp.einsum("nbhik,nbhjk->nbhij", q, k,
                       preferred_element_type=f32) * decay).astype(dt)
    qd = (q.astype(f32) * from_start).astype(dt)
    g_end = gc[..., -1:]
    kd = (k.astype(f32) * jnp.exp(g_end - gc)[..., None]).astype(dt)
    return qd, kd, w, u, attn, jnp.exp(g_end[..., 0])


def chunked_rule(q, k, v, g, beta, system, inputs):
    """A delta rule over a whole sequence, ``CHUNK`` tokens at a time:
    ``system(k, g, beta)`` gives every chunk's strictly lower-triangular
    matrix, ``inputs(q, k, v, g, beta, inv)`` what :func:`_chunk_body`
    reads, both from the chunked arrays (n, b, h, c, ...).  Any ``s``: the
    tail of the last chunk is padded with tokens that neither decay nor
    write (g = 0, beta = 0)."""
    b, s, h, dk = q.shape
    f32, chunk = jnp.float32, CHUNK
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (
            x.ndim - 2)) for x in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def chunks(x):       # (b, n*c, h, ...) -> (n, b, h, c, ...)
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    # outside this module's checkpoints: the inverse's residual (itself,
    # under the name KEPT_INVERSE) is stored
    inv = _unit_lower_inverse(system(k, g, beta))
    xs = inputs(q, k, v, g, beta, inv)
    state = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    _, out = jax.lax.scan(jax.checkpoint(_chunk_body), state, xs)
    out = jnp.moveaxis(out, (0, 2), (1, 3))              # (b, n, c, h, dv)
    return out.reshape(b, n * chunk, h, -1)[:, :s]


def gated_delta_rule_chunked(q, k, v, g, beta):
    """The rule over a whole sequence.  ``q``, ``k`` (b, s, h, dk), already
    normalised and scaled as the model wants them; ``v`` (b, s, h, dv);
    ``g`` (log decay, <= 0) and ``beta`` (b, s, h), taken to float32.
    Returns ``o`` (b, s, h, dv) in ``v``'s dtype."""
    return chunked_rule(q, k, v, g, beta, _chunk_system, _chunk_inputs)


def gated_delta_rule(q, k, v, g, beta):
    """:func:`gated_delta_rule_chunked` on Tensors, as one taped op."""
    args = [x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
            for x in (q, k, v, g, beta)]
    return apply_op("gated_delta_rule", gated_delta_rule_chunked, args)
