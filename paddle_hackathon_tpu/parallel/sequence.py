"""Sequence / context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence parallelism (SURVEY §5.7 — repo-wide grep
confirms absence); its long-sequence story is TP head-splitting + activation
recompute (``fleet/utils/recompute.py:350``). This module supplies the
capability at parity with the north star, TPU-native:

- **Ring attention** (`ring_attention`): sequence sharded over the 'sp'
  mesh axis; K/V blocks rotate around the ring with ``ppermute`` while each
  device accumulates flash-style online softmax — O(s/n) activation memory
  per device, compute/comm overlapped by XLA's latency-hiding scheduler
  over ICI. (Liu et al. 2023 ring attention; blockwise softmax from flash
  attention.)
- **Ulysses** (`ulysses_attention`): all-to-all re-shard seq->heads before
  attention and heads->seq after — one a2a pair instead of a ring, best
  when num_heads >= sp_degree.

Both are written with ``shard_map`` over 'sp' (other axes stay
GSPMD-managed) and are exact — tests check equality with single-device
attention.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..incubate.nn.kernels import flash_attention as _fa


def _block_attn(q, k, v, scale, bias):
    """One (q-block x kv-block) attention partial: returns (out_unnorm,
    row_max, row_sumexp) for online-softmax accumulation.
    q: (b, sq, h, d), k/v: (b, sk, h, d)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                       # (b, h, q)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # (b, h, q)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m, l


def _flash_ok(seq_local, dtype):
    """Whether the Pallas kernel can run the per-chunk attention (else the
    XLA composition below materializes O(s_local^2) scores).  Gates on the
    backend and on the kernel's real constraints: block divisibility and
    the dtype-dependent VMEM block cap."""
    return (jax.default_backend() == "tpu"
            and _fa._block_sizes(seq_local, seq_local, dtype) is not None)


# ---------------------------------------------------------------------------
# Flash-in-ring: each ring step runs the Pallas flash kernel on the held kv
# chunk and folds the chunk result into the running output with log-sum-exp
# arithmetic — O(block^2) VMEM per step instead of the O(s_local^2) score
# matrix of the einsum path, so 128k+ global sequences fit.  The whole ring
# is one custom_vjp: the backward re-runs the ring with the *global* lse /
# delta statistics, rotating (k, v, dk, dv) together so every chunk's grad
# arrives back at its owner after n steps (Liu et al. 2023 ring attention).
# ---------------------------------------------------------------------------

def _to_bhd(x):
    # (b, sl, h, d) -> (b*h, sl, d)
    b, sl, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, sl, d)


def _from_bhd(x, b, h):
    bh, sl, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, sl, d), 1, 2)


def _ring_flash_spmd(axis: str, n: int, causal: bool, scale: float):
    """Build the per-device ring function (custom_vjp over local chunks)."""
    neg = jnp.float32(-1e30)

    def _fwd_impl(ql, kl, vl):
        b, sl, h, d = ql.shape
        my = jax.lax.axis_index(axis)
        qb = _to_bhd(ql)
        perm = [(i, (i + 1) % n) for i in range(n)]

        # diagonal step: this device's own kv chunk
        o0, lse0 = _fa._fwd(qb, _to_bhd(kl), _to_bhd(vl), causal, scale)
        o = o0.astype(jnp.float32)
        lse = lse0[:, 0, :]                       # (bh, sl)

        def step(carry, i):
            kc, vc, o, lse = carry
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            kv_rank = (my - i) % n                # owner of the held chunk

            def run(_):
                oi, lsei = _fa._fwd(qb, _to_bhd(kc), _to_bhd(vc), False,
                                    scale)
                return oi.astype(jnp.float32), lsei[:, 0, :]

            def skip(_):
                return (jnp.zeros_like(o),
                        jnp.full_like(lse, neg))

            if causal:
                oi, lsei = jax.lax.cond(kv_rank < my, run, skip, None)
            else:
                oi, lsei = run(None)
            new = jnp.logaddexp(lse, lsei)
            o = (o * jnp.exp(lse - new)[..., None]
                 + oi * jnp.exp(lsei - new)[..., None])
            return (kc, vc, o, new), None

        (kc, vc, o, lse), _ = jax.lax.scan(
            step, (kl, vl, o, lse), jnp.arange(1, n))
        out = _from_bhd(o, b, h).astype(ql.dtype)
        return out, lse

    @jax.custom_vjp
    def ring(ql, kl, vl):
        out, _ = _fwd_impl(ql, kl, vl)
        return out

    def ring_fwd(ql, kl, vl):
        out, lse = _fwd_impl(ql, kl, vl)
        return out, (ql, kl, vl, out, lse)

    def ring_bwd(res, do):
        ql, kl, vl, out, lse = res
        b, sl, h, d = ql.shape
        my = jax.lax.axis_index(axis)
        qb = _to_bhd(ql)
        dob = _to_bhd(do)
        outb = _to_bhd(out)
        perm = [(i, (i + 1) % n) for i in range(n)]
        # global per-row stats of MY q rows, in the kernels' layouts
        delta_row = jnp.sum(dob.astype(jnp.float32)
                            * outb.astype(jnp.float32), axis=-1)
        lse_t = jnp.broadcast_to(lse[:, None, :],
                                 (lse.shape[0], _fa._SUB, sl))

        # diagonal pair
        dq0, dk0, dv0 = _fa._bwd_pair(qb, _to_bhd(kl), _to_bhd(vl), dob,
                                      lse_t, delta_row, causal, scale)

        def step(carry, i):
            kc, vc, dkc, dvc, dq = carry
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            dkc = jax.lax.ppermute(dkc, axis, perm)
            dvc = jax.lax.ppermute(dvc, axis, perm)
            kv_rank = (my - i) % n

            def run(_):
                return _fa._bwd_pair(qb, _to_bhd(kc), _to_bhd(vc), dob,
                                     lse_t, delta_row, False, scale)

            def skip(_):
                z = jnp.zeros((qb.shape[0], sl, d), qb.dtype)
                return z, z, z

            if causal:
                dqi, dki, dvi = jax.lax.cond(kv_rank < my, run, skip, None)
            else:
                dqi, dki, dvi = run(None)
            dq = dq + dqi.astype(jnp.float32)
            dkc = dkc + _from_bhd(dki, b, h).astype(jnp.float32)
            dvc = dvc + _from_bhd(dvi, b, h).astype(jnp.float32)
            return (kc, vc, dkc, dvc, dq), None

        dkc0 = _from_bhd(dk0, b, h).astype(jnp.float32)
        dvc0 = _from_bhd(dv0, b, h).astype(jnp.float32)
        (kc, vc, dkc, dvc, dq), _ = jax.lax.scan(
            step, (kl, vl, dkc0, dvc0, dq0.astype(jnp.float32)),
            jnp.arange(1, n))
        # after n-1 rotations the grad chunks sit one hop short of their
        # owners — one more rotation completes the circle
        dkc = jax.lax.ppermute(dkc, axis, perm)
        dvc = jax.lax.ppermute(dvc, axis, perm)
        return (_from_bhd(dq, b, h).astype(ql.dtype),
                dkc.astype(kl.dtype), dvc.astype(vl.dtype))

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = True, scale: Optional[float] = None,
                   use_flash: Optional[bool] = None):
    """Exact attention over a sequence sharded on ``axis``.

    q, k, v: (b, s, h, d) global arrays with s sharded over ``axis``
    (P(None, axis, None, None)). Returns same-shaped, same-sharded output.
    On TPU the per-chunk attention runs the Pallas flash kernel (O(block^2)
    memory); elsewhere, or for unsupported shapes, the XLA online-softmax
    composition below is used.  ``use_flash`` overrides the auto choice
    (True forces the kernel — including the interpreter on CPU, which the
    parity tests use).
    """
    n = mesh.shape.get(axis, 1)
    if n == 1:
        return _plain_attention(q, k, v, causal, scale)
    from ._smap import active_manual_axes, run_shard_map
    # inside an enclosing shard_map already manual over `axis` (e.g. the
    # pp pipeline region): inputs are LOCAL chunks; run the per-device
    # body directly — a nested shard_map would re-bind the axis (Shardy
    # rejects it)
    in_manual = axis in active_manual_axes()
    seq_local = q.shape[1] if in_manual else q.shape[1] // n
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5

    flash = use_flash if use_flash is not None else _flash_ok(
        seq_local, q.dtype)
    if flash:
        spmd = _ring_flash_spmd(axis, n, causal, float(scale_))
        if in_manual:
            return spmd(q, k, v)
        return run_shard_map(
            spmd, mesh,
            in_specs=(P(None, axis), P(None, axis), P(None, axis)),
            out_specs=P(None, axis),
            manual_axes={axis},
            args=(q, k, v),
            # spmd is a fresh closure per call over exactly these values
            # — the key keeps the eager-path jit cache hitting
            cache_key=("ring_flash", axis, n, causal, float(scale_)))

    def spmd(ql, kl, vl):
        # ql/kl/vl: (b, s/n, h, d) — this device's sequence chunk
        my = jax.lax.axis_index(axis)
        neg = -1e30  # finite: exp()=0 without the inf-inf NaNs of finfo.min

        def chunk_bias(kv_rank):
            if not causal:
                return None
            # global positions: q rows my*seq_local + i, k cols kv_rank*seq_local + j
            qpos = my * seq_local + jnp.arange(seq_local)
            kpos = kv_rank * seq_local + jnp.arange(seq_local)
            mask = qpos[:, None] >= kpos[None, :]
            return jnp.where(mask, 0.0, neg)[None, None]  # (1,1,sq,sk)

        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, i):
            kc, vc, o, m, l = carry
            kv_rank = (my - i) % n  # whose chunk we currently hold
            bias = chunk_bias(kv_rank)
            oi, mi, li = _block_attn(ql.astype(jnp.float32),
                                     kc.astype(jnp.float32),
                                     vc.astype(jnp.float32), scale_, bias)
            m_new = jnp.maximum(m, mi)
            alpha = jnp.exp(m - m_new)        # rescale old accumulator
            beta = jnp.exp(mi - m_new)
            l_new = l * alpha + li * beta
            o_new = (o * alpha.transpose(0, 2, 1)[..., None]
                     + oi * beta.transpose(0, 2, 1)[..., None])
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return (kc, vc, o_new, m_new, l_new), None

        b, sl, h, d = ql.shape
        o0 = jnp.zeros((b, sl, h, d), jnp.float32)
        m0 = jnp.full((b, h, sl), jnp.finfo(jnp.float32).min)
        l0 = jnp.zeros((b, h, sl))
        (kc, vc, o, m, l), _ = jax.lax.scan(
            step, (kl, vl, o0, m0, l0), jnp.arange(n))
        out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(ql.dtype)

    if in_manual:
        return spmd(q, k, v)
    return run_shard_map(
        spmd, mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
        manual_axes={axis},
        args=(q, k, v),
        # seq_local is baked into the closure's causal bias — it MUST
        # key the cache, or a retrace at a new shape would reuse a
        # stale-bias closure
        cache_key=("ring_xla", axis, n, causal, float(scale_), seq_local))


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = True, scale: Optional[float] = None,
                      use_flash: Optional[bool] = None):
    """DeepSpeed-Ulysses style SP: a2a seq->head shards, full-sequence local
    attention over h/n heads, a2a back. Requires num_heads % sp == 0.
    The local full-sequence attention runs the Pallas flash kernel when
    supported (its custom_vjp handles the backward)."""
    n = mesh.shape.get(axis, 1)
    if n == 1:
        return _plain_attention(q, k, v, causal, scale)
    from ._smap import active_manual_axes, run_shard_map
    in_manual = axis in active_manual_axes()
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    assert q.shape[2] % n == 0, "ulysses needs num_heads divisible by sp"
    s_full = q.shape[1] * n if in_manual else q.shape[1]
    flash = use_flash if use_flash is not None else _flash_ok(
        s_full, q.dtype)

    def spmd(ql, kl, vl):
        def seq_to_heads(x):
            # (b, s/n, h, d) -> (b, s, h/n, d)
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qh, kh, vh = seq_to_heads(ql), seq_to_heads(kl), seq_to_heads(vl)
        if flash:
            b, s, hl, d = qh.shape
            ob = _fa.flash_attention_bhd(
                _to_bhd(qh), _to_bhd(kh), _to_bhd(vh), causal,
                float(scale_))
            out = _from_bhd(ob, b, hl)
        else:
            bias = None
            if causal:
                s = qh.shape[1]
                mask = jnp.tril(jnp.ones((s, s), bool))
                bias = jnp.where(mask, 0.0,
                                 jnp.finfo(jnp.float32).min)[None, None]
            o, m, l = _block_attn(qh.astype(jnp.float32),
                                  kh.astype(jnp.float32),
                                  vh.astype(jnp.float32), scale_, bias)
            out = (o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
                   ).astype(ql.dtype)
        return heads_to_seq(out.astype(ql.dtype))

    if in_manual:
        return spmd(q, k, v)
    return run_shard_map(
        spmd, mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
        manual_axes={axis},
        args=(q, k, v),
        cache_key=("ulysses", axis, n, causal, float(scale_), flash))


def _sp_dropout_rate(layer) -> float:
    """The attention-dropout rate of an sp-capable layer — part of the
    ``supports_sequence_parallel`` contract: a ``_sp_dropout()`` hook, a
    numeric ``dropout_p``/``dropout`` attribute, or a Dropout-like module
    under ``.dropout`` (read via its ``p``/``rate``).  A layer whose rate
    cannot be determined is REJECTED rather than assumed 0: nonzero
    attention dropout under sp would silently generate divergent masks
    per sequence chunk."""
    hook = getattr(layer, "_sp_dropout", None)
    if callable(hook):
        return float(hook())
    for attr in ("dropout_p", "dropout"):
        v = getattr(layer, attr, None)
        if isinstance(v, (int, float)):
            return float(v)
        if v is not None:
            for sub in ("p", "rate", "dropout_p"):
                r = getattr(v, sub, None)
                if isinstance(r, (int, float)):
                    return float(r)
            raise ValueError(
                f"{type(layer).__name__}.{attr} is a "
                f"{type(v).__name__}; cannot determine its dropout rate "
                "for sequence parallelism — expose a float dropout_p or "
                "a _sp_dropout() hook on the layer")
    raise ValueError(
        f"{type(layer).__name__} advertises supports_sequence_parallel "
        "but exposes no attention-dropout rate (float dropout_p/dropout "
        "or a _sp_dropout() hook); refusing to assume 0")


def enable_sequence_parallel(model, axis: str = "sp", mesh: Optional[Mesh]
                             = None, mode: str = "auto") -> int:
    """Switch every sp-capable attention layer in ``model`` to the
    sequence-parallel schedule — the model-agnostic hook (any model built
    on attention modules carrying ``supports_sequence_parallel`` gets
    ring/Ulysses for free; ``nn.layers.transformer.SequenceParallelMixin``).

    ``mode``: 'ring' | 'ulysses' | 'auto' (ulysses when the sp degree
    divides the head count). Returns the number of layers switched; raises if the model
    has none, or if any switched layer has attention dropout (the ring
    kernels regenerate dropout only on the single-chip path).
    """
    n = 0
    for layer in model.sublayers(include_self=True):
        if not getattr(layer, "supports_sequence_parallel", False):
            continue
        drop = _sp_dropout_rate(layer)
        if drop > 0:
            raise ValueError(
                "sequence parallelism requires attention dropout 0 "
                f"(found {drop} on {type(layer).__name__})")
        layer.seq_parallel_axis = axis
        layer.seq_parallel_mesh = mesh
        layer.seq_parallel_mode = mode
        n += 1
    if n == 0:
        raise ValueError(
            f"{type(model).__name__} has no sequence-parallel-capable "
            "attention layers (supports_sequence_parallel)")
    return n


def disable_sequence_parallel(model) -> int:
    """Clear the sp switch on every capable layer (a non-sp step must not
    inherit the ring schedule from a previous sp step)."""
    n = 0
    for layer in model.sublayers(include_self=True):
        if getattr(layer, "supports_sequence_parallel", False):
            layer.seq_parallel_axis = None
            layer.seq_parallel_mesh = None
            n += 1
    return n


def _plain_attention(q, k, v, causal, scale):
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    bias = None
    if causal:
        s, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s, sk), bool), k=sk - s)
        bias = jnp.where(mask, 0.0, jnp.finfo(jnp.float32).min)[None, None]
    o, m, l = _block_attn(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), scale_, bias)
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
