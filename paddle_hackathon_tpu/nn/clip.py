"""Gradient clipping (ref ``python/paddle/fluid/clip.py``:
``ClipGradByValue``, ``ClipGradByNorm``, ``ClipGradByGlobalNorm:420``).

Clip objects transform a list of gradient arrays; they are traceable so the
optimizer can fuse clipping into its jitted update step (the reference fuses
this via ``fused_allreduce_gradients`` + clip ops).
"""

from __future__ import annotations

import jax.numpy as jnp


class ClipGradBase:
    def _clip(self, grads):
        raise NotImplementedError

    def __call__(self, params_grads):
        """paddle-style API: list of (param, grad) Tensors -> same."""
        from ..core.tensor import Tensor
        grads = [g._value for _, g in params_grads]
        clipped = self._clip(grads)
        return [(p, Tensor(g)) for (p, _), g in zip(params_grads, clipped)]


class ClipGradByValue(ClipGradBase):
    def __init__(self, max, min=None):  # noqa: A002
        self.max = float(max)
        self.min = float(min) if min is not None else -self.max

    def _clip(self, grads):
        return [jnp.clip(g, self.min, self.max) for g in grads]


class ClipGradByNorm(ClipGradBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def _clip(self, grads):
        out = []
        for g in grads:
            norm = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            scale = jnp.where(norm > self.clip_norm,
                              self.clip_norm / jnp.maximum(norm, 1e-12), 1.0)
            out.append((g.astype(jnp.float32) * scale).astype(g.dtype))
        return out


class ClipGradByGlobalNorm(ClipGradBase):
    """Global-norm clip (ref ``fluid/clip.py:420``). The distributed variant
    (per-group norm psum, ``hybrid_parallel_optimizer.py:52``) falls out
    automatically under pjit: the sum-of-squares reduces across shards."""

    def __init__(self, clip_norm, group_name="default_group",
                 auto_skip_clip=False):
        self.clip_norm = float(clip_norm)

    def _clip(self, grads):
        """The norm is summed in float32; the scale is rounded to each
        gradient's dtype and the product taken there, so a bf16 gradient
        tree is never widened to float32 (for float32 gradients the two
        forms are the same bits).  THE global-norm clip of the package:
        the eager optimizers and all three compiled trainers call it."""
        if not grads:
            return grads
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads)
        global_norm = jnp.sqrt(sq)
        scale = self.clip_norm / jnp.maximum(global_norm, self.clip_norm)
        return [g * scale.astype(g.dtype) for g in grads]
