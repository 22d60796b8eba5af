"""Fleet meta-strategy optimizer wrappers.

Capability parity with the reference's ``fleet/meta_optimizers/*`` program
rewrites (SURVEY §2.4 "Misc strategies"): gradient merge, LocalSGD, Deep
Gradient Compression, and fp16-allreduce — each a wrapper over an inner
``Optimizer`` instead of a static-graph pass.

TPU-native note on communication: in the reference every strategy inserts
explicit ``c_allreduce`` ops; here data-parallel gradient reduction is emitted
by GSPMD inside the one compiled step, so on a single controller these
wrappers transform *when* and *what* is averaged (``comm_fn`` hook). Under a
multi-process ``jax.distributed`` run, pass ``comm_fn`` bound to a
``shard_map`` collective over the ``dp`` axis.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..optimizer.optimizer import Optimizer

__all__ = ["GradientMergeOptimizer", "LocalSGDOptimizer",
           "DGCMomentumOptimizer", "FP16AllReduceOptimizer"]


@functools.partial(jax.jit, static_argnums=(2,))
def _dgc_sparsify(v, u, k):
    """Top-k selection with momentum factor masking (arXiv:1712.01887 §3.2):
    communicated coordinates are cleared from BOTH the error accumulator v
    and the velocity u, so already-applied history is not re-injected."""
    flat = v.reshape(-1)
    thresh_vals, _ = jax.lax.top_k(jnp.abs(flat), k)
    thresh = thresh_vals[-1]
    mask = (jnp.abs(flat) >= thresh).reshape(v.shape)
    kept = jnp.where(mask, v, 0.0)
    residual = jnp.where(mask, 0.0, v)
    u_masked = jnp.where(mask, 0.0, u)
    return kept, residual, u_masked


class _OptimizerWrapper:
    """Delegates the Optimizer surface to the wrapped inner optimizer."""

    def __init__(self, inner: Optimizer):
        self._inner = inner

    def __getattr__(self, name):
        # Full Optimizer surface (_get_accumulators, get_lr, state_dict, ...)
        # delegates to the wrapped optimizer; step()/minimize() are the
        # strategy override points.
        return getattr(self._inner, name)

    @property
    def inner_optimizer(self) -> Optimizer:
        """The wrapped optimizer, through any nesting — the update rule a
        compiled trainer takes, which has no eager ``step()`` to wrap
        (``PipelineParallel.train_batch``)."""
        return getattr(self._inner, "inner_optimizer", self._inner)

    def step(self):
        raise NotImplementedError

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        # Must route through the *wrapper's* step() — delegating minimize to
        # the inner optimizer would silently disable the strategy.
        from ..core import autograd as _ag
        sm = _ag._static_module
        if sm is not None and isinstance(loss, sm.Variable):
            # static mode: strategies are eager-mode wrappers; the program
            # records the inner optimizer's update.
            return self._inner.minimize(loss, startup_program, parameters,
                                        no_grad_set)
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None


class GradientMergeOptimizer(_OptimizerWrapper):
    """Accumulate grads over ``k_steps`` micro-steps, apply once.

    Ref ``fleet/meta_optimizers/gradient_merge_optimizer.py`` (static pass
    adding gradient-merge vars + cond-gated optimize block); here: the
    accumulator lives beside each parameter and the inner step runs on every
    k-th call.
    """

    def __init__(self, inner: Optimizer, k_steps: int = 1, avg: bool = True):
        super().__init__(inner)
        self.k_steps = int(k_steps)
        self.avg = bool(avg)
        self._acc = {}
        self._micro = 0

    def step(self):
        self._micro += 1
        for p in self._parameter_list:
            if p._grad_value is None:
                continue
            a = self._acc.get(id(p))
            self._acc[id(p)] = p._grad_value if a is None else a + p._grad_value
            p._grad_value = None
        if self._micro % self.k_steps != 0:
            return
        inv = 1.0 / self.k_steps if self.avg else 1.0
        for p in self._parameter_list:
            a = self._acc.pop(id(p), None)
            if a is not None:
                p._grad_value = a * inv if inv != 1.0 else a
        self._inner.step()


class LocalSGDOptimizer(_OptimizerWrapper):
    """Step locally every call; average parameters every ``k_steps``.

    Ref ``fleet/meta_optimizers/localsgd_optimizer.py``. ``comm_fn(value)``
    must return the cross-replica mean of ``value`` (defaults to identity on a
    single controller, where parameters are already globally consistent).
    """

    def __init__(self, inner: Optimizer, k_steps: int = 1,
                 comm_fn: Optional[Callable] = None):
        super().__init__(inner)
        self.k_steps = int(k_steps)
        self._comm_fn = comm_fn
        self._local_steps = 0

    def step(self):
        self._inner.step()
        self._local_steps += 1
        if self._local_steps % self.k_steps != 0:
            return
        if self._comm_fn is not None:
            for p in self._parameter_list:
                p._set_value(self._comm_fn(p._value))


class DGCMomentumOptimizer(_OptimizerWrapper):
    """Deep Gradient Compression (arXiv:1712.01887) momentum optimizer.

    Ref ``fleet/meta_optimizers/dgc_optimizer.py`` + ``operators/dgc_op.cc``:
    momentum correction (u), error-feedback residual (v), top-k selection at
    ``sparsity``, ramp-up schedule. The reference communicates (index, value)
    pairs through a custom allreduce; XLA collectives are dense, so the
    sparsified tensor is reduced dense — the compression still provides DGC's
    *convergence* semantics (momentum correction + error feedback), and the
    comm transform is pluggable via ``comm_fn`` for bandwidth-constrained DCN
    paths.
    """

    def __init__(self, inner: Optimizer, momentum: float = 0.9,
                 rampup_begin_step: int = 0,
                 sparsity: Sequence[float] = (0.999,),
                 comm_fn: Optional[Callable] = None):
        super().__init__(inner)
        # This wrapper IS the momentum optimizer (like the reference's
        # DGCMomentumOptimizer replacing Momentum): the inner must be a
        # momentum-free update or momentum would be applied twice.
        if float(getattr(inner, "_momentum", 0.0) or 0.0) != 0.0:
            raise ValueError(
                "DGCMomentumOptimizer applies momentum itself; wrap a "
                "momentum-free optimizer (e.g. SGD), not "
                f"{type(inner).__name__} with momentum="
                f"{inner._momentum}")
        self.momentum = float(momentum)
        self.rampup_begin_step = int(rampup_begin_step)
        self.sparsity = list(sparsity)
        self._comm_fn = comm_fn
        self._u = {}  # momentum-corrected velocity
        self._v = {}  # error-feedback residual
        self._step_no = 0

    def _current_sparsity(self) -> float:
        # 0-based position in the ramp: first compressed step (the one right
        # after rampup_begin_step warm-up steps) uses sparsity[0].
        i = min(max(self._step_no - self.rampup_begin_step - 1, 0),
                len(self.sparsity) - 1)
        return float(self.sparsity[i])

    @staticmethod
    def _sparsify(v, u, k):
        return _dgc_sparsify(v, u, k)

    def step(self):
        self._step_no += 1
        m = self.momentum
        if self._step_no <= self.rampup_begin_step:
            # warm-up: dense, but with the SAME momentum rule, so the update
            # dynamics are continuous across rampup_begin_step
            for p in self._parameter_list:
                g = p._grad_value
                if g is None:
                    continue
                u = self._u.get(id(p))
                u = g if u is None else m * u + g
                self._u[id(p)] = u
                p._grad_value = u
            self._inner.step()
            return
        sp = self._current_sparsity()
        for p in self._parameter_list:
            g = p._grad_value
            if g is None:
                continue
            u = self._u.get(id(p))
            u = g if u is None else m * u + g          # momentum correction
            v = self._v.get(id(p))
            v = u if v is None else v + u              # error accumulation
            n = int(v.size)
            k = max(1, int(round(n * (1.0 - sp))))
            if k >= n:
                kept, residual = v, jnp.zeros_like(v)
            else:
                # momentum factor masking: clear u too at sent coordinates
                kept, residual, u = self._sparsify(v, u, k)
            self._u[id(p)] = u
            self._v[id(p)] = residual
            if self._comm_fn is not None:
                kept = self._comm_fn(kept)
            p._grad_value = kept
        self._inner.step()


class FP16AllReduceOptimizer(_OptimizerWrapper):
    """Halve grad-communication volume by casting to fp16/bf16 around comm.

    Ref ``fleet/meta_optimizers/fp16_allreduce_optimizer.py``. On TPU the
    natural wire dtype is bfloat16 (no loss-scale needed for the dynamic
    range of gradients).
    """

    def __init__(self, inner: Optimizer, comm_fn: Optional[Callable] = None,
                 wire_dtype=jnp.bfloat16):
        super().__init__(inner)
        self._comm_fn = comm_fn
        self.wire_dtype = wire_dtype

    def step(self):
        if self._comm_fn is not None:
            # cast only around the communication — without a comm hook there
            # is nothing to compress and the round-trip would just lose bits
            for p in self._parameter_list:
                g = p._grad_value
                if g is None or not jnp.issubdtype(g.dtype, jnp.floating):
                    continue
                orig = g.dtype
                p._grad_value = self._comm_fn(
                    g.astype(self.wire_dtype)).astype(orig)
        self._inner.step()


class AMPOptimizer(_OptimizerWrapper):
    """Dynamic-loss-scaling wrapper behind ``strategy.amp`` (ref
    ``fleet/meta_optimizers/amp_optimizer.py`` decorating the inner
    optimizer with ``mixed_precision``).  This owns the loss-scaling half;
    the cast half is ``paddle.amp.auto_cast`` around the forward, exactly
    as the reference's dygraph flow pairs them.  ``minimize(loss)`` scales
    before backward; ``step()`` unscales, skips the update on inf/nan, and
    adapts the scale."""

    def __init__(self, inner: Optimizer, configs=None):
        super().__init__(inner)
        cfg = configs or {}
        from ..amp import GradScaler
        self._scaler = GradScaler(
            enable=True,
            init_loss_scaling=float(cfg.get("init_loss_scaling", 2.0 ** 15)),
            incr_ratio=float(cfg.get("incr_ratio", 2.0)),
            decr_ratio=float(cfg.get("decr_ratio", 0.5)),
            incr_every_n_steps=int(cfg.get("incr_every_n_steps", 1000)),
            decr_every_n_nan_or_inf=int(
                cfg.get("decr_every_n_nan_or_inf", 2)),
            use_dynamic_loss_scaling=bool(
                cfg.get("use_dynamic_loss_scaling", True)))
        self._loss_scaled = False

    @property
    def scaler(self):
        return self._scaler

    def step(self):
        # unscale_ divides every gradient by the loss scale — running it
        # on gradients from an UNSCALED backward (the plain
        # `loss.backward(); opt.step()` pattern) would shrink updates by
        # 1/init_loss_scaling and silently stall training
        if not self._loss_scaled:
            raise RuntimeError(
                "strategy.amp wraps the optimizer with loss scaling: call "
                "minimize(loss) so the loss is scaled before backward, or "
                "drive scaling yourself via optimizer.scaler "
                "(scaler.scale(loss).backward(); scaler.step(inner)); a "
                "bare step() after an unscaled backward would divide the "
                "gradients by the loss scale")
        self._loss_scaled = False
        self._scaler.step(self._inner)

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..core import autograd as _ag
        sm = _ag._static_module
        if sm is not None and isinstance(loss, sm.Variable):
            return self._inner.minimize(loss, startup_program, parameters,
                                        no_grad_set)
        self._scaler.scale(loss).backward()
        self._loss_scaled = True
        self.step()
        self.clear_grad()
        return None, None
