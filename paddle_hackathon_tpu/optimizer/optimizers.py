"""Concrete optimizers (ref ``python/paddle/optimizer/{sgd,momentum,adam,
adamw,adagrad,rmsprop,adadelta,adamax,lamb}.py``; fused kernels ref
``paddle/phi/kernels/gpu/adam_kernel.cu`` etc. — here every rule is fused by
XLA across the whole parameter tree, see optimizer.py).
"""

from __future__ import annotations

import jax.numpy as jnp

from .optimizer import Optimizer


class SGD(Optimizer):
    def _apply_one(self, v, g, s, lr, step_t):
        return v - lr * g, s


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_accumulators(self, p):
        return {"velocity": jnp.zeros(p._value.shape, jnp.float32)}

    def _apply_one(self, v, g, s, lr, step_t):
        vel = self._momentum * s["velocity"] + g
        if self._nesterov:
            new_v = v - lr * (g + self._momentum * vel)
        else:
            new_v = v - lr * vel
        return new_v, {"velocity": vel}


def _moment_dtype(moment_dtype):
    """The dtype moments are STORED in (the math stays f32)."""
    return jnp.float32 if moment_dtype is None else jnp.dtype(moment_dtype)


def adam_update(value, grad, m, v, lr, t, beta1, beta2, eps,
                moment_dtype=jnp.float32):
    """One Adam tensor update — THE single owner of the update math
    (bias-corrected moments computed in f32, stored in ``moment_dtype``).
    ``Adam._apply_one`` is its one caller: the eager ``step()`` and all
    three compiled trainers reach it through the class.  Returns
    ``(new_value_f32, new_m_stored, new_v_stored)``.
    """
    g32 = grad.astype(jnp.float32)
    m32 = beta1 * m.astype(jnp.float32) + (1 - beta1) * g32
    v32 = beta2 * v.astype(jnp.float32) + (1 - beta2) * jnp.square(g32)
    t = t.astype(jnp.float32)
    mhat = m32 / (1 - beta1 ** t)
    vhat = v32 / (1 - beta2 ** t)
    new_value = value.astype(jnp.float32) - lr * mhat / (jnp.sqrt(vhat) + eps)
    return (new_value, m32.astype(moment_dtype), v32.astype(moment_dtype))


class Adam(Optimizer):
    """Adam (ref ``optimizer/adam.py:317`` → fused ``final_state_adam_``).

    ``moment_dtype='bfloat16'`` stores m/v in bf16 (compute stays f32) —
    an optax ``mu_dtype``-style TPU option the reference lacks: halves the
    optimizer state's HBM traffic and capacity (the benchmark's
    ``gpt3-1.3b`` cell trains with it, ``gpt2-medium`` with the default;
    PERF.md has their numbers).  Default f32 matches the reference's
    fused adam bit-for-bit behavior class.
    """

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, moment_dtype=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._eps = epsilon
        self._moment_dtype = _moment_dtype(moment_dtype)

    def _init_accumulators(self, p):
        return {"moment1": jnp.zeros(p._value.shape, self._moment_dtype),
                "moment2": jnp.zeros(p._value.shape, self._moment_dtype)}

    def _apply_one(self, v, g, s, lr, step_t):
        new_v, m, u = adam_update(v, g, s["moment1"], s["moment2"], lr,
                                  step_t, self._beta1, self._beta2,
                                  self._eps, self._moment_dtype)
        return new_v, {"moment1": m, "moment2": u}


class _PerParamDecayMixin:
    """Per-parameter weight-decay exclusion (AdamW's
    ``apply_decay_param_fun``, LAMB's and LARS's exclusion lists).

    ``_apply_one`` has no access to the parameter identity, so the step is
    intercepted to precompute a decay on/off flag per live parameter (in
    the same trainable+has-grad order the base ``step`` uses) and
    ``_apply_one`` consumes them positionally at trace time — the flags
    are Python constants baked into the compiled update, and the jit
    cache key (param ids) already guards staleness."""

    def _decay_excluded(self, p) -> bool:
        raise NotImplementedError

    def step(self):
        self._wd_on = tuple(
            not self._decay_excluded(p) for p in self._parameter_list
            if p.trainable and p._grad_value is not None)
        super().step()

    def _prepare_functional(self, params):
        super()._prepare_functional(params)
        self._wd_on = (() if params is None else
                       tuple(not self._decay_excluded(p) for p in params))

    def _apply_rules(self, vals, grads, states, lr, step_t, param_lrs):
        flags = getattr(self, "_wd_on", ())
        self._wd_iter = iter(flags if len(flags) == len(vals)
                             else (True,) * len(vals))
        return super()._apply_rules(vals, grads, states, lr, step_t,
                                    param_lrs)


class AdamW(_PerParamDecayMixin, Adam):
    """AdamW with decoupled weight decay (ref ``optimizer/adamw.py``);
    parameters whose name ``apply_decay_param_fun`` rejects (biases,
    LayerNorm) take plain Adam."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, moment_dtype=None,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision,
                         moment_dtype=moment_dtype, name=name)
        self._wd_coeff = float(weight_decay) if not hasattr(
            weight_decay, "coeff") else weight_decay.coeff
        self._apply_decay_param_fun = apply_decay_param_fun

    def _decoupled_weight_decay(self):
        return True

    def _decay_excluded(self, p):
        fn = self._apply_decay_param_fun
        return fn is not None and not fn(p.name)

    def _apply_one(self, v, g, s, lr, step_t):
        new_v, ns = super()._apply_one(v, g, s, lr, step_t)
        if next(self._wd_iter, True):
            new_v = new_v - lr * self._wd_coeff * v.astype(jnp.float32)
        return new_v, ns


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._eps = epsilon
        self._init_val = initial_accumulator_value

    def _init_accumulators(self, p):
        return {"moment": jnp.full(p._value.shape, self._init_val, jnp.float32)}

    def _apply_one(self, v, g, s, lr, step_t):
        g32 = g.astype(jnp.float32)
        mom = s["moment"] + jnp.square(g32)
        new_v = v.astype(jnp.float32) - lr * g32 / (jnp.sqrt(mom) + self._eps)
        return new_v, {"moment": mom}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._rho = rho
        self._eps = epsilon
        self._momentum = momentum
        self._centered = centered

    def _init_accumulators(self, p):
        s = {"mean_square": jnp.zeros(p._value.shape, jnp.float32),
             "momentum": jnp.zeros(p._value.shape, jnp.float32)}
        if self._centered:
            s["mean_grad"] = jnp.zeros(p._value.shape, jnp.float32)
        return s

    def _apply_one(self, v, g, s, lr, step_t):
        g32 = g.astype(jnp.float32)
        ms = self._rho * s["mean_square"] + (1 - self._rho) * jnp.square(g32)
        out = dict(s, mean_square=ms)
        denom = ms
        if self._centered:
            mg = self._rho * s["mean_grad"] + (1 - self._rho) * g32
            out["mean_grad"] = mg
            denom = ms - jnp.square(mg)
        mom = self._momentum * s["momentum"] + lr * g32 / jnp.sqrt(
            denom + self._eps)
        out["momentum"] = mom
        return v.astype(jnp.float32) - mom, out


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._rho = rho
        self._eps = epsilon

    def _init_accumulators(self, p):
        return {"avg_squared_grad": jnp.zeros(p._value.shape, jnp.float32),
                "avg_squared_update": jnp.zeros(p._value.shape, jnp.float32)}

    def _apply_one(self, v, g, s, lr, step_t):
        g32 = g.astype(jnp.float32)
        asg = self._rho * s["avg_squared_grad"] + (1 - self._rho) * jnp.square(g32)
        update = (jnp.sqrt(s["avg_squared_update"] + self._eps) /
                  jnp.sqrt(asg + self._eps)) * g32
        asu = self._rho * s["avg_squared_update"] + (1 - self._rho) * jnp.square(update)
        return v.astype(jnp.float32) - lr * update, {
            "avg_squared_grad": asg, "avg_squared_update": asu}


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _init_accumulators(self, p):
        return {"moment": jnp.zeros(p._value.shape, jnp.float32),
                "inf_norm": jnp.zeros(p._value.shape, jnp.float32)}

    def _apply_one(self, v, g, s, lr, step_t):
        g32 = g.astype(jnp.float32)
        m = self._beta1 * s["moment"] + (1 - self._beta1) * g32
        inf = jnp.maximum(self._beta2 * s["inf_norm"], jnp.abs(g32))
        t = step_t.astype(jnp.float32)
        new_v = v.astype(jnp.float32) - (lr / (1 - self._beta1 ** t)) * m / (
            inf + self._eps)
        return new_v, {"moment": m, "inf_norm": inf}


class Lamb(_PerParamDecayMixin, Optimizer):
    """LAMB (ref ``optimizer/lamb.py``; fused-sharded variant
    ``incubate/optimizer/distributed_fused_lamb.py:86``).
    ``moment_dtype`` as on :class:`Adam`."""

    _per_param_norm = True

    def __init__(self, learning_rate=0.001,
                 lamb_weight_decay=None, beta1=None,
                 beta2=None, epsilon=None, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 moment_dtype=None, name=None):
        lamb_weight_decay = (LAMB_DEFAULTS["lamb_weight_decay"]
                             if lamb_weight_decay is None
                             else lamb_weight_decay)
        beta1 = LAMB_DEFAULTS["beta1"] if beta1 is None else beta1
        beta2 = LAMB_DEFAULTS["beta2"] if beta2 is None else beta2
        epsilon = LAMB_DEFAULTS["epsilon"] if epsilon is None else epsilon
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn
        self._moment_dtype = _moment_dtype(moment_dtype)

    def _decay_excluded(self, p):
        return bool(self._exclude_fn(p)) if self._exclude_fn else False

    def _init_accumulators(self, p):
        return {"moment1": jnp.zeros(p._value.shape, self._moment_dtype),
                "moment2": jnp.zeros(p._value.shape, self._moment_dtype)}

    def _apply_one(self, v, g, s, lr, step_t):
        wd = self._wd if next(self._wd_iter, True) else 0.0
        new_v, m, u = lamb_update(v, g, s["moment1"], s["moment2"], lr,
                                  step_t, self._beta1, self._beta2,
                                  self._eps, wd, self._moment_dtype)
        return new_v, {"moment1": m, "moment2": u}


def lamb_update(value, grad, m, v, lr, t, beta1, beta2, eps, wd,
                moment_dtype=jnp.float32):
    """One LAMB tensor update — THE single owner of the update math (ref
    ``optimizer/lamb.py``; the sharded-trust-ratio contract of
    ``incubate/optimizer/distributed_fused_lamb.py:86``).
    :class:`Lamb` is its one caller.  Inside a compiled trainer the
    param/update norms are computed on the *logical* arrays, so under
    ZeRO / zero_stage=3 / TP sharding XLA inserts the cross-shard
    reductions automatically: the trust ratio is globally correct by
    construction, which is the entire point of the reference's hand-fused
    distributed LAMB.  Returns
    (new_value_f32, new_m_stored, new_v_stored)."""
    g32 = grad.astype(jnp.float32)
    w32 = value.astype(jnp.float32)
    m32 = beta1 * m.astype(jnp.float32) + (1 - beta1) * g32
    u32 = beta2 * v.astype(jnp.float32) + (1 - beta2) * jnp.square(g32)
    t = t.astype(jnp.float32)
    mhat = m32 / (1 - beta1 ** t)
    uhat = u32 / (1 - beta2 ** t)
    r = mhat / (jnp.sqrt(uhat) + eps) + wd * w32
    w_norm = jnp.sqrt(jnp.sum(jnp.square(w32)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
    return (w32 - lr * trust * r,
            m32.astype(moment_dtype), u32.astype(moment_dtype))


# THE single home of the LARS/LAMB hyperparameter defaults (ref
# lars_momentum_op.cc attribute defaults; optimizer/lamb.py) — consulted
# by the classes and fleet's strategy configs/_swap_update_rule, so the
# same nominal configuration means the same numbers on every path.
LARS_DEFAULTS = {"momentum": 0.9, "lars_coeff": 0.001,
                 "lars_weight_decay": 0.0005, "epsilon": 0.0}
LAMB_DEFAULTS = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6,
                 "lamb_weight_decay": 0.01}


def lars_update(value, grad, velocity, lr, momentum, lars_coeff, lars_wd,
                epsilon=LARS_DEFAULTS["epsilon"]):
    """One LARS-momentum tensor update — single owner of the update math
    (ref ``fleet/meta_optimizers/lars_optimizer.py`` wrapping
    ``operators/optimizers/lars_momentum_op.cc``):

        local_lr = lr * coeff * ||w|| / (||g|| + wd * ||w|| + eps)
        velocity = mu * velocity + local_lr * (g + wd * w)
        w       -= velocity

    :class:`Lars` is its one caller.  All math in f32; returns
    (new_value_f32, new_velocity).
    """
    g32 = grad.astype(jnp.float32)
    v32 = value.astype(jnp.float32)
    w_norm = jnp.sqrt(jnp.sum(jnp.square(v32)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g32)))
    local_lr = jnp.where(
        (w_norm > 0) & (g_norm > 0),
        lr * lars_coeff * w_norm / (g_norm + lars_wd * w_norm + epsilon),
        lr)
    vel = momentum * velocity + local_lr * (g32 + lars_wd * v32)
    return v32 - vel, vel


class Lars(_PerParamDecayMixin, Optimizer):
    """LARS momentum — layer-adaptive rate scaling for large-batch SGD
    (ref ``fleet/meta_optimizers/lars_optimizer.py`` +
    ``operators/optimizers/lars_momentum_op.cc``; You et al. 2017).
    ``fleet.distributed_optimizer`` swaps a Momentum optimizer to this
    class when ``strategy.lars`` is set.  ``moment_dtype`` stores the
    velocity narrower (the math stays f32), as on :class:`Adam`."""

    _per_param_norm = True

    def __init__(self, learning_rate=0.001,
                 momentum=LARS_DEFAULTS["momentum"],
                 lars_coeff=LARS_DEFAULTS["lars_coeff"],
                 lars_weight_decay=LARS_DEFAULTS["lars_weight_decay"],
                 epsilon=LARS_DEFAULTS["epsilon"], parameters=None,
                 grad_clip=None, exclude_from_weight_decay=None,
                 multi_precision=False, moment_dtype=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._moment_dtype = _moment_dtype(moment_dtype)
        self._momentum = momentum
        self._coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._eps = epsilon
        # name substrings excluded from lars weight decay (proto
        # LarsConfig.exclude_from_weight_decay semantics)
        self._exclude = tuple(exclude_from_weight_decay or ())

    def _decay_excluded(self, p):
        if not self._exclude:
            return False
        pname = getattr(p, "name", "") or ""
        if not pname:
            # parameters only carry names when built with ParamAttr(name=)
            # — matching exclusion substrings against "" would silently
            # apply weight decay the user excluded
            if not any(getattr(q, "name", None)
                       for q in self._parameter_list):
                raise ValueError(
                    "exclude_from_weight_decay needs named parameters to "
                    "match against, but none of this optimizer's "
                    "parameters has a name — give the relevant parameters "
                    "ParamAttr(name=...) or drop the exclusion list")
        return any(s in pname for s in self._exclude)

    def _init_accumulators(self, p):
        return {"velocity": jnp.zeros(p._value.shape, self._moment_dtype)}

    def _apply_one(self, v, g, s, lr, step_t):
        wd = self._lars_wd if next(self._wd_iter, True) else 0.0
        new_v, vel = lars_update(v, g, s["velocity"].astype(jnp.float32),
                                 lr, self._momentum, self._coeff, wd,
                                 self._eps)
        return new_v, {"velocity": vel.astype(self._moment_dtype)}


LarsMomentum = Lars  # the reference exposes both spellings
