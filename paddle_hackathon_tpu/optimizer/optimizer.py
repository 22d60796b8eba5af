"""Optimizer base.

Equivalent of the reference's ``python/paddle/optimizer/optimizer.py``
(``Optimizer.step:1232``, ``_apply_optimize:979``). The TPU-native mechanism:
instead of launching one fused CUDA kernel per parameter
(``_C_ops.final_state_adam_``, ``optimizer/adam.py:345``) or the multi-tensor
path (``optimizer.py:1352``), the whole update — grad clip, weight decay, the
update rule for EVERY parameter — is one jitted XLA program over the parameter
pytree, with donated buffers (in-place HBM update, zero copies).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

import numpy as np

from ..core.tensor import Tensor
from ..nn.clip import ClipGradBase
from ..observability.sanitizers import sanitize_donation
from .lr import LRScheduler


class L2Decay:
    """paddle.regularizer.L2Decay — adds wd*param to the gradient."""

    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


def _make_zero_update(opt, shard_info):
    """Shard-aware update target for the eager ``step()`` jit (module
    level so the jit binding has a stable shape; per-call identity is
    guarded by ``Optimizer._jit_key``, same as the ``_update_all``
    binding it replaces)."""
    def zero_update(vals, grads, states, lr, step_t, param_lrs):
        return opt._sharded_update(vals, grads, states, lr, step_t,
                                   param_lrs, shard_info)
    return zero_update


class StackedParameter:
    """What a trainer hands ``functional_update(params=)`` for a leaf that
    holds N same-shaped parameters stacked on a leading layer dim (the pp
    trainers' ``(L, ...)`` blocks): one layer's metadata (name, lr scale)
    and the stacked value.  A rule that takes per-parameter norms (LAMB,
    LARS) is vmapped over dim 0 of such a leaf, so every layer keeps its
    own trust ratio — a stack-wide norm would be a different optimizer
    (the reference computes it per parameter:
    ``distributed_fused_lamb.py:86``)."""

    layer_stacked = True

    def __init__(self, like, value):
        self.name = like.name
        self.optimize_attr = like.optimize_attr
        self._value = value


class Optimizer:
    _accum_names: List[str] = []
    # the rule reduces over the whole parameter (a norm), so a
    # layer-stacked leaf must be vmapped rather than updated as one array
    _per_param_norm = False
    _stacked = ()

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        if parameters is None:
            from ..core import autograd as _ag
            sm = _ag._static_module
            if not (sm is not None and sm.in_static_mode()):
                raise ValueError(
                    "parameters must be given in dygraph mode "
                    "(pass model.parameters()); in static mode the program's "
                    "parameters are collected by minimize()")
            parameters = []
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        if grad_clip is not None and not isinstance(grad_clip, ClipGradBase):
            raise TypeError("grad_clip must be a paddle.nn.ClipGrad* instance")
        self._weight_decay = weight_decay
        self._multi_precision = multi_precision
        self._accumulators: Dict[int, Dict[str, jax.Array]] = {}
        self._step_count = 0
        self._jit_update = None
        self._jit_key = None

    # -- public API --------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the learning rate is a scheduler")
        self._learning_rate = float(value)

    @property
    def _param_groups(self):
        return self._parameter_list

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def step(self):
        """Apply one update (ref ``Optimizer.step`` ``optimizer.py:1232``)."""
        from ..core import autotune as _autotune
        _autotune.step()  # advances the incubate.autotune tuning window
        params = [p for p in self._parameter_list
                  if p.trainable and p._grad_value is not None]
        if not params:
            return
        grads = [p._grad_value for p in params]
        states = [self._get_accumulators(p) for p in params]
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        step_t = jnp.asarray(self._step_count + 1, jnp.int32)

        zi = getattr(self, "_zero_info", None)
        # zi rides the key BY REFERENCE (held strongly in _jit_key, so a
        # replaced shard-info can never alias a freed one's id) — a
        # re-wrap after an elastic resize rebuilds the jitted update
        key = (tuple((id(p), g.shape, str(g.dtype))
                     for p, g in zip(params, grads)), zi)
        if self._jit_key != key:
            # Donate only the accumulator buffers (arg 2): parameter buffers
            # may still be aliased by vjp residuals of a retained graph or by
            # user-held references, so they must not be invalidated.
            if zi is not None:
                # eager ZeRO (parallel.sharding.group_sharded_parallel):
                # the jitted update is the shard-aware path, so the eager
                # workflow runs the SAME reduce-scatter/shard-update/
                # all-gather program the compiled trainers compile
                update_fn = _make_zero_update(self, zi.with_param_specs([
                    tuple(getattr(p, "pspec", None)
                          or (None,) * p._value.ndim) for p in params]))
            else:
                update_fn = self._update_all
            self._jit_update = sanitize_donation(
                jax.jit(update_fn, donate_argnums=(2,)),
                donate_argnums=(2,), site="optimizer.update")
            self._jit_key = key

        vals = [p._value for p in params]
        lrs = [p.optimize_attr.get("learning_rate", 1.0) for p in params]
        new_vals, new_states = self._jit_update(vals, grads, states, lr,
                                                step_t, tuple(lrs))
        for p, v, s in zip(params, new_vals, new_states):
            p._set_value(v)
            self._accumulators[id(p)] = s
        self._step_count += 1

    # -- functional (pure pytree) surface ----------------------------------
    # The compiled trainers (parallel/auto_parallel.Engine and hapi's
    # Model.fit fast path) inline the whole update into THEIR jitted train
    # step — they hold the accumulators functionally and call this instead
    # of step().  ``params`` carries the ordered Parameter objects the
    # positional buffers correspond to, so per-parameter metadata (lr
    # scale, weight-decay exclusions) resolves without the eager path's
    # "has a grad" filtering (nothing has ``_grad_value`` under a trace).

    def functional_state(self, params) -> List[Dict[str, jax.Array]]:
        """Current accumulator dicts for ``params`` (created on demand),
        in order — the optimizer half of a functional train state."""
        return [self._get_accumulators(p) for p in params]

    def load_functional_state(self, params, states, step_count=None):
        """Write functionally-updated accumulators back into the live
        optimizer (so ``state_dict``/checkpointing see them)."""
        for p, s in zip(params, states):
            self._accumulators[id(p)] = s
        if step_count is not None:
            self._step_count = int(step_count)

    def functional_update(self, vals, grads, states, lr, step_t,
                          param_lrs=None, params=None, shard_info=None):
        """Pure update rule over explicit buffers — safe under jit/grad.

        ``(vals, grads, states)`` are positional lists of param values,
        gradients and accumulator dicts; returns ``(new_vals,
        new_states)``.  Pass ``params`` (the matching Parameter objects)
        to let the rule derive per-parameter metadata; they are consumed
        at trace time only and never cross the jit boundary.

        ``shard_info`` (a ``parallel.sharding.ZeroShardInfo``) selects
        the ZeRO shard-aware path: each rank owns a 1/dp slice of every
        moment — gradients are constraint-pinned to the moment sharding
        (GSPMD lowers the pending grad psum + slice to a reduce-scatter),
        the rule runs on the shard, and the updated params are pinned
        back to their own sharding (per-tensor all-gathers the scheduler
        can overlap with the remaining update compute).
        """
        if params is not None and param_lrs is None:
            param_lrs = tuple(p.optimize_attr.get("learning_rate", 1.0)
                              for p in params)
        elif param_lrs is None:
            param_lrs = (1.0,) * len(vals)
        self._prepare_functional(params)
        try:
            grads = self._preprocess_grads(
                self._decay_vals(vals, states, shard_info), grads)
            # the phase names of every compiled trainer's program
            # (observability/programs.py phase_census): "clip" is in
            # _preprocess_grads, "update" is here, each written once
            with jax.named_scope("update"):
                if shard_info is not None:
                    return self._sharded_rules(vals, grads, states, lr,
                                               step_t, tuple(param_lrs),
                                               shard_info)
                return self._apply_rules(vals, grads, states, lr, step_t,
                                         tuple(param_lrs))
        finally:
            self._prepare_functional(None)

    def _prepare_functional(self, params):
        """Hook: derive per-parameter trace-time metadata from an explicit
        param list (``None`` restores the eager ``step()`` behavior)."""
        self._stacked = () if params is None else tuple(
            getattr(p, "layer_stacked", False) for p in params)

    def _preprocess_grads(self, vals, grads):
        """The grad preamble shared by every update path: f32 cast,
        coupled weight decay, grad clip.  Runs on the UNPINNED (fully
        replicated) gradients in the ZeRO path too, so the global clip
        norm is computed in exactly the reduction order the replicated
        update uses — sharded-vs-replicated stays bit-exact."""
        grads = [g.astype(jnp.float32) if v.dtype == jnp.float32 else g
                 for g, v in zip(grads, vals)]
        if isinstance(self._weight_decay, L2Decay) and self._weight_decay.coeff:
            grads = [g + self._weight_decay.coeff * v.astype(g.dtype)
                     for g, v in zip(grads, vals)]
        elif isinstance(self._weight_decay, L1Decay) and self._weight_decay.coeff:
            grads = [g + self._weight_decay.coeff * jnp.sign(v).astype(g.dtype)
                     for g, v in zip(grads, vals)]
        elif isinstance(self._weight_decay, float) and self._weight_decay:
            if not self._decoupled_weight_decay():
                grads = [g + self._weight_decay * v.astype(g.dtype)
                         for g, v in zip(grads, vals)]
        if self._grad_clip is not None:
            with jax.named_scope("clip"):
                grads = self._grad_clip._clip(grads)
        return grads

    def _update_all(self, vals, grads, states, lr, step_t, param_lrs):
        grads = self._preprocess_grads(vals, grads)
        return self._apply_rules(vals, grads, states, lr, step_t, param_lrs)

    def _apply_rules(self, vals, grads, states, lr, step_t, param_lrs):
        """The per-tensor rule over already preprocessed gradients."""
        new_vals, new_states = [], []
        stacked = self._stacked if len(self._stacked) == len(vals) \
            else (False,) * len(vals)
        for v, g, s, plr, st in zip(vals, grads, states, param_lrs, stacked):
            rule = self._apply_one
            if st and self._per_param_norm:
                rule = jax.vmap(rule, in_axes=(0, 0, 0, None, None))
            nv, ns = rule(v, g, s, lr * plr, step_t)
            new_vals.append(nv.astype(v.dtype))
            new_states.append(ns)
        return new_vals, new_states

    @staticmethod
    def _decay_vals(vals, states, shard_info):
        """What the preamble's coupled decay (and its f32-cast selector)
        reads as the parameter: the f32 master where one is kept."""
        if shard_info is None or not shard_info.master_weights:
            return vals
        return [s.get("master", v) for v, s in zip(vals, states)]

    def _sharded_update(self, vals, grads, states, lr, step_t, param_lrs,
                        shard_info):
        """The eager ``step()``'s ZeRO update: the preamble on the
        replicated gradients, then :meth:`_sharded_rules`."""
        grads = self._preprocess_grads(
            self._decay_vals(vals, states, shard_info), grads)
        return self._sharded_rules(vals, grads, states, lr, step_t,
                                   param_lrs, shard_info)

    def _sharded_rules(self, vals, grads, states, lr, step_t, param_lrs,
                       shard_info):
        """ZeRO shard-aware update (``parallel.sharding.ZeroShardInfo``)
        over already preprocessed gradients.

        Per tensor: grad pinned to the moment sharding → the pending dp
        grad psum fuses with the slice into a reduce-scatter; moments
        (and the optional f32 ``"master"`` slot) pinned in AND out so
        GSPMD cannot re-replicate them anywhere in the program; the
        update rule itself is the unmodified ``_apply_rules`` core run on
        the 1/dp slice; the new param value is cast to the param dtype
        FIRST and then pinned to the param's own spec — a per-tensor
        all-gather (bf16-sized under master weights) that depends only
        on its own update, so the scheduler overlaps it with the other
        params' update compute and the next step's forward entry.

        Weight decay + global-norm clip ran BEFORE the pins (on the
        replicated grads) — see ``_preprocess_grads`` — keeping the
        sharded loss series bit-exact vs the replicated update for
        elementwise rules.  Per-param-norm rules (LAMB/LARS) compute
        their norms on the sharded slices with GSPMD-inserted
        cross-shard reductions — globally correct, reassociated.

        A ``shard_info`` whose ``axis`` is ``None`` (no ZeRO axis on the
        mesh) pins everything to the parameter's own spec: that is how a
        master slot is carried without ZeRO."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = shard_info.mesh
        pspecs = shard_info.param_specs or (None,) * len(vals)

        def pin(a, spec):
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(*spec)))

        mspecs = [shard_info.moment_spec(v.shape, existing=ps)
                  for v, ps in zip(vals, pspecs)]
        g_sh = [pin(g, ms) for g, ms in zip(grads, mspecs)]
        if shard_info.master_weights:
            compute_vals = [pin(s["master"], ms) if "master" in s
                            else pin(v, ms)
                            for v, s, ms in zip(vals, states, mspecs)]
            inner_states = [{k: v for k, v in s.items() if k != "master"}
                            for s in states]
        else:
            compute_vals = [pin(v, ms) for v, ms in zip(vals, mspecs)]
            inner_states = states
        inner_states = [{k: pin(v, ms) for k, v in s.items()}
                        for s, ms in zip(inner_states, mspecs)]
        new_vals, new_states = self._apply_rules(
            compute_vals, g_sh, inner_states, lr, step_t, param_lrs)
        out_states = [{k: pin(v, ms) for k, v in s.items()}
                      for s, ms in zip(new_states, mspecs)]
        if shard_info.master_weights:
            for st, nv, s_in, ms in zip(out_states, new_vals, states,
                                        mspecs):
                if "master" in s_in:
                    st["master"] = pin(nv, ms)   # f32, stays sharded
        out_vals = [
            pin(nv.astype(v.dtype),
                ps if ps is not None and len(ps) == v.ndim
                else (None,) * v.ndim)
            for nv, v, ps in zip(new_vals, vals, pspecs)]
        return out_vals, out_states

    def preprocess_grads_offload(self, vals, grads, master_weights=False):
        """Grad preamble for the ZeRO-offload path — runs inside the
        grads-only device program on the REPLICATED gradients, exactly
        the code/order ``_sharded_update`` uses, so the streamed update
        that follows stays bit-exact vs the resident ZeRO path for the
        non-master case.

        Under ``master_weights`` the resident path feeds the f32 masters
        to the preamble; those live in host RAM here, so the cast of the
        device param stands in: the f32-cast *selector* matches exactly
        (cast-of-param is f32 whenever the master is), only the coupled
        weight-decay term sees cast-of-param instead of the master —
        identical until param and master diverge in the low bits, and a
        non-issue for decoupled-decay optimizers (AdamW)."""
        if master_weights:
            vals = [v.astype(jnp.float32) for v in vals]
        return self._preprocess_grads(vals, grads)

    def _sharded_tensor_update(self, val, grad, state, lr, step_t,
                               shard_info, param_lr=1.0):
        """One tensor of the ZeRO update, for the offload streaming pipe:
        ``grad`` is already preprocessed (``preprocess_grads_offload``),
        so ``_sharded_rules`` runs on single-element lists — the
        identical per-tensor core the resident path traces.
        ``shard_info.param_specs`` must carry exactly this tensor's
        spec.  Returns ``(new_val, new_state)``."""
        nvs, nss = self._sharded_rules(
            [val], [grad], [state], lr, step_t, (param_lr,), shard_info)
        return nvs[0], nss[0]

    def _decoupled_weight_decay(self) -> bool:
        return False

    # -- per-optimizer rule ------------------------------------------------
    def _init_accumulators(self, param) -> Dict[str, jax.Array]:
        return {}

    def _get_accumulators(self, param):
        s = self._accumulators.get(id(param))
        if s is None:
            s = self._init_accumulators(param)
            self._accumulators[id(param)] = s
        return s

    def _apply_one(self, value, grad, state, lr, step_t):
        raise NotImplementedError

    # -- state dict --------------------------------------------------------
    def state_dict(self):
        state = {}
        for i, p in enumerate(self._parameter_list):
            acc = self._accumulators.get(id(p))
            if acc:
                for k, v in acc.items():
                    state[f"{p.name or i}_{k}"] = Tensor(v)
        state["@step"] = self._step_count
        if isinstance(self._learning_rate, LRScheduler):
            state["LR_Scheduler"] = self._learning_rate.state_dict()
        return state

    def set_state_dict(self, state):
        self._step_count = int(state.get("@step", 0))
        if "LR_Scheduler" in state and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        for i, p in enumerate(self._parameter_list):
            acc = self._init_accumulators(p)
            found = False
            for k in list(acc):
                key = f"{p.name or i}_{k}"
                if key in state:
                    v = state[key]
                    acc[k] = v._value if isinstance(v, Tensor) else jnp.asarray(
                        np.asarray(v))
                    found = True
            if found:
                self._accumulators[id(p)] = acc

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Dygraph: backward+step+clear. Static mode: records the
        backward+update extension onto the loss's Program (the reference's
        append-backward + optimizer-op rewrite, ``optimizer.py:1232``
        static branch); the Executor compiles it into the train program."""
        from ..core import autograd as _ag
        sm = _ag._static_module
        if sm is not None and isinstance(loss, sm.Variable):
            loss._program._minimize = (self, loss)
            return None, None
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    def _append_optimize_op(self, *a, **k):  # static-graph shim (not used)
        raise NotImplementedError("static graph path handled by jit module")
