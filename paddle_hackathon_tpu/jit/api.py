"""to_static: trace-and-cache compilation of Layers/functions.

The ``StaticFunction`` program cache is keyed by (shapes, dtypes, training
mode) — the same idea as the reference's ``ProgramCache`` keyed by InputSpec
(``program_translator.py:475``).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from ..core import autograd, flags
from ..core import random as core_random
from ..core.autograd import GradNode, _LeafSlot
from ..core.dtype import convert_dtype
from ..core.tensor import Tensor
from ..nn.layer import Layer

class _TraceState(threading.local):
    # threading.local subclass: every thread sees the default, not just the
    # importing thread.
    tracing = False


_trace_state = _TraceState()


def _trace_state_clean() -> bool:
    """True when no jax trace is active (safe to enter our own jit)."""
    from jax._src import core as _core
    return _core.trace_state_clean()


class InputSpec:
    """paddle.static.InputSpec equivalent."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(-1 if s is None else int(s) for s in shape)
        self.dtype = convert_dtype(dtype)
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"

    @classmethod
    def from_tensor(cls, t: Tensor, name=None):
        return cls(t.shape, str(t.dtype), name)


def _spec_key(args):
    parts = []
    for a in args:
        if isinstance(a, Tensor):
            parts.append(("T", tuple(a._value.shape), str(a._value.dtype)))
        elif isinstance(a, (jnp.ndarray, jax.Array)):
            parts.append(("A", tuple(a.shape), str(a.dtype)))
        elif isinstance(a, (int, float, bool, str, type(None))):
            parts.append(("S", a))
        else:
            parts.append(("O", type(a).__name__))
    return tuple(parts)


class StaticFunction:
    """Compiled wrapper over a function or Layer method
    (ref ``StaticFunction`` ``program_translator.py:340``)."""

    def __init__(self, function, input_spec: Optional[Sequence[InputSpec]] = None,
                 build_strategy=None, backend=None):
        self._raw_fn = function
        self._conv_fn = None  # dy2static-converted, built lazily
        self._input_spec = input_spec
        self._cache = {}
        self._layer: Optional[Layer] = getattr(function, "__self__", None)
        # program-cache key of a just-traced build, consumed by __call__ to
        # time the (lazy) first compile+run and report it to the program
        # observatory
        self._pending_build = None
        functools.update_wrapper(self, function)

    def _site_label(self) -> str:
        """Observatory site label: the layer class when bound (one label
        per user Layer type — bounded, PHT005), else the function name."""
        if self._layer is not None:
            return f"to_static.{type(self._layer).__name__}"
        return f"to_static.{getattr(self._raw_fn, '__name__', 'fn')}"

    def _report_build(self, key, t0) -> None:
        """Report a program build (cache-miss trace + lazy compile) to the
        program observatory; best-effort — telemetry never fails user code."""
        if key is None:
            return
        try:
            from ..observability.programs import observe_static_build
            observe_static_build(self._site_label(), key,
                                 time.perf_counter() - t0)
        except Exception:
            pass

    @property
    def _fn(self):
        """The function to trace: AST control-flow-converted (dy2static) so
        Python if/while/for on tensor values become lax.cond/while_loop
        (ref program_translator.py:340 + ifelse/loop transformers)."""
        if self._conv_fn is None:
            from . import dy2static
            self._conv_fn = dy2static.convert_function(self._raw_fn)
        return self._conv_fn

    # -- program construction ---------------------------------------------
    def _build(self, key, n_args, training):
        layer = self._layer
        fn = self._fn

        def pure(param_list, buffer_list, rng_key, *jax_args):
            param_keys, buffer_keys = key_meta
            params = dict(zip(param_keys, param_list))
            buffers = dict(zip(buffer_keys, buffer_list))
            targs = [Tensor(a) if isinstance(a, jax.Array) else a
                     for a in jax_args]
            prev = getattr(_trace_state, "tracing", False)
            _trace_state.tracing = True
            try:
                with core_random.rng_scope(rng_key), autograd.no_grad():
                    if layer is not None:
                        with layer._swap_state(params, buffers):
                            out = fn(*targs)
                            new_buffers = [
                                b._value for b in _buffer_tensors(layer)]
                    else:
                        out = fn(*targs)
                        new_buffers = []
            finally:
                _trace_state.tracing = prev
            out_vals = jax.tree.map(
                lambda t: t._value if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))
            return out_vals, new_buffers

        if layer is not None:
            params, buffers = layer.functional_state()
            key_meta = (sorted(params), sorted(buffers))
        else:
            key_meta = ((), ())
        # Non-tensor python args are static: they are already baked into the
        # program-cache key, so each distinct value gets its own trace (the
        # reference's per-InputSpec specialization).
        spec_key = key[0]
        static_argnums = tuple(
            3 + i for i, part in enumerate(spec_key) if part[0] == "S")
        jitted = jax.jit(pure, static_argnums=static_argnums)
        return jitted, key_meta

    def get_concrete_program(self, *args):
        """Trace (or fetch) the program for this arg signature."""
        training = self._layer.training if self._layer is not None else False
        key = (_spec_key(args), training)
        if key not in self._cache:
            if len(self._cache) >= flags.flag("jit_cache_size"):
                self._cache.pop(next(iter(self._cache)))  # evict oldest
                try:
                    from ..observability.programs import \
                        observe_static_eviction
                    observe_static_eviction(self._site_label())
                except Exception:
                    pass
            self._cache[key] = self._build(key, len(args), training)
            self._pending_build = key
        return self._cache[key]

    # -- execution ---------------------------------------------------------
    def __call__(self, *args):
        layer = self._layer
        # Nested-trace transparency: when invoked inside another jax trace
        # (e.g. a to_static layer used from a compiled train step /
        # functional_call), inline the raw function into the enclosing trace
        # instead of nesting jax.jit — nesting re-traces needlessly and a
        # split of the global RNG under the outer trace would poison it with
        # a tracer (the run_program op composes for the same reason in the
        # reference). Detected from the trace state itself so raw-array /
        # container / closure tracers are covered too.
        if not _trace_state_clean():
            if layer is None:
                return self._fn(*args)
            # guard in-place buffer updates (BN stats): if the enclosing
            # caller did not swap state (functional_call does), a traced
            # update would corrupt the live layer — snapshot and drop any
            # buffer value that became a tracer.
            bufs = list(_buffer_tensors(layer))
            saved = [b._value for b in bufs]
            try:
                return self._fn(*args)
            finally:
                for b, old in zip(bufs, saved):
                    if isinstance(b._value, jax.core.Tracer):
                        b._value = old
        jitted, (param_keys, buffer_keys) = self.get_concrete_program(*args)
        build_key, self._pending_build = self._pending_build, None
        t_build = time.perf_counter()
        if layer is not None:
            params, buffers = layer.functional_state()
            param_list = [params[k] for k in param_keys]
            buffer_list = [buffers[k] for k in buffer_keys]
            param_tensors = dict(layer.named_parameters())
        else:
            param_list, buffer_list, param_tensors = [], [], {}
        jax_args = [a._value if isinstance(a, Tensor) else a for a in args]
        rng_key = core_random.split_key()

        # Which inputs require grad? (tape integration = run_program grad)
        tape_on = autograd.is_grad_enabled()
        diff_params = [k for k in param_keys
                       if tape_on and not param_tensors[k].stop_gradient]
        diff_args = [i for i, a in enumerate(args)
                     if tape_on and isinstance(a, Tensor)
                     and not a.stop_gradient
                     and jnp.issubdtype(a._value.dtype, jnp.inexact)]

        if not diff_params and not diff_args:
            out_vals, new_buffers = jitted(param_list, buffer_list, rng_key,
                                           *jax_args)
            self._report_build(build_key, t_build)
            self._write_buffers(buffer_keys, new_buffers)
            return _wrap_tree(out_vals, None)

        dp_vals = [params[k] for k in diff_params]
        da_vals = [jax_args[i] for i in diff_args]

        def closed(dp, da):
            plist = list(param_list)
            for k, v in zip(diff_params, dp):
                plist[param_keys.index(k)] = v
            alist = list(jax_args)
            for i, v in zip(diff_args, da):
                alist[i] = v
            return jitted(plist, buffer_list, rng_key, *alist)

        (out_vals, new_buffers), vjp_fn = jax.vjp(closed, dp_vals, da_vals)
        self._report_build(build_key, t_build)
        self._write_buffers(buffer_keys, new_buffers)

        flat_out, treedef = jax.tree.flatten(out_vals)
        n_out = len(flat_out)
        out_avals = [(o.shape, o.dtype) for o in flat_out]
        # buffers receive zero cotangent automatically (they are not node
        # outputs); vjp runs on the full (out, new_buffers) structure.
        zero_bufs = [jnp.zeros(b.shape, b.dtype) for b in new_buffers]

        def node_vjp(cotangents):
            with autograd.no_grad():
                cot_tree = jax.tree.unflatten(treedef, list(cotangents))
                dp_g, da_g = vjp_fn((cot_tree, zero_bufs))
                return tuple(dp_g) + tuple(da_g)

        parents = []
        for k in diff_params:
            t = param_tensors[k]
            parents.append((t._grad_node, t._out_idx) if t._grad_node
                           else _LeafSlot(t))
        for i in diff_args:
            t = args[i]
            parents.append((t._grad_node, t._out_idx) if t._grad_node
                           else _LeafSlot(t))
        node = GradNode("static_program", node_vjp, parents, n_out, out_avals)

        wrapped_flat = [Tensor(o, stop_gradient=False, _grad_node=node,
                               _out_idx=i) for i, o in enumerate(flat_out)]
        return jax.tree.unflatten(treedef, wrapped_flat)

    def _write_buffers(self, buffer_keys, new_buffers):
        if self._layer is None or not buffer_keys:
            return
        lookup = {}
        for name, b in _named_buffer_tensors(self._layer):
            lookup[name] = b
        for k, v in zip(buffer_keys, new_buffers):
            lookup[k]._set_value(v)

    @property
    def concrete_programs(self):
        return list(self._cache.values())

    def rollback(self):
        """Return the original (eager) function."""
        return self._raw_fn


def _named_buffer_tensors(layer):
    for name, sub in layer._traverse("", True):
        for bname, b in sub._buffers.items():
            if b is not None:
                yield (f"{name}.{bname}" if name else bname), b


def _buffer_tensors(layer):
    return [b for name, b in sorted(_named_buffer_tensors(layer))]


def _wrap_tree(out_vals, node):
    return jax.tree.map(lambda v: Tensor(v) if isinstance(v, jax.Array) else v,
                        out_vals)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """@paddle.jit.to_static equivalent."""

    def deco(fn):
        if isinstance(fn, Layer):
            # decorate the layer's forward; keep layer callable semantics
            static = StaticFunction(fn.forward, input_spec, build_strategy)
            fn.forward = static
            return fn
        return StaticFunction(fn, input_spec, build_strategy)

    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    fn.__not_to_static__ = True
    return fn


def ignore_module(modules):
    return None
