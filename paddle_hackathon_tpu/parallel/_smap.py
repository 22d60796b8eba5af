"""shard_map invocation helper for jax 0.9 semantics.

Partial-manual shard_map (some mesh axes manual, the rest GSPMD-auto) must
run inside ``jit`` under an ambient ``jax.set_mesh`` context — but
``set_mesh`` is forbidden while tracing. This helper picks the right mode:

- top-level (eager) call: wrap in ``jit`` under ``set_mesh``;
- already inside a trace with all axes manual: pass ``mesh=`` directly;
- already inside a trace with auto axes remaining: rely on the caller's
  ambient mesh (the outer jit must run under ``jax.set_mesh``).
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax import set_mesh, shard_map

# Axes already bound manual by an enclosing shard_map region (Shardy
# forbids re-binding them in a nested shard_map). Collective programs
# (ring/ulysses attention) consult this to run their per-device bodies
# directly instead of opening a second region — see pipeline_apply.
# Thread-local (mirroring the autograd tape's _tls pattern): the set is
# mutated at TRACE time, and two traces on different threads (a pipeline
# program compiling while an sp-only program compiles) must not leak
# manual-axes state into each other.
_tls = threading.local()


def _axes() -> set:
    if not hasattr(_tls, "manual_axes"):
        _tls.manual_axes = set()
    return _tls.manual_axes


@contextlib.contextmanager
def manual_axes_scope(axes):
    active = _axes()
    added = set(axes) - active
    active.update(added)
    try:
        yield
    finally:
        active.difference_update(added)


def active_manual_axes() -> frozenset:
    return frozenset(_axes())


# Eager-path program cache.  ``jax.jit``'s own cache keys on the
# function's identity, and every eager run_shard_map call used to build
# a FRESH shard_map closure — so each call was a full retrace+compile
# (pht-lint PHT002).  Key on everything the closure semantics depend on:
# the wrapped fn (or the caller's ``cache_key``, for callers whose fn is
# itself a fresh closure over values the key captures), the mesh, the
# manual axes, and the in/out spec trees.  Bounded LRU (hits refresh
# recency; the least-recently-USED entry is evicted): keys hold strong
# refs to callables, and an unbounded map would pin every mesh a test
# suite ever built.
import collections

_prog_cache = collections.OrderedDict()
_PROG_CACHE_MAX = 64


def run_shard_map(fn, mesh, in_specs, out_specs, manual_axes, args,
                  cache_key=None):
    """``cache_key`` contract: when given, it REPLACES ``fn`` in the
    program-cache key, so it must capture everything ``fn``'s closure
    does (two calls with equal keys must want the same program)."""
    manual = frozenset(manual_axes)
    from jax._src import core as _core
    if _core.trace_state_clean():
        spec_leaves, spec_def = jax.tree.flatten((in_specs, out_specs))
        key = (cache_key if cache_key is not None else fn,
               mesh, manual, tuple(spec_leaves), spec_def)
        jitted = _prog_cache.get(key)
        if jitted is not None:
            # LRU, not FIFO: refresh recency on hit so a per-token-hot
            # program (pipeline decode) is never the eviction victim
            # just because it was built first.  move_to_end is one
            # GIL-atomic call — a pop/reinsert pair would open a window
            # where a concurrent reader misses and pays a full retrace
            try:
                _prog_cache.move_to_end(key)
            except KeyError:
                pass   # concurrently evicted; we still hold the program
        if jitted is None:
            sm = shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, axis_names=manual,
                           check_vma=False)
            if len(_prog_cache) >= _PROG_CACHE_MAX:
                try:   # concurrent eager callers may race the eviction
                    _prog_cache.pop(next(iter(_prog_cache)), None)
                except (StopIteration, RuntimeError):
                    pass
            jitted = _prog_cache[key] = jax.jit(sm)
        with set_mesh(mesh):
            return jitted(*args)
    if manual == frozenset(mesh.axis_names):
        sm = shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        return sm(*args)
    sm = shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                   axis_names=manual, check_vma=False)
    return sm(*args)
