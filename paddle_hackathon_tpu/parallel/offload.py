"""ZeRO-offload: optimizer state in pinned host RAM, update streamed.

Ref ``distributed/fleet`` sharding's ``offload=True`` (the reference
parks the fp32 masters + moments in host memory and runs the update on
CPU).  TPU-native version: the moments (and optional f32 masters) live
as host numpy, but the update RULE still runs on-device per tensor —
each step streams one tensor's state through a depth-bounded h2d → jit
→ d2h pipe (``io.transfer.TransferRing``, the same overlap pattern the
dataloader's ``device_prefetch`` uses), so opt-state HBM residency is
~``depth+1`` tensor shards instead of the whole state, while the math
is the unmodified ``Optimizer._sharded_rules`` core — bit-exact vs the
resident ZeRO path on identical gradients.

Dataflow per step (tensor ``i`` of ``n``):

    host moments[i] --h2d (async, scattered to the moment sharding)-->
    per-tensor jitted ``_sharded_tensor_update`` (state donated) -->
    new param (stays on device) + new moments --d2h (async)-->
    fresh host numpy (never mutated in place: the checkpoint writer
    thread may still hold the previous step's arrays)

The trade is stated, never silent: tokens/s drops by the h2d+d2h
traffic that no longer overlaps perfectly (bench ``hapi_fit_offload``
records the curve; ``tools/perf_gate.py`` holds the floor), in exchange
for opt-state HBM ~0 (``train_opt_state_bytes{placement=device|host}``
exports both sides).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..io.transfer import TransferRing, finish_d2h, start_d2h
from ..observability import metrics as _obs
from ..observability.sanitizers import sanitize_donation
from .sharding import ZeroShardInfo

__all__ = ["ZeroOffloadUpdater", "host_state_bytes"]


def host_state_bytes(tree) -> int:
    """Total bytes of the host-resident numpy leaves of an offloaded
    optimizer state — the ``placement=host`` gauge value."""
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree)
               if isinstance(a, np.ndarray))


class ZeroOffloadUpdater:
    """Streams a ZeRO-sharded optimizer update through host RAM.

    ``tensor_update(i, val, grad, state, lr, step_t)`` is the traced
    per-tensor rule (``i`` static); ``state_shardings[i]`` is where
    tensor ``i``'s slots live on device while in flight (the ZeRO
    moment sharding).  One ``jax.jit`` object is constructed up front
    (PHT002: nothing is built on the hot path); jax caches one trace
    per tensor index.  ``depth`` bounds in-flight tensors: the blocking
    d2h completion of tensor ``i`` happens only after ``i+depth`` has
    been issued, so its transfers hide behind younger tensors' compute.
    """

    def __init__(self, tensor_update: Callable, state_shardings: Sequence,
                 depth: int = 2, site: str = "zero_offload"):
        self._state_sh = list(state_shardings)
        self._depth = max(int(depth), 0)
        self._jit = sanitize_donation(
            _obs.instrument_jit(
                jax.jit(tensor_update, static_argnums=(0,),
                        donate_argnums=(3,)),
                site=site),
            donate_argnums=(3,), site=site)

    @property
    def depth(self) -> int:
        return self._depth

    @property
    def state_shardings(self) -> list:
        return self._state_sh

    # -- construction from a paddle Optimizer ------------------------------
    @classmethod
    def for_optimizer(cls, optimizer, plist, shard_info: ZeroShardInfo,
                      depth: int = 2, site: str = "zero_offload"):
        """Build the updater for ``Optimizer.functional_update``-style
        trainers (hapi compiled, auto-parallel Engine): the per-tensor
        rule is ``Optimizer._sharded_tensor_update`` — the same core the
        resident path traces — with per-param lr/metadata resolved from
        ``plist`` exactly as ``functional_update(params=plist)`` does."""
        pspecs = shard_info.param_specs or (None,) * len(plist)
        plrs = tuple(p.optimize_attr.get("learning_rate", 1.0)
                     for p in plist)
        def tensor_update(i, val, grad, state, lr, step_t):
            si = shard_info.with_param_specs((pspecs[i],))
            optimizer._prepare_functional([plist[i]])
            try:
                return optimizer._sharded_tensor_update(
                    val, grad, state, lr, step_t, si, param_lr=plrs[i])
            finally:
                optimizer._prepare_functional(None)

        shardings = [
            NamedSharding(shard_info.mesh,
                          P(*shard_info.moment_spec(np.shape(p._value),
                                                    existing=ps)))
            for p, ps in zip(plist, pspecs)]
        return cls(tensor_update, shardings, depth=depth, site=site)

    @staticmethod
    def host_state_for_optimizer(optimizer, plist,
                                 shard_info: ZeroShardInfo) -> List[dict]:
        """Initial host-side state: the optimizer's own zero-initialized
        slots as numpy, plus the f32 ``"master"`` slot for floating
        params under ``master_weights`` — value-identical to
        ``place_zero_state`` (bf16→f32 widening is exact), just parked
        in host RAM instead of HBM."""
        out = []
        for p in plist:
            st = {k: np.asarray(v)
                  for k, v in optimizer._init_accumulators(p).items()}
            if shard_info.master_weights and jnp.issubdtype(
                    p._value.dtype, jnp.floating):
                st["master"] = np.asarray(p._value).astype(np.float32)
            out.append(st)
        return out

    # -- the streaming update ----------------------------------------------
    def apply(self, vals, grads, host_states, lr, step_t):
        """Run the update for every tensor, streaming state through the
        ring.  ``host_states`` is a list of ``{slot: np.ndarray}``;
        returns ``(new_vals, new_host_states)`` where the new host
        arrays are FRESH buffers (a concurrently-flushing checkpoint
        writer may still read the previous step's)."""
        n = len(vals)
        out_vals: List = [None] * n
        out_states: List[Optional[dict]] = [None] * n
        ring = TransferRing(self._depth)

        def _finish(entry):
            i, nv, ns = entry
            out_vals[i] = nv
            out_states[i] = finish_d2h(ns)

        for i in range(n):
            dev_state = {k: jax.device_put(a, self._state_sh[i])
                         for k, a in host_states[i].items()}
            nv, ns = self._jit(i, vals[i], grads[i], dev_state, lr, step_t)
            done = ring.push((i, nv, start_d2h(ns)))
            if done is not None:
                _finish(done)
        for entry in ring.drain():
            _finish(entry)
        return out_vals, out_states
