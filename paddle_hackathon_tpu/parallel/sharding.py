"""Group-sharded (ZeRO) data parallel — user-facing API.

Ref ``python/paddle/distributed/sharding/group_sharded.py:40``
(``group_sharded_parallel``: level 'os' = stage 1, 'os_g' = stage 2,
'p_g_os' = stage 3) and the stage implementations
``group_sharded_stage2.py:49`` / ``group_sharded_stage3.py:60`` +
``GroupShardedOptimizerStage2`` (param-to-rank assignment, grad slice
reduce) and flat storage ``group_sharded_storage.py``.

TPU-native design: "assign param/grad/state shards to ranks" becomes
"shard the arrays over the 'sharding' mesh axis" — XLA then keeps grads
reduce-scattered and gathers params on use (stage-3/FSDP) automatically;
the hand-written bucket storage, slice-reduce hooks and gather-on-forward
of the reference all fall out of GSPMD sharding propagation. In the
one-program training path (``parallel.make_sharded_train_step``) this is
the ``zero_stage`` argument; this module provides the same capability for
the *eager* model+optimizer workflow.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.layer import Layer
from . import api as _mesh_api

_LEVELS = ("os", "os_g", "p_g_os")


def _shard_spec_for(shape, mesh, axis="sharding", existing=None):
    """Shard the first divisible, unsharded dim over ``axis``.

    Spec entries may be tuples (a dim sharded over several mesh axes)."""
    spec = list(existing) if existing else [None] * len(shape)

    def _axes(entry):
        return entry if isinstance(entry, (tuple, list)) else (entry,)

    n = mesh.shape.get(axis, 1)
    if n > 1 and all(axis not in _axes(s) for s in spec):
        for i, (dim, s) in enumerate(zip(shape, spec)):
            if s is None and dim % n == 0:
                spec[i] = axis
                break
    return _mesh_api._filter_spec(spec, mesh)


def zero_data_axis(mesh: Optional[Mesh]) -> Optional[str]:
    """The mesh axis ZeRO shards optimizer state over: the dedicated
    'sharding' axis when present, else the 'dp' axis (ref
    ``sharding_optimizer.py`` partitions over the dp ring when no
    separate sharding ring exists).  None when neither axis is >1 —
    ZeRO is then inert and callers keep state replicated."""
    if mesh is None:
        return None
    for axis in ("sharding", "dp"):
        if mesh.shape.get(axis, 1) > 1:
            return axis
    return None


@dataclasses.dataclass(frozen=True)
class ZeroShardInfo:
    """Static description of a ZeRO-sharded optimizer update — the
    argument ``Optimizer.functional_update(shard_info=...)`` (and the
    trainers that inline it) consume at trace time.

    ``stage`` follows the reference's ``group_sharded_parallel`` levels:
    1 ('os') shards the optimizer state, 2 ('os_g') additionally keeps
    gradients reduce-scattered — in the one-program GSPMD formulation
    both lower identically (the grad pin below makes the gradient
    materialize already scattered; there is no eager window where a
    full gradient could persist), so the field is recorded for API
    parity and telemetry, not branched on.  Stage 3 (params sharded) is
    the trainers' ``zero_stage=3`` placement; the update path here is
    the same — the param pin is then a no-op because the param spec
    already carries the axis.

    ``master_weights=True`` expects every state dict to carry a
    ``"master"`` slot (f32, placed like the moments): the update reads
    and writes the master copy and the gathered param is its cast.
    """
    mesh: Mesh
    # None: the mesh has no ZeRO axis; every slot then sits on its
    # parameter's own spec (how a master slot is carried without ZeRO)
    axis: Optional[str]
    stage: int = 1
    master_weights: bool = False
    # per-param base specs (TP/placement), aligned with the positional
    # buffers; None = all-replicated
    param_specs: Optional[tuple] = None

    def moment_spec(self, shape, existing=None):
        """Spec for a moment/master leaf of ``shape``: the param's own
        spec (TP dims preserved) with the first divisible unsharded dim
        additionally split over the ZeRO axis."""
        ex = list(existing) if existing is not None else None
        if ex is not None and len(ex) != len(tuple(shape)):
            ex = None
        return _shard_spec_for(shape, self.mesh, axis=self.axis,
                               existing=ex)

    def with_param_specs(self, specs: Sequence) -> "ZeroShardInfo":
        return dataclasses.replace(self, param_specs=tuple(
            tuple(s) if s is not None else None for s in specs))


def place_zero_state(shard_info: "ZeroShardInfo", param_values, states):
    """Place per-param optimizer slot dicts at their ZeRO moment
    sharding, adding the f32 ``"master"`` slot for floating params when
    ``shard_info.master_weights`` — THE single owner of the placement,
    shared by all three compiled trainers (a pp-stacked leaf's spec
    comes in through ``param_specs`` like any other; a ``shard_info``
    with no axis places on the parameter's own spec).  Returns the
    placed list."""
    pspecs = shard_info.param_specs or (None,) * len(param_values)
    placed = []
    for v, st, ps in zip(param_values, states, pspecs):
        sh = NamedSharding(shard_info.mesh,
                           P(*shard_info.moment_spec(v.shape, existing=ps)))
        d = {k: jax.device_put(s, sh) for k, s in st.items()}
        if shard_info.master_weights and jnp.issubdtype(v.dtype,
                                                        jnp.floating):
            d["master"] = jax.device_put(master_copy(v), sh)
        placed.append(d)
    return placed


def master_copy(value):
    """The f32 master-weight INITIAL value for ``value`` — a fresh
    buffer, always.  An f32 param's ``astype`` is a no-op returning the
    same array, and an aliased master would be the same buffer donated
    twice (through the params arg AND the opt-state arg) — Execute()
    rejects that.  Single owner of the invariant; every trainer's
    master init must go through here."""
    return jnp.copy(value.astype(jnp.float32))


def state_bytes(tree):
    """``(logical_bytes, per_device_bytes)`` over a placed state pytree —
    pure sharding metadata (``NamedSharding.shard_shape``), no transfer.
    ``logical`` is what a replicated placement would hold per device, so
    ``per_device / logical`` is the measured ZeRO shrink (~1/dp)."""
    logical = per_dev = 0
    for a in jax.tree.leaves(tree):
        if not isinstance(a, jax.Array):
            continue
        logical += a.nbytes
        sh = getattr(a, "sharding", None)
        if hasattr(sh, "shard_shape"):
            per_dev += int(np.prod(sh.shard_shape(a.shape),
                                   dtype=np.int64)) * a.dtype.itemsize
        else:
            per_dev += a.nbytes
    return logical, per_dev


def observe_opt_state_bytes(path: str, tree, host_tree=None) -> int:
    """Set ``train_opt_state_bytes{path,sharded}`` and
    ``train_opt_state_bytes{path,placement}`` at trainer build
    (docs/OBSERVABILITY.md) — sharding metadata only, no transfer.

    ``sharded="false"`` carries what a REPLICATED placement holds per
    device (the state's logical bytes); ``sharded="true"`` carries the
    ACTUAL placed per-device bytes — equal to the replicated value when
    ZeRO is off, so the true/false ratio IS the measured shrink (~1/dp
    under ZeRO, 1.0 otherwise).  ``placement="device"`` is the placed
    per-device bytes again and ``placement="host"`` the numpy bytes of
    ``host_tree`` (the ZeRO-offload state) — together they export the
    offload HBM win AND its host-RAM cost honestly.  ALL children are
    written on every build: a non-sharded (or non-offloaded) rebuild on
    the same path must overwrite a previous build's values, never leave
    a stale shrink/offload exported.  Returns the per-device bytes."""
    from ..observability import metrics as _obs
    logical, per_dev = state_bytes(tree)
    host = 0 if host_tree is None else sum(
        int(a.nbytes) for a in jax.tree.leaves(host_tree)
        if isinstance(a, np.ndarray))
    # the replicated-footprint baseline must count the offloaded slots
    # too (they ARE optimizer state a resident build would hold in HBM)
    logical += host
    fam = _obs.get_registry().gauge(
        "train_opt_state_bytes",
        "optimizer-state bytes per device at trainer build (placement "
        "metadata, no transfer): sharded=false = the replicated "
        "footprint, sharded=true = the actual placed footprint; their "
        "ratio is the ZeRO shrink (~1/dp; 1.0 when not sharded); "
        "placement=device|host split the placed bytes by residency "
        "(host > 0 only under ZeRO-offload)")
    fam.labels(path=path, sharded="false").set(logical)
    fam.labels(path=path, sharded="true").set(per_dev)
    fam.labels(path=path, placement="device").set(per_dev)
    fam.labels(path=path, placement="host").set(host)
    return per_dev


def group_sharded_parallel(model: Layer, optimizer, level: str = "os_g",
                           scaler=None, group=None, offload: bool = False,
                           sync_buffers: bool = False, buffer_max_size=None,
                           segment_size=None, sync_comm: bool = False):
    """Shard a model/optimizer over the 'sharding' mesh axis
    (ref ``group_sharded.py:40`` — same signature shape).

    level:
      'os'     — optimizer states sharded (ZeRO-1)
      'os_g'   — + gradients effectively reduce-scattered (ZeRO-2); with
                 XLA this is the same placement, grads inherit it
      'p_g_os' — + parameters sharded, gathered on use (ZeRO-3 / FSDP)
    """
    if level not in _LEVELS:
        raise ValueError(f"level must be one of {_LEVELS}, got {level!r}")
    if offload:
        # the reference's offload=True parks moments+masters in host RAM
        # inside this eager wrapper; here host offload is a property of
        # the compiled train step (the streaming pipe in
        # ``parallel.offload``), not of eager placement — say so instead
        # of silently accepting the flag
        import warnings
        warnings.warn(
            "group_sharded_parallel(offload=True): eager offload is not "
            "supported — use zero_offload=True on Model.fit / "
            "Strategy(zero_offload=True) / make_sharded_train_step "
            "(docs/PARALLELISM.md 'Optimizer offload & overlap'); "
            "continuing with device-resident sharded state",
            stacklevel=2)
    mesh = _mesh_api.get_mesh()
    if mesh is None or mesh.shape.get("sharding", 1) <= 1:
        return model, optimizer, scaler  # degenerate: nothing to shard over

    if level == "p_g_os":
        for name, p in model.named_parameters():
            spec = _shard_spec_for(p.shape, mesh,
                                   existing=getattr(p, "pspec", None))
            p._set_value(jax.device_put(
                p._value, NamedSharding(mesh, P(*spec))))
            p.pspec = spec

    # optimizer states always shard (that's stage >= 1): wrap accumulator
    # creation so every new state lands 'sharding'-sharded.
    orig_init = optimizer._init_accumulators

    def sharded_init(param):
        acc = orig_init(param)
        out = {}
        for k, v in acc.items():
            spec = _shard_spec_for(v.shape, mesh,
                                   existing=getattr(param, "pspec", None))
            out[k] = jax.device_put(v, NamedSharding(mesh, P(*spec)))
        return out

    optimizer._init_accumulators = sharded_init
    # ...and the UPDATE runs through the same functional sharded path the
    # compiled trainers use (``Optimizer._sharded_rules``): grads pinned
    # to the moment sharding (reduce-scatter), shard-local rule, params
    # all-gathered back — eager and compiled ZeRO agree on the program,
    # instead of the old placement-only wrapping that let GSPMD
    # re-replicate the moments inside ``Optimizer.step``'s jitted update.
    optimizer._zero_info = ZeroShardInfo(
        mesh=mesh, axis="sharding",
        stage={"os": 1, "os_g": 2, "p_g_os": 3}[level])
    return model, optimizer, scaler


def save_group_sharded_model(model: Layer, output: str, optimizer=None):
    """Ref ``group_sharded.py`` ``save_group_sharded_model`` — gathers shards
    (device_get replicates) and saves full state."""
    import os
    from ..framework import io as fio
    os.makedirs(output, exist_ok=True)
    fio.save(model.state_dict(), os.path.join(output, "model.pdparams"))
    if optimizer is not None:
        fio.save(optimizer.state_dict(), os.path.join(output, "model.pdopt"))
