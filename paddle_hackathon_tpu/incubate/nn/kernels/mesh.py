"""Running the attention kernels inside a multi-device program.

jax refuses to lower a Mosaic custom call in a program that GSPMD
partitions ("Mosaic kernels cannot be automatically partitioned. Please
wrap the call in a shard_map."): the partitioner cannot look inside the
kernel.  Under the interpreter (CPU meshes) the same kernels are plain
ops, so this only ever shows on real chips — the dp x mp train step
never reached them before PR 21.

Attention is embarrassingly parallel over batch and heads, which are
exactly the dims the trainers shard: batch over the data axes
(``parallel.api.batch_spec``: dp / sharding / ep) and heads over 'mp'
(the qkv projection's column-parallel output).  :func:`plan` reads the
mesh the trace runs under (``jax.set_mesh``) and says how to split, and
:func:`over_batch_and_heads` runs a per-shard function under a
``shard_map`` that is manual over every axis — each device then calls
the kernel on its own batch rows and heads, no collective involved.
The delta rule's inverse (``delta_rule_inverse.py``) is split the same
way, with its batch at dim 1 (``batch_dim``).

Meshes this does not cover — a 'pp' or 'sp' axis, a region that is
already manual (the pipeline's), a batch or head count the axes do not
divide — get ``None`` from :func:`plan`; the callers' ``supported()``
checks treat that like any other unsupported shape and take the XLA
attention path.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

BATCH_AXES = ("dp", "sharding", "ep")     # parallel.api.batch_spec
HEAD_AXIS = "mp"


class Plan(NamedTuple):
    batch_axes: Tuple[str, ...]     # () on one device
    head_axis: Optional[str]
    batch_shards: int
    head_shards: int

    @property
    def partitioned(self) -> bool:
        return self.batch_shards * self.head_shards > 1


_ONE_DEVICE = Plan((), None, 1, 1)


def partitioned_axes() -> dict:
    """``{axis: size}`` of the >1 axes of the mesh this trace runs under
    (the ``jax.set_mesh`` context the train steps and decode programs
    enter); ``{}`` on one device or with no mesh set."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return {a: int(n) for a, n in mesh.shape.items() if n > 1}


def plan(batch: int, heads: int) -> Optional[Plan]:
    """How to split a (batch, heads) attention call over the ambient
    mesh, or ``None`` when this mesh is not covered (module docstring)."""
    axes = partitioned_axes()
    if not axes:
        return _ONE_DEVICE
    from ....parallel._smap import active_manual_axes
    if active_manual_axes() or \
            any(a not in BATCH_AXES + (HEAD_AXIS,) for a in axes):
        return None
    bax = tuple(a for a in BATCH_AXES if a in axes)
    nb = math.prod(axes[a] for a in bax)
    nh = axes.get(HEAD_AXIS, 1)
    if batch % nb or heads % nh:
        return None
    return Plan(bax, HEAD_AXIS if nh > 1 else None, nb, nh)


def over_batch_and_heads(local_fn, p: Plan, arrays, head_dims, out_ndim,
                         out_head_dim, seed=None, batch_dim=0):
    """``local_fn(*shards, seed)`` on every device's own batch rows and
    heads.  ``arrays[i]`` has batch at dim ``batch_dim`` and heads at dim
    ``head_dims[i]``; the ``out_ndim``-dimensional result has batch at
    ``batch_dim`` and heads at ``out_head_dim``.  ``seed`` (a (1,) int32 dropout
    seed, or None) is decorrelated per shard — the kernels hash the LOCAL
    batch/head index into the mask, so shards sharing a seed would share
    masks."""
    bax = p.batch_axes or None

    def spec(ndim, hdim):
        s = [None] * ndim
        s[batch_dim], s[hdim] = bax, p.head_axis
        return P(*s)

    in_specs = tuple(spec(a.ndim, h) for a, h in zip(arrays, head_dims))
    out_spec = spec(out_ndim, out_head_dim)
    if seed is None:
        return jax.shard_map(lambda *shards: local_fn(*shards, None),
                             in_specs=in_specs, out_specs=out_spec,
                             check_vma=False)(*arrays)

    def seeded(*args):
        *shards, s = args
        shard = jnp.zeros((), jnp.int32)
        for a in p.batch_axes + ((p.head_axis,) if p.head_axis else ()):
            shard = shard * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return local_fn(*shards, s + shard * jnp.int32(7919))

    return jax.shard_map(seeded, in_specs=(*in_specs, P()),
                         out_specs=out_spec, check_vma=False)(*arrays, seed)
