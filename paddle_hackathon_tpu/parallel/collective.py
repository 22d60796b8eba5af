"""Collective communication API over mesh axes.

Reference surface: ``paddle.distributed.{all_reduce, all_gather, reduce,
broadcast, scatter, alltoall, reduce_scatter, send, recv, barrier}``
(``python/paddle/distributed/collective.py``) backed by
``ProcessGroup`` (``collective/ProcessGroup.h:53``) + NCCL rings.

TPU-native design: a "process group" is a set of named mesh axes
(``Group``). Collectives are XLA HLO ops (psum / all_gather /
reduce_scatter / all_to_all / ppermute) which XLA schedules as async
ICI transfers — the role NCCL comm streams play in the reference
(``ProcessGroupNCCL.cc:227``). Each function is dual-mode:

- **inside a traced SPMD program** (``shard_map``): thin wrapper over the
  ``jax.lax`` collective using the group's axis names — this is the hot
  path, equivalent to the reference's per-rank eager collective calls.
- **eager**, for test parity with the reference's collective API tests
  (``test_collective_api_base.py:34``): operates on an array whose leading
  dim is the "rank" dim of the group (the single-controller analog of
  every process holding its own tensor), and runs the same shard_map
  program over the current mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from . import api as _mesh_api


class ReduceOp:
    """Ref ``distributed/collective.py`` ReduceOp enum."""
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator = one or more named mesh axes (ref ``ProcessGroup.h:53``;
    the (ring_id → comm) registry ``collective_helper.h:71`` becomes the
    (axis name → mesh axis) association)."""

    def __init__(self, axes: Union[str, Sequence[str]],
                 mesh: Optional[Mesh] = None):
        if isinstance(axes, str):
            axes = (axes,)
        self.axes: Tuple[str, ...] = tuple(axes)
        self._mesh = mesh

    @property
    def mesh(self) -> Mesh:
        m = self._mesh or _mesh_api.get_mesh()
        if m is None:
            raise RuntimeError(
                "no device mesh active — call parallel.create_mesh first")
        return m

    @property
    def nranks(self) -> int:
        m = self.mesh
        return int(np.prod([m.shape[a] for a in self.axes]))

    @property
    def world_size(self) -> int:
        return self.nranks

    def axis_name(self):
        """Axis-name argument for jax.lax collectives."""
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def __repr__(self):
        return f"Group(axes={self.axes}, nranks={self.nranks})"


def new_group(axes: Union[str, Sequence[str]] = None,
              mesh: Optional[Mesh] = None) -> Group:
    """Ref ``paddle.distributed.new_group`` (``collective.py:366``) — but
    instead of a rank list, a group is named mesh axes (subgroups along the
    orthogonal axes are implicit in SPMD)."""
    if axes is None:
        m = mesh or _mesh_api.get_mesh()
        axes = tuple(m.axis_names)
    return Group(axes, mesh)


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _unwrap(x):
    return x._value if hasattr(x, "_value") else jnp.asarray(x)


def _eager(group: Group, local_fn, x, extra_rank_dims: int = 0):
    """Run ``local_fn`` as a shard_map over the group's axes with the leading
    dim of ``x`` as the stacked rank dim."""
    mesh = group.mesh
    n = group.nranks
    if x.shape[0] != n:
        raise ValueError(
            f"eager collective expects leading 'rank' dim == group size "
            f"({n}), got shape {x.shape}")
    spec = P(group.axes if len(group.axes) > 1 else group.axes[0])
    fn = shard_map(local_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                   check_vma=False)
    return fn(x)


_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


def _reduce_local(xs, op, axis):
    if op == ReduceOp.AVG:
        return jax.lax.pmean(xs, axis)
    if op == ReduceOp.PROD:
        # XLA has no product collective: exp(psum(log|x|)) with explicit
        # sign/zero tracking (log of a negative would NaN).
        mag = jnp.exp(jax.lax.psum(
            jnp.log(jnp.where(xs == 0, 1.0, jnp.abs(xs))), axis))
        n_neg = jax.lax.psum((xs < 0).astype(jnp.int32), axis)
        n_zero = jax.lax.psum((xs == 0).astype(jnp.int32), axis)
        sign = jnp.where(n_neg % 2 == 0, 1.0, -1.0)
        return jnp.where(n_zero > 0, 0.0, sign * mag).astype(xs.dtype)
    return _REDUCERS[op](xs, axis)


def all_reduce(x, op: str = ReduceOp.SUM, group: Optional[Group] = None):
    """Every rank ends with the reduction (ref ``c_allreduce_op.h:81``).

    Traced: reduces over the group's axes. Eager: ``x`` is (nranks, ...)
    stacked; returns the same shape with every rank slice equal."""
    group = group or new_group()
    xv = _unwrap(x)
    if _is_traced(xv):
        return _reduce_local(xv, op, group.axis_name())
    return _eager(group, lambda xs: _reduce_local(xs, op, group.axis_name()),
                  xv)


def all_gather(x, group: Optional[Group] = None, axis: int = 0):
    """Ref ``c_allgather``. Traced: gather along the group axes onto a new
    leading dim. Eager: (nranks, ...) -> (nranks, nranks, ...): every rank
    sees every rank's tensor."""
    group = group or new_group()
    xv = _unwrap(x)
    if _is_traced(xv):
        return jax.lax.all_gather(xv, group.axis_name(), axis=axis)

    def local(xs):  # xs: (1, *s)
        g = jax.lax.all_gather(xs[0], group.axis_name(), axis=axis)
        return g[None]  # (1, ..., n, ...) -> stacked (n, ..., n, ...)

    return _eager(group, local, xv)


def reduce_scatter(x, op: str = ReduceOp.SUM, group: Optional[Group] = None):
    """Ref ``c_reducescatter`` / ``_ReduceScatterBase`` (``ProcessGroup.h:181``).
    Traced: psum_scatter over leading dim. Eager: (nranks, nranks, *s) where
    in[r, j] is rank r's slice destined for rank j -> (nranks, *s)."""
    group = group or new_group()
    xv = _unwrap(x)
    if _is_traced(xv):
        return jax.lax.psum_scatter(
            xv, group.axis_name(), scatter_dimension=0, tiled=True)

    def local(xs):  # xs: (1, n, *s)
        return jax.lax.psum_scatter(
            xs[0], group.axis_name(), scatter_dimension=0, tiled=False)[None]

    out = _eager(group, local, xv)
    return out.reshape((group.nranks,) + tuple(xv.shape[2:]))


def broadcast(x, src: int = 0, group: Optional[Group] = None):
    """Ref ``c_broadcast``. Eager: (nranks, ...) -> every slice = in[src]."""
    group = group or new_group()
    xv = _unwrap(x)
    axis = group.axis_name()

    def local(xs):
        idx = jax.lax.axis_index(axis)
        contrib = jnp.where(idx == src, xs, jnp.zeros_like(xs))
        return jax.lax.psum(contrib, axis)

    if _is_traced(xv):
        return local(xv)
    return _eager(group, local, xv)


def reduce(x, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None):
    """Ref ``ProcessGroup::Reduce`` — only ``dst`` keeps the reduction; other
    ranks keep their input (matching paddle's in-place semantics)."""
    group = group or new_group()
    xv = _unwrap(x)
    axis = group.axis_name()

    def local(xs):
        red = _reduce_local(xs, op, axis)
        idx = jax.lax.axis_index(axis)
        return jnp.where(idx == dst, red, xs)

    if _is_traced(xv):
        return local(xv)
    return _eager(group, local, xv)


def scatter(x, src: int = 0, group: Optional[Group] = None):
    """Ref ``ProcessGroup::Scatter``. Eager: in (nranks, nranks, *s) with
    in[src, j] the tensor for rank j -> out (nranks, *s)."""
    group = group or new_group()
    xv = _unwrap(x)
    axis = group.axis_name()

    def local(xs):  # (1, n, *s)
        row = jax.lax.psum(
            jnp.where(jax.lax.axis_index(axis) == src, xs,
                      jnp.zeros_like(xs)), axis)  # (1, n, *s) replicated
        idx = jax.lax.axis_index(axis)
        return jax.lax.dynamic_index_in_dim(row[0], idx, 0, keepdims=True)

    if _is_traced(xv):
        idx = jax.lax.axis_index(axis)
        row = jax.lax.psum(
            jnp.where(idx == src, xv, jnp.zeros_like(xv)), axis)
        return jax.lax.dynamic_index_in_dim(row, idx, 0, keepdims=False)
    return _eager(group, local, xv)


def alltoall(x, group: Optional[Group] = None):
    """Ref ``alltoall`` op / MoE ``global_scatter`` transport
    (``global_scatter_op.cc:20``). Traced: lax.all_to_all on leading dim.
    Eager: (nranks, nranks, *s) -> transposed on first two dims, i.e.
    out[r, j] = in[j, r]."""
    group = group or new_group()
    xv = _unwrap(x)
    axis = group.axis_name()
    if _is_traced(xv):
        return jax.lax.all_to_all(xv, axis, split_axis=0, concat_axis=0,
                                  tiled=True)

    def local(xs):  # (1, n, *s) -> (1, n, *s): slot j = chunk from rank j
        return jax.lax.all_to_all(xs, axis, split_axis=1, concat_axis=1,
                                  tiled=True)

    return _eager(group, local, xv)


def ppermute(x, perm, group: Optional[Group] = None):
    """Point-to-point ring transfer (ref ``send_v2``/``recv_v2`` pairs,
    ``partial_send/recv`` — PP's p2p layer ``p2p_communication.py:276``).
    ``perm`` is a list of (src, dst) pairs; ranks not named as a dst
    receive zeros. Traced-only (p2p only makes sense inside a program)."""
    group = group or new_group()
    xv = _unwrap(x)
    axis = group.axis_name()
    if _is_traced(xv):
        return jax.lax.ppermute(xv, axis, perm)

    def local(xs):
        return jax.lax.ppermute(xs, axis, perm)

    return _eager(group, local, xv)


def shift(x, offset: int = 1, group: Optional[Group] = None):
    """Ring shift by ``offset`` (rank r -> rank (r+offset) % n): the building
    block of ring attention and PP stage handoff."""
    group = group or new_group()
    n = group.nranks
    perm = [(i, (i + offset) % n) for i in range(n)]
    return ppermute(x, perm, group)


def barrier(group: Optional[Group] = None):
    """Ref ``ProcessGroup::Barrier`` (``ProcessGroup.h:101``). In
    single-controller SPMD a barrier is a no-op device-side; we run a psum of
    ones and block on it (host sync)."""
    group = group or new_group()
    x = jnp.ones((group.nranks, 1), jnp.float32)
    out = all_reduce(x, ReduceOp.SUM, group)
    jax.block_until_ready(out)


def axis_index(group: Optional[Group] = None):
    """Rank within the group — only valid inside a traced SPMD program
    (ref ``paddle.distributed.get_rank`` per-group)."""
    group = group or new_group()
    return jax.lax.axis_index(group.axis_name())
