"""Mesh construction + sharded training step.

The reference composes parallelism by rewriting programs per-strategy
(``fleet/meta_optimizers/``, 20 program-rewrite passes) or wrapping models
(``meta_parallel/``). Here a single mechanism covers DP/TP/ZeRO: annotate
parameter and batch shardings over a named mesh and let GSPMD insert the
collectives (psum for DP grads = the EagerReducer's fused allreduce;
all-gather/reduce-scatter for ZeRO = sharding stage 1-3; TP collectives =
c_identity/c_allreduce pairs). PP and SP are explicit shard_map programs
(see pipeline.py / sequence.py).
"""

from __future__ import annotations

import itertools
import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import set_mesh as _set_mesh
from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..observability import metrics as _obs
from ..observability import tracing as _tracing
from .layer_outputs import collect_layer_counters

_current_mesh: Optional[Mesh] = None

# 'ep' (expert parallel) is data-like for non-expert params (batch shards
# over it, grads psum) and model-like for the stacked expert weights
# (leading expert dim shards over it) — the reference dispatches through
# global_scatter/global_gather inside hybrid training
# (operators/collective/global_scatter_op.cc:20); here GSPMD lowers the
# capacity einsums to the same all_to_all pair.
AXES = ("pp", "dp", "sharding", "ep", "mp", "sp")


def create_mesh(mesh_dims: Dict[str, int], devices=None) -> Mesh:
    """Build a named-axis device mesh (ref ``CommunicateTopology``
    ``topology.py:52`` — the cartesian [data,pipe,sharding,model] mesh).

    ``mesh_dims`` maps axis name -> size, e.g. {"dp": 2, "mp": 4}. Axes are
    ordered (pp, dp, sharding, mp, sp) — outermost first, so 'mp' and 'sp'
    land on the innermost (fastest ICI) device dimension, matching the
    reference's hybrid-parallel ordering where model-parallel groups are
    nearest neighbours.
    """
    devices = devices if devices is not None else jax.devices()
    names = [a for a in AXES if mesh_dims.get(a, 1) > 1 or a in mesh_dims]
    if not names:
        names = ["dp"]
        mesh_dims = {"dp": len(devices)}
    sizes = [mesh_dims.get(a, 1) for a in names]
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(
            f"mesh dims {dict(zip(names, sizes))} require {total} devices, "
            f"but {len(devices)} are visible")
    arr = np.asarray(devices).reshape(sizes)
    mesh = Mesh(arr, tuple(names))
    set_mesh(mesh)
    return mesh


def set_mesh(mesh: Mesh) -> None:
    global _current_mesh
    _current_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _current_mesh


def _filter_spec(spec, mesh: Mesh):
    """Drop axis names the mesh doesn't have; keep dims aligned.

    Entries may be a single axis name or a tuple of axis names (a dim sharded
    over several mesh axes, e.g. vocab over ('mp', 'sharding'))."""
    out = []
    for a in spec:
        if isinstance(a, (tuple, list)):
            kept = tuple(x for x in a if x in mesh.axis_names)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(a if (a in mesh.axis_names) else None)
    return tuple(out)


def shard_params(model: Layer, mesh: Mesh,
                 rule: Optional[Callable] = None,
                 zero_stage: int = 0) -> Dict[str, jax.Array]:
    """Place model parameters onto the mesh per a sharding rule.

    ``rule(name, shape) -> spec tuple`` supplies TP specs (e.g.
    ``models.gpt.param_sharding_spec``); ``zero_stage>=3`` additionally shards
    the largest replicated dim over the 'sharding' axis (FSDP/stage-3,
    ref ``group_sharded_stage3.py:60``).
    Parameters are updated in place to device-sharded arrays.
    """
    from .sharding import _shard_spec_for
    placed = {}
    for name, p in model.named_parameters():
        spec = list(rule(name, p.shape)) if rule else [None] * p.ndim
        spec = list(_filter_spec(spec, mesh))
        if zero_stage >= 3:
            spec = list(_shard_spec_for(p.shape, mesh, existing=spec))
        sharding = NamedSharding(mesh, P(*spec))
        arr = jax.device_put(p._value, sharding)
        p._set_value(arr)
        placed[name] = arr
    return placed


def batch_spec(mesh: Mesh) -> P:
    """Batch axis sharded over every data-like axis present (dp x sharding
    x ep: the reference's dp-degree x sharding-degree both consume batch,
    and MoE expert-parallel ranks are data-parallel for non-expert
    params)."""
    data_axes = tuple(a for a in ("dp", "sharding", "ep")
                      if a in mesh.axis_names)
    if not data_axes:
        return P()
    return P(data_axes)


def decode_cache_sharding(mesh: Mesh):
    """NamedSharding for a (B, T, heads, head_dim) KV-cache leaf: batch
    over the data axes, heads on 'mp' (the qkv projection's natural
    output sharding).  Single home for ``GPTForCausalLM._generate_static``
    and the serving engine's slot cache — the layout must never diverge
    between them."""
    from jax.sharding import NamedSharding
    bspec = batch_spec(mesh)
    bax = bspec[0] if len(bspec) else None
    hax = "mp" if mesh.shape.get("mp", 1) > 1 else None
    return NamedSharding(mesh, P(bax, None, hax, None))


def token_batch_sharding(mesh: Mesh):
    """NamedSharding for host-staged per-slot serving inputs — the
    (B, K+1) speculative verify token block and the (B,) start/length
    vectors: batch over the data axes, trailing dims replicated.  Shares
    :func:`decode_cache_sharding`'s batch layout so the widened verify
    program's per-slot cache writes need no GSPMD reshard between the
    token gather and the KV dynamic_update_slice."""
    bspec = batch_spec(mesh)
    bax = bspec[0] if len(bspec) else None
    return NamedSharding(mesh, P(bax))


def page_pool_sharding(mesh: Mesh):
    """NamedSharding for a paged-KV pool leaf (num_pages, page_size,
    heads, head_dim): heads on 'mp' like :func:`decode_cache_sharding`
    (the qkv projection's natural output sharding), pages REPLICATED
    over the data axes — pages are slot-agnostic, so there is no batch
    dim to shard, and any page must be gatherable by any slot's table
    row without a cross-rank collective per page."""
    from jax.sharding import NamedSharding
    hax = "mp" if mesh.shape.get("mp", 1) > 1 else None
    return NamedSharding(mesh, P(None, None, hax, None))


def stack_block_params(model, mesh: Mesh, rule, block_prefix: str,
                       n_layers: int, zero_stage: int = 0):
    """Split a model's parameters into (other, stacked) and PLACE both:
    per-layer block params (``{block_prefix}{i}.{rel}``) stack into
    ``(n_layers, ...)`` arrays sharded over 'pp' (+ TP axes per ``rule``,
    + 'sharding' when ``zero_stage>=3``); everything else places per the
    rule. Shared by the pp train step and pp-sharded decode.

    Returns ``(other, stacked)`` — ``other`` keyed by full param name,
    ``stacked`` keyed by the per-layer relative name.
    """
    import re

    from .sharding import _shard_spec_for
    pat = re.compile(re.escape(block_prefix) + r"(\d+)\.(.+)")
    per_layer: Dict[str, dict] = {}
    other = {}
    for k, p in model.named_parameters():
        v = p._value
        m = pat.match(k)
        if m:
            per_layer.setdefault(m.group(2), {})[int(m.group(1))] = v
        else:
            spec = list(rule(k, v.shape)) if rule else [None] * v.ndim
            spec = list(_filter_spec(spec, mesh))
            if zero_stage >= 3:
                spec = list(_shard_spec_for(v.shape, mesh, existing=spec))
            other[k] = jax.device_put(v, NamedSharding(mesh, P(*spec)))
    stacked = {}
    for rel, d in sorted(per_layer.items()):
        arr = jnp.stack([d[i] for i in range(n_layers)])
        stacked[rel] = jax.device_put(
            arr, NamedSharding(mesh, P(*_pp_stacked_spec(
                rel, arr, mesh, rule, block_prefix, zero_stage >= 3))))
    return other, stacked


def _pp_stacked_spec(rel: str, arr, mesh: Mesh, rule, prefix: str,
                     extra_sharding: bool, axis: str = "sharding"):
    """PartitionSpec for a stacked block parameter: leading layer dim on
    'pp', remaining dims per the TP rule of the per-layer param (layer 0's
    name is representative), optionally + a ZeRO dim over ``axis``
    ('sharding' for param placement; optimizer-state specs pass the
    dp-fallback axis from ``sharding.zero_data_axis``)."""
    from .sharding import _shard_spec_for
    per = list(rule(prefix + "0." + rel, arr.shape[1:])) if rule \
        else [None] * (arr.ndim - 1)
    spec = ["pp"] + list(_filter_spec(per, mesh))
    if extra_sharding:
        spec = list(_shard_spec_for(arr.shape, mesh, axis=axis,
                                    existing=spec))
    return _filter_spec(spec, mesh)


def _make_pipeline_loss(mesh: Mesh, pp_spec: dict, pp_degree: int,
                        n_micro: int, stacked_rel_keys):
    """Loss over the 1F1B pipelined forward (see make_sharded_train_step).

    Microbatching uses a strided regroup — ``(B, ...) -> (mb, n_micro, ...)
    -> swapaxes`` — so the dp/sharding-sharded batch dim splits without any
    cross-device data motion (microbatch m = rows {j*n_micro + m}; the loss
    is a mean over all rows, so the grouping is semantically free)."""
    from .pipeline import pipeline_apply
    from ..core import random as core_random

    prefix = pp_spec["block_prefix"]
    pre_fn, layer_fn, post_fn = (pp_spec["pre_fn"], pp_spec["layer_fn"],
                                 pp_spec["post_fn"])
    n_local = pp_spec["num_layers"] // pp_degree
    data_axes = tuple(a for a in ("dp", "sharding", "ep")
                      if a in mesh.axis_names)
    # sp x pp: the seq dim (the one after the microbatch/batch dims) stays
    # sharded on 'sp' through every regroup pin, so the ring attention
    # inside each pipeline stage sees its sequence chunk without a gather
    sp_axis = "sp" if mesh.shape.get("sp", 1) > 1 else None

    def loss_fn(model, params, buffers, batch, rng):
        ids, labels = batch
        k_pre, k_blocks = jax.random.split(rng)
        x = pre_fn(params, buffers, ids, k_pre)
        B = x.shape[0]
        if B % n_micro:
            raise ValueError(
                f"batch {B} must divide into pp_microbatches={n_micro}")
        mb = B // n_micro
        n_data = int(np.prod([mesh.shape[a] for a in data_axes])) \
            if data_axes else 1
        if mb % n_data:
            raise ValueError(
                f"microbatch size {mb} (= batch {B} / pp_microbatches "
                f"{n_micro}) must divide over the {n_data} dp*sharding "
                "devices — a smaller microbatch would idle data ranks and "
                "force resharding; raise the batch or lower pp_microbatches")

        def pin(a, spec_head):
            # explicit motion-free sharding chain: without these pins GSPMD
            # propagates the batch sharding onto the wrong regroup dim and
            # falls back to involuntary full rematerialization
            if not data_axes and sp_axis is None:
                return a
            spec = spec_head + (sp_axis,)
            spec = spec + tuple([None] * (a.ndim - len(spec)))
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(*spec)))

        xr = pin(x.reshape((mb, n_micro) + x.shape[1:]), (data_axes, None))
        xm = pin(jnp.swapaxes(xr, 0, 1), (None, data_axes))
        stacked = {rel: params[prefix + "$stacked." + rel]
                   for rel in stacked_rel_keys}

        use_aux = bool(pp_spec.get("layer_aux"))

        def block_fn(stage_params, xb, mb_idx):
            stage = jax.lax.axis_index("pp")

            def body(h, inp):
                lp, j = inp
                # unique dropout stream per (layer, microbatch) — folding
                # only the layer would reuse one mask across microbatches
                lk = jax.random.fold_in(
                    k_blocks, (stage * n_local + j) * n_micro + mb_idx)
                if sp_axis is not None:
                    # the sp axis is manual inside the pipeline region:
                    # each device sees its LOCAL sequence chunk, so the
                    # mask must differ per chunk (iid over positions)
                    lk = jax.random.fold_in(
                        lk, jax.lax.axis_index(sp_axis))
                with core_random.rng_scope(lk):
                    out = layer_fn(lp, h)
                return (out, None) if not use_aux else out

            h, auxes = jax.lax.scan(body, xb,
                                    (stage_params, jnp.arange(n_local)))
            if use_aux:
                return h, jnp.sum(auxes)
            return h

        ym = pipeline_apply(block_fn, stacked, xm, mesh,
                            extra=jnp.arange(n_micro),
                            seq_axis=sp_axis, with_aux=use_aux)
        aux_total = None
        if use_aux:
            ym, aux_total = ym
        ym = pin(ym, (None, data_axes))
        ys = pin(jnp.swapaxes(ym, 0, 1), (data_axes, None))
        y = pin(ys.reshape((B,) + ym.shape[2:]), (data_axes,))
        loss = post_fn(params, y, labels)
        if use_aux:
            # aux is computed per microbatch (the reference's gradient-
            # accumulation semantics), weighted by the layer_fn as the
            # loss takes it; mean over microbatches matches the
            # full-batch estimator in expectation
            loss = loss + aux_total / n_micro
        return loss

    return loss_fn


def make_functional_train_step(optimizer, plist, order, grads_of,
                               merge_k: int = 1, scan_batch: bool = False,
                               shard_info=None, grad_overlap: bool = False):
    """Compose a loss-gradient function with the optimizer's pure
    ``Optimizer.functional_update`` into

        train_step(params, opt_states, step, lr, batch)
            -> (new_params, new_opt_states, new_step, loss)

    — THE single owner of the forward+backward+update step body, shared
    by ``auto_parallel.Engine`` (per-batch SPMD program, gradient merge),
    ``hapi.Model``'s compiled fit path (K-step ``lax.scan`` unroll) and
    ``make_sharded_train_step`` (whose ``grads_of`` returns what it has
    already differentiated from its own frame).

    - ``grads_of(params, xs, ys, step) -> (loss, grads)``, grads keyed
      like ``params``; ``order`` maps ``plist`` (the optimizer's ordered
      Parameter objects) to param-dict keys.
    - ``merge_k > 1``: split the batch into k micro-batches, average
      grads, single update (the reference's gradient_merge pass).
    - ``scan_batch``: every batch leaf carries a leading stacked-step dim
      ``(K, B, ...)``; one ``lax.scan`` runs K full optimizer steps
      inside the same XLA program and ``loss`` returns as a (K,) vector
      — Python touches the device once per K steps.
    - ``shard_info`` (``sharding.ZeroShardInfo``): the optimizer update
      runs ZeRO-sharded — reduce-scattered grads, shard-local moments
      (+ optional f32 master slot), per-tensor param all-gathers pinned
      so the scanned program's scheduler overlaps step k+1's gathers
      with the tail of step k's update instead of serializing on one
      fused gather (``Optimizer.functional_update`` shard-aware path).
    - ``grad_overlap`` (with ``shard_info``): pin every gradient to its
      moment sharding the moment the backward produces it — per
      microbatch inside the ``merge_k`` accumulation scan, and straight
      after the backward in the per-step body — so each tensor's
      reduce-scatter is an independent collective the XLA scheduler can
      overlap with the remaining backward/accumulation compute, instead
      of the whole grad set staying logically replicated until the
      update's fused preamble.  The global-norm clip then runs on the
      scattered shards (GSPMD cross-shard reductions — globally
      correct, reassociated), so the loss series matches the fused path
      to f32 reassociation tolerance rather than bit-exactly.
    """
    if grad_overlap and shard_info is None:
        grad_overlap = False  # nothing to scatter onto — inert

    def _pin_to_moments(grads):
        """Constraint-pin each ordered grad to its ZeRO moment sharding
        (the explicit per-tensor reduce-scatter schedule)."""
        pspecs = shard_info.param_specs or (None,) * len(order)
        out = dict(grads)
        for k, ps in zip(order, pspecs):
            ms = shard_info.moment_spec(out[k].shape, existing=ps)
            out[k] = jax.lax.with_sharding_constraint(
                out[k], NamedSharding(shard_info.mesh, P(*ms)))
        return out

    def one_step(params, opt_states, step, lr, xs, ys):
        if merge_k > 1:
            def split(a):
                return a.reshape((merge_k, a.shape[0] // merge_k)
                                 + a.shape[1:])

            def body(carry, mb):
                mx, my = mb
                l, g = grads_of(params, mx, my, step)
                if grad_overlap:
                    g = _pin_to_moments(g)
                acc_l, acc_g = carry
                return (acc_l + l, jax.tree.map(jnp.add, acc_g, g)), None

            zero_g = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            if grad_overlap:
                zero_g = _pin_to_moments(zero_g)
            (loss_sum, grad_sum), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_g),
                (jax.tree.map(split, xs), jax.tree.map(split, ys)))
            loss = loss_sum / merge_k
            grads = jax.tree.map(lambda g: g / merge_k, grad_sum)
        else:
            loss, grads = grads_of(params, xs, ys, step)
            if grad_overlap:
                grads = _pin_to_moments(grads)
        vals = [params[k] for k in order]
        gs = [grads[k] for k in order]
        new_vals, new_states = optimizer.functional_update(
            vals, gs, opt_states, lr, step.astype(jnp.int32) + 1,
            params=plist, shard_info=shard_info)
        new_params = dict(params)
        for k, v in zip(order, new_vals):
            new_params[k] = v
        return new_params, new_states, step + 1, loss

    def train_step(params, opt_states, step, lr, batch):
        xs, ys = batch
        if not scan_batch:
            return one_step(params, opt_states, step, lr, xs, ys)

        def body(carry, xy):
            p, s, t = carry
            p, s, t, loss = one_step(p, s, t, lr, xy[0], xy[1])
            return (p, s, t), loss

        (params, opt_states, step), losses = jax.lax.scan(
            body, (params, opt_states, step), (xs, ys))
        return params, opt_states, step, losses

    return train_step


# optimizer="adam" | "lamb" | "lars" is shorthand for a class of
# ``optimizer/`` built with ``optimizer_kwargs``, ``moment_dtype`` and a
# global-norm clip at ``grad_clip_norm``: the class, and the defaults the
# shorthand has that the class has not (adam: the LM-pretraining beta2)
_SHORTHAND = {"adam": ("Adam", {"beta2": 0.95}),
              "lamb": ("Lamb", {}), "lars": ("Lars", {})}

# the sharded step's state names its slots ``m`` / ``v`` (lars: ``m``), as
# the benchmark and the checkpoints of it read them; ``optimizer/`` keeps
# the reference's names, which ``state_dict`` and the flat ``opt::i::slot``
# checkpoint layout of the other two trainers carry
_SLOT_NAMES = {"moment1": "m", "moment2": "v", "velocity": "m"}


def _resolve_optimizer(optimizer, kwargs, learning_rate, moment_dtype,
                       grad_clip_norm, parameters):
    from .. import optimizer as _opt
    from ..nn.clip import ClipGradByGlobalNorm
    if isinstance(optimizer, _opt.Optimizer):
        if kwargs or moment_dtype is not None:
            raise ValueError(
                "optimizer_kwargs / moment_dtype belong to the "
                "adam/lamb/lars shorthand; an Optimizer instance brings its "
                "own hyper-parameters, moment dtype and grad_clip")
        return optimizer
    kind = str(optimizer).lower()
    if kind not in _SHORTHAND:
        raise ValueError("optimizer must be adam/lamb/lars or an Optimizer "
                         f"instance, got {optimizer}")
    cls, defaults = _SHORTHAND[kind]
    return getattr(_opt, cls)(
        learning_rate=learning_rate, parameters=parameters,
        grad_clip=None if grad_clip_norm is None
        else ClipGradByGlobalNorm(grad_clip_norm),
        moment_dtype=moment_dtype, **{**defaults, **(kwargs or {})})


def make_sharded_train_step(model: Layer, mesh: Mesh,
                            rule: Optional[Callable] = None,
                            learning_rate: float = 1e-4,
                            zero_stage: Optional[int] = None,
                            loss_fn: Optional[Callable] = None,
                            param_dtype=None,
                            grad_clip_norm: Optional[float] = 1.0,
                            recompute: bool = False,
                            recompute_policy: Optional[str] = None,
                            pp_microbatches: Optional[int] = None,
                            moment_dtype=None,
                            sp_mode: str = "auto",
                            optimizer="adam",
                            optimizer_kwargs: Optional[dict] = None,
                            master_weights: bool = False,
                            zero_offload: bool = False,
                            grad_overlap: bool = False,
                            offload_depth: int = 2):
    """Build (step_fn, state) — one compiled SPMD program per step covering
    forward, backward, grad psum over dp, and the optimizer's update on
    (optionally 'sharding'/'dp'-sharded) optimizer state.

    This function owns placement, the pp / sp / rng / recompute forward,
    jit + donation, the state's layout and the host-side ``step``.  The
    step body (grads -> clip -> update) is ``make_functional_train_step``
    over ``Optimizer.functional_update``, the one the other two trainers
    compile.  ``optimizer`` is an ``Optimizer`` instance (``AdamW`` with
    its decay mask, a ``Lamb`` with its exclusions, ...: its own
    ``grad_clip`` and learning rate hold, ``step(lr=)`` overrides the
    rate per call), or the shorthand ``"adam"`` / ``"lamb"`` / ``"lars"``
    built from ``optimizer_kwargs``, ``moment_dtype``, ``learning_rate``
    and a global-norm clip at ``grad_clip_norm`` (``_SHORTHAND``).

    ``zero_stage=None`` (default) means stage 1 wherever the mesh has a
    data axis; ``zero_stage>=1`` shards the OPTIMIZER STATE over the ZeRO
    data axis
    (the 'sharding' axis when present, else 'dp' —
    ``sharding.zero_data_axis``): each rank owns a 1/dp slice of every
    moment; the step's update is constraint-pinned end to end — grads
    reduce-scattered onto the slice, shard-local rule, per-tensor param
    all-gathers the scheduler overlaps with the remaining update compute
    (stage 2 = the same program; the grads only ever materialize
    scattered).  ``zero_stage>=3`` additionally shards the params
    themselves ('sharding' axis, FSDP).  ``master_weights=True`` keeps
    an f32 master copy of every floating param sharded alongside the
    moments (classic multi-precision; params may then be bf16) — the
    all-gather ships the CAST param, so master mode gathers bf16 bytes.

    This one function subsumes: EagerReducer fused allreduce (DP), sharding
    stage-1/2 (optimizer state + grads live sharded — XLA keeps them
    reduce-scattered), stage-3/FSDP (zero_stage=3 shards params too), TP
    (rule specs), and — when the mesh has a 'pp' axis — 1F1B pipeline
    parallelism composed INSIDE the same program (the reference's 4-D
    hybrid: ``fleet_base.py:381-408`` topology + ``pipeline_parallel.py:
    82-152`` schedule + ``hybrid_parallel_optimizer.py:172`` grad sync; the
    dp/sharding grad psum and the TP collectives stay GSPMD-managed while
    'pp' runs manual ppermute ticks via ``pipeline_apply``).
    Ref: SURVEY §2.4 table.

    The pp path requires the model to implement ``pipeline_stage_spec()``
    (see ``models/gpt.py``); ``pp_microbatches`` sets the microbatch count
    (default: the pp degree).

    ``zero_offload=True`` (with an active ZeRO axis) keeps the moments
    (+ f32 masters) in host RAM: the step splits into a grads-only
    device program (forward + backward + the replicated global clip —
    bit-identical preamble to the resident path) and a per-tensor
    streamed update through ``parallel.offload.ZeroOffloadUpdater``
    (h2d → the SAME per-tensor pinned update body → d2h, ``offload_depth``
    tensors in flight).  Opt-state HBM ~0; update math bit-exact vs the
    resident ZeRO step; tokens/s pays the stream (docs/PARALLELISM.md).

    ``grad_overlap=True`` (with an active ZeRO axis; composes with
    ``zero_offload``) pins every gradient to
    its moment sharding IMMEDIATELY after the backward — per-tensor
    reduce-scatters the scheduler can overlap with the remaining
    backward — and computes the global clip norm on the scattered
    shards (reassociated, series-tolerance vs the default
    clip-then-scatter order which stays bit-exact vs replicated).
    """
    from ..nn.layer import functional_call

    # zero_stage=None (the default) means "stage 1 where the mesh allows
    # it"; an explicit value is remembered so an inert ask can warn below
    zero_explicit = zero_stage is not None
    zero_stage = 1 if zero_stage is None else int(zero_stage)

    pp_degree = mesh.shape.get("pp", 1)
    sp_degree = mesh.shape.get("sp", 1)
    if sp_degree > 1:
        # sequence parallelism composed into the one-program step: every
        # sp-capable attention switches to the ring/ulysses schedule
        # (parallel/sequence.py) and the batch's seq dim shards on 'sp'
        # (SURVEY §5.7 — capability beyond the reference).  Model-agnostic:
        # the generic walker flips any attention carrying
        # supports_sequence_parallel; a model-level method (GPT keeps one
        # for API compatibility) takes precedence.
        from .sequence import enable_sequence_parallel as _enable_sp
        if hasattr(model, "enable_sequence_parallel"):
            model.enable_sequence_parallel("sp", mesh=mesh, mode=sp_mode)
        else:
            _enable_sp(model, "sp", mesh=mesh, mode=sp_mode)
    else:
        # a previous sp step may have switched the model's attention to
        # the ring schedule — a non-sp mesh must not inherit it
        from .sequence import disable_sequence_parallel as _disable_sp
        if hasattr(model, "disable_sequence_parallel"):
            model.disable_sequence_parallel()
        elif hasattr(model, "sublayers"):
            _disable_sp(model)
    if param_dtype is not None:
        for _, p in model.named_parameters():
            if jnp.issubdtype(p._value.dtype, jnp.floating):
                p._set_value(p._value.astype(param_dtype))

    from .sharding import _shard_spec_for

    pp_spec = None
    stacked_rel_keys = ()
    if pp_degree > 1:
        if loss_fn is not None:
            raise ValueError(
                "a custom loss_fn cannot be combined with a 'pp' mesh axis; "
                "the pipeline schedule owns the forward decomposition")
        if not hasattr(model, "pipeline_stage_spec"):
            raise ValueError(
                f"{type(model).__name__} does not implement "
                "pipeline_stage_spec(); required for a 'pp' mesh axis")
        pp_spec = model.pipeline_stage_spec()
        n_layers = pp_spec["num_layers"]
        if n_layers % pp_degree:
            raise ValueError(
                f"num_layers={n_layers} must divide evenly over "
                f"pp={pp_degree} stages")
        prefix = pp_spec["block_prefix"]
        other, stacked = stack_block_params(model, mesh, rule, prefix,
                                            n_layers, zero_stage)
        params = dict(other)
        for rel, arr in stacked.items():
            params[prefix + "$stacked." + rel] = arr
        stacked_rel_keys = tuple(sorted(stacked))
        # rebind the live model's tensors to the placed (non-stacked) arrays
        for k, p in model.named_parameters():
            if k in params:
                p._set_value(params[k])
    else:
        shard_params(model, mesh, rule, zero_stage)
        params = {k: p._value for k, p in model.named_parameters()}
    _, buffers = model.functional_state()

    # the ZeRO data axis: the dedicated 'sharding' axis when present,
    # else 'dp' (the reference's sharding_optimizer partitions over the
    # dp ring when no separate sharding ring exists) — a dp-only mesh no
    # longer replicates the moments.  An EXPLICIT zero_stage>=1 on a mesh
    # with no data axis warns — keeping dp full copies after an explicit
    # ask must never be silent (same rule as Engine/Model.fit)
    from ..optimizer.optimizer import StackedParameter
    from .sharding import (ZeroShardInfo, observe_opt_state_bytes,
                           place_zero_state, zero_data_axis)
    zaxis = zero_data_axis(mesh)
    zero_on = zero_stage >= 1 and zaxis is not None
    if zero_explicit and zero_stage >= 1 and zaxis is None:
        import warnings
        warnings.warn(
            f"make_sharded_train_step(zero_stage={zero_stage}) on a mesh "
            f"with no >1 'sharding'/'dp' axis ({dict(mesh.shape)}); "
            "optimizer state stays REPLICATED", RuntimeWarning,
            stacklevel=2)
    offload_on = bool(zero_offload) and zero_on
    if zero_offload and not zero_on:
        import warnings
        warnings.warn(
            "make_sharded_train_step(zero_offload=True) needs an active "
            "ZeRO axis (zero_stage>=1 on a mesh with a >1 'sharding'/'dp' "
            "axis); optimizer state stays device-resident", RuntimeWarning,
            stacklevel=2)
    if grad_overlap and not zero_on:
        grad_overlap = False  # nothing to scatter onto — inert

    default_loss = loss_fn is None and pp_degree == 1
    if pp_degree > 1:
        loss_fn = _make_pipeline_loss(
            mesh, pp_spec, pp_degree,
            pp_microbatches or pp_degree, stacked_rel_keys)
    elif loss_fn is None:
        def loss_fn(model, params, buffers, batch, rng):
            ids, labels = batch
            from ..core import random as core_random
            with core_random.rng_scope(rng):
                logits = functional_call(model, params, (Tensor(ids),),
                                         buffers={k: v for k, v in buffers.items()})
            from ..nn.functional.loss import fused_softmax_ce_rows
            lg = logits._value if isinstance(logits, Tensor) else logits
            with jax.named_scope("ce"):
                loss = jnp.mean(fused_softmax_ce_rows(lg, labels))
            # the layers' auxiliary losses (the capacity MoE's load
            # balance, ref moe/grad_clip.py context + GShard; the sparse
            # attention's indexer KL): each forward left this trace's value
            # on its layer
            from .moe import collect_moe_aux
            aux = collect_moe_aux(model)
            if aux is not None:
                loss = loss + aux
            return loss

    opt = _resolve_optimizer(optimizer, optimizer_kwargs, learning_rate,
                             moment_dtype, grad_clip_norm,
                             list(model.parameters()))
    # what optimizer/ is told of each leaf: the model's own Parameter, or
    # for a pp-stacked (L, ...) block layer 0's, marked as stacked
    param_tensors = dict(model.named_parameters())
    order = sorted(params)
    stacked_head = pp_spec["block_prefix"] + "$stacked." if pp_spec else None

    def _meta(k):
        if stacked_head and k.startswith(stacked_head):
            return StackedParameter(
                param_tensors[pp_spec["block_prefix"] + "0."
                              + k[len(stacked_head):]], params[k])
        return param_tensors[k]

    plist = [_meta(k) for k in order]
    # the update's placement: every slot extends its parameter's own spec
    # (TP dims, 'pp' on a stacked leaf) by the ZeRO axis; with no ZeRO
    # axis it is the parameter's own spec, which also carries the master
    # slot.  The update takes the pinned path only when there is
    # something to pin.
    si = ZeroShardInfo(
        mesh=mesh, axis=zaxis if zero_on else None, stage=zero_stage,
        master_weights=bool(master_weights)).with_param_specs([
            tuple(params[k].sharding.spec) + (None,) * (
                params[k].ndim - len(params[k].sharding.spec))
            for k in order])
    update_si = si if (zero_on or master_weights) else None
    if offload_on:
        # moments (+ f32 masters) parked in host RAM; the step splits into
        # a grads-only device program and the streamed per-tensor update
        from .offload import ZeroOffloadUpdater
        updater = ZeroOffloadUpdater.for_optimizer(
            opt, plist, si, depth=offload_depth,
            site="parallel.zero_offload")
        slots = ZeroOffloadUpdater.host_state_for_optimizer(opt, plist, si)
    else:
        slots = place_zero_state(
            si, [params[k] for k in order],
            [opt._init_accumulators(p) for p in plist])
    # the state's slot names are the short ones (_SLOT_NAMES); optimizer/
    # reads and writes its own, renamed at trace time
    full = {_SLOT_NAMES.get(s, s): s for s in slots[0]}

    def _short(st):
        return {_SLOT_NAMES.get(s, s): v for s, v in st.items()}

    def _full(st):
        return {full.get(s, s): v for s, v in st.items()}

    opt_state = dict(zip(order, map(_short, slots)))
    if offload_on:
        observe_opt_state_bytes("sharded_step", {}, host_tree=opt_state)
    else:
        observe_opt_state_bytes("sharded_step", opt_state)
    # placed like the jitted step returns it: an unplaced counter made
    # call 2 see a different argument sharding and compile the whole
    # step a second time (the program observatory's first finding)
    step_no = jax.device_put(jnp.zeros((), jnp.int32),
                             NamedSharding(mesh, P()))

    # the default loss traces the model's forward in this frame, so what
    # its layers left on themselves (their counters) can leave the program
    # beside the loss; a custom or pipelined loss owns its forward, and
    # what it traced inside may not escape it
    counters_of = collect_layer_counters if default_loss else \
        (lambda _model: {})

    def loss_of(batch, rng):
        def pure_loss(p):
            loss = loss_fn(model, p, buffers, batch, rng)
            return loss, counters_of(model)

        if recompute:
            # remat the whole forward (ref recompute meta-optimizer /
            # auto_parallel_recompute pass) — XLA re-runs it in backward.
            from .recompute import jit_recompute
            pure_loss = jit_recompute(pure_loss, policy=recompute_policy)
        return pure_loss

    # Both programs differentiate from their OWN frame and hand the shared
    # body the result, instead of letting it call back into the forward:
    # on the chip's host every Python frame between the jit and the model's
    # forward cost the trace of a 24-layer step 0.3 s and more (PERF.md §6,
    # PR 30: three frames, +0.85 to +3.0 s of set-up).

    def train_step(params, opt_state, step_no, batch, rng, lr):
        (loss, counters), grads = jax.value_and_grad(
            loss_of(batch, rng), has_aux=True)(params)
        # the shared body: [overlap pins] -> clip -> update
        body = make_functional_train_step(
            opt, plist, order, lambda *_: (loss, grads),
            shard_info=update_si, grad_overlap=grad_overlap)
        new_params, new_states, new_step, loss = body(
            params, [_full(opt_state[k]) for k in order], step_no, lr, batch)
        return (new_params, dict(zip(order, map(_short, new_states))),
                new_step, loss, counters)

    def grads_step(params, step_no, batch, rng, lr):
        # offload's device half: forward + backward + the grad preamble on
        # the replicated gradients (the resident ZeRO step's own), no update
        (loss, _), grads = jax.value_and_grad(
            loss_of(batch, rng), has_aux=True)(params)
        gs = [grads[k] for k in order]
        if grad_overlap:
            gs = [jax.lax.with_sharding_constraint(g, sh)
                  for g, sh in zip(gs, updater.state_shardings)]
        gs = opt.preprocess_grads_offload(
            [params[k] for k in order], gs,
            master_weights=bool(master_weights))
        return loss, gs, step_no + 1

    bspec = batch_spec(mesh)
    if sp_degree > 1:
        # (batch, seq): seq dim additionally sharded over 'sp'
        bspec = P(bspec[0] if len(bspec) else None, "sp")
    param_sh = jax.tree.map(lambda a: a.sharding, params)
    # offload: the opt state is host numpy — it has no device shardings
    # and never enters the device program
    opt_sh = None if offload_on else jax.tree.map(
        lambda a: a.sharding, opt_state)
    scalar_sh = NamedSharding(mesh, P())

    def _make_jitted(batch_sh):
        # instrument_jit: trace+compile events (count + wall time) land in
        # jit_builds_total{site=parallel.sharded_train_step} — a step that
        # silently recompiles mid-run shows up in telemetry, not just as a
        # mystery stall
        site = "parallel.sharded_train_step"
        if not offload_on:
            from ..observability.sanitizers import sanitize_donation
            return sanitize_donation(_obs.instrument_jit(jax.jit(
                train_step,
                donate_argnums=(0, 1, 2),
                in_shardings=(param_sh, opt_sh, scalar_sh, batch_sh, None,
                              None),
                # pin output shardings to the input layout — without this
                # XLA may pick a different layout for the updated params,
                # forcing a re-jit (and a second full compile) on the next
                # step.
                out_shardings=(param_sh, opt_sh, scalar_sh, scalar_sh,
                               scalar_sh),
            ), site=site), donate_argnums=(0, 1, 2), site=site)
        grads_jitted = _obs.instrument_jit(jax.jit(
            grads_step,
            in_shardings=(param_sh, scalar_sh, batch_sh, None, None),
            out_shardings=(scalar_sh, [param_sh[k] for k in order],
                           scalar_sh)), site=site)

        def streamed(params, opt_state, step_no, batch, rng, lr):
            loss, gs, t = grads_jitted(params, step_no, batch, rng, lr)
            new_vals, new_host = updater.apply(
                [params[k] for k in order], gs,
                [_full(opt_state[k]) for k in order], lr, t)
            return (dict(zip(order, new_vals)),
                    dict(zip(order, map(_short, new_host))), t, loss, {})

        streamed._jit_fn = grads_jitted._jit_fn
        return streamed

    jitted = _make_jitted(
        (NamedSharding(mesh, bspec), NamedSharding(mesh, bspec)))

    # Batch elements may be pytrees (e.g. (ids, masked_positions) feeding a
    # custom loss_fn — the reference's pretraining-heads contract passes the
    # masked indices as data, auto_parallel_gpt_model.py:929).  Each leaf
    # shards on the data axes truncated to its rank; structure-keyed cache.
    _jit_cache = {}

    def _get_jitted(batch):
        leaves, treedef = jax.tree.flatten(batch)
        key = (treedef, tuple(l.ndim for l in leaves))
        if key not in _jit_cache:
            bsh = jax.tree.unflatten(treedef, [
                NamedSharding(mesh, P(*tuple(bspec)[:l.ndim]))
                for l in leaves])
            _jit_cache[key] = _make_jitted(bsh)
        return _jit_cache[key]

    state = {"params": params, "opt_state": opt_state, "step": step_no}
    host_steps = itertools.count()

    def step(state, ids, labels, rng, lr=None):
        # lr is a dynamic scalar: schedules (PipelineParallel.train_batch
        # passes the optimizer's current lr) never trigger a recompile
        if sp_degree > 1:
            # validate every ≥2-D batch leaf (the batch slots may be
            # pytrees), keeping the clear error instead of a deep GSPMD one
            for leaf in jax.tree.leaves((ids, labels)):
                if getattr(leaf, "ndim", 0) >= 2 and \
                        leaf.shape[1] % sp_degree:
                    raise ValueError(
                        f"sequence length {leaf.shape[1]} must divide "
                        f"evenly over the 'sp' axis (degree {sp_degree})")
        # on the profiler's clock, for whoever records a session: one
        # "train_step" event per call, numbered by the host's own count
        # (the device's counter would be a read), and under it the two
        # things this function does on the host
        with jax.profiler.StepTraceAnnotation("train_step",
                                              step_num=next(host_steps)):
            lr_now = jnp.float32(opt.get_lr() if lr is None else lr)
            fn = jitted if (hasattr(ids, "ndim")
                            and hasattr(labels, "ndim")) \
                else _get_jitted((ids, labels))
            # partial-manual shard_map (the pp pipeline) requires the
            # ambient mesh at trace time (_smap.run_shard_map); harmless
            # otherwise
            with _tracing.span("train.dispatch"), _set_mesh(mesh):
                new_params, new_opt, new_step, loss, counters = fn(
                    state["params"], state["opt_state"], state["step"],
                    (ids, labels), rng, lr_now)
            if counters:
                # device values, not read here: the observatory keeps the
                # newest step's and reads them when asked
                from ..observability.programs import get_program_registry
                get_program_registry().note_counters(
                    "parallel.sharded_train_step", counters)
            # The old param buffers were donated; rebind the live model's
            # tensors to the updated arrays so the Layer stays usable
            # (eval, jit.save, checkpointing) throughout training.  Stacked
            # pp block params are NOT unstacked per step (that would gather
            # across the pp axis every iteration) — call
            # step.sync_model(state) before eval/save.
            with _tracing.span("train.rebind"):
                for k, v in new_params.items():
                    t = param_tensors.get(k)
                    if t is not None:
                        t._set_value(v)
        return ({"params": new_params, "opt_state": new_opt,
                 "step": new_step}, loss)

    def sync_model(state):
        """Write the (possibly pp-stacked) state back into the live model."""
        for k, v in state["params"].items():
            t = param_tensors.get(k)
            if t is not None:
                t._set_value(v)
                continue
            if pp_spec is not None:
                prefix = pp_spec["block_prefix"]
                rel = k[len(prefix) + len("$stacked."):]
                for i in range(pp_spec["num_layers"]):
                    param_tensors[f"{prefix}{i}.{rel}"]._set_value(v[i])

    # exposed for AOT lowering / HLO inspection (the RAW jit function —
    # the instrumentation wrapper has no .lower); under zero_offload it is
    # the grads-only program, (params, step, batch, rng, lr)
    step._jitted = jitted._jit_fn
    step.sync_model = sync_model
    return step, state
