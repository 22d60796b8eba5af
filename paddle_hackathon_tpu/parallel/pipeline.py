"""Pipeline parallelism as a single SPMD program.

Ref ``python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py``:
``PipelineParallel.forward_backward_pipeline`` (:82-152) runs a 1F1B
schedule with explicit p2p send/recv between per-stage processes
(``pp_utils/p2p_communication.py:276``), microbatches = accumulate_steps,
and ``PipelineLayer`` (``parallel_layers/pp_layers.py:162``) segments a
layer list across stages.

TPU-native design (single-controller SPMD — there is no per-stage process
to run a 1F1B loop in): the whole pipeline is ONE jitted program over the
'pp' mesh axis. Stage weights live sharded on 'pp' (leading stage dim);
a ``lax.scan`` over ``n_micro + n_stages - 1`` ticks runs every stage in
lockstep, handing activations to the next stage with ``ppermute`` — the
collective-permute schedule SURVEY §7 prescribes. ``jax.grad`` through the
scan + ppermute yields the reverse pipeline automatically (the backward
bubble mirrors the forward one), and XLA's latency-hiding scheduler
overlaps the permute transfers with stage compute — the role of the
reference's dedicated comm streams. Other mesh axes (dp/mp/sharding) stay
GSPMD-managed via ``shard_map(..., auto=...)``, so PP composes with
TP/DP/ZeRO exactly like the reference's 4-D hybrid.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..nn.container import LayerList
from ..nn.layer import Layer


def num_stages(mesh: Mesh) -> int:
    return mesh.shape.get("pp", 1)


def pipeline_apply(block_fn: Callable, stage_params: Any, x_mb: jax.Array,
                   mesh: Mesh, extra: Any = None, seq_axis: str = None,
                   with_aux: bool = False):
    """Run microbatches through ``n_stages`` sequential stage applications.

    Args:
      block_fn: ``(params_slice, x, extra) -> y`` — one stage's compute.
        ``params_slice`` leaves have leading dim ``layers_per_stage`` (the
        stage's chunk of the stacked layer params); ``x`` and ``y`` must have
        identical shape/dtype (transformer-block invariant).
      stage_params: pytree whose leaves are stacked over stages on dim 0
        (total leading dim = n_stages * layers_per_stage), sharded P('pp').
      x_mb: (n_micro, mb, ...) microbatched stage-0 input, replicated on pp.
      extra: per-microbatch side input pytree, leaves (n_micro, ...), passed
        to every stage (e.g. position ids); replicated on pp.
      seq_axis: sp x pp composition — name of a mesh axis sharding x_mb's
        dim 2 (the sequence). The region goes manual over BOTH axes (Shardy
        forbids nesting a second shard_map on the same mesh), and the ring
        attention inside block_fn detects the already-manual axis and runs
        its per-device body directly (``_smap.active_manual_axes``).
      with_aux: ``block_fn`` returns ``(y, aux_scalar)`` (e.g. the MoE
        load-balance loss); aux sums over every VALID (stage, microbatch)
        pair — warmup/cooldown ticks process clamped garbage microbatches
        and are masked out — and psums over 'pp'.

    Returns (n_micro, mb, ...) last-stage outputs, replicated over 'pp';
    with ``with_aux``, a ``(outputs, aux_total)`` tuple.
    """
    n_stages_ = num_stages(mesh)
    n_micro = x_mb.shape[0]

    if n_stages_ == 1:
        if extra is not None:
            out = jax.vmap(
                lambda x, e: block_fn(stage_params, x, e))(x_mb, extra)
        else:
            out = jax.vmap(lambda x: block_fn(stage_params, x, None))(x_mb)
        if with_aux:
            y, aux = out
            return y, jnp.sum(aux)
        return out

    manual = {"pp"}
    x_spec = P()
    if seq_axis is not None and mesh.shape.get(seq_axis, 1) > 1:
        manual.add(seq_axis)
        x_spec = P(None, None, seq_axis)

    def spmd(params, xs, ex):
        # params leaves: (layers_per_stage, ...) local slice
        from ._smap import manual_axes_scope
        stage = jax.lax.axis_index("pp")
        is_first = stage == 0
        is_last = stage == n_stages_ - 1
        perm = [(i, (i + 1) % n_stages_) for i in range(n_stages_)]

        zero_state = jnp.zeros(xs.shape[1:], xs.dtype)
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            recv, outputs, aux_acc = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
            state = jnp.where(is_first, x_in, recv)
            e_t = None
            if ex is not None:
                # stage s at tick t is processing microbatch t - s
                my_mb = jnp.clip(t - stage, 0, n_micro - 1)
                e_t = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, my_mb, 0, keepdims=False), ex)
            if with_aux:
                y, aux = block_fn(params, state, e_t)
                valid = (t >= stage) & (t - stage < n_micro)
                aux_acc = aux_acc + jnp.where(
                    valid, aux.astype(jnp.float32), 0.0)
            else:
                y = block_fn(params, state, e_t)
            out_idx = t - (n_stages_ - 1)
            idx = jnp.maximum(out_idx, 0)
            cur = jax.lax.dynamic_index_in_dim(outputs, idx, 0,
                                               keepdims=False)
            newval = jnp.where(out_idx >= 0, y, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, newval, idx, 0)
            send = jax.lax.ppermute(y, "pp", perm)
            return (send, outputs, aux_acc), None

        with manual_axes_scope(manual):
            (_, outputs, aux_acc), _ = jax.lax.scan(
                tick, (zero_state, outputs, jnp.zeros((), jnp.float32)),
                jnp.arange(n_micro + n_stages_ - 1))
        # only the last stage holds real outputs — replicate over pp
        mask = jnp.where(is_last, 1.0, 0.0).astype(outputs.dtype)
        out = jax.lax.psum(outputs * mask, "pp")
        if with_aux:
            aux = jax.lax.psum(aux_acc, "pp")
            if len(manual) > 1:       # sp also manual: aux is per-chunk
                aux = jax.lax.pmean(aux, tuple(a for a in manual
                                               if a != "pp"))
            return out, aux
        return out

    from ._smap import run_shard_map
    return run_shard_map(
        spmd, mesh,
        in_specs=(jax.tree.map(lambda _: P("pp"), stage_params),
                  x_spec, jax.tree.map(lambda _: P(), extra)
                  if extra is not None else P()),
        out_specs=(x_spec, P()) if with_aux else x_spec,
        manual_axes=manual,
        args=(stage_params, x_mb, extra),
        # spmd is rebuilt per call; everything it closes over is here
        # (shapes are jit's problem, specs are in run_shard_map's key)
        cache_key=("pipeline_apply", block_fn, n_stages_, n_micro,
                   with_aux))


def pipeline_decode_apply(layer_step: Callable, stacked: Any, caches: Any,
                          x: jax.Array, pos, mesh: Mesh):
    """Pipelined layer application for autoregressive decode.

    Decode is latency-bound and stateful (KV caches), so the 1F1B
    microbatch schedule of :func:`pipeline_apply` does not apply; instead
    each token (or prefill chunk) crosses the stages SEQUENTIALLY: every
    tick all stages run their layer chunk on their current activation,
    the activation ppermutes forward, and only the stage whose tick it is
    commits its cache updates (masked select — the idle-stage compute is
    the inherent single-stream pipeline bubble; multi-request interleaving
    would fill it). Ref: the reference serves pipelined models through
    per-stage processes in ``DistModel`` (``dist_model.cc``); here the
    whole pipeline is ONE SPMD program.

    Args:
      layer_step: ``(layer_params, cache, x, pos) -> (y, new_cache)`` —
        one layer with its KV cache (x/y same shape).
      stacked: pytree, leaves (L, ...) stacked over layers, sharded P('pp').
      caches: pytree, leaves (L, ...) per-layer cache state, sharded P('pp').
      x: (b, s, h) stage-0 input, replicated over 'pp'.
      pos: () int32 cache write position.
    Returns (y, new_caches) with y replicated over 'pp'.
    """
    n = num_stages(mesh)

    def chunk(st, cl, xc0, posv):
        def body(xc, inp):
            lp, c = inp
            y, nc = layer_step(lp, c, xc, posv)
            return y, nc
        return jax.lax.scan(body, xc0, (st, cl))

    if n == 1:
        return chunk(stacked, caches, x, pos)

    def spmd(st_local, c_local, xv, posv):
        stage = jax.lax.axis_index("pp")
        perm = [(i, (i + 1) % n) for i in range(n)]
        for t in range(n):
            y, nc = chunk(st_local, c_local, xv, posv)
            sel = stage == t
            c_local = jax.tree.map(
                lambda new, old: jnp.where(sel, new, old), nc, c_local)
            # send my output forward; only stage t's is meaningful, and
            # exactly stage t+1 consumes what it receives next tick
            xv = jax.lax.ppermute(y, "pp", perm)
        # after the last permute stage 0 holds stage n-1's output
        out = jax.lax.psum(
            jnp.where(stage == 0, xv, jnp.zeros_like(xv)), "pp")
        return out, c_local

    from ._smap import run_shard_map
    return run_shard_map(
        spmd, mesh,
        in_specs=(jax.tree.map(lambda _: P("pp"), stacked),
                  jax.tree.map(lambda _: P("pp"), caches), P(), P()),
        out_specs=(P(), jax.tree.map(lambda _: P("pp"), caches)),
        manual_axes={"pp"},
        args=(stacked, caches, x, pos),
        # per-decode-step call site: without the key every token paid a
        # fresh trace+compile of the whole pipelined program
        cache_key=("pipeline_decode", layer_step, n))


class LayerDesc:
    """Deferred layer construction for stage segmentation
    (ref ``parallel_layers/pp_layers.py:120`` ``LayerDesc``)."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args, self.kwargs = args, kwargs

    def build(self):
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """Ref ``pp_layers.py:77`` — weight shared across stages (e.g. tied
    embedding/head). Descs with the same ``key`` resolve to ONE module
    instance; later occurrences apply ``forward_func(module, x)`` instead
    of the module's own forward (the reference's shared-weight pattern).
    In SPMD the tied weight simply lives replicated on 'pp'; the
    grad-allreduce the reference does by hand
    (``pipeline_parallel.py:149``) falls out of AD."""

    def __init__(self, key, layer_cls, *args, forward_func=None, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.key = key
        self.forward_func = forward_func


def _structure_sig(mod) -> tuple:
    """Structural identity of a layer: class + (name, shape, dtype) of every
    parameter. Two layers with equal signatures can be stacked into one
    leading-dim array (the pipeline_apply layout)."""
    return (type(mod), tuple(
        (k, tuple(p.shape), str(p._value.dtype))
        for k, p in sorted(mod.named_parameters(), key=lambda kv: kv[0])))


def _apply_positions(positions, params, buffers, x):
    """Run ``x`` through [(prefix, module, fwd)] sequentially, with each
    module's state substituted from the flat ``params``/``buffers`` dicts
    at its owner prefix (tied/shared modules read their first-occurrence
    prefix, so the traced value — and its gradient — flows to every use)."""
    import jax as _jax

    from ..core import autograd as _autograd
    from ..core.tensor import Tensor as _T
    from ..nn.layer import functional_call

    for prefix, mod, fwd in positions:
        sub = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        subbuf = {k[len(prefix):]: v for k, v in (buffers or {}).items()
                  if k.startswith(prefix)} or None
        if fwd is None:
            x = functional_call(mod, sub, (_T(x),), buffers=subbuf)
        else:
            # forward_func positions (shared-weight reuse) substitute the
            # owner's state by hand — functional_call has no custom-forward
            # hook
            with mod._swap_state(sub, subbuf), _autograd.no_grad():
                out = fwd(mod, _T(x))
            x = _jax.tree.map(
                lambda t: t._value if isinstance(t, _T) else t, out,
                is_leaf=lambda t: isinstance(t, _T))
    return x


class PipelineLayer(Layer):
    """Segment ANY layer list across pipeline stages — the framework-level
    counterpart of the reference's ``PipelineLayer``
    (``parallel_layers/pp_layers.py:162``), which turns a ``LayerDesc`` list
    into per-stage submodels. Here the same desc list is partitioned into

    - ``pre``:    layers before the homogeneous block run (replicated on 'pp')
    - ``blocks``: the maximal contiguous run of structurally-identical layers
                  (stacked on a leading layer dim, sharded over 'pp')
    - ``post``:   layers after the run (replicated on 'pp')

    and :meth:`pipeline_stage_spec` derives ``block_prefix``/``pre_fn``/
    ``layer_fn``/``post_fn`` automatically, so ``make_sharded_train_step``
    composes the model with dp/mp/sharding exactly like the hand-written
    GPT spec (``models/gpt.py``). ``SharedLayerDesc`` entries with one key
    build ONE module (tied weights, e.g. embedding + LM head); the tied
    gradient contribution from every use site falls out of AD because all
    sites read the same traced parameter.

    ``loss_fn(outputs, labels) -> scalar`` (on jnp arrays) closes the
    training objective; :meth:`make_loss_fn` exposes the equivalent
    non-pipelined loss for single-device parity and pp=1 meshes.

    Constraints (checked): at least 2 structurally-identical contiguous
    layers; block layers must be plain ``LayerDesc`` (not shared); blocks
    must map ``x -> same shape/dtype x`` (transformer invariant). Dropout
    inside pre/blocks is RNG-keyed by the train step; dropout in ``post``
    is not supported under pp (keep heads deterministic, as in GPT/BERT).
    """

    def __init__(self, layers, loss_fn=None, aux_weight: float = 0.01):
        super().__init__()
        self._aux_weight = aux_weight
        entries = []           # (module, fwd, is_new, shareable)
        shared_mods = {}
        for d in layers:
            if isinstance(d, SharedLayerDesc):
                # explicit membership test — keying reuse on module
                # TRUTHINESS would rebuild (and silently untie) any
                # shared module whose class defines a zero __len__
                is_new = d.key not in shared_mods
                if is_new:
                    shared_mods[d.key] = d.build()
                mod = shared_mods[d.key]
                entries.append((mod, d.forward_func, is_new, True))
            elif isinstance(d, LayerDesc):
                entries.append((d.build(), None, True, False))
            elif isinstance(d, Layer):
                entries.append((d, None, True, False))
            else:
                raise TypeError(
                    f"PipelineLayer entries must be LayerDesc/SharedLayerDesc"
                    f"/Layer, got {type(d).__name__}")

        # maximal contiguous run of stackable (plain, structurally equal)
        # layers = the pipelined block stack
        sigs = [None if (fwd is not None or shared or not new)
                else _structure_sig(mod)
                for mod, fwd, new, shared in entries]
        best = (0, 0)          # (length, start)
        i = 0
        while i < len(entries):
            if sigs[i] is None:
                i += 1
                continue
            j = i
            while j < len(entries) and sigs[j] == sigs[i]:
                j += 1
            if j - i > best[0]:
                best = (j - i, i)
            i = j
        run_len, run_start = best
        if run_len < 2:
            raise ValueError(
                "PipelineLayer found no contiguous run of >=2 structurally-"
                "identical layers to segment across stages — pipeline "
                "parallelism needs a homogeneous block stack")
        run_end = run_start + run_len

        pre_mods, block_mods, post_mods = [], [], []
        owner_prefix = {}       # id(module) -> registered prefix
        self._positions = []    # (prefix, module, fwd)
        for idx, (mod, fwd, is_new, _) in enumerate(entries):
            if run_start <= idx < run_end:
                block_mods.append(mod)
                prefix = f"blocks.{len(block_mods) - 1}."
                owner_prefix[id(mod)] = prefix
            elif is_new:
                seg, lst = (("pre", pre_mods) if idx < run_end
                            else ("post", post_mods))
                lst.append(mod)
                prefix = f"{seg}.{len(lst) - 1}."
                owner_prefix[id(mod)] = prefix
            else:
                prefix = owner_prefix[id(mod)]   # shared reuse
            self._positions.append((prefix, mod, fwd))
        self._run_bounds = (run_start, run_end)
        self.pre = LayerList(pre_mods)
        self.blocks = LayerList(block_mods)
        self.post = LayerList(post_mods)
        self._loss_fn = loss_fn

    def forward(self, x):
        for _, mod, fwd in self._positions:
            x = mod(x) if fwd is None else fwd(mod, x)
        return x

    def loss(self, x, labels):
        if self._loss_fn is None:
            raise ValueError("PipelineLayer was built without a loss_fn")
        from ..core.tensor import Tensor
        out = self.forward(x)
        out = out._value if isinstance(out, Tensor) else out
        labels = labels._value if isinstance(labels, Tensor) else labels
        return Tensor(self._loss_fn(out, labels))

    def make_loss_fn(self):
        """Non-pipelined loss with ``make_sharded_train_step``'s
        ``loss_fn(model, params, buffers, batch, rng)`` signature — the
        single-device / pp=1 counterpart of the pipelined objective (used
        by the parity tests; numerics match the pp path exactly when
        dropout is off — the MoE aux term here is the full-batch
        estimator vs the pp path's per-microbatch mean)."""
        if self._loss_fn is None:
            raise ValueError("PipelineLayer was built without a loss_fn")
        positions, user_loss = self._positions, self._loss_fn
        aux_w = self._aux_weight
        from ..core import random as core_random

        def loss_fn(model, params, buffers, batch, rng):
            from .moe import collect_moe_aux
            ids, labels = batch
            with core_random.rng_scope(rng):
                y = _apply_positions(positions, params, buffers, ids)
            loss = user_loss(y, labels)
            aux = collect_moe_aux(model, weight=aux_w)
            if aux is not None:
                loss = loss + aux
            return loss

        return loss_fn

    def pipeline_stage_spec(self) -> dict:
        """The pp decomposition ``make_sharded_train_step`` consumes —
        derived from the desc list instead of hand-written per model
        (ref ``pp_layers.py:162`` segmentation)."""
        if self._loss_fn is None:
            raise ValueError(
                "PipelineLayer needs a loss_fn to build the pipeline "
                "objective (post_fn returns the scalar loss)")
        run_start, run_end = self._run_bounds
        pre_pos = self._positions[:run_start]
        post_pos = self._positions[run_end:]
        template = self.blocks[0]
        user_loss = self._loss_fn
        _, captured_buffers = self.functional_state()
        from ..core import random as core_random
        from ..core.tensor import Tensor
        from ..nn.layer import functional_call

        def pre_fn(params, buffers, ids, key):
            with core_random.rng_scope(key):
                return _apply_positions(pre_pos, params,
                                        buffers or captured_buffers, ids)

        # blocks carrying an l_aux side channel (MoE layers) feed the
        # pipeline's aux accumulator, weighted as the loss takes it — the
        # channel cannot escape the stage scan by itself (same mechanism
        # as models/gpt.py)
        from .moe import collect_moe_aux
        aux_w = self._aux_weight
        has_aux = any(hasattr(m, "l_aux")
                      for m in template.sublayers(include_self=True))

        def layer_fn(layer_params, x):
            h = functional_call(template, layer_params, (Tensor(x),))
            if not has_aux:
                return h
            aux = collect_moe_aux(template, weight=aux_w)
            if aux is None:
                aux = jnp.zeros((), jnp.float32)
            return h, aux.astype(jnp.float32)

        # l_aux-bearing layers OUTSIDE the block run (pre/post segments)
        # run at trace level — their side channels are readable when
        # post_fn executes (same trace, no scan in between) and join the
        # objective with the full-batch estimator
        outer_mods = []
        for prefix, mod, _ in pre_pos + post_pos:
            if any(id(mod) == id(m) for m in outer_mods):
                continue
            outer_mods.append(mod)

        def post_fn(params, x, labels):
            y = _apply_positions(post_pos, params, captured_buffers, x)
            loss = user_loss(y, labels)
            for mod in outer_mods:
                aux = collect_moe_aux(mod, weight=aux_w)
                if aux is not None:
                    loss = loss + aux
            return loss

        return {"block_prefix": "blocks.",
                "num_layers": len(self.blocks),
                "pre_fn": pre_fn, "layer_fn": layer_fn, "post_fn": post_fn,
                "layer_aux": has_aux}


class PipelineParallel:
    """Model wrapper returned by ``fleet.distributed_model`` when the mesh
    has a 'pp' axis (ref ``meta_parallel/pipeline_parallel.py:31`` —
    same role and ``train_batch`` surface as the reference's wrapper).

    The 1F1B schedule, TP/DP/ZeRO composition and the optimizer update all
    live in ONE compiled SPMD program (``make_sharded_train_step``), built
    lazily on the first ``train_batch`` from the optimizer's lr and the
    strategy's pipeline/sharding configs (microbatches =
    ``pipeline_configs["accumulate_steps"]``, matching the reference)."""

    def __init__(self, model, mesh: Mesh, strategy=None, rule=None):
        self._model = model
        self._mesh = mesh
        self._strategy = strategy
        self._rule = rule
        self._step = None
        self._state = None

    def __getattr__(self, name):  # delegate everything else to the model
        return getattr(self._model, name)

    def __call__(self, *args, **kwargs):
        return self._model(*args, **kwargs)

    def train_batch(self, data, optimizer=None, lr_scheduler=None,
                    scaler=None):
        """Ref ``PipelineParallel.train_batch`` (``pipeline_parallel.py:154``):
        one full pipelined forward+backward+update; returns the loss."""
        if scaler is not None:
            raise NotImplementedError(
                "GradScaler is not supported in the pipelined train step — "
                "use bf16 params (no loss scaling needed on TPU) instead")
        from ..core import random as core_random
        from ..core.tensor import Tensor
        ids, labels = data
        ids = ids._value if isinstance(ids, Tensor) else jnp.asarray(ids)
        labels = (labels._value if isinstance(labels, Tensor)
                  else jnp.asarray(labels))
        if self._step is None:
            from .api import make_sharded_train_step
            from .mp_layers import sharding_rule_from_model
            n_micro = None
            zero = 0
            # the update rule is the user's optimizer, as it is: its class,
            # hyper-parameters, decay mask and grad_clip go into the one
            # compiled program.  A fleet wrapper (amp, gradient merge, ...)
            # wraps the eager step() this trainer does not run; its inner
            # optimizer is the rule
            opt = getattr(optimizer, "inner_optimizer", optimizer)
            if self._strategy is not None:
                n_micro = int(self._strategy.pipeline_configs.get(
                    "accumulate_steps", 0)) or None
                if self._strategy.sharding:
                    zero = int((self._strategy.sharding_configs or {}).get(
                        "stage", 1))
                if self._strategy.lamb or self._strategy.lars:
                    # the eager-optimizer swap of fleet.distributed_optimizer
                    # cannot reach inside this program, so a caller who
                    # skipped it gets the same swap here: one mapping from
                    # a strategy to a rule, fleet._swap_update_rule
                    from .. import optimizer as _optim
                    from .fleet import _swap_update_rule
                    if opt is None:
                        base = (_optim.Momentum if self._strategy.lars
                                else _optim.Adam)
                        opt = base(parameters=self._model.parameters())
                    opt = _swap_update_rule(opt, self._strategy)
            rule = self._rule or sharding_rule_from_model(self._model)
            self._step, self._state = make_sharded_train_step(
                self._model, self._mesh, rule=rule,
                zero_stage=zero, pp_microbatches=n_micro,
                optimizer="adam" if opt is None else opt)
        # lr read fresh every call: schedules stay live (the step takes lr
        # as a dynamic scalar, so this never recompiles); without an
        # optimizer, None lets the step use its own configured default
        lr = float(optimizer.get_lr()) if optimizer is not None else None
        self._state, loss = self._step(self._state, ids, labels,
                                       core_random.split_key(), lr=lr)
        if lr_scheduler is not None:
            lr_scheduler.step()
        return Tensor(loss)

    def sync_model(self):
        """Unstack the pipelined block params back into the live model."""
        if self._step is not None:
            self._step.sync_model(self._state)


def stack_layer_params(layers) -> dict:
    """Stack the parameters of N structurally-identical layers into single
    arrays with a leading layer dim — the layout ``pipeline_apply`` (and
    ``lax.scan`` over layers) consumes. Returns {param_name: (N, ...)}."""
    all_params = [dict(l.named_parameters()) for l in layers]
    keys = list(all_params[0].keys())
    return {k: jnp.stack([p[k]._value for p in all_params]) for k in keys}


def unstack_into_layers(layers, stacked: dict) -> None:
    """Write stacked (N, ...) arrays back into N layers' parameters."""
    for i, l in enumerate(layers):
        for k, p in dict(l.named_parameters()).items():
            p._set_value(stacked[k][i])
