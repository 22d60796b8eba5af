"""Compiled multi-step trainer behind ``Model.fit``.

The eager ``Model.train_batch`` re-dispatches the network op-by-op every
batch, runs the eager tape backward, and forces a device→host sync via
``float(loss)`` — per-step dispatch overhead the hardware never sees in
the hand-rolled jitted train step (``parallel/api.py
make_sharded_train_step``).  This trainer lifts the same design into the
high-level API:

- ONE jitted program per step covering forward + backward + the
  configured optimizer's functional update (``Optimizer.functional_update``),
  with the whole train state (params + accumulators + step counter)
  donated — in-place HBM update, zero copies;
- optional K-step unroll: K prefetched batches stack into a superbatch
  and a single ``lax.scan`` advances K steps per Python→device round trip
  (the step body comes from the shared builder
  ``parallel.api.make_functional_train_step``);
- losses stay device scalars; the fit loop fetches them only at
  ``log_freq`` boundaries and epoch end.

``Model.fit`` falls back transparently to the eager path when the
network/optimizer is not pure-functional-capable — see
``CompiledTrainer.unsupported_reason`` and the trace-failure handling in
``Model._run_compiled_epoch``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as core_random
from ..core.tensor import Tensor
from ..nn.layer import functional_call
from ..observability import metrics as _obs
from ..observability.sanitizers import sanitize_donation
from ..parallel.api import make_functional_train_step
from ..parallel.moe import collect_moe_aux


def has_moe_layers(network) -> bool:
    """Whether any sublayer carries the MoE aux side channel."""
    return any(hasattr(l, "l_aux")
               for l in network.sublayers(include_self=True))


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _mutating_layer_types():
    """Layer classes whose forward mutates registered buffers in training
    mode (running BN stats, spectral-norm power iterates) — state the
    functional trace cannot carry, so fit must stay eager for them."""
    from ..nn.layers.norm import SpectralNorm, _BatchNormBase
    return (_BatchNormBase, SpectralNorm)


def unsupported_reason(model, accumulate_grad_batches=1):
    """Why ``model`` cannot take the compiled fit path (None = it can).

    Cheap structural checks only; data-dependent Python control flow in
    ``forward`` is caught at first trace and falls back at runtime.
    """
    network, opt, loss = model.network, model._optimizer, model._loss
    if opt is None or loss is None:
        return "prepare() with an optimizer and a loss is required"
    if model._metrics:
        return ("metrics need per-step host outputs; the compiled path "
                "keeps losses on device")
    if accumulate_grad_batches != 1:
        return ("accumulate_grad_batches relies on the eager tape's "
                "update=False staging")
    if not (hasattr(opt, "functional_update")
            and hasattr(opt, "_parameter_list")):
        return (f"{type(opt).__name__} exposes no functional update rule")
    by_id = {id(p) for _, p in network.named_parameters()}
    if any(id(p) not in by_id for p in opt._parameter_list):
        return "optimizer holds parameters outside the fitted network"
    mutating = _mutating_layer_types()
    for layer in network.sublayers(include_self=True):
        if isinstance(layer, mutating):
            return (f"{type(layer).__name__} updates buffers in-place "
                    "during training (running stats)")
    return None


class CompiledTrainer:
    """Functional train state + donated jitted K-step program for one
    ``Model.fit`` run.  Parameters are rebound into the live network
    after every program call (the donated buffers are dead), so eval,
    checkpointing and callbacks keep seeing current weights; optimizer
    accumulators sync back at epoch boundaries via ``sync_optimizer``.

    ``zero_stage>=1`` (``Model.fit(zero_stage=)``) runs the donated
    K-step program ZeRO-sharded over the ambient mesh
    (``parallel.create_mesh``): params replicated, batch sharded over
    the data axes, and every optimizer moment (plus the optional f32
    ``master_weights`` copy) owned 1/dp per rank — the scan body
    reduce-scatters grads, updates the shard, and all-gathers the
    updated params per tensor, so step k+1's gathers overlap the tail
    of step k's update inside the scanned program instead of
    serializing on one fused gather.  The flat checkpoint layout is
    unchanged (the sharded slots ride ``opt::i::slot``), so
    ``parallel.checkpointing.restore_like`` resumes ZeRO state across a
    changed dp size for free.
    """

    def __init__(self, model, seed=0, zero_stage=0, master_weights=False,
                 zero_offload=False, grad_overlap=False,
                 offload_depth=2):
        import warnings

        network, opt, loss = model.network, model._optimizer, model._loss
        self._opt = opt
        self._network = network
        plist = opt._parameter_list
        by_id = {id(p): k for k, p in network.named_parameters()}
        order = [by_id[id(p)] for p in plist]
        self._plist, self._order = plist, order
        self._param_tensors = dict(network.named_parameters())

        self._zero = None
        self._zero_jits = {}
        self._armed_prog = None
        self._n_data = 1
        self._offload = None
        self._offload_depth = int(offload_depth)
        step0 = jnp.asarray(opt._step_count, jnp.int32)
        opt_states = opt.functional_state(plist)
        if int(zero_stage or 0) >= 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.api import get_mesh
            from ..parallel.sharding import ZeroShardInfo, zero_data_axis
            mesh = get_mesh()
            zaxis = zero_data_axis(mesh)
            if zaxis is None:
                warnings.warn(
                    "Model.fit(zero_stage>=1) needs an ambient mesh with "
                    "a >1 'sharding' or 'dp' axis (parallel.create_mesh); "
                    "optimizer state stays replicated for this fit",
                    RuntimeWarning, stacklevel=3)
            else:
                si = ZeroShardInfo(
                    mesh=mesh, axis=zaxis, stage=int(zero_stage),
                    master_weights=bool(master_weights)).with_param_specs(
                        [(None,) * p._value.ndim for p in plist])
                self._zero = si
                self._n_data = int(np.prod([
                    mesh.shape[a] for a in ("dp", "sharding", "ep")
                    if a in mesh.axis_names], dtype=np.int64))
                repl = NamedSharding(mesh, P())
                # params replicated onto the mesh (ZeRO 1/2 keeps the
                # forward's params whole; only the optimizer state
                # shards) — the live network rebinds to the placed
                # arrays so eval/save/checkpoint see mesh arrays
                for t in self._param_tensors.values():
                    t._set_value(jax.device_put(t._value, repl))
                step0 = jax.device_put(step0, repl)
                if zero_offload:
                    # moments (+ masters) live in host RAM; the update
                    # streams shard-at-a-time (parallel.offload) — no
                    # device placement of the optimizer state at all
                    from ..parallel.offload import ZeroOffloadUpdater
                    opt_states = ZeroOffloadUpdater.host_state_for_optimizer(
                        opt, plist, si)
                    self._offload = ZeroOffloadUpdater.for_optimizer(
                        opt, plist, si, depth=self._offload_depth,
                        site="hapi.zero_offload")
                else:
                    from ..parallel.sharding import place_zero_state
                    opt_states = place_zero_state(
                        si, [p._value for p in plist], opt_states)
        if self._zero is None and master_weights:
            warnings.warn(
                "Model.fit(master_weights=True) only takes effect with "
                "zero_stage>=1 on a mesh; ignored", RuntimeWarning,
                stacklevel=3)
        if self._zero is None and zero_offload:
            warnings.warn(
                "Model.fit(zero_offload=True) needs zero_stage>=1 on an "
                "ambient mesh with a >1 data axis; optimizer state stays "
                "device-resident for this fit", RuntimeWarning,
                stacklevel=3)

        params = {k: p._value for k, p in network.named_parameters()}
        _, buffers = network.functional_state()
        self.state = {
            "params": params,
            "opt": opt_states,
            "step": step0,
        }
        from ..parallel.sharding import observe_opt_state_bytes
        if self._offload is not None:
            observe_opt_state_bytes("hapi_compiled", [],
                                    host_tree=opt_states)
        else:
            observe_opt_state_bytes("hapi_compiled", opt_states)
        self.ever_ran = False
        # MoE: thread the load-balance aux INTO the donated program's
        # loss (the PR 2 contract — no extra dispatches) and return it
        # as a ride-along (K,) vector so Model.fit can observe the
        # train_moe_aux_loss metric at the log_freq sync points it
        # already pays for the loss fetch
        self._has_moe = has_moe_layers(network)
        self.last_aux = None

        def forward_loss(p, xs, ys, step):
            rng = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            with core_random.rng_scope(rng):
                outs = functional_call(network, p,
                                       tuple(Tensor(x) for x in xs),
                                       buffers=buffers, training=True)
            outs = [Tensor(o) if not isinstance(o, Tensor) else o
                    for o in _to_list(outs)]
            losses = _to_list(loss(*(outs + [Tensor(y) for y in ys])))
            total = losses[0]
            for l in losses[1:]:
                total = total + l
            total = total._value if isinstance(total, Tensor) else total
            total = total.astype(jnp.float32)
            if not self._has_moe:
                return total
            # the forward just traced left each layer's aux on the layer
            # (the collect_moe_aux side-channel contract the sharded
            # train step already uses): the loss takes them weighted,
            # the ride-along reports the MoE balance unweighted
            aux = collect_moe_aux(network)
            if aux is None:
                zero = jnp.zeros((), jnp.float32)
                return total + zero, zero
            raw = collect_moe_aux(network, weight=1.0)
            return total + aux.astype(jnp.float32), raw.astype(jnp.float32)

        if self._has_moe:
            def grads_of(p, xs, ys, step):
                # has_aux: the aux scalar rides the loss slot as a
                # (total, aux) pair — lax.scan stacks both into (K,)
                # vectors, so the program's outputs grow by K floats,
                # not by a dispatch
                return jax.value_and_grad(
                    lambda pp: forward_loss(pp, xs, ys, step),
                    has_aux=True)(p)
        else:
            def grads_of(p, xs, ys, step):
                return jax.value_and_grad(
                    lambda pp: forward_loss(pp, xs, ys, step))(p)

        if self._offload is not None:
            # grads-only device program: forward + backward + the grad
            # preamble (f32 cast / decay / clip — the exact code the
            # resident ZeRO preamble runs, on the replicated grads), no
            # update.  The update streams through the host pipe in
            # ``run``'s per-step Python loop instead of a lax.scan.
            mw = bool(master_weights)
            has_moe = self._has_moe

            def grads_step(p, step, batch):
                xs, ys = batch
                if has_moe:
                    (total, aux), g = grads_of(p, xs, ys, step)
                else:
                    total, g = grads_of(p, xs, ys, step)
                    aux = jnp.zeros((), jnp.float32)
                vals = [p[k] for k in order]
                gs = opt.preprocess_grads_offload(
                    vals, [g[k] for k in order], master_weights=mw)
                return total, aux, gs, step + 1

            self._grads_step = grads_step
            self._train_step = None
            self._jit = None
            return
        train_step = make_functional_train_step(opt, plist, order, grads_of,
                                                scan_batch=True,
                                                shard_info=self._zero,
                                                grad_overlap=grad_overlap)
        self._train_step = train_step
        # donate the ENTIRE train state: params + accumulators + step all
        # update in place on device; the live network's Tensors rebind to
        # the fresh arrays after each call.  instrument_jit records every
        # trace+compile (a new batch shape = a new program) into
        # jit_builds_total{site=hapi.compiled_trainer}.
        self._jit = sanitize_donation(_obs.instrument_jit(
            jax.jit(train_step, donate_argnums=(0, 1, 2)),
            site="hapi.compiled_trainer"),
            donate_argnums=(0, 1, 2), site="hapi.compiled_trainer")

    def _zero_struct_key(self, xs, ys):
        """(treedef, ranks, ragged?, batch) — the first three select the
        cached program wrapper (``ragged`` = the batch does not divide
        over the data axes, so the replicated-batch flavor applies);
        the batch size rides along for the warning only."""
        leaves, treedef = jax.tree.flatten((xs, ys))
        b = int(np.shape(leaves[0])[1]) if np.ndim(leaves[0]) >= 2 else 0
        return (treedef, tuple(np.ndim(l) for l in leaves),
                bool(b % self._n_data), b)

    def ensure_program(self, xs, ys):
        """Build-or-reuse the ZeRO program for this batch structure.
        ZeRO runs need explicit in/out shardings (batch over the data
        axes, state pinned to its placement so XLA cannot pick a
        re-replicated layout for the donated moments), and the batch
        pytree structure is only known at the first batch — cached per
        (treedef, ranks), mirroring ``make_sharded_train_step``'s
        structure-keyed cache.  The fit loop calls this BEFORE ``run``
        so the hot step path itself never constructs a program
        (PHT002); a structure hit is one dict probe.

        A batch that does not divide over the data axes — typically the
        ragged FINAL batch of an epoch under the default
        ``drop_last=False`` — selects a replicated-batch flavor of the
        program instead of crashing the fit: every rank computes the
        whole (small) batch, which is mathematically the same update
        (the moments stay sharded), it just forgoes dp compute scaling
        for that one superstep.  A once-per-fit warning points at
        ``drop_last=True`` / a divisible batch for runs where EVERY
        batch is indivisible."""
        if self._zero is None:
            return self._jit
        key = self._zero_struct_key(xs, ys)
        if key[2] and not getattr(self, "_warned_ragged", False):
            self._warned_ragged = True
            import warnings
            warnings.warn(
                f"Model.fit(zero_stage>=1): batch size {key[3]} does not "
                f"divide over the mesh's {self._n_data} data-axis "
                "devices; this superstep runs with a REPLICATED batch "
                "(correct, but no dp compute scaling) — pass "
                "drop_last=True or a divisible batch size if this is "
                "not just an epoch's ragged tail", RuntimeWarning,
                stacklevel=3)
        fn = self._zero_jits.get(key[:3])
        if fn is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.api import batch_spec
            leaves, treedef = jax.tree.flatten((xs, ys))
            mesh = self._zero.mesh
            bspec = batch_spec(mesh)
            # ragged (indivisible) batch flavor: batch dim replicated
            bax = (bspec[0] if len(bspec) else None) \
                if not key[2] else None
            repl = NamedSharding(mesh, P())
            param_sh = jax.tree.map(lambda a: a.sharding,
                                    self.state["params"])
            if self._offload is not None:
                # grads-only program over ONE step's batch slice (run's
                # Python loop peels the K dim): nothing donated — params
                # are reused by the streaming update right after
                def leaf_sh1(l):
                    nd = max(np.ndim(l) - 1, 0)
                    spec = ((bax,) + (None,) * (nd - 1))[:nd]
                    return NamedSharding(mesh, P(*spec))

                bsh = jax.tree.unflatten(
                    treedef, [leaf_sh1(l) for l in leaves])
                fn = _obs.instrument_jit(
                    jax.jit(self._grads_step,
                            in_shardings=(param_sh, repl, bsh),
                            out_shardings=repl),
                    site="hapi.compiled_trainer")
                self._zero_jits[key[:3]] = fn
                self._armed_prog = fn
                return fn

            def leaf_sh(l):
                nd = np.ndim(l)
                # stacked (K, B, ...) superbatch leaves: K replicated,
                # batch dim over the data axes, trailing dims whole
                spec = ((None, bax) + (None,) * (nd - 2))[:nd]
                return NamedSharding(mesh, P(*spec))

            bsh = jax.tree.unflatten(treedef, [leaf_sh(l) for l in leaves])
            opt_sh = jax.tree.map(lambda a: a.sharding, self.state["opt"])
            fn = sanitize_donation(_obs.instrument_jit(
                jax.jit(self._train_step, donate_argnums=(0, 1, 2),
                        in_shardings=(param_sh, opt_sh, repl, None, bsh),
                        # repl is a PREFIX spec for the loss slot: it
                        # covers both the (K,) loss vector and the MoE
                        # (losses, aux) pair
                        out_shardings=(param_sh, opt_sh, repl, repl)),
                site="hapi.compiled_trainer"),
                donate_argnums=(0, 1, 2), site="hapi.compiled_trainer")
            self._zero_jits[key[:3]] = fn
        # arm for the next run(): the fit loop calls ensure_program
        # immediately before run with the same batch, so the hot path
        # reads this slot instead of re-deriving the structure key
        self._armed_prog = fn
        return fn

    def run(self, xs, ys):  # pht-lint: hot-root (compiled-trainer step)
        """One compiled superstep over stacked batches (leaves (K, B, …));
        returns the (K,) per-step loss vector as a DEVICE array."""
        if self._zero is None:
            fn = self._jit
        else:
            # armed by the ensure_program the fit loop just called (no
            # re-derivation of the structure key on the hot path); the
            # dict lookup only serves direct callers out of sequence
            fn = self._armed_prog
            if fn is None:
                fn = self._zero_jits.get(self._zero_struct_key(xs, ys)[:3])
            if fn is None:
                # program construction lives OUTSIDE the hot step path —
                # the fit loop (Model._run_compiled_epoch) prepares it
                raise RuntimeError(
                    "CompiledTrainer.run: no program for this batch "
                    "structure — call ensure_program(xs, ys) first")
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        if self._offload is not None:
            return self._run_offload(fn, lr, xs, ys)
        p, s, t, losses = fn(self.state["params"], self.state["opt"],
                             self.state["step"], lr, (xs, ys))
        if self._has_moe:
            # (totals, auxes) — aux stays a device vector until a
            # log_freq fetch reads it alongside the loss
            losses, self.last_aux = losses
        self.state.update(params=p, opt=s, step=t)
        for k, v in p.items():
            self._param_tensors[k]._set_value(v)
        self.ever_ran = True
        return losses

    def _run_offload(self, fn, lr, xs, ys):
        """The offload flavor of one superstep: a Python loop over the K
        stacked batches — each iteration runs the grads-only device
        program, then streams the sharded update through the host pipe
        (``parallel.offload.ZeroOffloadUpdater``).  The host state list
        is REBOUND to fresh arrays every step (never mutated), so a
        checkpoint writer thread holding the previous step's arrays
        stays consistent."""
        k_steps = int(np.shape(jax.tree.leaves(xs)[0])[0])
        params, hstate = self.state["params"], self.state["opt"]
        step = self.state["step"]
        losses, auxes = [], []
        for k in range(k_steps):
            bk = jax.tree.map(lambda a: a[k], (xs, ys))
            total, aux, gs, step = fn(params, step, bk)
            vals = [params[n] for n in self._order]
            new_vals, hstate = self._offload.apply(vals, gs, hstate, lr,
                                                   step)
            params = dict(params)
            params.update(zip(self._order, new_vals))
            losses.append(total)
            auxes.append(aux)
        self.state.update(params=params, opt=hstate, step=step)
        losses = jnp.stack(losses)
        if self._has_moe:
            self.last_aux = jnp.stack(auxes)
        for k, v in params.items():
            self._param_tensors[k]._set_value(v)
        self.ever_ran = True
        return losses

    def checkpoint_flat(self):
        """Flat checkpoint namespace over the CURRENT train state
        (``params::*`` / ``opt::i::slot`` / ``step`` — the layout
        ``parallel.checkpointing`` persists).  Values are the live
        device refs; callers snapshot (``device_snapshot``) before the
        next ``run()`` donates them."""
        from ..parallel.checkpointing import flatten_train_state
        return flatten_train_state(self.state["params"], self.state["opt"],
                                   self.state["step"])

    def load_checkpoint_flat(self, placed):
        """Install a restored flat state (arrays already placed with
        :meth:`checkpoint_flat`'s shardings): train state, the live
        network's Parameters, and the optimizer's accumulators + step
        count all see the resumed values (LR schedules included — one
        tiny host sync of the step scalar, resume-time only)."""
        from ..parallel.checkpointing import unflatten_train_state
        params, opt_states, step = unflatten_train_state(placed)
        self.state = {"params": params, "opt": opt_states, "step": step}
        for k, v in params.items():
            self._param_tensors[k]._set_value(v)
        self.sync_optimizer()

    def sync_optimizer(self):
        """Write accumulators + step count back into the live optimizer
        (one small host sync for the step scalar — epoch-boundary cost)."""
        self._opt.load_functional_state(
            self._plist, self.state["opt"],
            step_count=int(jax.block_until_ready(self.state["step"])))

    def restore_eager(self):
        """Abandon the functional state (trace failure fallback): the live
        network already holds the last good params; accumulators return
        to the optimizer so the eager path continues seamlessly."""
        self.sync_optimizer()
