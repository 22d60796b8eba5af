"""Keras-like high-level Model API.

Ref ``python/paddle/hapi/model.py`` — ``Model`` (:915), ``fit`` (:1574),
``train_batch`` (:1055), evaluate/predict, save/load. The reference
branches into dygraph vs static adapters; here there is one eager path
(jit-compiling happens inside the layers / fused ops).
"""

from __future__ import annotations

import os
import pickle

import numpy as np

from ..core.autograd import no_grad
from ..core.tensor import Tensor
from ..metric import Metric
from .callbacks import CallbackList, ModelCheckpoint, ProgBarLogger


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _as_tensor(x):
    if isinstance(x, Tensor):
        return x
    return Tensor(np.asarray(x))


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self.stop_training = False

    # -- configuration ----------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        metrics = _to_list(metrics)
        for m in metrics:
            assert isinstance(m, Metric), (
                f"metrics must be paddle.metric.Metric instances, got {m}")
        self._metrics = metrics

    # -- single-batch ops (ref train_batch:1055) --------------------------
    def train_batch(self, inputs, labels=None, update=True):
        self.network.train()
        inputs = [_as_tensor(x) for x in _to_list(inputs)]
        labels = [_as_tensor(x) for x in _to_list(labels)]
        outputs = self.network(*inputs)
        outs = _to_list(outputs)
        losses = _to_list(self._loss(*(outs + labels)))
        total = losses[0]
        for l in losses[1:]:
            total = total + l
        aux = self._moe_aux_tensor()
        if aux is not None:
            # the layers' aux terms, weighted (same term the compiled path
            # threads into its donated program — the eager tape must
            # train the router too, not just the experts)
            total = total + aux
        total.backward()
        if aux is not None:
            # report the OPTIMIZED objective as the headline loss so the
            # eager and compiled fit paths log the same quantity — a
            # trace-failure fallback mid-fit must not discontinuously
            # drop the loss series by the aux term
            losses = [total] + losses[1:]
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
        metrics = self._update_metrics(outs, labels)
        out_loss = [float(l.numpy()) for l in losses]
        if aux is not None:
            # observe AFTER the out_loss fetch above already synced the
            # device pipeline — a pre-backward fetch would stall the
            # step on the forward's completion just to feed telemetry
            from ..parallel.moe import collect_moe_aux
            self._observe_moe_aux(
                float(collect_moe_aux(self.network, weight=1.0)),
                "hapi_eager")
        return (out_loss, metrics) if metrics else out_loss

    def _moe_aux_tensor(self):
        """The aux Tensors the eager forward just left on the network's
        layers, weighted as the loss takes them and still ON the tape so
        ``backward`` trains the router; None when the network has no
        (traced-this-forward) aux.  Delegates to the single owner of the
        ``l_aux`` walk (``parallel.moe.collect_moe_aux``)."""
        from ..parallel.moe import collect_moe_aux
        return collect_moe_aux(self.network, tensors=True)

    @staticmethod
    def _observe_moe_aux(value, path):
        """train_moe_aux_loss histogram (docs/OBSERVABILITY.md): the
        UNWEIGHTED aux value at the sync points each fit path already
        pays — a rising series means routing is collapsing onto few
        experts faster than the weighted term can rebalance it."""
        from ..observability import metrics as _obs
        _obs.get_registry().histogram(
            "train_moe_aux_loss",
            "MoE load-balance aux loss (unweighted) at loss-fetch sync "
            "points").labels(path=path).observe(float(value))

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = [_as_tensor(x) for x in _to_list(inputs)]
        labels = [_as_tensor(x) for x in _to_list(labels)]
        with no_grad():
            outputs = self.network(*inputs)
            outs = _to_list(outputs)
            losses = _to_list(self._loss(*(outs + labels))) if self._loss else []
        metrics = self._update_metrics(outs, labels)
        out_loss = [float(l.numpy()) for l in losses]
        return (out_loss, metrics) if metrics else out_loss

    def _update_metrics(self, outs, labels):
        metrics = []
        for m in self._metrics:
            # Metric protocol (ref hapi/model.py _update_metrics): compute()
            # turns (preds, labels) into the per-batch statistic update()
            # consumes; metrics without compute take raw outputs.
            if hasattr(m, "compute"):
                stat = m.compute(*(outs + labels))
                m.update(*[np.asarray(s_.numpy()) if isinstance(s_, Tensor)
                           else np.asarray(s_) for s_ in _to_list(stat)])
            else:
                m.update(*[t.numpy() for t in outs + labels])
            metrics.append(m.accumulate())
        return metrics

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = [_as_tensor(x) for x in _to_list(inputs)]
        with no_grad():
            outputs = self.network(*inputs)
        return [o.numpy() for o in _to_list(outputs)]

    # -- loops (ref fit:1574) ---------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, jit_compile=None,
            steps_per_execution=1, prefetch_buffer=2, nan_policy="record",
            checkpoint=None, zero_stage=0, master_weights=False,
            zero_offload=False, grad_overlap=False):
        """Train loop.  ``jit_compile=None`` (default) tries the compiled
        fast path — one donated jitted program per step (see
        ``hapi/compiled.py``) — and falls back to the eager
        ``train_batch`` loop when the network/optimizer isn't
        pure-functional-capable (metrics, grad accumulation, in-place
        buffer updates, Python-side control flow); ``True`` requires it,
        ``False`` forces eager.  ``steps_per_execution=K`` unrolls K
        steps into one ``lax.scan`` program (losses surface per step;
        within a window the learning rate is read once, and a callback
        setting ``stop_training`` mid-window stops AFTER the window's
        remaining updates already ran — stop granularity is K steps).
        ``prefetch_buffer`` batches are staged onto the device ahead of
        compute (``io.device_prefetch``).

        ``nan_policy``: the non-finite-loss watchdog, checked at the
        sync points the loop already pays (``log_freq`` loss fetches,
        epoch end) so it costs no extra device round trip.  A NaN/Inf
        loss always increments ``train_nonfinite_total`` and records a
        flight-recorder event; ``"raise"`` additionally aborts with a
        clear error instead of silently training on garbage (default
        ``"record"``: keep going — some recipes ride through spikes).

        ``checkpoint``: a directory (or
        ``parallel.checkpointing.CheckpointConfig``) enabling async
        crash-safe checkpoints on the compiled path: at the ``log_freq``
        sync points the loop already pays, the train state (params +
        optimizer accumulators + step + data cursor) is snapshot with
        ONE on-device copy dispatch (no added host sync) and committed
        atomically by a background writer; a crashed fit resumes from
        the latest VALID checkpoint — torn shards/manifests are detected
        and fall back — restoring step/epoch/RNG/cursor so the loss
        series continues where it stopped (docs/CHECKPOINTING.md).

        ``zero_stage>=1`` (ZeRO-sharded optimizer, compiled path only):
        the donated K-step program shards every optimizer moment 1/dp
        over the ambient mesh's 'sharding'/'dp' axis
        (``parallel.create_mesh`` first; the batch shards over the same
        axes) — grads reduce-scatter, the update runs on the shard, and
        the updated params all-gather per tensor with the gathers
        overlapping the update tail inside the scanned program.  Cuts
        per-chip optimizer HBM to ~1/dp; the loss series matches the
        replicated update to f32 reassociation (the reduce-scatter
        changes the grad-psum summation order by design).
        ``master_weights=True`` additionally keeps f32 master copies
        sharded alongside the moments (params may then be bf16).
        Checkpoints flow through ``parallel/checkpointing.py``
        unchanged, so resume across a changed dp size re-shards the
        ZeRO state automatically (docs/PARALLELISM.md).

        ``zero_offload=True`` (with ``zero_stage>=1``) parks the
        moments (+ f32 masters) in host RAM and streams the update
        shard-at-a-time through a double-buffered h2d/d2h pipe —
        opt-state HBM goes to ~0 for a stated tokens/s cost
        (docs/PARALLELISM.md "Optimizer offload & overlap").
        ``grad_overlap=True`` schedules each scanned microstep's grad
        reduce-scatter as the grads materialize instead of relying on
        sharding propagation alone — numerics match the fused path to
        f32 reassociation."""
        train_loader = self._to_loader(train_data, batch_size, shuffle,
                                       drop_last, num_workers)
        eval_loader = (self._to_loader(eval_data, batch_size, False, False,
                                       num_workers)
                       if eval_data is not None else None)
        cbks = _to_list(callbacks) or [ProgBarLogger(log_freq, verbose)]
        if save_dir:
            cbks.append(ModelCheckpoint(save_freq, save_dir))
        cbk = CallbackList(cbks)
        cbk.set_model(self)
        try:
            steps = len(train_loader)
        except TypeError:
            steps = None
        cbk.set_params({"epochs": epochs, "steps": steps, "verbose": verbose})

        if nan_policy not in ("record", "raise"):
            raise ValueError(
                f"nan_policy must be 'record' or 'raise', got {nan_policy!r}")
        trainer = None
        if jit_compile is not False:
            from .compiled import CompiledTrainer, unsupported_reason
            reason = unsupported_reason(self, accumulate_grad_batches)
            if reason is None:
                trainer = CompiledTrainer(self, zero_stage=zero_stage,
                                          master_weights=master_weights,
                                          zero_offload=zero_offload,
                                          grad_overlap=grad_overlap)
            elif jit_compile:
                raise ValueError(
                    f"jit_compile=True, but the compiled fit path is "
                    f"unavailable: {reason}")
            else:
                self._log_fallback_once(
                    f"Model.fit: using the eager path ({reason})")
        if zero_stage and trainer is None:
            # losing the ZeRO sharding must never be silent — the run
            # would quietly hold dp full copies of the optimizer state
            import warnings
            warnings.warn(
                "Model.fit: zero_stage>=1 requires the compiled fit "
                "path; training continues with REPLICATED optimizer "
                "state", RuntimeWarning, stacklevel=2)
        self._fit_used_compiled = trainer is not None

        # crash-safe checkpointing (compiled path only — the eager tape
        # has no functional state to snapshot donation-safely)
        ckpt_driver = None
        start_epoch = 0
        skip_batches = 0
        if checkpoint is not None:
            if trainer is None:
                # direct warn, NOT _log_fallback_once: the once-only
                # flag may already be spent on the eager-fallback log,
                # and losing crash safety must never be silent
                import warnings
                warnings.warn(
                    "Model.fit: checkpoint= requires the compiled fit "
                    "path; training continues WITHOUT crash-safe "
                    "checkpoints", RuntimeWarning, stacklevel=2)
            else:
                from ..parallel.checkpointing import FitCheckpointer
                ckpt_driver = FitCheckpointer(checkpoint)
                ckpt_driver.global_step = int(
                    getattr(self._optimizer, "_step_count", 0) or 0)
                resumed = ckpt_driver.resume(trainer.checkpoint_flat())
                if resumed is not None:
                    placed, start_epoch, skip_batches = resumed
                    trainer.load_checkpoint_flat(placed)

        self.stop_training = False
        logs = {}   # epochs=0: on_train_end still needs a value
        try:
            cbk.on_train_begin()
            for epoch in range(start_epoch, epochs):
                cbk.on_epoch_begin(epoch)
                if ckpt_driver is not None:
                    # capture the shuffle RNG before the epoch's
                    # permutation draws from it (exact-data-order resume)
                    ckpt_driver.mark_epoch()
                for m in self._metrics:
                    m.reset()
                logs = {}
                if trainer is not None:
                    logs, trainer = self._run_compiled_epoch(
                        trainer, train_loader, cbk, log_freq, num_iters,
                        steps_per_execution, prefetch_buffer, nan_policy,
                        epoch=epoch, ckpt=ckpt_driver,
                        skip_batches=(skip_batches
                                      if epoch == start_epoch else 0))
                    self._fit_used_compiled = trainer is not None
                    if ckpt_driver is not None and trainer is not None:
                        # epoch-boundary save: the epoch-end fetch just
                        # drained the pipeline; the snapshot is still
                        # one device-copy dispatch, no extra sync
                        ckpt_driver.maybe_save(
                            trainer.checkpoint_flat(), epoch=epoch + 1,
                            cursor=0, force=True)
                else:
                    from ..observability import tracing as _tr
                    for step, batch in enumerate(train_loader):
                        if num_iters is not None and step >= num_iters:
                            break
                        cbk.on_train_batch_begin(step)
                        ins, lbs = self._split_batch(batch)
                        update = ((step + 1) % accumulate_grad_batches == 0)
                        res = self.train_batch(ins, lbs, update=update)
                        logs = self._pack_logs(res)
                        # eager losses are already host floats
                        # (train_batch float()s them): watch EVERY step —
                        # no log_freq=0 hole, no missed epoch tail
                        self._watch_nonfinite(logs.get("loss"), step,
                                              "hapi_eager", nan_policy)
                        # eager steps are host-synced, so each is a real
                        # liveness signal — without one a wedged eager
                        # fit never trips /healthz?max_age (an absent
                        # beacon passes; only a stale one alerts)
                        _tr.heartbeat("train.hapi_fit")
                        cbk.on_train_batch_end(step, logs)
                        if self.stop_training:
                            break
                if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                    eval_logs = self.evaluate(eval_loader, verbose=0,
                                              _callbacks=cbk)
                    logs.update({f"eval_{k}": v
                                 for k, v in eval_logs.items()})
                cbk.on_epoch_end(epoch, logs)
                if self.stop_training:
                    break
            cbk.on_train_end(logs)
            if ckpt_driver is not None:
                # drain the writer before returning: a fit that exits
                # with its last checkpoint still queued isn't durable
                ckpt_driver.finish()
            # clean completion: a finished fit must not leave a
            # forever-stale beacon 503ing /healthz?max_age (a crashed
            # fit keeps its beacon — going stale IS the alert)
            from ..observability import tracing as _tr_
            _tr_.remove_beacon("train.hapi_fit")
        except BaseException as e:
            if ckpt_driver is not None:
                # an IN-PROCESS failure can still flush the last parked
                # snapshot — the resume point should be as fresh as the
                # crash allows (a hard kill can't flush; that is what
                # the atomic commit protocol covers)
                try:
                    ckpt_driver.finish()
                except Exception:  # noqa: BLE001 — never mask the crash
                    pass
            # every crashed fit leaves a post-mortem: the flight ring
            # holds the recent step/telemetry events (and the watchdog's
            # nonfinite marks) that led up to the failure
            from ..observability import flight as _flight
            _flight.crash_dump("hapi.Model.fit", e)
            raise
        return logs

    def _log_fallback_once(self, msg):
        if not getattr(self, "_fallback_warned", False):
            self._fallback_warned = True
            import warnings
            warnings.warn(msg, RuntimeWarning, stacklevel=3)

    def _watch_nonfinite(self, value, step, path, nan_policy):
        """Non-finite training watchdog (``fit(nan_policy=...)``): runs
        only at sync points where the loss is already on the host, so it
        never adds a device round trip.  Counts + flight-records every
        NaN/Inf; ``nan_policy='raise'`` aborts with a clear error."""
        import math
        try:
            v = float(value)
        except (TypeError, ValueError):
            return
        if math.isfinite(v):
            return
        from ..observability import flight as _flight
        from ..observability import metrics as _obs
        _obs.get_registry().counter(
            "train_nonfinite_total",
            "non-finite (NaN/Inf) losses seen at fit sync points").labels(
                path=path).inc()
        _flight.get_flight_recorder().record(
            "train.nonfinite", path=path, step=int(step), loss=repr(v))
        if nan_policy == "raise":
            raise FloatingPointError(
                f"Model.fit: loss is non-finite ({v}) at step {step} — "
                "aborting instead of training on garbage (check the "
                "learning rate / data; nan_policy='record' continues "
                "and only counts)")

    def _run_compiled_epoch(self, trainer, loader, cbk, log_freq, num_iters,
                            k, prefetch_buffer, nan_policy="record",
                            epoch=0, ckpt=None, skip_batches=0):
        """One epoch through the compiled trainer.  Returns
        ``(logs, trainer_or_None)`` — None when the first program trace
        failed (Python-side control flow in forward, unjittable op) and
        the epoch finished on the eager path instead.

        ``ckpt`` (a ``parallel.checkpointing.FitCheckpointer``) saves at
        the ``log_freq`` fetches below; ``skip_batches`` fast-forwards
        the loader past batches a resumed checkpoint already trained
        (host-side pulls only — no device work for skipped batches)."""
        import itertools
        import time

        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..io.dataloader import device_prefetch
        from ..observability import metrics as _obs
        from ..observability import tracing as _tr

        # step-time/throughput telemetry rides the sync points the loop
        # ALREADY pays (the log_freq loss fetch and the epoch-end
        # block_until_ready) — between them dispatch is async and a wall
        # clock around trainer.run() would measure only Python dispatch.
        _reg = _obs.get_registry()
        _h_step = _reg.histogram(
            "train_step_seconds",
            "mean per-step wall time between loss fetches",
            unit="s").labels(path="hapi_compiled")
        _g_tps = _reg.gauge(
            "train_tokens_per_sec",
            "training throughput between loss fetches "
            "(tokens = batch x seqlen; batch for 1-D samples)").labels(
                path="hapi_compiled")
        # MFU + step-phase attribution (docs/OBSERVABILITY.md, "Trainer
        # MFU and step-phase attribution"): both derive ONLY from
        # timestamps the loop already takes — the program-call wall
        # (dispatch), the log_freq fetch wall (host wait), and the
        # window wall between fetches — so arming them adds no host
        # sync to the step loop.
        _phase_fam = _reg.gauge(
            "train_phase_seconds_per_step",
            "mean wall seconds per step attributed to each step phase "
            "over the last telemetry window (dispatch = Python program "
            "calls, host_wait = loss-fetch stalls, device = the "
            "remainder the async pipeline overlapped)", unit="s")
        _g_phase = {ph: _phase_fam.labels(path="hapi_compiled", phase=ph)
                    for ph in ("dispatch", "host_wait", "device")}
        from ..cost_model import device_peak_flops, train_flops_per_token
        # ONE chip's peak: the hapi compiled trainer is an unsharded
        # jax.jit — it executes on the default device only, so a
        # device_count multiplier would understate MFU by the host's
        # chip count (the sharded auto_parallel.Engine scales by its
        # OWN mesh size instead).  The gauge child is created only when
        # the peak is known — an eager child would export
        # train_mfu=0.0 (alarm-worthy) where the honest answer is
        # "unknown" (docs: unset).
        _peak = device_peak_flops()
        _g_mfu = _reg.gauge(
            "train_mfu",
            "model FLOPs utilization between loss fetches "
            "(analytic cost_model.train_flops_per_token x tokens/s over "
            "device_peak_flops; MoE-active-params-aware; unset when the "
            "chip peak is unknown)").labels(path="hapi_compiled") \
            if _peak else None
        _flops_tok = None      # resolved lazily (needs the seqlen)
        _seqlen = None
        _t_mark = None
        _steps_since = _tokens_since = 0
        _disp_ns = _fetch_ns = 0

        def _telemetry_tick():
            """Close the current telemetry window; returns the phase/
            MFU attribution dict (for the loss_fetch span) or None on
            the first window (compile time must pollute neither the
            step histogram nor the phase split)."""
            nonlocal _t_mark, _steps_since, _tokens_since, _disp_ns, \
                _fetch_ns, _flops_tok
            _tr.heartbeat("train.hapi_fit")   # /healthz last-step recency
            now = time.perf_counter()
            out = None
            if _t_mark is not None and _steps_since:
                dt = now - _t_mark
                if dt > 0:
                    per_step = dt / _steps_since
                    _h_step.observe(per_step)
                    tps = _tokens_since / dt
                    _g_tps.set(tps)
                    disp = _disp_ns / 1e9 / _steps_since
                    wait = _fetch_ns / 1e9 / _steps_since
                    dev = max(per_step - disp - wait, 0.0)
                    _g_phase["dispatch"].set(disp)
                    _g_phase["host_wait"].set(wait)
                    _g_phase["device"].set(dev)
                    out = {"steps": _steps_since,
                           "dispatch_ms_per_step": round(disp * 1e3, 3),
                           "host_wait_ms_per_step": round(wait * 1e3, 3),
                           "device_ms_per_step": round(dev * 1e3, 3)}
                    if _peak:
                        if _flops_tok is None:
                            _flops_tok = train_flops_per_token(
                                self.network, seqlen=_seqlen)
                        mfu = tps * _flops_tok / _peak
                        _g_mfu.set(mfu)
                        out["mfu"] = round(mfu, 4)
            _t_mark, _steps_since, _tokens_since = now, 0, 0
            _disp_ns = _fetch_ns = 0
            return out

        k = max(int(k), 1)
        it = iter(loader)
        pulled = 0
        # resume fast-forward: the checkpoint's cursor counts batches its
        # state already trained this epoch — consume them host-side so
        # the resumed run sees the SAME data order a crash-free run saw
        skip_batches = int(skip_batches)
        for _ in range(skip_batches):
            if next(it, None) is None:
                break
        if num_iters is not None:
            num_iters = max(int(num_iters) - skip_batches, 0)
        consumed = skip_batches   # batches the train STATE has absorbed

        def _leaf(v):
            return v._value if isinstance(v, Tensor) else np.asarray(v)

        def _stack(vals):
            if all(isinstance(v, np.ndarray) for v in vals):
                return np.stack(vals)
            return jnp.stack(vals)

        def host_groups():
            nonlocal pulled
            while not self.stop_training:
                group = []
                while len(group) < k and (num_iters is None
                                          or pulled < num_iters):
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    pulled += 1
                    ins, lbs = self._split_batch(batch)
                    group.append((tuple(_leaf(v) for v in ins),
                                  tuple(_leaf(v) for v in lbs)))
                if not group:
                    return
                xs = tuple(_stack([g[0][i] for g in group])
                           for i in range(len(group[0][0])))
                ys = tuple(_stack([g[1][i] for g in group])
                           for i in range(len(group[0][1])))
                yield (xs, ys)

        step = 0
        last_watched = -1   # last step index the watchdog already saw
        logs = {}
        last = None
        groups = device_prefetch(host_groups(), size=prefetch_buffer)
        for xs, ys in groups:
            # ZeRO program build-or-reuse happens HERE, outside the
            # trainer's hot step path (a structure hit is a dict probe;
            # non-ZeRO trainers return their one program unconditionally)
            trainer.ensure_program(xs, ys)
            t0n = time.perf_counter_ns()
            try:
                losses = trainer.run(xs, ys)
            except Exception as e:  # noqa: BLE001 — unjittable network
                # only TRACE-time failures fall back: an execution-time
                # failure (XlaRuntimeError, e.g. device OOM) happens after
                # the state buffers were donated, so neither the eager
                # replay nor restore_eager could run — surface it
                if trainer.ever_ran or "XlaRuntimeError" in type(e).__name__:
                    raise
                self._log_fallback_once(
                    "Model.fit: compiled trainer failed to trace "
                    f"({type(e).__name__}: {e}); falling back to eager")
                if getattr(trainer, "_zero", None) is not None:
                    # the once-only fallback log above may already be
                    # spent, and losing the ZeRO sharding mid-run must
                    # never be silent: the eager tape trains with dp
                    # FULL replicated copies of the optimizer state
                    import warnings
                    warnings.warn(
                        "Model.fit: the ZeRO-sharded compiled trainer "
                        "fell back to eager MID-RUN; optimizer state is "
                        "REPLICATED for the rest of this fit",
                        RuntimeWarning, stacklevel=2)
                if ckpt is not None:
                    # the once-only fallback log above may already be
                    # spent — losing crash safety mid-run deserves its
                    # own explicit warning, not silence
                    import warnings
                    warnings.warn(
                        "Model.fit: the compiled trainer fell back to "
                        "eager MID-RUN; crash-safe checkpointing is "
                        "DISABLED for the rest of this fit (the eager "
                        "tape has no functional state to snapshot)",
                        RuntimeWarning, stacklevel=2)
                trainer.restore_eager()
                for exs, eys in itertools.chain([(xs, ys)], groups):
                    n = int(jax.tree.leaves(exs)[0].shape[0])
                    for j in range(n):
                        cbk.on_train_batch_begin(step)
                        res = self.train_batch([Tensor(x[j]) for x in exs],
                                               [Tensor(y[j]) for y in eys])
                        logs = self._pack_logs(res)
                        # host floats already — watch every replayed step
                        self._watch_nonfinite(logs.get("loss"), step,
                                              "hapi_eager", nan_policy)
                        _tr.heartbeat("train.hapi_fit")
                        cbk.on_train_batch_end(step, logs)
                        step += 1
                        if self.stop_training:
                            break
                    if self.stop_training:
                        break
                return logs, None
            t1n = time.perf_counter_ns()
            if _tr.tracing_enabled():
                # dispatch wall of the K-step donated program (first call
                # includes trace+compile; the async device time shows up
                # in the loss_fetch spans instead)
                _tr.add_span("hapi.fit.superstep", t0n, t1n, step=step, k=k)
            lead = jax.tree.leaves(xs)[0]   # (K, B, ...) stacked batches
            # tokens = B*S only for token batches (K, B, S); any other
            # rank (vision NCHW etc.) counts samples — shape[2] would be
            # a channel count, not a sequence length
            _seqlen = int(lead.shape[2]) if lead.ndim == 3 else None
            toks_per_step = int(lead.shape[1]) * (_seqlen or 1)
            n = int(losses.shape[0])
            consumed += n
            if ckpt is not None:
                ckpt.advance(n)
            # phase attribution: amortize the K-step program-call wall
            # over its K inner steps — a telemetry window closing MID-
            # superstep (log_freq % k != 0, the default shapes) must
            # get dispatch time proportional to the steps it contains,
            # not a whole superstep's wall dumped into one window
            disp_step_ns = (t1n - t0n) / n
            for j in range(n):
                cbk.on_train_batch_begin(step)
                _steps_since += 1
                _tokens_since += toks_per_step
                _disp_ns += disp_step_ns
                # async loss fetch: the scalar leaves the device only at
                # log_freq boundaries — other steps hand callbacks the
                # device scalar (float()-able on demand)
                v = losses[j]
                if log_freq and step % log_freq == 0:
                    tf0 = time.perf_counter_ns()
                    v = float(v)
                    tf1 = time.perf_counter_ns()
                    _fetch_ns += tf1 - tf0   # phase: host wait on fetch
                    phases = _telemetry_tick()
                    if _tr.tracing_enabled():
                        # host wait for the async device pipeline to
                        # deliver this step's loss scalar — carrying the
                        # closed window's phase/MFU attribution so the
                        # trace answers "where did this window go"
                        _tr.add_span("hapi.fit.loss_fetch", tf0, tf1,
                                     step=step, **(phases or {}))
                    self._watch_nonfinite(v, step, "hapi_compiled",
                                          nan_policy)
                    if trainer.last_aux is not None:
                        # MoE aux ride-along: the loss fetch above
                        # already drained the pipeline, so this is one
                        # more tiny d2h of an already-computed scalar,
                        # not a dispatch
                        self._observe_moe_aux(
                            float(trainer.last_aux[j]), "hapi_compiled")
                    if ckpt is not None:
                        # async checkpoint at the sync point just paid:
                        # one on-device copy dispatch + a queue handoff —
                        # the d2h fetch and disk I/O happen on the
                        # writer thread (parallel/checkpointing.py)
                        ckpt.maybe_save(trainer.checkpoint_flat(),
                                        epoch=epoch, cursor=consumed)
                    last_watched = step
                logs = {"loss": v}
                cbk.on_train_batch_end(step, logs)
                step += 1
                last = (losses, j)
                if self.stop_training:
                    break
            if self.stop_training:
                break
        if last is not None:
            # epoch-end sync; report the loss of the last step callbacks
            # actually saw (a mid-window stop must not report past it)
            losses, j = last
            tf0 = time.perf_counter_ns()
            jax.block_until_ready(losses)
            tf1 = time.perf_counter_ns()
            _fetch_ns += tf1 - tf0
            phases = _telemetry_tick()
            if _tr.tracing_enabled():
                _tr.add_span("hapi.fit.loss_fetch", tf0, tf1,
                             step=step - 1, epoch_end=True,
                             **(phases or {}))
            logs = {"loss": float(losses[j])}
            if step - 1 != last_watched:
                # skip when the final step already hit a log_freq fetch:
                # one bad step must count once, not twice
                self._watch_nonfinite(logs["loss"], step - 1,
                                      "hapi_compiled", nan_policy)
        trainer.sync_optimizer()
        return logs, trainer

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None,
                 _callbacks=None):
        loader = self._to_loader(eval_data, batch_size, False, False,
                                 num_workers)
        cbk = _callbacks or CallbackList(_to_list(callbacks))
        for m in self._metrics:
            m.reset()
        cbk.on_eval_begin()
        logs = {}
        for step, batch in enumerate(loader):
            if num_iters is not None and step >= num_iters:
                break
            cbk.on_eval_batch_begin(step)
            ins, lbs = self._split_batch(batch)
            res = self.eval_batch(ins, lbs)
            logs = self._pack_logs(res)
            cbk.on_eval_batch_end(step, logs)
        cbk.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, num_iters=None):
        loader = self._to_loader(test_data, batch_size, False, False,
                                 num_workers)
        outputs = []
        for step, batch in enumerate(loader):
            if num_iters is not None and step >= num_iters:
                break
            ins, _ = self._split_batch(batch, has_labels=False)
            outputs.append(self.predict_batch(ins))
        if stack_outputs and outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    # -- save / load (ref model.py save:1373) -----------------------------
    def save(self, path, training=True):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        from ..framework.io import save as fsave
        fsave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            fsave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load as fload
        self.network.set_state_dict(fload(path + ".pdparams"))
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(fload(path + ".pdopt"))

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        from .summary import summary
        return summary(self.network, input_size, dtypes=dtype)

    # -- helpers ----------------------------------------------------------
    def _to_loader(self, data, batch_size, shuffle, drop_last, num_workers):
        from ..io.dataloader import DataLoader
        from ..io.dataset import Dataset
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              drop_last=drop_last, num_workers=num_workers)
        return data  # already a loader/iterable

    def _split_batch(self, batch, has_labels=True):
        batch = list(batch) if isinstance(batch, (list, tuple)) else [batch]
        n_in = len(_to_list(self._inputs))
        if not n_in:
            if has_labels and len(batch) > 1:
                n_in = len(batch) - 1
            else:
                # no inputs spec: cap at the network's forward arity so a
                # labelled dataset still works for predict()
                import inspect
                try:
                    sig = inspect.signature(self.network.forward)
                    n_pos = sum(
                        1 for p in sig.parameters.values()
                        if p.kind in (p.POSITIONAL_ONLY,
                                      p.POSITIONAL_OR_KEYWORD))
                    n_in = min(len(batch), n_pos)
                except (TypeError, ValueError):
                    n_in = len(batch)
        ins = batch[:n_in]
        lbs = batch[n_in:] if has_labels else []
        return ins, lbs

    def _pack_logs(self, res):
        logs = {}
        if isinstance(res, tuple):
            losses, metrics = res
            for m, v in zip(self._metrics, metrics):
                name = m.name()
                logs[name if isinstance(name, str) else name[0]] = (
                    v if not isinstance(v, (list, tuple)) else v[0])
        else:
            losses = res
        logs["loss"] = losses[0] if isinstance(losses, list) else losses
        return logs
