"""Continuous-batching serving engine (multi-request decode).

Ref: the reference serves multi-rank inference through
``fleet_executor/dist_model.cc`` (DistModel — a persistent runtime that
feeds requests through per-stage processes) and a thread-safe
``AnalysisPredictor::ZeroCopyRun`` (``inference/api/analysis_predictor.h:182``)
so many client threads can share one loaded model.

TPU-native design: ONE jitted tick program over a slot-based static KV
cache (``max_slots`` x ``max_len``).  Each tick advances every occupied
slot by up to ``chunk`` tokens — prompt prefill is chunked into the SAME
program that decodes (mixed prefill+decode batching), so a new request
joins mid-flight without recompiling or stalling streams already
decoding.  Per-slot cache depths ride a vector ``cache_pos`` through the
model (``models/gpt.py`` static-cache attention); sampling happens
in-program at each slot's last valid position.  The host side is a slot
scheduler: admit from a FIFO into free slots, stage each slot's next
token chunk, retire finished requests.

``cache_mode="paged"`` swaps the per-slot dense regions for a global
page pool with per-slot page tables (PagedAttention/RadixAttention
lineage): admission reserves each request's actual page footprint
instead of a ``max_len`` slot, a radix prefix cache lets requests
sharing a page-aligned prompt prefix map the same physical pages and
prefill only their suffix, and attention gathers K/V through the table
(``incubate/nn/kernels/paged_attention.py``).  Host-side bookkeeping
lives in ``inference/paged.py``; docs/SERVING.md has the layout diagram
and sizing guidance.

Under pipeline parallelism the tick runs the interleaved-wave schedule:
the slot batch splits into ``pp`` waves, each wave occupying a different
stage every tick, so ALL stages do useful work each tick — the
multi-request bubble-fill that the single-stream masked schedule
(``parallel/pipeline.py pipeline_decode_apply``) documents as "would
fill it".  A wave's sample surfaces ``pp - 1`` ticks after its tokens
enter stage 0; the engine advances a wave's slot state only when its
sample exits, so every stage mid-flight sees the wave's entry-time cache
positions.
"""

from __future__ import annotations

import collections
import collections.abc
import itertools
import threading
import time
import zlib
from typing import List, Optional

import numpy as np

from ..observability import faults as _faults
from ..observability import flight as _flight
from ..observability import metrics as _obs
from ..observability.sanitizers import (make_lock, sanitize_donation,
                                        share_object)
from ..observability import tracing as _tr

_ENGINE_IDS = itertools.count()
_REQ_IDS = itertools.count()

# SLO priority classes (submit(priority=)): lower rank schedules first.
# Aging (ServingEngine priority_aging_s) promotes a waiting request one
# rank per interval, so batch work cannot starve forever under a
# sustained interactive load.
PRIORITY_RANK = {"interactive": 0, "default": 1, "batch": 2}


class _EngineStats(collections.abc.Mapping):
    """Back-compat dict view over the engine's registry counters: the
    historical ``engine.stats`` keys read straight from the labelled
    ``serving_*_total`` series, so existing callers (tests, bench rows)
    keep working while scrapers get the full labelled families."""

    _KEYS = ("ticks", "tokens", "requests",
             "spec_ticks", "spec_drafted", "spec_accepted",
             "prefix_hit_tokens", "prompt_tokens", "prefix_hit_rate",
             "session_resumes", "session_hit_tokens", "preemptions")

    def __init__(self, counters):
        self._counters = counters   # key -> Counter child

    def __getitem__(self, k):
        if k == "prefix_hit_rate":
            # derived: prompt tokens the prefix cache saved re-prefilling
            # over all prompt tokens admitted (0.0 until any admit)
            pt = int(self._counters["prompt_tokens"].value)
            hit = int(self._counters["prefix_hit_tokens"].value)
            return hit / pt if pt else 0.0
        return int(self._counters[k].value)

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self))


def _storage_dtype(dtype):
    """npz-safe storage dtype for a param dtype: ml_dtypes extension
    types (bfloat16, float8_*) round-trip through ``np.savez`` as raw
    void blobs ('|V2') that numpy cannot interpret back — store them as
    same-width unsigned ints and record the logical dtype name in
    config.json instead."""
    if dtype.kind == "V" or dtype.name not in np.sctypeDict:
        return np.dtype(f"u{dtype.itemsize}")
    return None


def _named_dtype(name):
    """np.dtype for a recorded dtype name, resolving ml_dtypes extension
    names (e.g. 'bfloat16') that ``np.dtype(str)`` does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


class TornArtifactError(RuntimeError):
    """A serving artifact directory is incomplete — a crash mid-save by
    a pre-atomic writer, or a partial copy.  :func:`save_for_serving`
    commits atomically (tmp dir + rename), so a torn directory is
    always externally produced; :func:`load_for_serving` refuses to
    half-load it."""


def save_for_serving(model, path, quant=None):
    """Persist ``{config.json, params.npz}`` so a serving process — in
    particular the C++ shim (``native/serving.cc pht_engine_create``) —
    can rebuild the model without the training script (the role of the
    reference's ``save_inference_model`` artifact for ``DistModel``).

    ATOMIC: both files land in a tmp directory (``params.npz`` first,
    ``config.json`` — the manifest — last, both fsync'd) which is then
    renamed over ``path``; a crash mid-save leaves the previous artifact
    (or nothing) — never a torn directory a later
    :func:`load_for_serving` would half-load.

    Works for any param dtype: bf16 (the expected serving dtype — the
    bench casts GPT-2 to bf16) and other ml_dtypes store as uint views
    with the logical dtype recorded per param in ``config.json``.

    ``quant="int8"`` (or ``"fp8"`` = fp8-e4m3) post-training-quantizes
    the attention/MLP projection
    weights at save time: the artifact stores int8 values plus f32
    per-output-channel ``<name>_scale`` entries (~halving weight bytes),
    and ``config.json`` records ``{"quant": {"scheme", "params"}}`` so
    :func:`load_for_serving` installs the fused-GEMM serving layers
    before loading state — no wide copy of the SAVED weights is ever
    built (model construction still transiently allocates the default
    f32 initializers, the same load peak as the bf16 path).  A model
    ALREADY holding
    quantized Linears (``nn.quant.convert_to_weight_only`` — the QAT
    export) records the same manifest without ``quant=``; embeddings,
    layernorms and the tied logits head stay in the float dtype either
    way (docs/SERVING.md, "Weight-only quantized serving")."""
    import dataclasses
    import json
    import os
    import shutil
    params = {k: v._value for k, v in model.named_parameters()}
    scheme = None
    if quant is not None:
        from ..nn.quant import weight_only as _wo
        scheme = _wo.resolve_scheme(quant)
        params, _ = _wo.quantize_weights(params, scheme)
    # manifest by inspection (covers both quant= and pre-quantized
    # trees): a weight with a `_scale` sibling is a serving-quantized
    # Linear the loader must swap before loading state
    manifest = sorted(k for k in params if k + "_scale" in params)
    arrs, dtypes = {}, {}
    for k, v in params.items():
        a = np.asarray(v)
        dtypes[k] = a.dtype.name
        store = _storage_dtype(a.dtype)
        arrs[k] = a.view(store) if store is not None else a
    meta = {"model": type(model).__name__,
            "config": dataclasses.asdict(model.config),
            "param_dtypes": dtypes}
    if manifest:
        if scheme is None:
            scheme = ("int8" if dtypes[manifest[0]] == "int8"
                      else "fp8-e4m3")
        meta["quant"] = {"scheme": scheme, "params": manifest}
    # atomic commit: params first, the config manifest last, rename the
    # whole directory into place (same trio as the training checkpoints,
    # parallel/checkpointing.py — docs/CHECKPOINTING.md)
    import uuid
    path = os.fspath(path)
    # pid identifies the owner for the liveness sweep; the uuid keeps
    # concurrent saves from different THREADS of one process (same pid)
    # off each other's tmp dirs
    tmp = f"{path}.saving-{os.getpid()}-{uuid.uuid4().hex[:8]}"
    old = f"{path}.old"
    # sweep tmp dirs orphaned by a DEAD process's hard kill: each holds
    # a full-model-size params.npz nothing else would ever delete.  A
    # dir whose owner pid is still alive (this process included — a
    # concurrent thread's save) is left alone
    import glob as _glob
    for stale in _glob.glob(f"{path}.saving-*"):
        try:
            pid = int(stale.split(".saving-", 1)[1].split("-", 1)[0])
            os.kill(pid, 0)       # raises if the owner is gone
            continue              # owner alive: not ours to sweep
        except (ValueError, ProcessLookupError):
            pass                  # malformed name or dead owner: sweep
        except PermissionError:
            continue              # alive under another uid
        shutil.rmtree(stale, ignore_errors=True)
    os.makedirs(tmp)
    try:
        with open(os.path.join(tmp, "params.npz"), "wb") as f:
            np.savez(f, **arrs)
            f.flush()
            os.fsync(f.fileno())
        with open(os.path.join(tmp, "config.json"), "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        # carry sidecar files (tokenizer.json etc.) the user keeps next
        # to the framework's two into the replacement — a re-export must
        # not silently destroy them.  After a swap-window crash the live
        # artifact is .old, so sidecars come from there.
        side_src = path if os.path.isdir(path) else (
            old if os.path.isdir(old) else None)
        if side_src is not None:
            for n in os.listdir(side_src):
                if n in ("config.json", "params.npz"):
                    continue
                src, dst = os.path.join(side_src, n), os.path.join(tmp, n)
                if os.path.isdir(src):
                    shutil.copytree(src, dst)
                else:
                    shutil.copy2(src, dst)
        if os.path.isdir(path):
            # `path` is a complete artifact, so a stale .old (leftover
            # of a crash AFTER a previous commit) is disposable.  Never
            # delete .old while it may be the only valid copy — when
            # `path` is missing (crash inside a previous swap window),
            # .old survives until the rename below commits.
            shutil.rmtree(old, ignore_errors=True)
            os.rename(path, old)
        os.rename(tmp, path)
        shutil.rmtree(old, ignore_errors=True)
        # durability of the rename itself (same protocol step as
        # checkpointing._write_checkpoint_dir's root fsync)
        from ..parallel.checkpointing import _fsync_dir
        _fsync_dir(os.path.dirname(os.path.abspath(path)))
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise


def load_for_serving(path):
    """Rebuild the model saved by :func:`save_for_serving`.

    A torn artifact (missing/truncated ``config.json`` or missing
    ``params.npz``) raises :class:`TornArtifactError` instead of
    half-loading; a directory caught between the two renames of an
    atomic re-save falls back to the surviving ``.old`` artifact."""
    import json
    import os

    from ..core.tensor import Tensor
    from ..models import gpt as _gpt
    path = os.fspath(path)
    if not os.path.isdir(path) and os.path.isdir(path + ".old"):
        # crash inside save_for_serving's swap window: the previous
        # artifact is complete at .old — serve that
        path = path + ".old"
    if not os.path.isdir(path):
        raise FileNotFoundError(path)
    cfg_p = os.path.join(path, "config.json")
    npz_p = os.path.join(path, "params.npz")
    for p in (cfg_p, npz_p):
        if not os.path.exists(p):
            raise TornArtifactError(
                f"serving artifact at {path} is torn: {os.path.basename(p)} "
                f"is missing — the save crashed mid-write (pre-atomic "
                f"writer) or the copy was partial; re-export with "
                f"save_for_serving")
    try:
        with open(cfg_p) as f:
            meta = json.load(f)
    except ValueError as e:
        raise TornArtifactError(
            f"serving artifact at {path} is torn: config.json does not "
            f"parse ({e}) — re-export with save_for_serving") from e
    cls = getattr(_gpt, meta["model"])
    model = cls(_gpt.GPTConfig(**meta["config"]))
    model.eval()
    q = meta.get("quant")
    if q:
        # quantize-at-load: install empty WeightOnlyLinear shells at the
        # manifest paths BEFORE loading state, so the int8/fp8 weights
        # land directly in the fused-GEMM layers — no wide copy of the
        # SAVED weights is ever built.  (Construction above still paid
        # the default f32 initializers transiently — the same load peak
        # as any load_for_serving; the swap frees those right here,
        # before params.npz streams in.)
        from ..nn.quant.weight_only import apply_weight_only
        apply_weight_only(model, q["scheme"], names=q["params"])
    z = np.load(os.path.join(path, "params.npz"))
    dtypes = meta.get("param_dtypes", {})
    state = {}
    for k in z.files:
        a = np.asarray(z[k])
        want = dtypes.get(k)
        if want is not None and a.dtype.name != want:
            a = a.view(_named_dtype(want))
        state[k] = Tensor(a)
    model.set_state_dict(state)
    # set_state_dict casts into the fresh model's (f32) param dtypes;
    # serving wants the SAVED dtypes back (bf16 halves HBM and is the
    # dtype the engine was benched/validated in)
    import jax.numpy as jnp
    for k, p in model.named_parameters():
        want = dtypes.get(k)
        if want is not None and p._value.dtype.name != want:
            p._set_value(p._value.astype(_named_dtype(want)))
    return model


class DeadlineExceededError(RuntimeError):
    """A request blew past its ``submit(deadline_s=)`` budget — either
    still queued (queue-wait is where overload deadlines actually die)
    or mid-decode — and was aborted: waiting longer can only return an
    answer the caller has already given up on.  Generated-so-far tokens
    are counted into ``serving_aborted_tokens_total`` and the lifecycle
    record is stamped ``t_abort``/``where="deadline"`` (also visible in
    ``/debug/requests`` under ``recent_aborts``)."""


class EngineDraining(RuntimeError):
    """:meth:`ServingEngine.submit` was called on a draining engine.
    :meth:`ServingEngine.drain` stops admission while queued + inflight
    requests run to completion — the graceful half of removal (hard
    ``shutdown(timeout=)`` is the other half).  A fleet router treats
    this as "place elsewhere", never as a replica failure."""


class Request:
    """One in-flight generation request.

    ``temperature``/``top_k``/``top_p`` override the engine-global
    sampling defaults for this request only (None = inherit).

    ``lifecycle`` is the request's SLO record — one flat dict stamped at
    each stage (submit → admit → first token → per-tick decode → finish
    or abort), the per-request ground truth behind the rolling window
    percentiles in :meth:`ServingEngine.load_report`.  Times are
    ``time.perf_counter()`` values (the engine's monotonic clock);
    derived durations (``queue_s``/``ttft_s``/``tpot_s``/``e2e_s``)
    land next to them so callers never re-derive.  Plain data on the
    request object, NOT metric labels: per-request ids as labels would
    mint one time series per request and grow the registry without
    bound (pht-lint PHT005).

    ``on_token`` is the per-token streaming hand-off: a callable the
    engine invokes with each committed token id, then exactly once with
    ``None`` at the request's terminal (finish, abort, or loop
    failure).  Calls run on the engine's driver thread AFTER the engine
    lock is released, so a hook that blocks (a bounded queue doing
    backpressure — the fleet router's ``submit_stream``) stalls only
    the decode loop, never ``submit()``/introspection."""

    __slots__ = ("prompt", "max_new_tokens", "tokens", "done", "error",
                 "temperature", "top_k", "top_p", "_event",
                 "_t_submit", "_t_first", "rid", "_span_queue",
                 "_span_life", "lifecycle", "_tick_mark", "deadline_s",
                 "on_token", "session", "priority", "_prank",
                 "_preempts", "_t_queued", "trace_ctx")

    def __init__(self, prompt, max_new_tokens, temperature=None,
                 top_k=None, top_p=None, deadline_s=None, on_token=None,
                 session=None, priority=None, trace_ctx=None):
        self.rid = next(_REQ_IDS)   # process-wide request id (spans/flight)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = None if temperature is None else float(temperature)
        self.top_k = None if top_k is None else int(top_k)
        self.top_p = None if top_p is None else float(top_p)
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.on_token = on_token
        self.session = session   # multi-turn KV session key (or None)
        self.priority = "default" if priority is None else priority
        if self.priority not in PRIORITY_RANK:
            raise ValueError(
                f"priority must be one of {sorted(PRIORITY_RANK)}, "
                f"got {priority!r}")
        self._prank = PRIORITY_RANK[self.priority]
        self._preempts = 0   # times this request was preempted (cap)
        self.tokens: List[int] = []  # generated so far
        self.done = False
        self.error: Optional[BaseException] = None
        self._event = threading.Event()
        self._t_submit = time.perf_counter()   # TTFT/e2e reference point
        # last time the request (re-)entered the queue: submit, or a
        # preemption's re-queue — the queue-wait the SLO windows and
        # /load's oldest_wait_s measure (deadlines/aging stay on
        # _t_submit: total-budget semantics)
        self._t_queued = self._t_submit
        self._t_first: Optional[float] = None  # first generated token
        # (last commit time, tokens then) — the per-tick TPOT sample base
        self._tick_mark: Optional[tuple] = None
        self.lifecycle = {"rid": self.rid,
                          "prompt_len": int(self.prompt.shape[0]),
                          "max_new_tokens": self.max_new_tokens,
                          "t_submit": self._t_submit,
                          "priority": self.priority}
        if self.deadline_s is not None:
            self.lifecycle["deadline_s"] = self.deadline_s
        # fleet trace context (docs/OBSERVABILITY.md, "Fleet telemetry"):
        # a plain dict minted by the router — fleet id, fleet-wide
        # request id, dispatch attempt ordinal.  Stamped into the
        # lifecycle record so this replica's view of the request links
        # back to the router decision that placed it (and, post-HTTP,
        # to the header the context will ride in).
        self.trace_ctx = dict(trace_ctx) if trace_ctx else None
        if self.trace_ctx is not None:
            if self.trace_ctx.get("fleet_rid") is not None:
                self.lifecycle["fleet_rid"] = self.trace_ctx["fleet_rid"]
            if self.trace_ctx.get("attempt") is not None:
                self.lifecycle["dispatch_attempt"] = \
                    self.trace_ctx["attempt"]
        # lifecycle spans (no-ops while tracing is disabled): queued =
        # submit->admit, life = submit->finish/EOS
        self._span_queue = self._span_life = _tr._NOOP

    def wait(self, timeout=None):
        self._event.wait(timeout)
        return self.done

    def result(self):
        """Full sequence (prompt + generated), like ``model.generate``."""
        if self.error is not None:
            raise RuntimeError("request failed in the engine") from self.error
        if not self.done:
            raise RuntimeError("request not finished; wait() first")
        return np.concatenate([self.prompt, np.asarray(self.tokens, np.int32)])


class _LoadDebugSource:
    """Adapter publishing an engine's :meth:`ServingEngine.load_report`
    through the ``/debug/requests`` introspection registry (as
    ``"<engine>.load"``) so the capacity document is inspectable from
    the debug surface too, not only the router-facing ``/load``.  The
    engine holds the strong reference; the registry holds it weakly."""

    __slots__ = ("_engine", "__weakref__")

    def __init__(self, engine):
        self._engine = engine

    def introspect_requests(self) -> dict:
        return self._engine.load_report()


class _Slot:
    __slots__ = ("req", "off", "last", "seq", "resume")

    def __init__(self):
        self.req: Optional[Request] = None
        self.off = 0      # prefill-source tokens consumed
        self.last = 0     # last sampled token (decode feed)
        # the slot's prefill source: the request's prompt, or — for a
        # request resuming after preemption — prompt + committed tokens
        # minus the last one (the rows whose KV must be resident before
        # decode continues; the last committed token is the decode feed)
        self.seq = None
        # resume=True: the final prefill chunk's sample must NOT commit
        # (it would re-predict an already-committed token); decode
        # restarts from the preset ``last`` instead
        self.resume = False


class _Session:
    """One retained multi-turn KV session (``submit(session=)``).

    After a turn finishes, the engine keeps the request's page chain
    alive here (the session holds the refs a slot normally drops at
    release): ``tokens`` is the full conversation so far (prompt +
    generated), ``pages`` its page chain, and ``kv_len`` the rows of
    that chain holding token-exact KV of ``tokens[:kv_len]`` — a
    returning turn whose prompt extends the conversation resumes from
    that tail instead of re-prefilling the history.  ``digests`` are
    the crc32 chain digests of the full retained pages (same form as
    ``paged.page_digests``), published through ``/load`` so the fleet
    router's cache-affinity scoring lands returning turns here.

    ``busy``/``owner``: while a resumed turn is in flight the refs
    live on its slot (``pages`` is empty) and only that owner's finish
    installs the session's next state — a concurrently forked
    regeneration (same session key while busy) serves independently
    off the prefix cache and never clobbers the owner's install."""

    __slots__ = ("sid", "tokens", "pages", "kv_len", "digests",
                 "last_used", "busy", "owner")

    def __init__(self, sid):
        self.sid = sid
        self.tokens = np.zeros(0, np.int32)
        self.pages: List[int] = []
        self.kv_len = 0
        self.digests: List[int] = []
        self.last_used = time.perf_counter()
        self.busy = False
        self.owner: Optional[int] = None   # owning request's rid


class ServingEngine:
    """Slot-based continuous batching over one compiled decode tick.

    Args:
      model: a ``GPTForCausalLM``-shaped model (``.gpt`` backbone with
        ``caches``/``cache_pos`` support, tied LM head).  A weight-only
        quantized model (``load_for_serving`` of a ``quant=`` artifact)
        serves through the same tick programs — its projections route to
        the fused dequant GEMM inside the jitted tick, halving the
        weight bytes every decode step streams (docs/SERVING.md,
        "Weight-only quantized serving").
      max_slots: concurrent request capacity (the static batch B).
      max_len: per-slot KV capacity; a request needs
        ``len(prompt) + max_new_tokens <= max_len - max(chunk, spec_k+1)``
        (headroom for the widest in-flight cache write).
      chunk: prefill chunk width per tick (decode uses 1 of it).
      temperature/top_k/top_p: engine-default sampling config (0.0 =
        greedy, matching ``model.generate(temperature=0.0)``
        token-for-token); :meth:`submit` may override per request.
      eos_token_id: optional early-stop token.
      spec_k: >0 enables speculative decoding — on all-decode ticks a
        drafter proposes up to ``spec_k`` tokens per slot and ONE fused
        verify program scores all ``spec_k+1`` positions, committing the
        longest prefix matching the target's greedy argmax (exact greedy
        equivalence; slots sampling at temperature>0 simply draft 0 and
        advance 1 token/tick).  Prefilling slots keep the chunk-wide
        program unchanged.  Acceptance counters land in ``stats``
        (``spec_ticks``/``spec_drafted``/``spec_accepted``).
      drafter: 'ngram' (model-free prompt-lookup, default), a small
        ``GPTForCausalLM`` draft model, or any object speaking the
        ``nn.decode`` drafter interface.
      cache_mode: "dense" (the historical per-slot ``max_slots x
        max_len`` regions) or "paged" — a global page pool
        (``num_pages x page_size`` KV rows per layer) with per-slot page
        tables.  Paged admission reserves each request's ACTUAL page
        footprint (``prompt + max_new`` plus the write-window reserve,
        in pages) instead of a whole ``max_len`` slot, so short requests
        stop stranding HBM and more streams fit the same pool
        (``inference/paged.py``; attention gathers through the table via
        ``incubate/nn/kernels/paged_attention.py`` — the Pallas decode
        kernel on TPU, a token-exact jnp reference elsewhere).
      page_size: KV rows per page (paged mode).  16 balances internal
        fragmentation (~page_size/2 rows wasted per request) against
        page-table width; keep it a multiple of 8 so the decode kernel
        engages (sublane alignment).
      num_pages: pool size INCLUDING the reserved null page 0.  Default
        ``max_slots * ceil(max_len/page_size) + 1`` (the dense worst
        case); size it down to your HBM budget — admission simply queues
        requests whose footprint doesn't fit yet.
      prefix_cache: keep finished prompts' full pages in a radix cache
        so a later request sharing a page-aligned prompt prefix (e.g. a
        system prompt) maps the same physical pages and prefills only
        its suffix (copy-on-write by recompute: the shared tail page is
        re-prefilled privately, so shared pages are never written).
      slo_window_s: span of the rolling TTFT/TPOT/e2e/queue-wait
        percentile windows :meth:`load_report` (and the ``/load``
        endpoint) publishes — "p99 over the last N seconds", the signal
        a least-loaded router dispatches on (docs/OBSERVABILITY.md,
        "SLO telemetry and the /load report").
      session_ttl_s: idle lifetime of a retained multi-turn session
        (``submit(session=)``); ``None`` (default) disables the TTL
        sweep — sessions then live until LRU/admission-pressure
        eviction, :meth:`drain`, or :meth:`drop_sessions`.
      max_sessions: LRU cap on retained sessions (docs/SERVING.md,
        "Multi-turn sessions").
      priority_aging_s: seconds of queue wait that promote a request
        one priority class (batch → default → interactive) — the
        anti-starvation guarantee under sustained higher-priority
        load; ``None`` disables aging (strict class order).
      prefill_budget: per-tick PREFILL token budget across slots
        (chunked-prefill fairness): prefill chunks are granted in
        priority order up to this many tokens per tick, the rest
        defer — a long batch prompt then interleaves with decode
        ticks instead of monopolizing every tick's width.  ``None``
        (default) = unbounded, the historical behavior.
      preempt: allow admission pressure to preempt a strictly
        lower-priority in-flight stream (release its pages, re-queue
        it; re-admission replays the committed tokens through the
        prefix/session cache — token-exact for greedy requests).
        Disabled automatically while draining and under pp.
      preempt_limit: max preemptions of one request (thrash bound);
        past it the request is never picked as a victim again.
        docs/SERVING.md, "Priority and preemption".
    """

    # bounded count of radix-cache chain digests the /load report's
    # prefix_digest block carries (class attr so a deployment with a
    # huge shared-prefix population can widen it)
    PREFIX_DIGEST_LIMIT = 64

    def __init__(self, model, max_slots=8, max_len=512, chunk=16,
                 temperature=0.0, top_k=None, eos_token_id=None,
                 auto_run=True, decode_window=8, top_p=None, spec_k=0,
                 drafter="ngram", cache_mode="dense", page_size=16,
                 num_pages=None, prefix_cache=True, slo_window_s=60.0,
                 session_ttl_s=None, max_sessions=64,
                 priority_aging_s=30.0, prefill_budget=None,
                 preempt=True, preempt_limit=2):
        import jax
        import jax.numpy as jnp

        model.eval()
        self.model = model
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.chunk = int(chunk)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.eos_token_id = eos_token_id
        self.auto_run = bool(auto_run)
        self._decode_window = max(1, min(int(decode_window), self.chunk))
        self.spec_k = int(spec_k)
        self._aging_s = (None if priority_aging_s is None
                         else float(priority_aging_s))
        # >= 1 so the highest-priority prefilling slot always makes
        # progress — a zero budget would stall every prefill forever
        self._prefill_budget = (None if prefill_budget is None
                                else max(1, int(prefill_budget)))
        self._preempt = bool(preempt)
        self._preempt_limit = max(0, int(preempt_limit))

        cfg = model.config
        self._head_dim = cfg.hidden_size // cfg.num_heads
        self._dtype = model.gpt.wte.weight._value.dtype

        params, bufs = model.functional_state()
        # the head ties wte, so the backbone owns every parameter
        self._params = {k[len("gpt."):]: v for k, v in params.items()
                        if k.startswith("gpt.")}
        self._bufs = {k[len("gpt."):]: v for k, v in bufs.items()
                      if k.startswith("gpt.")}
        self._mesh = model._param_mesh()
        self._pp = 1
        amb = self._ambient_pp_mesh()
        if amb is not None:
            self._mesh = amb
            self._pp = amb.shape["pp"]

        self._lock = make_lock("serving.engine")
        self._pending = collections.deque()
        # graceful-removal flag (drain()): submit refuses, queued +
        # inflight requests run to completion, then the loop idles out
        self._draining = False
        # terminal loop-crash record (the fail-all path stamps it): a
        # drain() in progress must report the crash — the backlog was
        # FAILED, not completed — instead of reading the emptied
        # slots/queue as a clean drain
        self._crashed = None
        # per-token streaming hand-off buffer: (req, token|None) pairs
        # appended under the engine lock by the commit/abort paths and
        # delivered by _flush_streams on the driver thread AFTER the
        # lock is released (a blocking on_token — bounded-queue
        # backpressure — must stall only the decode loop)
        self._stream_emit = []
        # bounded terminal-abort ring for /debug/requests: aborted
        # requests leave the slot table immediately, so the debug
        # surface needs its own short memory of WHERE they died
        self._recent_aborts = collections.deque(maxlen=32)
        # count of queued requests carrying a submit(deadline_s=): the
        # per-tick expiry sweep is gated on this, so the common
        # no-deadline case pays one int check, not an O(queue) scan
        self._deadline_queued = 0
        self._slots = [_Slot() for _ in range(self.max_slots)]
        self._lengths = np.zeros(self.max_slots, np.int32)
        self._inflight = {}  # wave -> (consumed, finishing, reqs) at entry
        self._running = False
        self._loop_thread = None
        self._tickno = 0
        # device-resident per-tick constants, rebuilt only when slot
        # membership / page tables change (tick-dispatch trim): a
        # steady-state decode tick then issues ONE program dispatch plus
        # the designed token fetch — no per-tick host->device staging of
        # unchanged sampling vectors or page tables
        self._sampling_cache = None
        self._sampling_dev = None
        self._pt_dev = None
        # MoE serving: per-token routing runs INSIDE the jitted tick (the
        # MoELayer MLP is cache-independent, so the dense/paged programs
        # need no structural change); arming collect_router_stats makes
        # each tick additionally return (mean router entropy, per-expert
        # load) which ride the tick's single designed fetch into the
        # moe_router_entropy / moe_expert_load histograms.  Eval routing
        # is DROPLESS (parallel/moe.py), so a token's output never
        # depends on which other slots share its tick batch — the
        # engine's token-exactness contract vs generate() holds for MoE.
        from ..parallel.moe import MoELayer as _MoELayer
        moe_layers = [l for l in model.sublayers(include_self=True)
                      if isinstance(l, _MoELayer)]
        self._moe = bool(moe_layers) and self._pp == 1
        self._moe_num_experts = (moe_layers[0].num_experts
                                 if moe_layers else 0)
        if self._moe:
            # armed for the MODEL's lifetime, deliberately: the flag is
            # read at trace time, so disarming on shutdown would break a
            # second live engine's next lazily-built tick flavor (it
            # expects the 3-output trace).  Cost to non-engine users of
            # the same model is nil where it matters — a jitted
            # generate() never consumes the stats, so XLA dead-code
            # eliminates them from the compiled program; only fully
            # eager forwards pay the per-layer entropy/load arithmetic.
            for l in moe_layers:
                l.collect_router_stats = True
        self._slo_window_s = float(slo_window_s)
        # weight-only quantized serving flag for the /load mode block —
        # by class NAME so the (Pallas-importing) quant module stays off
        # the unquantized engine's import path
        self._quantized = any(
            type(l).__name__ == "WeightOnlyLinear"
            for l in model.sublayers(include_self=True))
        self._init_metrics()
        # per-replica fault point name, precomputed (probed every tick)
        self._tick_fault_point = f"serving.tick[{self._engine_id}]"
        self._key = jax.random.key(0)

        self._spec = None
        if self.spec_k > 0 and self._pp > 1:
            import warnings
            warnings.warn("spec_k is not supported on the pipeline-"
                          "parallel tick yet; serving without "
                          "speculative decoding", stacklevel=2)
            self.spec_k = 0
        if self.spec_k > 0:
            from ..nn.decode import get_drafter
            self._spec = get_drafter(drafter, self.spec_k)
            self._spec.begin(self.max_slots, self.max_len)

        if cache_mode not in ("dense", "paged"):
            raise ValueError(f"cache_mode must be 'dense' or 'paged', "
                             f"got {cache_mode!r}")
        if cache_mode == "paged" and self._pp > 1:
            import warnings
            warnings.warn("cache_mode='paged' is not supported on the "
                          "pipeline-parallel tick yet; serving dense",
                          stacklevel=2)
            cache_mode = "dense"
        self.cache_mode = cache_mode
        self._paged = cache_mode == "paged"
        self._pool = self._prefix = None
        self._peak_occupancy = 0
        # multi-turn KV sessions (submit(session=)): sid -> _Session.
        # Works in dense mode too (conversation tokens + fleet
        # stickiness; only paged mode retains KV pages to resume from)
        self._sessions = {}
        self._session_ttl_s = (None if session_ttl_s is None
                               else float(session_ttl_s))
        self._max_sessions = int(max_sessions)
        # page-pool defrag/compaction: while a compaction's device copy
        # is in flight (driver thread, unlocked), admission must not
        # hand out pages the move plan treats as free
        self._defrag_busy = False
        self._defrag_fn = None
        if self._paged:
            from .paged import PagePool, PrefixCache
            self._page_size = int(page_size)
            if self._page_size < 1:
                raise ValueError("page_size must be >= 1")
            self._pages_per_slot = -(-self.max_len // self._page_size)
            if num_pages is None:
                num_pages = self.max_slots * self._pages_per_slot + 1
            self._pool = PagePool(int(num_pages), self._page_size)
            if prefix_cache:
                self._prefix = PrefixCache(self._pool)
            self._page_tables = np.zeros(
                (self.max_slots, self._pages_per_slot), np.int32)
            self._slot_pages = [[] for _ in range(self.max_slots)]
            self._g_pages_free.set(self._pool.free)
            self._defrag_fn = self._build_defrag_fn()

        if self._pp > 1:
            self._build_pp_tick()
        else:
            self._build_tick()
        self._alloc_caches(jnp)
        # declare this engine shared for the race sanitizer (zero cost
        # when off — returns self untouched).  atomic: _tickno is read
        # lock-free by its only writer, the driver thread (the same
        # single-aligned-read contract the `# pht-lint: gil-atomic`
        # annotations on the _run_tick* read sites claim statically).
        # _caches/_sampling_dev/_pt_dev/_xbuf are DRIVER-OWNED device
        # staging: touched lock-free on the tick path by design
        # (staging under _lock would be PHT003 lock-across-dispatch)
        # and invalidated under the lock by admission/release — safe
        # because the single-driver guard serializes every driver, and
        # driver handoff (loop exit -> next burst's fresh loop thread,
        # or sync step()) happens through _lock/_running; the Eraser
        # model only tolerates ONE silent owner handoff, and fleet
        # traffic restarts the loop thread per burst, so these are
        # declared rather than false-flagged on the third driver.
        share_object(self, f"serving.engine[{self._engine_id}]",
                     atomic=("_tickno", "_caches", "_sampling_dev",
                             "_pt_dev", "_xbuf"))

    # ------------------------------------------------------------------
    def _init_metrics(self):
        """Register this engine's telemetry series (metric catalog:
        docs/OBSERVABILITY.md).  One ``engine`` label per instance keeps
        concurrently-live engines (tests, A/B deploys) from mixing
        series; ``self.stats`` stays the historical dict-shaped view."""
        reg = self._registry = _obs.get_registry()
        self._engine_id = f"e{next(_ENGINE_IDS)}"
        lbl = {"engine": self._engine_id}
        counters = {
            "ticks": reg.counter(
                "serving_ticks_total", "engine ticks run"),
            "tokens": reg.counter(
                "serving_tokens_total", "generated tokens committed"),
            "requests": reg.counter(
                "serving_requests_total", "requests submitted"),
            "spec_ticks": reg.counter(
                "serving_spec_ticks_total", "speculative verify ticks"),
            "spec_drafted": reg.counter(
                "serving_spec_drafted_total",
                "draft tokens proposed (capped at request budget)"),
            "spec_accepted": reg.counter(
                "serving_spec_accepted_total",
                "draft tokens accepted AND committed"),
            "prefix_hit_tokens": reg.counter(
                "serving_prefix_hit_tokens_total",
                "prompt tokens served from cached prefix pages "
                "(re-prefill skipped; paged cache mode only)"),
            "prompt_tokens": reg.counter(
                "serving_prompt_tokens_total",
                "prompt tokens of admitted requests (all cache modes)"),
            # goodput pair: generated tokens that reached a COMPLETED
            # request vs tokens burned on requests the engine failed
            # (loop crash fail-all) — completed/(completed+aborted) is
            # the /load report's goodput ratio
            "completed_tokens": reg.counter(
                "serving_completed_tokens_total",
                "generated tokens of requests that finished"),
            "aborted_tokens": reg.counter(
                "serving_aborted_tokens_total",
                "generated tokens of requests that failed/aborted "
                "(work the caller never got)"),
            # multi-turn sessions (submit(session=)): resumes and the
            # history tokens those resumes served straight from retained
            # pages — the turn-N TTFT win the serving_chat bench gates
            "session_resumes": reg.counter(
                "serving_session_resumes_total",
                "turns resumed from a retained session's KV pages"),
            "session_hit_tokens": reg.counter(
                "serving_session_hit_tokens_total",
                "prompt tokens served from retained session pages "
                "(re-prefill skipped; paged cache mode only)"),
            "sessions_evicted": reg.counter(
                "serving_sessions_evicted_total",
                "retained sessions evicted (TTL/LRU/admission "
                "pressure/drain/drop)"),
            "defrag_total": reg.counter(
                "serving_defrag_total",
                "KV page-pool compactions run"),
            "defrag_pages_moved": reg.counter(
                "serving_defrag_pages_moved_total",
                "KV pages relocated by pool compactions"),
            # SLO scheduler (submit(priority=)): preempted streams are
            # RE-QUEUED, not aborted — their committed tokens replay on
            # resume, so goodput (completed vs aborted) must not move
            "preemptions": reg.counter(
                "serving_preemptions_total",
                "in-flight streams preempted by higher-priority "
                "admission (pages released/demoted, request re-queued)"),
            "preempt_replay_tokens": reg.counter(
                "serving_preempt_replay_tokens_total",
                "committed rows re-prefilled when a preempted stream "
                "resumed (rows the prefix/session cache did not cover "
                "— the preemption cost the cache could not absorb)"),
        }
        self._c = {k: fam.labels(**lbl) for k, fam in counters.items()}
        self.stats = _EngineStats(self._c)
        self._h_ttft = reg.histogram(
            "serving_ttft_seconds",
            "submit to first generated token", unit="s").labels(**lbl)
        self._h_tpot = reg.histogram(
            "serving_tpot_seconds",
            "mean inter-token latency past the first token",
            unit="s").labels(**lbl)
        self._h_e2e = reg.histogram(
            "serving_e2e_seconds",
            "submit to request completion", unit="s").labels(**lbl)
        tick_fam = reg.histogram(
            "serving_tick_seconds",
            "device tick wall time by program flavor", unit="s")
        self._h_tick = {f: tick_fam.labels(flavor=f, **lbl)
                        for f in ("prefill", "decode", "spec", "pp")}
        self._h_accept = reg.histogram(
            "serving_spec_accept_ratio",
            "per-spec-tick accepted/drafted ratio",
            buckets=_obs.RATIO_BUCKETS).labels(**lbl)
        self._g_occupancy = reg.gauge(
            "serving_batch_occupancy",
            "slots holding an active request this tick").labels(**lbl)
        self._g_queue = reg.gauge(
            "serving_queue_depth", "requests waiting for a slot").labels(**lbl)
        # per-priority-class queue depth: a shallow TOTAL queue can hide
        # an interactive queue starving behind a deep batch queue — the
        # router's least-loaded scoring needs the split (three bounded
        # children per engine, not a per-request series)
        cls_fam = reg.gauge(
            "serving_class_queue_depth",
            "queued requests per priority class")
        self._g_class_queue = {
            c: cls_fam.labels(**{"class": c}, **lbl) for c in PRIORITY_RANK}
        # achieved weight HBM: every param/buffer array the tick programs
        # stream per token (int8 quantization should read ~half the bf16
        # bytes — the serving_int8 bench row embeds this as evidence).
        # .nbytes is shape math on the jax Array, not a transfer.
        self._g_weight_bytes = reg.gauge(
            "serving_weight_bytes",
            "model weight bytes resident for the decode tick "
            "(params + quant scales + buffers)").labels(**lbl)
        self._g_weight_bytes.set(
            sum(int(v.nbytes) for v in self._params.values())
            + sum(int(v.nbytes) for v in self._bufs.values()))
        # paged-KV pool gauges (stay 0 in dense mode): admission headroom
        # and the leak tripwire tools/perf_gate.py reads off the bench row
        self._g_pages_used = reg.gauge(
            "serving_kv_pages_in_use",
            "KV pool pages currently allocated").labels(**lbl)
        self._g_pages_free = reg.gauge(
            "serving_kv_pages_free",
            "KV pool pages on the free list").labels(**lbl)
        # multi-turn session retention (docs/SERVING.md): how many
        # conversations this replica holds warm, and the pages they pin
        # (distinct — sessions can share prompt pages via the cache)
        self._g_sessions = reg.gauge(
            "serving_sessions_retained",
            "multi-turn KV sessions currently retained").labels(**lbl)
        self._g_session_pages = reg.gauge(
            "serving_session_pages_retained",
            "distinct KV pages pinned by retained sessions").labels(**lbl)
        # MoE router telemetry (registered only for MoE engines so dense
        # engines don't grow empty series): entropy distribution + one
        # per-expert load-share histogram — a hot expert shows up as its
        # series' mass moving right while the others move left
        self._h_moe_ent = None
        self._h_moe_load = ()
        if self._moe:
            self._h_moe_ent = reg.histogram(
                "moe_router_entropy",
                "mean per-token router entropy per MoE decode tick "
                "(nats; ln(num_experts) = uniform routing)").labels(**lbl)
            load_fam = reg.histogram(
                "moe_expert_load",
                "per-tick fraction of kept (dispatched) token slots "
                "routed to each expert", buckets=_obs.RATIO_BUCKETS)
            self._h_moe_load = tuple(
                load_fam.labels(expert=str(e), **lbl)
                for e in range(self._moe_num_experts))
        # event-level observability: always-on flight ring (request
        # lifecycle marks + tick summaries feed the crash post-mortem)
        # and the /debug/requests slot table (weakly registered — a
        # dropped engine vanishes from the endpoint)
        self._flight = _flight.get_flight_recorder()
        _tr.register_introspection_source(self._engine_id, self)
        # rolling SLO windows (NOT registry families: per-engine working
        # state, no labels, exact "last N seconds" semantics the
        # lifetime histograms cannot give) — the percentile source for
        # load_report()/the /load endpoint.  queue_wait feeds at admit,
        # ttft at first token, tpot per decode tick, e2e at finish.
        self._slo = {k: _obs.SlidingWindowHistogram(
            window_s=self._slo_window_s)
            for k in ("ttft", "tpot", "e2e", "queue_wait")}
        # per-priority-class ttft/queue-wait windows: the control signal
        # the SLO scheduler is judged by ("interactive ttft p99 under
        # mixed load"), published via /load's slo.classes block — 3x2
        # bounded windows, same exact last-N-seconds semantics
        self._slo_cls = {c: {k: _obs.SlidingWindowHistogram(
            window_s=self._slo_window_s) for k in ("ttft", "queue_wait")}
            for c in PRIORITY_RANK}
        # /load registration: the engine IS its own load source, and the
        # same report rides /debug/requests under "<eid>.load" via a
        # strongly-held adapter (both registries are weak — a dropped
        # engine vanishes from the endpoints without unregister)
        _tr.register_load_source(self._engine_id, self)
        self._load_debug = _LoadDebugSource(self)
        _tr.register_introspection_source(f"{self._engine_id}.load",
                                          self._load_debug)

    @property
    def engine_id(self) -> str:
        """Stable per-process replica name (``e<N>``): the label on this
        engine's metric series, its ``/load`` + ``/debug/requests``
        registrations, its liveness beacon (``serving.<id>``) and its
        per-replica fault point (``serving.tick[<id>]``) — the handle a
        fleet router addresses this replica by."""
        return self._engine_id

    @property
    def draining(self) -> bool:
        return self._draining

    # ------------------------------------------------------------------
    @staticmethod
    def _ambient_pp_mesh():
        from ..parallel.api import get_mesh
        m = get_mesh()
        if m is not None and m.shape.get("pp", 1) > 1:
            return m
        return None

    def _alloc_caches(self, jnp):
        import jax
        cfg = self.model.config
        if self._paged:
            # one global page pool per layer: pages are slot-agnostic, so
            # there is no batch dim to shard — heads ride 'mp' (the qkv
            # projection's natural output sharding), pages replicate over
            # the data axes (parallel/api.py page_pool_sharding)
            shape = (self._pool.num_pages, self._page_size,
                     cfg.num_heads, self._head_dim)
            sh = None
            if self._mesh is not None:
                from ..parallel.api import page_pool_sharding
                sh = page_pool_sharding(self._mesh)
            put = (lambda a: jax.device_put(a, sh)) if sh is not None \
                else (lambda a: a)
            self._caches = [(put(jnp.zeros(shape, self._dtype)),
                             put(jnp.zeros(shape, self._dtype)))
                            for _ in range(cfg.num_layers)]
            return
        B, L = self.max_slots, self.max_len
        shape = (B, L, cfg.num_heads, self._head_dim)
        if self._pp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            zeros = jnp.zeros((cfg.num_layers,) + shape, self._dtype)
            sh = NamedSharding(self._mesh, P("pp"))
            self._caches = (jax.device_put(zeros, sh),
                            jax.device_put(zeros, sh))
            return
        sh = None
        if self._mesh is not None:
            from ..parallel.api import decode_cache_sharding
            sh = decode_cache_sharding(self._mesh)
        mk = lambda: jnp.zeros(shape, self._dtype)  # noqa: E731
        put = (lambda a: jax.device_put(a, sh)) if sh is not None else \
            (lambda a: a)
        self._caches = [(put(mk()), put(mk()))
                        for _ in range(cfg.num_layers)]

    # ------------------------------------------------------------------
    def _build_tick(self):
        """Single/mp-sharded tick: one fused program = embed + blocks
        with per-slot cache writes + last-valid gather + head + sample.

        Two program widths are kept (jit cache by token-chunk width):
        the chunk-wide program runs only on ticks where some slot is
        prefilling; steady-state decode ticks run the width-1 program —
        otherwise every decode tick would compute ``chunk`` columns for
        one valid token (measured 3.2k vs 12.2k device tok/s at b8)."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        from ..nn.layer import functional_call
        from ..parallel.moe import collect_router_stats as _moe_stats

        model = self.model
        bufs = self._bufs
        moe = self._moe

        def mk_tick(sample):
            # pt=None compiles the dense trace; the paged engine passes
            # its (B, pages_per_slot) page table every tick (host numpy —
            # tiny — so admission/free only ever touch host state)
            def tick(params, caches, tokens, starts, nvalid, temps, topks,
                     topps, key, tickno, pt=None):
                hidden, caches = functional_call(
                    model.gpt, params, (Tensor(tokens),),
                    kwargs={"caches": caches, "cache_pos": starts,
                            "page_table": pt},
                    buffers=bufs, training=False)
                last = jnp.take_along_axis(
                    hidden, (nvalid - 1).astype(jnp.int32)[:, None, None],
                    axis=1)[:, 0]  # (B, h): each slot's last valid position
                logits = last @ params["wte.weight"].T
                # path tag 0: the single-step and multi-step programs must
                # draw from disjoint PRNG domains (tickno vs tickno*M+t
                # counters would otherwise collide for temperature>0)
                nxt = sample(
                    logits, temps, topks, topps,
                    jax.random.fold_in(jax.random.fold_in(key, 0), tickno))
                toks = nxt[:, 0].astype(jnp.int32)
                if moe:
                    # router stats left on the layers by the forward just
                    # traced — returned as program outputs so they ride
                    # the tick's single designed fetch
                    return caches, toks, _moe_stats(model.gpt)
                return caches, toks
            return sanitize_donation(jax.jit(tick, donate_argnums=(1,)),
                                     donate_argnums=(1,),
                                     site="serving.tick")

        self._tick, self._tick_mk = {}, mk_tick

        # multi-step decode window: when NO slot is prefilling, one tick
        # runs M in-program decode steps (lax.fori_loop with in-jit
        # sampling feedback), amortizing per-tick program overheads the
        # way generate()'s fused loop does — scheduling granularity drops
        # to M ticks, a standard serving trade (single-step: 7.1k device
        # tok/s at b8; window=8: 9.1k; the fused loop: 12.2k)
        M = self._decode_window

        E = self._moe_num_experts

        def mk_tick_multi(sample):
            def tick_multi(params, caches, last_tok, starts, temps, topks,
                           topps, key, tickno, pt=None):
                B = last_tok.shape[0]
                outbuf = jnp.zeros((B, M), jnp.int32)

                def body(t, carry):
                    if moe:
                        caches, cur, outbuf, acc = carry
                    else:
                        caches, cur, outbuf = carry
                    hidden, caches = functional_call(
                        model.gpt, params, (Tensor(cur[:, None]),),
                        kwargs={"caches": caches,
                                "cache_pos": starts + t.astype(jnp.int32),
                                "page_table": pt},
                        buffers=bufs, training=False)
                    logits = hidden[:, 0] @ params["wte.weight"].T
                    nxt = sample(
                        logits, temps, topks, topps,
                        jax.random.fold_in(jax.random.fold_in(key, 1),
                                           tickno * M + t)
                    )[:, 0].astype(jnp.int32)
                    outbuf = jax.lax.dynamic_update_slice(
                        outbuf, nxt[:, None],
                        (jnp.zeros((), jnp.int32), t.astype(jnp.int32)))
                    if moe:
                        # accumulate the in-loop steps' router stats in
                        # the carry (the side-channel values are local to
                        # each body trace; only the carry survives)
                        e, l = _moe_stats(model.gpt)
                        return caches, nxt, outbuf, (acc[0] + e, acc[1] + l)
                    return caches, nxt, outbuf

                if moe:
                    # per-token accumulators (B rows, width 1 per step):
                    # the engine masks inactive slots after the fetch
                    zero = (jnp.zeros((B,), jnp.float32),
                            jnp.zeros((B, E), jnp.float32))
                    caches, _, outbuf, acc = jax.lax.fori_loop(
                        0, M, body, (caches, last_tok, outbuf, zero))
                    return caches, outbuf, (acc[0] / M, acc[1] / M)
                caches, _, outbuf = jax.lax.fori_loop(
                    0, M, body, (caches, last_tok, outbuf))
                return caches, outbuf
            return sanitize_donation(
                jax.jit(tick_multi, donate_argnums=(1,)),
                donate_argnums=(1,), site="serving.tick_multi")

        self._tick_multi, self._tick_multi_mk = {}, mk_tick_multi

        if self.spec_k > 0:
            self._build_spec_tick()

    def _mk_sampler(self, skey):
        """The per-tick sampling closure, in static flavors compiled as
        separate programs.  ``skey=False`` bakes the engine-global scalar
        config (the historical single-argmax/top-k trace — no per-row
        sort/nucleus work on the hot path).  ``skey=(tk_on, tp_on)``
        routes the per-slot override vectors through ``_sample``'s vector
        mode, with the top-k sort and the nucleus softmax/cumsum each
        compiled in only when some row actually enables that filter.
        ``_sampling_vectors`` picks the flavor per tick, so engines whose
        requests never override sampling never even compile a vector
        variant."""
        model = self.model
        if skey is False:
            t, k, p = self.temperature, self.top_k, self.top_p

            def sample(logits, temps, topks, topps, key):
                return model._sample(logits, t, k, top_p=p, key=key)
            return sample
        tk_on, tp_on = skey

        def sample(logits, temps, topks, topps, key):
            return model._sample(logits, temps,
                                 topks if tk_on else None,
                                 top_p=topps if tp_on else None, key=key)
        return sample

    def _prog(self, name, skey):
        """Build-or-reuse the jitted ``name`` program for sampler flavor
        ``skey`` (flavors compile lazily on first use).  Every program is
        wrapped by ``observability.instrument_jit`` so builds — including
        shape-keyed retraces inside one flavor, e.g. the width-1 vs
        chunk-wide tick — land in ``jit_builds_total{site=serving.*}``:
        the recompilation-regression tripwire tools/perf_gate.py gates."""
        cache = getattr(self, name)
        fn = cache.get(skey)
        if fn is None:
            fn = cache[skey] = _obs.instrument_jit(
                getattr(self, name + "_mk")(self._mk_sampler(skey)),
                site=f"serving.{name.lstrip('_')}", engine=self._engine_id)
        return fn

    def _build_spec_tick(self):
        """Fused speculative VERIFY tick: score all ``spec_k+1`` positions
        of every decoding slot in one program over the same static-cache
        ``cache_pos`` write path the chunk program uses.  Position 0
        samples per-slot (greedy slots: argmax — the committed bonus
        token); positions >=1 are the greedy references the host-side
        acceptance compares drafts against.  Rejected tails need no cache
        rollback: the engine simply advances ``_lengths`` by accepted+1,
        and the next program rewrites ``[length, length+K]`` before any
        query can attend the stale rows (kpos <= qpos masking)."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        from ..nn.layer import functional_call
        from ..parallel.moe import collect_router_stats as _moe_stats

        model = self.model
        bufs = self._bufs
        K = self.spec_k
        moe = self._moe

        def mk_tick_spec(sample):
            def tick_spec(params, caches, tokens, starts, temps, topks,
                          topps, key, tickno, pt=None):
                B = tokens.shape[0]
                hidden, caches = functional_call(
                    model.gpt, params, (Tensor(tokens),),
                    kwargs={"caches": caches, "cache_pos": starts,
                            "page_table": pt},
                    buffers=bufs, training=False)
                logits = hidden @ params["wte.weight"].T  # (B, K+1, V)
                # position 0 is the committed bonus/sampled token — it
                # samples per slot config (path tag 3: disjoint PRNG
                # domain from the other programs); positions >= 1 exist
                # only as greedy references for acceptance (and as the
                # committed tokens of greedy slots) — one batched argmax,
                # the same scalar-greedy math generate()'s verify uses
                first = sample(
                    logits[:, 0], temps, topks, topps,
                    jax.random.fold_in(jax.random.fold_in(key, 3), tickno))
                ref = model._sample(
                    logits[:, 1:].reshape(B * K, -1), 0.0, None)
                out = jnp.concatenate([first, ref.reshape(B, K)], axis=1)
                out = out.astype(jnp.int32)
                if moe:
                    return caches, out, _moe_stats(model.gpt)
                return caches, out
            return sanitize_donation(
                jax.jit(tick_spec, donate_argnums=(1,)),
                donate_argnums=(1,), site="serving.tick_spec")

        self._tick_spec, self._tick_spec_mk = {}, mk_tick_spec

    def _sampling_vectors(self):
        """Per-slot (skey, temperature, top_k, top_p) for the tick
        programs: the engine defaults, overridden by each slot's request
        (the per-request sampling API).  ``skey`` is False when no
        active request overrides anything — the tick then runs the
        scalar-config program (the cheap argmax/top-k trace); otherwise
        it is a ``(top_k_live, top_p_live)`` pair selecting a vector-mode
        program that compiles only the filters some row enables.
        Encodings match ``_sample``'s vector mode: top_k=0 / top_p=1.0 =
        filter off.

        Cached until admission/finish changes slot membership; the
        device-side copies (:meth:`_sampling_dev3`) share the cache's
        lifetime, so steady-state ticks reuse resident arrays instead of
        paying three H2D stagings per tick (tick-dispatch trim).  This
        runs under the engine lock and is host-only — the device staging
        happens in the unlocked tick runners (PHT003: no device dispatch
        under ``_lock``)."""
        if self._sampling_cache is not None:
            return self._sampling_cache
        B = self.max_slots
        temps = np.full(B, self.temperature, np.float32)
        topks = np.full(B, 0 if self.top_k is None else int(self.top_k),
                        np.int32)
        topps = np.full(B, 1.0 if self.top_p is None else float(self.top_p),
                        np.float32)
        vec = False
        for i, slot in enumerate(self._slots):
            req = slot.req
            if req is None:
                continue
            if req.temperature is not None:
                temps[i] = req.temperature
            if req.top_k is not None:
                topks[i] = req.top_k
            if req.top_p is not None:
                topps[i] = req.top_p
            vec = vec or (req.temperature is not None
                          or req.top_k is not None
                          or req.top_p is not None)
        skey = (bool((topks != 0).any()),
                bool((topps != 1.0).any())) if vec else False
        self._sampling_cache = (skey, temps, topks, topps)
        self._sampling_dev = None
        return self._sampling_cache

    def _sampling_dev3(self, sampling):
        """Device-resident (temps, topks, topps) for the tick programs,
        staged once per membership change (called OUTSIDE the engine
        lock, from the tick runners only — single-driver contract)."""
        if self._sampling_dev is None:
            import jax
            self._sampling_dev = tuple(
                jax.device_put(v) for v in sampling[1:4])
        return self._sampling_dev

    def _pt_kw(self):
        """Extra program kwargs: the current page table (paged mode),
        staged to device only when admission/release changed it — the
        decode steady state reuses the resident copy."""
        if not self._paged:
            return {}
        # driver-owned staging, read lock-free by design: writers that
        # INVALIDATE (_pt_dev = None on admission/release/defrag) hold
        # the lock, but the restage here runs only on the single-driver
        # tick path — mirrored in share_object's atomic= declaration
        if self._pt_dev is None:  # pht-lint: gil-atomic
            import jax.numpy as jnp
            self._pt_dev = jnp.asarray(self._page_tables)  # pht-lint: gil-atomic
        return {"pt": self._pt_dev}

    # pht-lint: hot-root (MoE decode tick path — per-tick stats observe)
    def _observe_moe(self, st, mask):
        """Record a tick's router stats (host values — they rode the
        tick's designed fetch).  ``st`` is the layer-averaged PER-TOKEN
        (entropy (n,), kept-slot counts (n, E)) pair; ``mask`` (same
        row order as the tick's token batch, flattened) selects the
        rows that belong to an ACTIVE slot's real positions — inactive
        slots' scratch rows and prefill padding route garbage every
        tick, and letting them into the histograms at partial occupancy
        would fake the expert-collapse signals operators alarm on.
        No-op for dense engines/None stats."""
        if st is None or self._h_moe_ent is None:
            return
        mask = np.asarray(mask).reshape(-1)
        if not mask.any():
            return
        ent, load = st
        ent = np.asarray(ent).reshape(-1)[mask]
        load = np.asarray(load).reshape(mask.shape[0], -1)[mask]
        self._h_moe_ent.observe(float(ent.mean()))
        counts = load.sum(0)
        tot = max(float(counts.sum()), 1.0)
        for child, cnt in zip(self._h_moe_load, counts):
            child.observe(float(cnt) / tot)

    def _run_tick(self, tokens, starts, nvalid, sampling, active):
        import jax
        vec = sampling[0]
        temps_d, topks_d, topps_d = self._sampling_dev3(sampling)
        width = 1 if int(np.max(nvalid)) <= 1 else self.chunk
        # host numpy args (tokens/starts/nvalid/tickno) ride the ONE
        # jitted dispatch's H2D; the sampling vectors are already
        # resident (tick-dispatch trim)
        out = self._prog("_tick", vec)(
            self._params, self._caches, tokens[:, :width],
            starts, nvalid, temps_d, topks_d, topps_d, self._key,
            # single aligned int read by its only writer (driver thread)
            np.int32(self._tickno), **self._pt_kw())  # pht-lint: gil-atomic
        # the tick's ONE designed device->host fetch: explicit, so the
        # transfer-guard sanitizer (observability/sanitizers.py) can
        # tell it from an accidental implicit sync (MoE router stats
        # ride the same single fetch)
        if self._moe:
            self._caches, nxt, st = out
            nxt, st = jax.device_get((nxt, st))
            # valid rows: active slots' first nvalid positions (decode
            # rows are width 1; prefill rows beyond the chunk's valid
            # span are padding)
            self._observe_moe(st, active[:, None]
                              & (np.arange(width)[None, :]
                                 < nvalid[:, None]))
            return nxt
        self._caches, nxt = out
        return jax.device_get(nxt)

    def _run_tick_spec(self, tokens, starts, sampling, active=None,
                       ndraft=None):
        import jax
        import jax.numpy as jnp
        vec = sampling[0]
        temps_d, topks_d, topps_d = self._sampling_dev3(sampling)
        toks_j, starts_j = jnp.asarray(tokens), jnp.asarray(starts)
        if self._mesh is not None:
            # place the widened (B, K+1) verify block on the KV cache's
            # batch layout up front — GSPMD then needs no reshard before
            # the in-program per-slot cache writes
            from ..parallel.api import token_batch_sharding
            sh = token_batch_sharding(self._mesh)
            toks_j = jax.device_put(toks_j, sh)
            starts_j = jax.device_put(starts_j, sh)
        res = self._prog("_tick_spec", vec)(
            self._params, self._caches, toks_j, starts_j,
            temps_d, topks_d, topps_d,
            # single aligned int read by its only writer (driver thread)
            self._key, np.int32(self._tickno),  # pht-lint: gil-atomic
            **self._pt_kw())
        # designed once-per-tick fetch (see _run_tick)
        if self._moe:
            self._caches, out, st = res
            out, st = jax.device_get((out, st))
            # valid rows: active slots' bonus token + their real drafts
            # (positions past ndraft are stale draft padding)
            B, W = np.asarray(tokens).shape
            act = (np.ones(B, bool) if active is None
                   else np.asarray(active, bool))
            nd = (np.full(B, W - 1) if ndraft is None
                  else np.asarray(ndraft))
            self._observe_moe(
                st, act[:, None] & (np.arange(W)[None, :]
                                    <= nd[:, None]))
            return out
        self._caches, out = res
        return jax.device_get(out)

    # ------------------------------------------------------------------
    def _build_pp_tick(self):
        """Interleaved-wave pipelined tick (see module docstring)."""
        import functools

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..core.tensor import Tensor
        from ..models.gpt import param_sharding_spec
        from ..nn.layer import functional_call
        from ..parallel._smap import run_shard_map
        from ..parallel.api import stack_block_params

        model = self.model
        cfg = model.config
        mesh = self._mesh
        pp = self._pp
        if self.max_slots % pp:
            raise ValueError(
                f"max_slots={self.max_slots} must divide into pp={pp} waves")
        if cfg.num_layers % pp:
            raise ValueError(
                f"num_layers={cfg.num_layers} must divide over pp={pp}")
        self._wave = Bw = self.max_slots // pp
        C = self.chunk
        max_pos = cfg.max_position_embeddings

        prefix = model.pipeline_stage_spec()["block_prefix"]
        other, stacked = stack_block_params(
            model, mesh, param_sharding_spec, prefix, cfg.num_layers)
        self._pp_other, self._pp_stacked = other, stacked

        template = model.gpt.blocks[0]
        ln_f = model.gpt.ln_f

        def stage_chunk(st, kc, vc, x, pos):
            def body(xc, inp):
                lp, k1, v1 = inp
                y, (nk, nv) = functional_call(
                    template, lp, (Tensor(xc),),
                    kwargs={"cache": (k1, v1), "cache_pos": pos},
                    training=False)
                return y, (nk, nv)
            y, (nk, nv) = jax.lax.scan(body, x, (st, kc, vc))
            return y, nk, nv

        def spmd(sample, st_local, kcache, vcache, xbuf, tokens, starts,
                 nvalid, temps, topks, topps, wave_of_stage, other_p,
                 key, tickno):
            # kcache/vcache: (L_local, B, T, H, D) — this stage's layer
            #   slab over the FULL slot batch (a stage touches only its
            #   current wave's rows each tick).
            # xbuf: (1, Bw, C, h) local — the activation ppermuted here
            #   at the END of last tick (stage 0 replaces it with the
            #   entering wave's embedding).
            stage = jax.lax.axis_index("pp")
            wave = wave_of_stage[stage]  # my wave this tick
            sl0 = (wave * Bw).astype(jnp.int32)
            tok_w = jax.lax.dynamic_slice(
                tokens, (sl0, jnp.zeros((), jnp.int32)), (Bw, C))
            st_w = jax.lax.dynamic_slice(starts, (sl0,), (Bw,))
            nv_w = jax.lax.dynamic_slice(nvalid, (sl0,), (Bw,))

            pos_idx = jnp.clip(
                st_w[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :],
                0, max_pos - 1)
            emb = (jnp.take(other_p["gpt.wte.weight"], tok_w, axis=0)
                   + jnp.take(other_p["gpt.wpe.weight"], pos_idx, axis=0))
            x = jnp.where(stage == 0, emb.astype(xbuf.dtype), xbuf[0])

            kc_w = jax.lax.dynamic_slice_in_dim(kcache, sl0, Bw, axis=1)
            vc_w = jax.lax.dynamic_slice_in_dim(vcache, sl0, Bw, axis=1)
            y, nk, nv = stage_chunk(st_local, kc_w, vc_w, x, st_w)
            kcache = jax.lax.dynamic_update_slice_in_dim(
                kcache, nk.astype(kcache.dtype), sl0, axis=1)
            vcache = jax.lax.dynamic_update_slice_in_dim(
                vcache, nv.astype(vcache.dtype), sl0, axis=1)

            # head + sample run on every stage (uniform SPMD; the
            # (Bw,h)x(h,V) head is noise next to the layer slab) but only
            # the LAST stage's — the exiting wave's — sample is real
            xn = functional_call(
                ln_f, {"weight": other_p["gpt.ln_f.weight"],
                       "bias": other_p["gpt.ln_f.bias"]},
                (Tensor(y),), training=False)
            hid = jnp.take_along_axis(
                xn, (nv_w - 1).astype(jnp.int32)[:, None, None],
                axis=1)[:, 0]
            logits = hid @ other_p["gpt.wte.weight"].T
            nxt = sample(
                logits,
                jax.lax.dynamic_slice(temps, (sl0,), (Bw,)),
                jax.lax.dynamic_slice(topks, (sl0,), (Bw,)),
                jax.lax.dynamic_slice(topps, (sl0,), (Bw,)),
                jax.random.fold_in(jax.random.fold_in(key, 2), tickno)
            )[:, 0].astype(jnp.int32)
            is_exit = stage == pp - 1
            out = jnp.zeros((pp * Bw,), jnp.int32)
            out = jax.lax.dynamic_update_slice(
                out, jnp.where(is_exit, nxt, 0), (sl0,))
            out = jax.lax.psum(
                jnp.where(is_exit, out, jnp.zeros_like(out)), "pp")
            y = jax.lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)])
            return kcache, vcache, y[None], out

        st_specs = jax.tree.map(lambda _: P("pp"), stacked)
        other_specs = jax.tree.map(lambda _: P(), other)

        def mk_tick(sample):
            spmd_s = functools.partial(spmd, sample)

            def tick(stacked_p, kc, vc, xbuf, tokens, starts, nvalid,
                     temps, topks, topps, wave_of_stage, other_p, key,
                     tickno):
                return run_shard_map(
                    spmd_s, mesh,
                    in_specs=(st_specs, P("pp"), P("pp"), P("pp"),
                              P(), P(), P(), P(), P(), P(), P(),
                              other_specs, P(), P()),
                    out_specs=(P("pp"), P("pp"), P("pp"), P()),
                    manual_axes={"pp"},
                    args=(stacked_p, kc, vc, xbuf, tokens, starts, nvalid,
                          temps, topks, topps, wave_of_stage, other_p, key,
                          tickno))
            return sanitize_donation(
                jax.jit(tick, donate_argnums=(1, 2, 3)),
                donate_argnums=(1, 2, 3), site="serving.pp_tick")

        self._pp_tick, self._pp_tick_mk = {}, mk_tick
        self._xbuf = jax.device_put(
            jnp.zeros((pp, Bw, C, cfg.hidden_size), self._dtype),
            NamedSharding(mesh, P("pp")))

    def _run_pp_tick(self, tokens, starts, nvalid, sampling):
        import jax
        import jax.numpy as jnp
        pp = self._pp
        vec = sampling[0]
        temps_d, topks_d, topps_d = self._sampling_dev3(sampling)
        # wave at stage s this tick entered stage 0 s ticks ago (tickno:
        # single aligned int read by its only writer, the driver thread)
        wave_of_stage = np.array(
            [(self._tickno - s) % pp for s in range(pp)],  # pht-lint: gil-atomic
            np.int32)
        kc, vc = self._caches
        # partial-manual shard_map (pp manual, dp/mp auto) needs the
        # ambient mesh — same contract as _run_decode_program
        from jax import set_mesh as _set_mesh
        with _set_mesh(self._mesh):
            kc, vc, self._xbuf, nxt = self._prog("_pp_tick", vec)(
                self._pp_stacked, kc, vc, self._xbuf, jnp.asarray(tokens),
                jnp.asarray(starts), jnp.asarray(nvalid),
                temps_d, topks_d, topps_d,
                jnp.asarray(wave_of_stage), self._pp_other, self._key,
                np.int32(self._tickno))  # pht-lint: gil-atomic
        self._caches = (kc, vc)
        # designed once-per-tick fetch (see _run_tick)
        return jax.device_get(nxt)

    # ------------------------------------------------------------------
    # scheduling
    def submit(self, prompt, max_new_tokens=32, temperature=None,
               top_k=None, top_p=None, deadline_s=None,
               on_token=None, session=None, priority=None,
               trace_ctx=None) -> Request:
        """Queue a request.  ``deadline_s`` bounds the request's TOTAL
        wall budget from submit: still queued past it (queue-wait is
        where overload deadlines actually die) or still decoding past
        it, the request is aborted with :class:`DeadlineExceededError`
        (``req.error``; ``req.wait()`` returns, ``result()`` raises)
        instead of finishing an answer the caller has already given up
        on — aborted work counts against
        ``serving_aborted_tokens_total``, the lifecycle record reads
        ``where="deadline"``.  ``on_token`` streams committed tokens
        per tick (see :class:`Request`).  A draining engine
        (:meth:`drain`) refuses with :class:`EngineDraining`.

        ``session`` (any hashable key) makes this turn part of a
        multi-turn KV session: when the request finishes, its page
        chain is RETAINED under the key instead of released, and a
        later submit with the same key whose prompt extends the
        conversation (prompt + generated tokens of the last turn)
        resumes decoding from the retained tail — the history's pages
        are re-mapped, not re-prefilled, so turn-N TTFT is
        page-hit-dominated.  A prompt that diverges from the retained
        conversation keeps the longest common prefix (partial tail
        pages fork copy-on-write via ``PagePool.cow``).  Sessions are
        evicted LRU/TTL and under admission pressure — retention never
        starves admission (docs/SERVING.md, "Multi-turn sessions").

        ``priority`` ("interactive" | "default" | "batch", default
        "default") sets the request's SLO class: admission picks the
        best effective class first (FIFO within a class; queue wait
        ages a request upward every ``priority_aging_s``), and under
        admission pressure a strictly lower-priority in-flight stream
        may be PREEMPTED — re-queued, not aborted; its committed
        tokens replay through the prefix/session cache on re-admission
        (docs/SERVING.md, "Priority and preemption").

        ``trace_ctx`` (optional plain dict, minted by a fleet router —
        ``{"fleet", "fleet_rid", "attempt"}``) links this replica-local
        request back to the fleet-wide one that dispatched it: stamped
        into the lifecycle record and onto the lifecycle spans so a
        merged chrome trace shows router decision → replica ticks as
        one swimlane (docs/OBSERVABILITY.md, "Fleet telemetry").  The
        dict is the future HTTP header contract — an HTTP replica shim
        passes it through unchanged."""
        req = Request(prompt, max_new_tokens, temperature=temperature,
                      top_k=top_k, top_p=top_p, deadline_s=deadline_s,
                      on_token=on_token, session=session,
                      priority=priority, trace_ctx=trace_ctx)
        need = len(req.prompt) + req.max_new_tokens
        # reserve headroom past the last committed row for the widest
        # in-flight write: a prefill chunk, or the (spec_k+1)-wide verify
        # block — without it a tail write would clamp back onto (and
        # corrupt) committed cache rows
        reserve = max(self.chunk, self.spec_k + 1)
        if need > self.max_len - reserve:
            raise ValueError(
                f"request needs {need} cache rows; capacity is "
                f"max_len-max(chunk,spec_k+1)={self.max_len - reserve}")
        if self._paged:
            # page-granular footprint, computed on the final row index
            # (pages_for): a reserve window narrower than a page can
            # still STRADDLE a page boundary, so counting reserved
            # TOKENS (max(chunk, spec_k+1)) undercounts by one page
            # exactly when the window straddles — the allocator would
            # then hand the tail write a page the table doesn't have
            from .paged import pages_for
            npages = pages_for(need, reserve, self._page_size)
            if npages > self._pool.usable:
                raise ValueError(
                    f"request needs {npages} KV pages; the pool has "
                    f"{self._pool.usable} usable pages "
                    f"(num_pages={self._pool.num_pages}, "
                    f"page_size={self._page_size})")
        max_pos = getattr(self.model.config, "max_position_embeddings", None)
        if max_pos is not None and need > max_pos:
            # past max_pos the position lookup clips to the last row —
            # silently degraded generations; refuse up front
            raise ValueError(
                f"request needs {need} positions; the model's "
                f"max_position_embeddings is {max_pos}")
        # _tid=rid puts every span of one request — lifecycle, queued,
        # and the per-tick prefill/decode/verify shares below — on ONE
        # chrome-trace lane, so a request reads as a single swimlane
        # from submit to finish (slots are reused across requests, so a
        # slot-keyed lane would interleave strangers)
        # fleet trace context rides ONLY the lifecycle spans (they carry
        # both rid and fleet_rid, which is all the cross_stack stitcher
        # needs to re-lane the per-tick spans) — the per-token hot path
        # stays untouched, so armed fleet tracing adds zero per-tick cost
        fleet_attrs = ({"fleet_rid": req.trace_ctx["fleet_rid"]}
                       if req.trace_ctx is not None
                       and req.trace_ctx.get("fleet_rid") is not None
                       else {})
        req._span_life = _tr.start_span(
            "serving.request", _tid=req.rid, rid=req.rid,
            engine=self._engine_id,
            prompt_len=len(req.prompt), max_new=req.max_new_tokens,
            **fleet_attrs)
        req._span_queue = _tr.start_span(
            "serving.request.queued", _tid=req.rid, rid=req.rid,
            engine=self._engine_id, **fleet_attrs)
        self._flight.record(
            "req", phase="submit", rid=req.rid, engine=self._engine_id,
            prompt_len=len(req.prompt), max_new=req.max_new_tokens,
            **fleet_attrs)
        with self._lock:
            draining = self._draining
            if not draining:
                self._pending.append(req)
                if req.deadline_s is not None:
                    self._deadline_queued += 1
                self._c["requests"].inc()
                self._set_queue_gauges_locked()
                if self.auto_run and not self._running:
                    # a fresh burst supersedes a PAST crash: its failed
                    # requests already surfaced their errors, and a
                    # later drain() must judge THIS backlog, not
                    # history (the pinned stale beacon keeps alerting
                    # regardless until the new burst's first tick)
                    self._crashed = None
                    self._running = True
                    t = threading.Thread(target=self._loop, daemon=True)
                    self._loop_thread = t
                    t.start()
        if draining:
            # refuse OUTSIDE the lock: close the spans just opened and
            # leave a flight mark, then raise the typed error a router
            # reads as "place elsewhere" (drain is not a failure)
            req._span_queue.end(error="EngineDraining")
            req._span_life.end(error="EngineDraining")
            self._flight.record(
                "req", phase="reject", rid=req.rid,
                engine=self._engine_id, error="EngineDraining")
            raise EngineDraining(
                f"engine {self._engine_id} is draining: admission is "
                f"closed while queued + inflight requests finish "
                f"(drain(); shutdown() completes removal)")
        return req

    def generate(self, prompt, max_new_tokens=32, timeout=None):
        """Blocking, thread-safe: many caller threads share the engine
        (the ``ZeroCopyRun``-under-lock contract, but requests BATCH
        instead of serializing)."""
        req = self.submit(prompt, max_new_tokens)
        finished = req.wait(timeout)
        if req.error is not None:
            # engine-loop failure: surface the root cause, not a timeout
            return req.result()  # raises RuntimeError from req.error
        if not finished:
            raise TimeoutError("generation did not finish in time")
        return req.result()

    def _eff_rank_locked(self, req, now):
        """Effective priority class of a waiting request: its static
        rank, promoted one class per ``priority_aging_s`` of wait since
        SUBMIT (not the last re-queue — a preempted request keeps its
        accrued age).  The anti-starvation guarantee: any batch request
        eventually reaches rank 0 and outranks every fresh interactive
        arrival (ties break FIFO)."""
        r = req._prank
        if r and self._aging_s is not None:
            r -= int((now - req._t_submit) / self._aging_s)
            if r < 0:
                r = 0
        return r

    def _next_pending_idx_locked(self, now):
        """Index of the next request admission should try: best
        effective class first, FIFO within it (queue position breaks
        ties, so an all-default workload schedules exactly like the
        historical FIFO deque)."""
        best_i, best_k = 0, None
        for i, req in enumerate(self._pending):
            k = (self._eff_rank_locked(req, now), i)
            if best_k is None or k < best_k:
                best_i, best_k = i, k
        return best_i

    def _pick_victim_locked(self, cand, now):
        """Slot to preempt so ``cand`` can admit, or None.  A victim
        must be strictly lower effective priority than the candidate
        (so a just-preempted stream can never immediately evict its
        evictor back — no livelock) and under its preemption cap.
        Among victims: lowest effective class first, then least work
        to replay (committed rows), then the highest slot index."""
        if (not self._preempt or self._draining or self._pp > 1
                or not self._preempt_limit):
            return None
        ce = self._eff_rank_locked(cand, now)
        best = None
        for i, slot in enumerate(self._slots):
            req = slot.req
            if req is None or req._preempts >= self._preempt_limit:
                continue
            ve = self._eff_rank_locked(req, now)
            if ve <= ce:
                continue
            key = (-ve, int(self._lengths[i]), -i)
            if best is None or key < best[0]:
                best = (key, i)
        return None if best is None else best[1]

    def _preempt_slot_locked(self, i, now):
        """Preempt slot ``i``'s in-flight stream: retain its committed
        KV where a cache can hold it (session install for session
        streams — the chain must survive for the PR 16 leak/dead-session
        tripwires to stay meaningful; prefix-cache donation otherwise),
        release the slot, and RE-QUEUE the request at the front of the
        queue.  Nothing terminal happens: no error, no event, no abort
        books — re-admission replays the committed tokens (slot.seq)
        and decode continues token-exact from the last committed one."""
        slot = self._slots[i]
        req = slot.req
        if self._paged:
            if req.session is not None:
                # demote to session-retained, NOT released: the session
                # keeps the chain refs, re-admission session-resumes
                self._session_install_locked(i, req)
            elif self._prefix is not None:
                # donate the committed rows' full pages keyed by their
                # token content (prompt + generated): re-admission
                # matches them back; admission pressure can still evict
                # them (cached_only), so donation never blocks anyone
                kv_len = min(int(self._lengths[i]),
                             len(req.prompt)
                             + max(0, len(req.tokens) - 1))
                if kv_len >= self._page_size:
                    seq = np.concatenate(
                        [req.prompt, np.asarray(req.tokens, np.int32)])
                    self._prefix.insert(seq[:kv_len],
                                        self._page_tables[i],
                                        kv_len // self._page_size)
            self._release_pages_locked(i)
        slot.req = None
        slot.seq = None
        slot.resume = False
        self._sampling_cache = None  # membership changed: restage
        self._lengths[i] = 0
        req._preempts += 1
        req._t_queued = now
        self._pending.appendleft(req)
        if req.deadline_s is not None:
            self._deadline_queued += 1
        self._c["preemptions"].inc()
        req.lifecycle["preemptions"] = req._preempts
        req._span_queue = _tr.start_span(
            "serving.request.queued", _tid=req.rid, rid=req.rid,
            engine=self._engine_id, preempted=True)
        self._flight.record(
            "req", phase="preempt", rid=req.rid, engine=self._engine_id,
            slot=i, tokens=len(req.tokens), preempts=req._preempts)

    def _set_queue_gauges_locked(self):
        self._g_queue.set(len(self._pending))
        counts = dict.fromkeys(PRIORITY_RANK, 0)
        for r in self._pending:
            counts[r.priority] += 1
        for c, g in self._g_class_queue.items():
            g.set(counts[c])

    def _admit(self):
        """Move pending requests into free slots — best effective
        priority class first, FIFO within a class (aging promotes
        waiters, see ``_eff_rank_locked``).  Under pp a request admits
        into any free slot (its wave is slot // wave_size); its staged
        prompt is consumed when that wave next enters stage 0.

        Paged mode additionally requires the request's PAGE footprint to
        fit the pool — a free slot alone is not capacity.  When the
        pick cannot admit (no slot, or pages short), admission may
        PREEMPT a strictly lower-priority in-flight stream
        (``_preempt_slot_locked``) and retry; otherwise it stops —
        later same-or-lower-priority requests wait behind the pick
        rather than starving it (per-class FIFO preserved).

        A re-admitted (preempted) request resumes: its slot prefills
        ``prompt + tokens[:-1]`` (``slot.seq``) with the final chunk's
        sample discarded, and decode restarts from the last committed
        token — token-exact for greedy requests.

        Returns the prefix-hit drafter replays ``[(slot, req, skip,
        lengths_snapshot, seq)]`` for the CALLER to run after releasing
        the engine lock: the replay dispatches the drafter's jitted
        ingest program, and dispatching device work under ``_lock``
        stalls every concurrent submit()/introspection call behind the
        device (pht-lint PHT003 caught this).  Deferral is safe — only
        the driver thread touches slot state, and the replay only needs
        to land before this tick's post-verify ingest, which runs later
        on this same thread."""
        if self._defrag_busy:
            # a compaction's device copy is in flight: the move plan
            # treats low free pages as copy destinations, so admission
            # must not hand them out mid-copy — requests stay queued
            # for the tick after the commit
            return []
        self._expire_queued_locked()
        self._sweep_sessions_locked()
        replays = []
        free = [i for i, s in enumerate(self._slots) if s.req is None]
        while self._pending:
            now = time.perf_counter()
            idx = self._next_pending_idx_locked(now)
            req = self._pending[idx]
            if not free:
                v = self._pick_victim_locked(req, now)
                if v is None:
                    break
                self._preempt_slot_locked(v, now)
                free.append(v)
                continue   # re-pick: the victim joined the queue
            i = min(free)
            resume = bool(req.tokens)
            seq = (np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
                if resume else req.prompt)
            skip = 0
            if self._paged:
                skip = self._paged_admit_locked(i, req, seq, resume)
                if skip is None:
                    # pool exhausted for the pick: preempt a strictly
                    # lower-priority stream to free pages and retry, or
                    # stop admitting this tick
                    v = self._pick_victim_locked(req, now)
                    if v is None:
                        break
                    self._preempt_slot_locked(v, now)
                    free.append(v)
                    continue
            free.remove(i)
            del self._pending[idx]
            slot = self._slots[i]
            slot.req = req
            if req.deadline_s is not None:
                self._deadline_queued -= 1
            self._sampling_cache = None  # membership changed: restage
            slot.seq = seq
            slot.resume = resume
            slot.off = skip   # cache hit: those rows are already KV
            # a resumed stream decodes from its last committed token
            # (never re-sampled — the final replay chunk's sample is
            # discarded, see _stage)
            slot.last = int(req.tokens[-1]) if resume else 0
            self._lengths[i] = skip
            self._c["prompt_tokens"].inc(len(seq))
            if resume:
                self._c["preempt_replay_tokens"].inc(
                    max(0, len(seq) - skip))
            if skip and self._spec is not None:
                # snapshot the committed lengths UNDER the lock: the
                # replay itself runs after release (device dispatch must
                # not hold the engine lock — PHT003), and reading
                # self._lengths there would be an unguarded read of
                # lock-guarded state (PHT009); only slot i's row is
                # consumed (other slots replay zero tokens)
                replays.append((i, req, skip, self._lengths.copy(), seq))
            queue_s = now - req._t_queued
            req.lifecycle.update(t_admit=now, queue_s=queue_s, slot=i)
            self._slo["queue_wait"].observe(queue_s)
            self._slo_cls[req.priority]["queue_wait"].observe(queue_s)
            req._span_queue.end(slot=i)
            self._flight.record(
                "req", phase="admit", rid=req.rid, engine=self._engine_id,
                slot=i, prefix_hit=skip, queue_s=round(queue_s, 6))
        return replays

    def _expire_queued_locked(self):
        """Abort queued requests past their ``submit(deadline_s=)``
        budget (runs at every ``_admit``, i.e. every tick).  The common
        case — nobody set a deadline — is one int check, no queue scan,
        no clock read (``_deadline_queued`` is maintained by submit/
        admit/expiry/fail-all)."""
        if not self._deadline_queued:
            return
        now = time.perf_counter()
        keep = collections.deque()
        for req in self._pending:
            wait_s = now - req._t_submit
            if req.deadline_s is None or wait_s <= req.deadline_s:
                keep.append(req)
                continue
            self._deadline_queued -= 1
            req.error = DeadlineExceededError(
                f"request {req.rid} queued {wait_s:.3f}s, past its "
                f"deadline_s={req.deadline_s}; aborted un-admitted")
            # goodput accounting: same books as the loop fail-all —
            # a queued abort contributes its (zero) generated tokens
            self._c["aborted_tokens"].inc(len(req.tokens))
            req.lifecycle.update(
                t_abort=now, aborted=True, tokens=len(req.tokens),
                where="deadline", error="DeadlineExceededError")
            req._span_queue.end(error="DeadlineExceededError")
            req._span_life.end(error="DeadlineExceededError")
            self._flight.record(
                "req", phase="abort", rid=req.rid,
                engine=self._engine_id, where="deadline",
                wait_s=round(wait_s, 6), error="DeadlineExceededError")
            self._record_abort_locked(req, "deadline",
                                      "DeadlineExceededError", now)
            if req.on_token is not None:
                self._stream_emit.append((req, None))
            req._event.set()
        self._pending = keep

    def _expire_slots_locked(self):
        """The decode half of the ``submit(deadline_s=)`` budget: a
        request STILL DECODING past its deadline is aborted mid-flight
        (its slot frees this tick, its generated-so-far tokens count as
        aborted work).  Queue-wait expiry alone would let a request
        that squeaked into a slot overrun its caller's timeout by the
        whole decode.  One ``is not None`` check per slot per tick when
        nobody sets deadlines; the clock is read only when some slot
        carries one."""
        now = None
        for i, slot in enumerate(self._slots):
            req = slot.req
            if req is None or req.deadline_s is None:
                continue
            if self._pp > 1:
                # consult the record of the wave that OWNS slot i: every
                # record snapshots all slots, so matching req against
                # arbitrary records would defer forever under steady
                # decode (some wave is always mid-pipeline)
                rec = self._inflight.get(i // self._wave)
                if rec is not None and rec[2][i] is req:
                    # the slot's wave is mid-pipeline: freeing it now
                    # would let admission reuse rows the in-flight wave
                    # still writes — expire when the wave exits
                    # (<= pp ticks, _commit_pp_exit skips the stale
                    # commit either way)
                    continue
            if now is None:
                now = time.perf_counter()
            if now - req._t_submit <= req.deadline_s:
                continue
            self._abort_slot_locked(
                i, req, DeadlineExceededError(
                    f"request {req.rid} ran "
                    f"{now - req._t_submit:.3f}s, past its "
                    f"deadline_s={req.deadline_s}; aborted mid-decode "
                    f"after {len(req.tokens)} tokens"),
                "deadline", now)

    def _abort_slot_locked(self, i, req, err, where, now):
        """Terminal abort of an ADMITTED request (deadline expiry): free
        the slot like :meth:`_finish`, but book the generated tokens as
        aborted work and stamp the abort terminal on the lifecycle
        record / flight ring / ``recent_aborts`` debug ring."""
        req.error = err
        if req.session is not None:
            # retain what decoded before the abort: the next turn of
            # the conversation resumes from the partial chain instead
            # of a cold re-prefill (install takes the page refs BEFORE
            # the release below resets the slot's table)
            self._session_install_locked(i, req)
        self._slots[i].req = None
        self._sampling_cache = None  # membership changed: restage
        self._lengths[i] = 0
        if self._paged:
            self._release_pages_locked(i)
        self._c["aborted_tokens"].inc(len(req.tokens))
        req.lifecycle.update(
            t_abort=now, aborted=True, tokens=len(req.tokens),
            where=where, error=type(err).__name__)
        req._span_life.end(error=type(err).__name__)
        self._flight.record(
            "req", phase="abort", rid=req.rid, engine=self._engine_id,
            slot=i, where=where, tokens=len(req.tokens),
            error=type(err).__name__)
        self._record_abort_locked(req, where, type(err).__name__, now)
        if req.on_token is not None:
            self._stream_emit.append((req, None))
        req._event.set()

    def _record_abort_locked(self, req, where, error, now):
        """One row in the bounded ``recent_aborts`` ring
        (``/debug/requests``): aborted requests vanish from the slot
        table immediately, so WHERE they died must be visible
        somewhere curl can reach."""
        self._recent_aborts.append(
            {"rid": req.rid, "where": where, "error": error,
             "tokens": len(req.tokens), "t_abort": round(now, 6)})

    def _paged_admit_locked(self, i, req, seq, resume):
        """Reserve slot ``i``'s whole page footprint up front (worst-case
        rows = prompt + max_new + the write-window reserve, in pages):
        no mid-flight exhaustion, and the
        concurrency win is intact because the footprint tracks the
        REQUEST's need, not ``max_len``.  Cached prefix pages are mapped
        shared (refcount++) and their tokens skipped from prefill.
        ``seq`` is the prefill source (``req.prompt``, or ``prompt +
        tokens[:-1]`` when ``resume`` — a preempted stream re-admitting;
        its committed rows were donated to the prefix/session cache at
        preemption, so the match below is what makes preemption cheap).
        Returns the skipped token count, or None when the pool cannot
        fit the request yet (caller leaves it queued)."""
        from .paged import NULL_PAGE, pages_for
        P = self._page_size
        reserve = max(self.chunk, self.spec_k + 1)
        total = pages_for(len(req.prompt) + req.max_new_tokens, reserve, P)
        if req.session is not None:
            # returning turn of a retained session: resume from the
            # retained page chain instead of re-prefilling the history
            # (a busy session — its owner turn still decoding — falls
            # through to normal admission: the fork serves off the
            # prefix cache and never touches the owner's pages).  A
            # preempt-resume may take back every retained row (its last
            # committed token feeds decode, so seq's final row IS
            # consumable KV — no len-1 cap needed).
            sess = self._sessions.get(req.session)
            if sess is not None and not sess.busy and sess.pages:
                n = min(sess.kv_len, len(seq) - (0 if resume else 1))
                diff = np.nonzero(sess.tokens[:n]
                                  != seq[:n])[0]
                common = int(diff[0]) if len(diff) else int(n)
                if common > 0:
                    skip = self._session_resume_locked(i, req, sess,
                                                       total, common)
                    # None: the pool cannot cover the resume right now
                    # even after eviction — keep the head queued (FIFO;
                    # normal admission needs at least as many fresh
                    # pages, so falling through could not admit either)
                    return skip
        hit = (self._prefix.match(seq, allow_full=resume)
               if self._prefix is not None else [])
        fresh_n = total - len(hit)
        short = fresh_n - self._pool.free
        if short > 0:
            # evict ONLY when eviction can actually cover the shortfall
            # (cached_only counts exactly what evict can free leaf-up
            # right now, excluding cache-only nodes pinned under a live
            # slot's tail; session-evictable pages are the non-busy
            # sessions' exclusively-held pages — retention must never
            # starve admission) — otherwise an unadmittable head would
            # flush a hot prefix cache for nothing and still not admit
            cache_ev = (self._prefix.cached_only()
                        if self._prefix is not None else 0)
            if cache_ev + self._session_evictable_pages_locked() < short:
                if hit:
                    self._pool.decref(hit)  # hand the matched refs back
                return None
            if cache_ev:
                short -= self._prefix.evict(min(short, cache_ev))
            if short > 0:
                self._evict_sessions_for_locked(short)
        fresh = self._pool.alloc(fresh_n)
        if fresh is None:
            if hit:
                self._pool.decref(hit)
            return None
        pages = hit + fresh
        self._slot_pages[i] = pages
        self._page_tables[i] = NULL_PAGE
        self._page_tables[i, :len(pages)] = pages
        self._pt_dev = None   # table changed: restage on next tick
        self._c["prefix_hit_tokens"].inc(len(hit) * P)
        self._g_pages_used.set(self._pool.allocated)
        self._g_pages_free.set(self._pool.free)
        return len(hit) * P

    def _replay_skipped_to_drafter(self, i, req, skip, lengths, seq):
        """A prefix-cache hit skips re-prefilling rows [0, skip) — but
        the drafter's mirror only ever sees what the target tick feeds
        it, so without this replay it would propose from a hole in its
        history (never *wrong* tokens — verify rejects — just a silently
        degraded acceptance rate).  Replay in chunk-wide pieces: the
        width the drafter's ingest program is already compiled for, so
        no new trace per distinct hit length.  ``seq`` is the slot's
        prefill source (prompt, or prompt + committed tokens on a
        preempt-resume — the drafter must mirror the RESUMED history,
        not just the prompt).  ``lengths`` is the
        committed-lengths snapshot ``_admit`` took under the engine
        lock (this runs after release); other slots' rows follow the
        normal ingest convention (zero tokens written past their
        committed length — scratch the draft attention never reads)."""
        C = self.chunk
        for ofs in range(0, skip, C):
            n = min(C, skip - ofs)
            buf = np.zeros((self.max_slots, C), np.int32)
            buf[i, :n] = seq[ofs:ofs + n]
            starts = lengths.copy()
            starts[i] = ofs
            nvalid = np.zeros(self.max_slots, np.int32)
            nvalid[i] = n
            self._spec.ingest(buf, starts, nvalid)

    def _release_pages_locked(self, i):
        """Drop slot ``i``'s page references (request finished/failed).
        Pages the prefix cache also references stay allocated for future
        prefix hits; everything else returns to the free list."""
        from .paged import NULL_PAGE
        pages = self._slot_pages[i]
        if pages:
            self._pool.decref(pages)
            self._slot_pages[i] = []
        self._page_tables[i] = NULL_PAGE
        self._pt_dev = None   # table changed: restage on next tick
        self._g_pages_used.set(self._pool.allocated)
        self._g_pages_free.set(self._pool.free)

    # ------------------------------------------------------------------
    # multi-turn KV sessions (submit(session=)) — docs/SERVING.md
    # pht-lint: hot-root (session resume runs on the admission tick path)
    def _session_resume_locked(self, i, req, sess, total, common):
        """Admit slot ``i`` by resuming session ``sess``: the first
        ``common`` conversation tokens' KV rows are already resident in
        the session's retained page chain, so the slot takes those
        pages over (the session's refs transfer — no incref/decref
        churn) and prefills only the suffix.  A partial tail page that
        is SHARED (prompt pages the prefix cache also references, or a
        diverged turn cutting into cache-registered history) forks
        copy-on-write via ``PagePool.cow`` — the fork's rows re-prefill
        ("copy" by recompute), so the write-window invariant (no shared
        page in ``[start, start+reserve)``) holds by construction.

        Returns the skipped token count, or ``None`` when the pool
        cannot cover the resume even after evicting LRU sessions and
        prefix-cache pages (the request stays queued; nothing was
        mutated)."""
        P = self._page_size
        kept_n = -(-common // P)          # ceil: pages holding [0, common)
        keep = sess.pages[:kept_n]
        fresh_n = total - kept_n
        tail_shared = (common % P != 0
                       and self._pool.refcount(keep[-1]) > 1)
        need_free = fresh_n + (1 if tail_shared else 0)
        short = need_free - self._pool.free
        if short > 0:
            short -= self._evict_sessions_for_locked(
                short, exclude=sess.sid)
            if short > 0:
                if (self._prefix is None
                        or self._prefix.cached_only() < short):
                    return None
                self._prefix.evict(short)
        # commit point: the allocations below cannot fail (free pages
        # verified above; one lock hold, nothing runs in between)
        extra = sess.pages[kept_n:]
        if extra:
            # rows past the common prefix are a dead branch of the
            # conversation (diverged turn): the transfer takes ALL the
            # session's refs, the unused tail goes straight back
            self._pool.decref(extra)
        pages = list(keep)
        skip = common
        if common % P:
            page, forked = self._pool.cow(pages[-1])
            pages[-1] = page
            if forked:
                # shared tail forked to a private page: its rows are
                # re-prefilled, so round the skip down to the boundary
                skip = (common // P) * P
        from .paged import NULL_PAGE
        pages += self._pool.alloc(fresh_n)
        sess.busy = True
        sess.owner = req.rid
        sess.pages = []               # refs now live on the slot
        self._slot_pages[i] = pages
        self._page_tables[i] = NULL_PAGE
        self._page_tables[i, :len(pages)] = pages
        self._pt_dev = None   # table changed: restage on next tick
        self._c["session_resumes"].inc()
        self._c["session_hit_tokens"].inc(skip)
        self._g_pages_used.set(self._pool.allocated)
        self._g_pages_free.set(self._pool.free)
        self._update_session_gauges_locked()
        self._flight.record(
            "session", phase="resume", rid=req.rid,
            engine=self._engine_id, slot=i, hit_tokens=skip,
            kept_pages=kept_n)
        return skip

    # pht-lint: hot-root (session install runs on the tick commit path)
    def _session_install_locked(self, i, req):
        """Retain the finishing/aborting request's state as its session
        (called from ``_finish``/``_abort_slot_locked`` BEFORE the slot's
        lengths are zeroed and its pages released — the install takes
        the page refs the release would drop).  Rules: a session busy
        under ANOTHER owner is left alone (a forked regeneration must
        not clobber the owner's in-flight turn); otherwise the last
        finisher wins — previously retained pages are dropped and this
        turn's chain replaces them."""
        sid = req.session
        sess = self._sessions.get(sid)
        if sess is not None and sess.busy and sess.owner != req.rid:
            return
        if sess is None:
            if len(self._sessions) >= self._max_sessions:
                self._evict_lru_session_locked()
            sess = self._sessions[sid] = _Session(sid)
        sess.tokens = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        kv_len = 0
        if self._paged:
            # committed rows holding token-exact KV: the last generated
            # token is never fed back, and lengths can overrun actual
            # commits in multi/spec modes (window/verify advance, then
            # an early finish discards the tail) — rows [0, p + N - 1)
            # are valid in ALL modes, so clamp to that
            kv_len = min(int(self._lengths[i]),
                         len(req.prompt) + max(0, len(req.tokens) - 1))
            if sess.pages:
                # last-wins: a regeneration replaces the retained chain
                self._pool.decref(sess.pages)
                sess.pages = []
            n_keep = -(-kv_len // self._page_size)
            pages = self._slot_pages[i]
            sess.pages = pages[:n_keep]
            extra = pages[n_keep:]
            if extra:
                self._pool.decref(extra)   # write-window slack pages
            self._slot_pages[i] = []       # refs transferred to session
            P = self._page_size
            crc, digs = 0, []
            for k in range(kv_len // P):
                crc = zlib.crc32(
                    sess.tokens[k * P:(k + 1) * P].tobytes(), crc)
                digs.append(crc)
            sess.digests = digs
        sess.kv_len = kv_len
        sess.busy = False
        sess.owner = None
        sess.last_used = time.perf_counter()
        self._update_session_gauges_locked()

    def _evict_session_locked(self, sid, donate=True):
        """Evict one non-busy session.  ``donate=True`` (graceful: TTL,
        drain) hands the full retained pages to the prefix cache keyed
        by their token content first, so a turn re-admitted after the
        eviction replays from the cache instead of a cold re-prefill;
        ``donate=False`` (admission pressure, leak checks) drops the
        refs outright — the pool needs the pages NOW."""
        sess = self._sessions.pop(sid)
        if sess.pages:
            if donate and self._prefix is not None:
                self._prefix.insert(sess.tokens, sess.pages,
                                    sess.kv_len // self._page_size)
            self._pool.decref(sess.pages)
            self._g_pages_used.set(self._pool.allocated)
            self._g_pages_free.set(self._pool.free)
        self._c["sessions_evicted"].inc()
        self._flight.record(
            "session", phase="evict", engine=self._engine_id,
            donated=bool(donate and sess.pages is not None))
        self._update_session_gauges_locked()

    def _evict_lru_session_locked(self):
        cands = [s for s in self._sessions.values() if not s.busy]
        if cands:
            self._evict_session_locked(
                min(cands, key=lambda s: s.last_used).sid)

    def _evict_sessions_for_locked(self, need, exclude=None):
        """Evict LRU non-busy sessions (dropping, not donating — this
        runs under admission pressure and must FREE pages) until
        ``need`` pages came free or no candidates remain; returns the
        pages actually freed."""
        freed = 0
        while freed < need:
            cands = [s for s in self._sessions.values()
                     if not s.busy and s.sid != exclude]
            if not cands:
                break
            victim = min(cands, key=lambda s: s.last_used)
            before = self._pool.free
            self._evict_session_locked(victim.sid, donate=False)
            freed += self._pool.free - before
        return freed

    def _session_evictable_pages_locked(self):
        """Pages evicting every non-busy session would free RIGHT NOW:
        pages whose ONLY reference is a session (a refcount-1 page
        belongs to exactly one holder, so no dedup) — the session half
        of the admission headroom ``/load`` publishes next to the
        prefix cache's ``cached_only``; the two are disjoint (a page
        referenced by both has refcount >= 2 and counts in neither)."""
        if not self._paged:
            return 0
        return sum(1 for s in self._sessions.values() if not s.busy
                   for p in s.pages if self._pool.refcount(p) == 1)

    def _sweep_sessions_locked(self):
        """TTL sweep (every _admit): evict non-busy sessions idle past
        ``session_ttl_s``.  One dict check when the feature is off."""
        if self._session_ttl_s is None or not self._sessions:
            return
        now = time.perf_counter()
        for sid in [sid for sid, s in self._sessions.items()
                    if not s.busy
                    and now - s.last_used > self._session_ttl_s]:
            self._evict_session_locked(sid)

    def _update_session_gauges_locked(self):
        self._g_sessions.set(len(self._sessions))
        if self._paged:
            pages = set()
            for s in self._sessions.values():
                pages.update(s.pages)
            self._g_session_pages.set(len(pages))

    def drop_sessions(self) -> int:
        """Evict every non-busy retained session WITHOUT donating to
        the prefix cache (HBM reclaim / pool-leak checks — the bench
        rows call this before asserting ``kv_pages_in_use == 0``);
        returns how many sessions were dropped."""
        with self._lock:
            n = 0
            for sid in list(self._sessions):
                if not self._sessions[sid].busy:
                    self._evict_session_locked(sid, donate=False)
                    n += 1
            return n

    # ------------------------------------------------------------------
    # on-device page defrag / compaction — docs/SERVING.md
    #
    # A long-lived pool fragments: sessions and cache nodes free pages
    # scattered across the address range, so ``allocated`` stays small
    # while ``highest_allocated`` stays large — the region the tick's
    # gather actually touches.  Compaction moves every allocated page
    # into the low end in three phases: PLAN under the lock (pool is
    # idle-checked, ``_defrag_busy`` set so _admit stays out), device
    # COPY unlocked (PHT003: never dispatch under the lock), COMMIT
    # under the lock (``apply_moves`` re-validates per pair, then the
    # prefix cache, retained sessions and page tables remap).
    def defrag(self) -> int:
        """Compact the paged KV pool (no-op in dense mode or when the
        pool is already dense-packed); returns pages moved.  Runs only
        at a quiet point — zero active slots, empty queue, no inflight
        pp waves — and respects the single-driver contract (raises if
        the auto_run loop is concurrently driving; the loop runs
        compaction itself on idle ticks, see ``_maybe_defrag``)."""
        if not self._paged:
            return 0
        with self._lock:
            if self._running and \
                    threading.current_thread() is not self._loop_thread:
                err = RuntimeError(
                    "engine is being driven by its auto_run loop; "
                    "defrag() from another thread would touch donated "
                    "caches mid-tick — the loop compacts on idle ticks "
                    "itself")
                err._pht_usage_error = True
                raise err
        return self._defrag_impl()

    # pht-lint: hot-root (auto-defrag check runs on every idle tick)
    def _maybe_defrag(self):
        """Idle-tick auto-compaction (driver thread): trigger only when
        the touched region is more than twice the live page count —
        cheap two-int predicate, so probing every idle tick is free."""
        with self._lock:
            if (self._pool is None or self._defrag_busy
                    or self._pool.highest_allocated()
                    <= 2 * self._pool.allocated):
                return 0
        return self._defrag_impl()

    def _defrag_impl(self) -> int:
        moves = None
        try:
            with self._lock:
                if self._defrag_busy:
                    return 0
                # quiet point required: a live slot's page table (or a
                # pp wave's entry-time snapshot) would go stale under a
                # move; admission is re-gated below via _defrag_busy
                if (self._pending
                        or any(s.req is not None for s in self._slots)
                        or self._inflight_live()):
                    return 0
                moves = self._pool.compaction_plan()
                if not moves:
                    return 0
                self._defrag_busy = True
            # device copy OUTSIDE the lock (PHT003) — _admit returns []
            # while _defrag_busy, so no slot can map a moving page
            self._dispatch_defrag_moves(moves)
            with self._lock:
                applied = self._pool.apply_moves(moves)
                remap = dict(applied)
                if self._prefix is not None:
                    self._prefix.remap_pages(remap)
                for sess in self._sessions.values():
                    sess.pages = [remap.get(p, p) for p in sess.pages]
                self._pt_dev = None   # tables restage from the remap
                self._c["defrag_total"].inc()
                self._c["defrag_pages_moved"].inc(len(applied))
                self._g_pages_used.set(self._pool.allocated)
                self._g_pages_free.set(self._pool.free)
                self._flight.record(
                    "defrag", phase="commit", engine=self._engine_id,
                    moved=len(applied),
                    high=self._pool.highest_allocated())
                return len(applied)
        finally:
            if moves:
                with self._lock:
                    self._defrag_busy = False

    def _build_defrag_fn(self):
        """CONSTRUCT (not trace) the compaction copy program, called
        once from ``__init__`` — construction inside the defrag path
        itself would be a per-pass retrace hazard (PHT002); the actual
        trace happens on the first executed plan."""
        import jax

        def move(caches, srcs, dsts):
            return [(k.at[dsts].set(k[srcs]),
                     v.at[dsts].set(v[srcs])) for k, v in caches]

        return _obs.instrument_jit(
            sanitize_donation(jax.jit(move, donate_argnums=(0,)),
                              donate_argnums=(0,), site="serving.defrag"),
            site="serving.defrag", engine=self._engine_id)

    def _dispatch_defrag_moves(self, moves):
        """One jitted gather-scatter per cache layer copies every
        moving page's K and V rows src→dst in a single dispatch.  The
        src/dst vectors pad to the next power of two with (0, 0) pairs
        so plans of different sizes reuse one trace: page 0 is the
        NULL page — duplicate dst-0 writes all carry page 0's own rows,
        so the no-op padding is write-write safe."""
        import jax.numpy as jnp
        n = 1
        while n < len(moves):
            n *= 2
        srcs = np.zeros(n, np.int32)
        dsts = np.zeros(n, np.int32)
        for j, (s, d) in enumerate(moves):
            srcs[j], dsts[j] = s, d
        self._caches = self._defrag_fn(
            self._caches, jnp.asarray(srcs), jnp.asarray(dsts))

    def _check_write_windows_locked(self, starts):
        """Tripwire for the paged no-shared-writes invariant: no active
        slot's write window ``[start, start+reserve)`` may map a page
        with refcount > 1 — the prefix cache's round-down-to-a-page-
        boundary match (copy-on-write by recompute) guarantees it, so a
        violation is a refcount bug; fail the tick loudly rather than
        serve KV another request (or the cache) can see corrupted."""
        from .paged import NULL_PAGE
        P = self._page_size
        reserve = max(self.chunk, self.spec_k + 1)
        for i, slot in enumerate(self._slots):
            if slot.req is None:
                continue
            lo = int(starts[i]) // P
            hi = min((int(starts[i]) + reserve - 1) // P,
                     self._pages_per_slot - 1)
            for k in range(lo, hi + 1):
                pg = int(self._page_tables[i, k])
                if pg != NULL_PAGE and self._pool.refcount(pg) > 1:
                    raise RuntimeError(
                        f"paged KV invariant violated: slot {i} write "
                        f"window [{int(starts[i])}, "
                        f"{int(starts[i]) + reserve}) maps shared page "
                        f"{pg} (refcount {self._pool.refcount(pg)})")

    def _stage(self):
        """Build (tokens, starts, nvalid, consumed, finishing) for this
        tick from current slot state. ``consumed[i]``: tokens written for
        slot i (its length advance); ``finishing[i]``: the tick's sample
        for slot i is a real next token.  The prefill source is
        ``slot.seq`` (the prompt, or ``prompt + tokens[:-1]`` on a
        preempt-resume); a resume slot's final replay chunk stages with
        ``finishing`` FALSE — its sample would be a re-prediction of the
        already-committed last token, so it is discarded and decode
        restarts from ``slot.last`` next tick (token-exact for greedy).

        ``prefill_budget`` bounds the PREFILL tokens staged per tick
        (decode feeds are never deferred): chunks are granted in
        priority order and may be narrowed (nvalid is runtime data —
        no retrace); a slot past the budget stages a scratch token at
        its current length with ``consumed`` 0 — the row is rewritten
        by the real chunk before any of that chunk's queries attend it,
        the same rollback argument spec-verify relies on.  This bounds
        how long a wall of batch prefill can displace an interactive
        slot's decode ticks — the chunked-prefill fairness knob
        (docs/SERVING.md)."""
        B, C = self.max_slots, self.chunk
        tokens = np.zeros((B, C), np.int32)
        starts = self._lengths.copy()
        nvalid = np.ones(B, np.int32)
        consumed = np.zeros(B, np.int32)
        finishing = [False] * B
        prefilling = [i for i, s in enumerate(self._slots)
                      if s.req is not None and s.off < len(s.seq)]
        rem = self._prefill_budget
        if rem is not None:
            prefilling.sort(key=lambda i: (self._slots[i].req._prank, i))
        for i in prefilling:
            slot = self._slots[i]
            w = min(C, len(slot.seq) - slot.off)
            if rem is not None:
                w = min(w, rem)
                rem -= w
            if w <= 0:
                continue   # budget spent: deferred (scratch, no advance)
            tokens[i, :w] = slot.seq[slot.off:slot.off + w]
            nvalid[i] = w
            consumed[i] = w
            finishing[i] = (not slot.resume
                            and slot.off + w >= len(slot.seq))
        for i, slot in enumerate(self._slots):
            if slot.req is None or slot.off < len(slot.seq):
                continue
            tokens[i, 0] = slot.last
            nvalid[i] = 1
            consumed[i] = 1
            finishing[i] = True
        return tokens, starts, nvalid, consumed, finishing

    def _finish(self, slot_idx, req):
        req.done = True
        if req.session is not None:
            # retain the finished turn's page chain as its session
            # BEFORE the release below drops the slot's refs — the next
            # turn resumes decoding from this tail
            self._session_install_locked(slot_idx, req)
        self._slots[slot_idx].req = None
        self._sampling_cache = None  # membership changed: restage
        self._lengths[slot_idx] = 0
        if self._paged:
            self._release_pages_locked(slot_idx)
        now = time.perf_counter()
        e2e = now - req._t_submit
        self._h_e2e.observe(e2e)
        self._slo["e2e"].observe(e2e)
        self._c["completed_tokens"].inc(len(req.tokens))
        req.lifecycle.update(t_finish=now, e2e_s=e2e,
                             tokens=len(req.tokens), aborted=False)
        if req._t_first is not None and len(req.tokens) > 1:
            tpot = (now - req._t_first) / (len(req.tokens) - 1)
            self._h_tpot.observe(tpot)
            req.lifecycle["tpot_s"] = tpot
        req._span_life.end(slot=slot_idx, tokens=len(req.tokens))
        self._flight.record(
            "req", phase="finish", rid=req.rid, engine=self._engine_id,
            slot=slot_idx, tokens=len(req.tokens),
            e2e_s=round(now - req._t_submit, 6))
        if req.on_token is not None:
            # end-of-stream terminal, AFTER this tick's token emits in
            # the same buffer — a streaming consumer sees every token,
            # then exactly one None
            self._stream_emit.append((req, None))
        req._event.set()

    def _tick_progress(self, req, t_ns):
        """Per-tick TPOT sample for one request: this tick committed
        ``len(req.tokens) - n_prev`` tokens since the mark at ``t_prev``,
        so the rolling window sees ``(t - t_prev) / committed`` — the
        per-token decode latency of THIS tick, not the request-lifetime
        mean (a mid-run slowdown shifts the /load p99 within one window,
        where the lifetime mean would launder it).  The tick that
        produced the FIRST token only plants the mark (that latency is
        TTFT's); called once per slot per tick, host floats only."""
        if req._t_first is None:
            return
        t = t_ns / 1e9   # perf_counter_ns and perf_counter share a clock
        n = len(req.tokens)
        mark = req._tick_mark
        if mark is not None:
            t_prev, n_prev = mark
            if n > n_prev and t > t_prev:
                self._slo["tpot"].observe((t - t_prev) / (n - n_prev))
        req._tick_mark = (t, n)

    def _commit_token(self, i, tok):
        """Record slot i's sampled token; returns True if the request
        completed."""
        slot = self._slots[i]
        req = slot.req
        if not req.tokens:
            req._t_first = time.perf_counter()
            ttft = req._t_first - req._t_submit
            req.lifecycle.update(t_first_token=req._t_first, ttft_s=ttft)
            self._h_ttft.observe(ttft)
            self._slo["ttft"].observe(ttft)
            self._slo_cls[req.priority]["ttft"].observe(ttft)
        req.tokens.append(tok)
        slot.last = tok
        self._c["tokens"].inc()
        if req.on_token is not None:
            # buffered under the lock, delivered by _flush_streams on
            # this driver thread after release (the hook may block —
            # that is the streaming backpressure)
            self._stream_emit.append((req, tok))
        if (len(req.tokens) >= req.max_new_tokens
                or (self.eos_token_id is not None
                    and tok == self.eos_token_id)):
            self._finish(i, req)
            return True
        return False

    def step(self) -> bool:
        """One engine tick: stage under the lock, run the device program
        unlocked (submit()/generate() stay responsive), commit under the
        lock. Returns False when there was nothing to do.

        Single-driver contract: while the auto_run loop is live, only the
        loop thread may tick — a second driver would re-enter the jitted
        tick with the DONATED cache buffers the in-flight call already
        invalidated (crash/corruption), so it raises instead.

        An escaping exception writes the flight-recorder ring to disk
        first (``observability/flight.py``): the dump carries the recent
        tick summaries and the failing requests' lifecycle events —
        the post-mortem an aggregate counter cannot give."""
        try:
            return self._step_impl()
        except BaseException as e:
            # the single-driver guard raise is a usage error, not an
            # engine crash: a caller retrying step() against a live
            # auto_run loop must not flood $PHT_FLIGHT_DIR with dumps
            # (or evict the ring's real history with 'crash' events)
            if not getattr(e, "_pht_usage_error", False):
                _flight.crash_dump(f"serving.step[{self._engine_id}]", e)
            raise

    def _after_tick(self, flavor, t0n, t1n, committed, **extra):
        """Per-tick event-level bookkeeping (all modes): the liveness
        beacon /healthz reads, the always-on flight tick summary, and —
        only while tracing is armed — the tick-level span."""
        _tr.heartbeat(f"serving.{self._engine_id}")
        self._flight.record(
            "tick", engine=self._engine_id, flavor=flavor,
            tickno=self._tickno, dur_us=(t1n - t0n) // 1000,
            committed=committed, **extra)
        if _tr.tracing_enabled():
            _tr.add_span(f"serving.tick.{flavor}", t0n, t1n,
                         engine=self._engine_id, tickno=self._tickno,
                         committed=committed, **extra)

    def _step_impl(self) -> bool:
        """Tick + streaming flush: committed tokens (and stream
        terminals) buffered under the lock during :meth:`_step_inner`
        are handed to their ``on_token`` hooks here, on the driver
        thread, lock-free.  A raising tick skips the flush — the
        auto_run loop's fail-all appends the terminal marks first and
        flushes everything, in order, itself."""
        busy = self._step_inner()
        self._flush_streams()
        if not busy and self._paged:
            # idle tick on the driver: cheap two-int fragmentation
            # check, compaction only when the pool is badly scattered
            self._maybe_defrag()
        return busy

    def _flush_streams(self):
        """Deliver buffered ``on_token`` emissions (driver thread only,
        no lock held — a blocking hook is the backpressure design and
        must never stall ``submit()``/introspection behind the engine
        lock).  A hook that RAISES is dropped with a flight mark
        instead of killing the tick loop: the stream consumer is the
        broken party, the other slots' requests are not."""
        with self._lock:
            if not self._stream_emit:
                return
            buf, self._stream_emit = self._stream_emit, []
        for req, tok in buf:
            try:
                req.on_token(tok)
            except Exception as e:  # noqa: BLE001 — consumer's bug
                self._flight.record(
                    "stream", phase="hook_error", rid=req.rid,
                    engine=self._engine_id, error=type(e).__name__)

    def _step_inner(self) -> bool:  # pht-lint: hot-root (tick body)
        # fault-injection drill points (observability/faults.py):
        # armed, they kill/fail/delay a tick deterministically — how
        # the fail-all path below and the crash-dump post-mortem are
        # drilled; disarmed each is one empty-dict probe per tick.
        # serving.step is the historical global point; the per-replica
        # serving.tick[<engine_id>] point is how a fleet drill kills
        # ONE replica of many in the same process.
        _faults.point("serving.step")
        _faults.point(self._tick_fault_point)
        with self._lock:
            if self._running and \
                    threading.current_thread() is not self._loop_thread:
                err = RuntimeError(
                    "engine is being driven by its auto_run loop; "
                    "step()/run_until_idle() from another thread would "
                    "re-enter the tick with donated caches — wait for the "
                    "loop to drain (shutdown()) instead")
                err._pht_usage_error = True   # step(): no crash dump
                raise err
            replays = self._admit()
            # decode half of the deadline budget (queue half runs in
            # _admit): a slot past its deadline frees before this tick
            # wastes another program dispatch on it
            self._expire_slots_locked()
            self._set_queue_gauges_locked()
            occ = sum(s.req is not None for s in self._slots)
            self._g_occupancy.set(occ)
            if occ > self._peak_occupancy:
                # paged-vs-dense admitted-concurrency evidence (bench)
                self._peak_occupancy = occ
            sampling = self._sampling_vectors()
            # live-slot mask, shared by every mode: the tick programs
            # run ALL slots (inactive rows carry scratch), and the MoE
            # stats observer must see only the real ones
            active = np.asarray([s.req is not None for s in self._slots])
            if self._pp > 1:
                if (not any(s.req is not None for s in self._slots)
                        and not self._inflight_live()):
                    return False
                mode = "pp"
                tokens, starts, nvalid, exit_wave = self._stage_pp_locked()
            elif not any(s.req is not None for s in self._slots):
                return False
            # after _admit, a pending request implies no free slot — so
            # "every active slot is decoding" is the spec/multi-window gate
            elif all(s.req is None or s.off >= len(s.seq)
                     for s in self._slots):
                last_toks = np.asarray([s.last for s in self._slots],
                                       np.int32)
                starts = self._lengths.copy()
                # speculate only when some active slot is greedy — an
                # all-sampling tick would pay the K+1-wide verify for 1
                # token/slot where the fused M-step window commits M
                mode = ("spec" if self._spec is not None
                        and bool((active & (sampling[1] == 0.0)).any())
                        else "multi")
            else:
                mode = "chunk"
                tokens, starts, nvalid, consumed, finishing = self._stage()
            if self._paged:
                self._check_write_windows_locked(starts)

        for i, req, skip, lengths, seq in replays:
            # deferred from _admit: the drafter's jitted ingest must not
            # dispatch under the engine lock (only this driver thread
            # mutates slot state, so running it here — before this
            # tick's device program and its post-verify ingest — is
            # order-equivalent to replaying inside _admit)
            self._replay_skipped_to_drafter(i, req, skip, lengths, seq)

        if mode == "pp":
            t0n = time.perf_counter_ns()
            nxt = self._run_pp_tick(tokens, starts, nvalid, sampling)
            t1n = time.perf_counter_ns()
            self._h_tick["pp"].observe((t1n - t0n) / 1e9)
            with self._lock:
                self._tickno += 1
                self._c["ticks"].inc()
                committed = self._commit_pp_exit_locked(exit_wave, nxt, t1n)
                self._after_tick("pp", t0n, t1n, committed,
                                 exit_wave=int(exit_wave))
            return True
        if mode == "spec":
            # draft-and-verify: slot state is stable outside the lock
            # (only this driver thread mutates it), so drafting and the
            # device tick run unlocked like the other modes
            drafts, ndraft = self._spec.propose(last_toks, starts)
            # only active greedy slots draft; sampled slots (per-request
            # temperature>0) advance 1 token/tick with exact sampling
            ndraft = np.where(active & (sampling[1] == 0.0), ndraft, 0)
            ndraft = ndraft.astype(np.int32)
            if not ndraft.any():
                # nothing proposed this tick (e.g. no n-gram repeats yet):
                # the K+1-wide verify would commit 1 token/slot — the
                # fused M-step window is strictly better, demote
                mode = "multi"
        if mode == "spec":
            toks = np.concatenate([last_toks[:, None], drafts], axis=1)
            t0n = time.perf_counter_ns()
            out = self._run_tick_spec(toks, starts, sampling,
                                      active=active, ndraft=ndraft)
            t1n = time.perf_counter_ns()
            self._h_tick["spec"].observe((t1n - t0n) / 1e9)
            from ..nn.decode import accept_lengths
            acc = accept_lengths(drafts, ndraft, out)
            with self._lock:
                self._tickno += 1
                self._c["ticks"].inc()
                self._c["spec_ticks"].inc()
                tron = _tr.tracing_enabled()
                tick_drafted = tick_accepted = tick_committed = 0
                nvalid = np.zeros(self.max_slots, np.int32)
                for i, slot in enumerate(self._slots):
                    if slot.req is None:
                        continue
                    req = slot.req   # _commit_token may free the slot
                    rid = req.rid
                    rem = req.max_new_tokens - len(req.tokens)
                    adv = int(acc[i]) + 1
                    nvalid[i] = adv
                    self._lengths[i] += adv
                    committed = 0
                    for t in range(adv):
                        committed += 1
                        if self._commit_token(i, int(out[i, t])):
                            break  # freed; later accepted tokens discarded
                    self._tick_progress(req, t1n)
                    # count only what the commit loop could use: the
                    # request budget (rem) bounds drafts, and the commit
                    # count additionally bounds accepted (EOS truncation)
                    # — otherwise the acceptance counters claim tokens
                    # the tokens counter never saw
                    d = min(int(ndraft[i]), rem)
                    a = min(int(acc[i]), committed)
                    self._c["spec_drafted"].inc(d)
                    self._c["spec_accepted"].inc(a)
                    tick_drafted += d
                    tick_accepted += a
                    tick_committed += committed
                    if tron:
                        # each slot's share of the fused verify tick on
                        # the REQUEST's lane (_tid=rid: one request, one
                        # swimlane): request id + acceptance outcome
                        _tr.add_span("serving.spec_verify", t0n, t1n,
                                     _tid=rid, rid=rid, slot=i, drafted=d,
                                     accepted=a, committed=committed)
                if tick_drafted:
                    self._h_accept.observe(tick_accepted / tick_drafted)
                self._after_tick("spec", t0n, t1n, tick_committed,
                                 drafted=tick_drafted,
                                 accepted=tick_accepted)
            if getattr(self._spec, "ingest_after_verify", True):
                # self-ingesting drafters (ModelDrafter) already wrote
                # these rows into their own cache during propose()
                self._spec.ingest(toks, starts, nvalid)
            return True
        if mode == "multi":
            t0n = time.perf_counter_ns()
            out = self._run_tick_multi(last_toks, starts, sampling,
                                       active=active)
            t1n = time.perf_counter_ns()
            self._h_tick["decode"].observe((t1n - t0n) / 1e9)
            with self._lock:
                self._tickno += 1
                self._c["ticks"].inc()
                tron = _tr.tracing_enabled()
                tick_committed = 0
                M = self._decode_window
                for i, slot in enumerate(self._slots):
                    if slot.req is None:
                        continue
                    req = slot.req   # _commit_token may free the slot
                    rid = req.rid
                    committed = 0
                    self._lengths[i] += M
                    for t in range(M):
                        committed += 1
                        if self._commit_token(i, int(out[i, t])):
                            break  # freed; later window tokens discarded
                    self._tick_progress(req, t1n)
                    tick_committed += committed
                    if tron:
                        _tr.add_span("serving.decode", t0n, t1n, _tid=rid,
                                     rid=rid, slot=i, window=M,
                                     committed=committed)
                self._after_tick("decode", t0n, t1n, tick_committed,
                                 window=M)
            if self._spec is not None:
                # an all-sampling window can still precede a greedy
                # request: mirror the M cache rows the window wrote so
                # the drafter stays in sync for later spec ticks
                M = self._decode_window
                chunk = np.concatenate([last_toks[:, None], out[:, :M - 1]],
                                       axis=1)
                self._spec.ingest(chunk, starts,
                                  np.where(active, M, 0).astype(np.int32))
            return True
        t0n = time.perf_counter_ns()
        nxt = self._run_tick(tokens, starts, nvalid, sampling, active)
        t1n = time.perf_counter_ns()
        self._h_tick["prefill"].observe((t1n - t0n) / 1e9)
        with self._lock:
            self._tickno += 1
            self._c["ticks"].inc()
            tron = _tr.tracing_enabled()
            tick_committed = 0
            for i, slot in enumerate(self._slots):
                if slot.req is None:
                    continue
                req = slot.req   # _commit_token may free the slot
                rid = req.rid
                was_prefill = slot.off < len(slot.seq)
                if was_prefill:
                    slot.off += int(consumed[i])
                    if (self._prefix is not None
                            and slot.off >= len(slot.seq)):
                        # prefill source fully consumed: register its
                        # FULL pages so later requests sharing the
                        # prefix (or this stream's own re-admission
                        # after another preemption) skip them.
                        # Before _commit_token — a request that finishes
                        # this very tick must donate its pages to the
                        # cache before _finish releases the slot's refs.
                        self._prefix.insert(
                            slot.seq, self._page_tables[i],
                            len(slot.seq) // self._page_size)
                self._lengths[i] += int(consumed[i])
                if finishing[i]:
                    self._commit_token(i, int(nxt[i]))
                    tick_committed += 1
                    self._tick_progress(req, t1n)
                if tron:
                    _tr.add_span(
                        "serving.prefill_chunk" if was_prefill
                        else "serving.decode",
                        t0n, t1n, _tid=rid, rid=rid, slot=i,
                        tokens=int(consumed[i]))
            self._after_tick("prefill", t0n, t1n, tick_committed)
        if self._spec is not None:
            # keep the drafter's mirror in sync with what the chunk tick
            # wrote (prefill chunks and the 1-wide decode feeds alike)
            self._spec.ingest(tokens, starts, consumed)
        return True

    def _run_tick_multi(self, last_toks, starts, sampling, active=None):
        import jax
        vec = sampling[0]
        temps_d, topks_d, topps_d = self._sampling_dev3(sampling)
        # the steady-state hot path: one jitted dispatch (sampling
        # vectors + page table already device-resident) + one fetch
        res = self._prog("_tick_multi", vec)(
            self._params, self._caches, last_toks,
            starts, temps_d, topks_d, topps_d, self._key,
            # single aligned int read by its only writer (driver thread)
            np.int32(self._tickno), **self._pt_kw())  # pht-lint: gil-atomic
        # designed once-per-tick fetch (see _run_tick); MoE stats are
        # the window's M-step means and ride the same fetch
        if self._moe:
            self._caches, out, st = res
            out, st = jax.device_get((out, st))
            self._observe_moe(st, np.ones(len(out), bool)
                              if active is None else active)
            return out
        self._caches, out = res
        return jax.device_get(out)

    def _inflight_live(self):
        return any(any(r is not None for r in rec[2])
                   for rec in self._inflight.values())

    def _stage_pp_locked(self):
        """Stage a pp tick (lock held by the caller). The ENTERING wave's
        snapshot (consumed, finishing, request identity) is recorded now;
        its slot state advances and its token commits when the wave
        EXITS, pp-1 ticks later — mid-flight, every stage must keep
        seeing the wave's entry-time cache positions."""
        pp = self._pp
        enter_wave = self._tickno % pp
        exit_wave = (self._tickno - (pp - 1)) % pp
        tokens, starts, nvalid, consumed, finishing = self._stage()
        self._inflight[enter_wave] = (
            consumed.copy(), list(finishing), [s.req for s in self._slots])
        return tokens, starts, nvalid, exit_wave

    def _commit_pp_exit_locked(self, exit_wave, nxt, t_ns):
        """Advance the exiting wave's slots; returns tokens committed."""
        rec = self._inflight.pop(exit_wave, None)
        if rec is None:
            return 0
        committed = 0
        consumed_e, finishing_e, reqs_e = rec
        lo, hi = exit_wave * self._wave, (exit_wave + 1) * self._wave
        for i in range(lo, hi):
            slot = self._slots[i]
            # commit only if the slot still holds the request the wave
            # carried (not freed/re-admitted mid-flight)
            if slot.req is None or slot.req is not reqs_e[i]:
                continue
            req = slot.req   # _commit_token may free the slot
            if slot.off < len(slot.seq):
                slot.off += int(consumed_e[i])
            self._lengths[i] += int(consumed_e[i])
            if finishing_e[i]:
                self._commit_token(i, int(nxt[i]))
                committed += 1
                self._tick_progress(req, t_ns)
        return committed

    def _loop(self):
        while True:
            try:
                # _step_impl, not step(): the loop writes its own crash
                # dump below AFTER the fail-all marks, so the on-disk
                # post-mortem carries the failing requests' terminal
                # events (step()'s dump would fire before them)
                busy = self._step_impl()
            except BaseException as e:  # noqa: BLE001 — a dead loop with
                # _running stuck True would hang every current AND future
                # request; fail them all with the cause instead (donated
                # caches may be gone, so the engine is not reusable)
                with self._lock:
                    def _fail(req, where):
                        req.error = e
                        # goodput accounting: every token the failed
                        # request generated is aborted work the caller
                        # never got — the /load report's goodput ratio
                        # reads completed/(completed+aborted)
                        self._c["aborted_tokens"].inc(len(req.tokens))
                        now = time.perf_counter()
                        req.lifecycle.update(
                            t_abort=now, aborted=True,
                            tokens=len(req.tokens), where=where,
                            error=type(e).__name__)
                        self._record_abort_locked(
                            req, where, type(e).__name__, now)
                        if req.on_token is not None:
                            # terminal AFTER any already-buffered tokens
                            self._stream_emit.append((req, None))
                        # close the lifecycle spans (no-ops when tracing
                        # is off) and leave a terminal flight mark — the
                        # failing requests are the ones a post-mortem
                        # most needs to see
                        req._span_queue.end(error=type(e).__name__)
                        req._span_life.end(error=type(e).__name__)
                        self._flight.record(
                            "req", phase="fail", rid=req.rid,
                            engine=self._engine_id, where=where,
                            error=type(e).__name__)
                        req._event.set()
                    for req in list(self._pending):
                        _fail(req, "pending")
                    self._pending.clear()
                    self._deadline_queued = 0
                    for i, slot in enumerate(self._slots):
                        if slot.req is not None:
                            _fail(slot.req, "slot")
                            slot.req = None
                            if self._paged:
                                self._release_pages_locked(i)
                    for rec in self._inflight.values():
                        for req in rec[2]:
                            if req is not None and not req._event.is_set():
                                _fail(req, "inflight")
                    self._inflight.clear()
                    # retained sessions die with the engine (their pages
                    # live in the donated caches that may be gone); busy
                    # sessions hold no refs — their pages were on slots
                    for sess in list(self._sessions.values()):
                        if self._paged and sess.pages:
                            self._pool.decref(sess.pages)
                    self._sessions.clear()
                    self._update_session_gauges_locked()
                    self._running = False
                    self._crashed = e
                # deliver the failed requests' stream terminals (and any
                # tokens the crashing tick had committed) — a streaming
                # consumer blocked on its queue must learn the replica
                # died, not hang until its own timeout
                self._flush_streams()
                # the loop thread dies on this raise: PIN the beacon so
                # it survives the thread's exit and goes stale — the
                # /healthz?max_age alert a crashed engine must leave
                # (beacon_ages GCs dead-thread beacons otherwise)
                _tr.pin_beacon(f"serving.{self._engine_id}")
                if not getattr(e, "_pht_usage_error", False):
                    _flight.crash_dump(
                        f"serving.step[{self._engine_id}]", e)
                raise
            if not busy:
                with self._lock:
                    if (not self._pending
                            and all(s.req is None for s in self._slots)):
                        self._running = False
                        # clean drain between bursts: drop the beacon so
                        # an IDLE engine doesn't 503 /healthz?max_age —
                        # the next burst's first tick re-adds it (the
                        # crash path above raises instead, keeping the
                        # beacon: going stale is the alert)
                        _tr.remove_beacon(f"serving.{self._engine_id}")
                        return

    def introspect_requests(self) -> dict:
        """In-flight slot table for ``/debug/requests`` (and debugging):
        one row per slot — request id, prompt progress, tokens generated,
        committed cache depth — plus the pending-queue depth.  Snapshot
        under the engine lock; called from the introspection server's
        thread, so it must stay cheap (it is: B small dicts)."""
        with self._lock:
            slots = []
            for i, slot in enumerate(self._slots):
                req = slot.req
                if req is None:
                    slots.append(None)
                    continue
                row = {
                    "rid": req.rid, "slot": i,
                    "prompt_len": int(len(req.prompt)),
                    "prompt_consumed": int(slot.off),
                    "generated": len(req.tokens),
                    "max_new_tokens": req.max_new_tokens,
                    "cache_len": int(self._lengths[i]),
                    "priority": req.priority,
                    "preempted": req._preempts,
                }
                if self._paged:
                    row["pages"] = len(self._slot_pages[i])
                slots.append(row)
            out = {"engine": self._engine_id, "tickno": self._tickno,
                   "running": self._running,
                   "draining": self._draining,
                   "pending": len(self._pending), "slots": slots,
                   # bounded terminal ring: where recently-aborted
                   # requests died (where="deadline" for budget aborts,
                   # pending/slot/inflight for a loop failure)
                   "recent_aborts": list(self._recent_aborts)}
            out["sessions"] = len(self._sessions)
            if self._paged:
                out["kv_pages_in_use"] = self._pool.allocated
                out["kv_pages_free"] = self._pool.free
                out["prefix_cached_pages"] = (
                    len(self._prefix) if self._prefix is not None else 0)
            return out

    def slo_windows(self) -> dict:
        """The live rolling SLO windows (``{"ttft", "tpot", "e2e",
        "queue_wait"} -> SlidingWindowHistogram``) — the percentile
        source behind :meth:`load_report`'s ``slo`` block, exposed for
        in-process fleet aggregation (``metrics.merged_percentiles``
        pools several replicas' windows without losing the
        never-exceeds-observed-max clamp).  In-process only: HTTP
        replicas federate through ``/load``'s serialized percentiles
        instead."""
        return dict(self._slo)

    def load_report(self) -> dict:
        """The machine-readable load/capacity report — the versioned
        JSON document the ``/load`` endpoint serves and a least-loaded
        router polls (ROADMAP item 2; schema contract:
        docs/OBSERVABILITY.md, "SLO telemetry and the /load report").

        One snapshot under the engine lock (host dicts and counters
        only — no device touch), so polling never stalls a tick:

        - ``slots``/``queue``: free capacity and how long the
          longest-waiting queued request has been waiting since its
          last enqueue (submit or preemption re-queue), plus the
          per-priority-class breakdown (``queue.classes``) — a
          least-loaded router scoring total depth alone would let an
          interactive queue starve unseen behind a deep batch queue.
        - ``admission``: the headroom a router sizes a request against —
          largest admissible ``prompt + max_new`` right now (page-exact
          in paged mode via ``paged.tokens_admittable``, ``max_len``
          minus the write-window reserve in dense), plus the paged
          pool's free/used pages.
        - ``modes``: what this replica is (spec/quant/MoE/paged/pp) —
          a router must not mix replicas with different latency shapes
          in one SLO pool blindly.
        - ``slo``: rolling TTFT/TPOT/e2e/queue-wait percentiles over the
          last ``slo_window_s`` seconds (None when no traffic — never
          NaN, which is not JSON).
        - ``goodput``: completed vs aborted generated tokens and their
          ratio (None before any token).
        """
        reserve = max(self.chunk, self.spec_k + 1)
        with self._lock:
            now = time.perf_counter()
            active = sum(s.req is not None for s in self._slots)
            free_slots = self.max_slots - active
            oldest = max((now - r._t_queued for r in self._pending),
                         default=0.0)
            cls_q = {c: {"depth": 0, "oldest_wait_s": 0.0}
                     for c in PRIORITY_RANK}
            for r in self._pending:
                row = cls_q[r.priority]
                row["depth"] += 1
                w = round(now - r._t_queued, 6)
                if w > row["oldest_wait_s"]:
                    row["oldest_wait_s"] = w
            completed = int(self._c["completed_tokens"].value)
            aborted = int(self._c["aborted_tokens"].value)
            report = {
                "version": 1,
                "engine": self._engine_id,
                "ts": time.time(),
                "running": self._running,
                # a draining replica still finishes queued + inflight
                # work but refuses submits — a router must not dispatch
                # to it (field added within version 1: consumers that
                # don't know it keep working, routers that do stop
                # placing here the poll after drain() is called)
                "draining": self._draining,
                "tickno": self._tickno,
                "slots": {"max": self.max_slots, "active": active,
                          "free": free_slots},
                "queue": {"depth": len(self._pending),
                          "oldest_wait_s": round(oldest, 6),
                          # per-class block (added within version 1):
                          # all classes always present, zeroed when
                          # idle, so router code never key-checks
                          "classes": cls_q},
                "modes": {"cache": self.cache_mode,
                          "spec_k": self.spec_k,
                          "quant": self._quantized,
                          "moe": self._moe,
                          "pp": self._pp},
                "slo": {"window_s": self._slo_window_s,
                        **{k: h.percentiles()
                           for k, h in self._slo.items()},
                        # per-class TTFT/queue-wait percentiles (added
                        # within version 1): the control signal the
                        # scheduler exists to move — aggregate p99
                        # launders an interactive tail under batch bulk
                        "classes": {c: {k: h.percentiles()
                                        for k, h in hs.items()}
                                    for c, hs in self._slo_cls.items()}},
                # scheduler block (added within version 1): the knobs a
                # fleet operator tunes + the preemption count goodput
                # regressions get correlated against
                "scheduler": {
                    "preemptions": int(self._c["preemptions"].value),
                    "preempt_replay_tokens": int(
                        self._c["preempt_replay_tokens"].value),
                    "preempt": self._preempt,
                    "preempt_limit": self._preempt_limit,
                    "prefill_budget": self._prefill_budget,
                    "priority_aging_s": self._aging_s},
                "goodput": {
                    "completed_tokens": completed,
                    "aborted_tokens": aborted,
                    "ratio": (completed / (completed + aborted)
                              if completed + aborted else None)},
            }
            admission = {"reserve_tokens": reserve}
            # the per-slot caps every request faces regardless of pool
            # state: max_len minus the write-window reserve, and the
            # model's position table (submit() refuses past either)
            slot_cap = self.max_len - reserve
            max_pos = getattr(self.model.config,
                              "max_position_embeddings", None)
            if max_pos is not None:
                slot_cap = min(slot_cap, int(max_pos))
            sess_pages = set()
            for s in self._sessions.values():
                sess_pages.update(s.pages)
            sess_evictable = self._session_evictable_pages_locked()
            # sessions block (added within version 1): how much of the
            # pool conversation retention is pinning, and how much of
            # that admission pressure could take back RIGHT NOW
            report["sessions"] = {
                "count": len(self._sessions),
                "retained_pages": len(sess_pages),
                "evictable_pages": sess_evictable}
            if self._paged:
                from .paged import tokens_admittable
                # admission evicts cache-only prefix pages AND LRU
                # sessions' exclusively-held pages to cover a shortfall
                # (_paged_admit_locked), so the free list alone
                # UNDERSTATES what would actually admit — the router
                # contract is "would this request fit RIGHT NOW",
                # eviction included (sessions never starve admission)
                evictable = (self._prefix.cached_only()
                             if self._prefix is not None else 0)
                headroom = min(
                    tokens_admittable(
                        self._pool.free + evictable + sess_evictable,
                        reserve, self._page_size),
                    slot_cap)
                admission.update(
                    kv_pages_free=self._pool.free,
                    kv_pages_evictable=evictable,
                    kv_pages_in_use=self._pool.allocated,
                    page_size=self._page_size,
                    # a free slot is still required: pages alone don't
                    # admit when every slot is occupied
                    headroom_tokens=headroom if free_slots else 0)
            else:
                admission["headroom_tokens"] = (slot_cap if free_slots
                                                else 0)
            report["admission"] = admission
            if self._prefix is not None:
                # cache-affinity signal (added within version 1): chain
                # digests of resident radix-cache nodes.  A router
                # hashing a prompt's page-aligned prefixes the same way
                # (paged.page_digests) matches the deepest digest here
                # to find the replica already holding those KV pages.
                # Bounded (most-recent first) so a warm cache never
                # bloats the poll document.
                # retained sessions' chain digests lead: a returning
                # turn's page_digests match them deepest here, which is
                # exactly the fleet-tier session stickiness signal —
                # then the cache's recency-ordered digests fill the cap
                digs = []
                seen = set()
                for s in self._sessions.values():
                    for d in s.digests:
                        if d not in seen:
                            seen.add(d)
                            digs.append(d)
                for d in self._prefix.digests(self.PREFIX_DIGEST_LIMIT):
                    if d not in seen:
                        seen.add(d)
                        digs.append(d)
                report["prefix_digest"] = {
                    "algo": "crc32-pages",
                    "page_size": self._page_size,
                    "digests": digs[:self.PREFIX_DIGEST_LIMIT]}
            return report

    @property
    def kv_pages_in_use(self) -> int:
        """Allocated pool pages (0 in dense mode) — includes pages held
        only by the prefix cache or by retained sessions;
        :meth:`drop_prefix_cache` + :meth:`drop_sessions` reclaim those,
        after which a drained engine must read 0 (the pool-leak assert
        tools/perf_gate.py gates via the bench row)."""
        return self._pool.allocated if self._paged else 0

    @property
    def kv_pages_free(self) -> int:
        return self._pool.free if self._paged else 0

    def drop_prefix_cache(self) -> int:
        """Release every cached prefix page (HBM reclaim / leak checks);
        returns how many the cache held.  Pages a live slot still maps
        stay allocated until that slot frees."""
        with self._lock:
            if self._prefix is None:
                return 0
            n = self._prefix.drop()
            self._g_pages_used.set(self._pool.allocated)
            self._g_pages_free.set(self._pool.free)
            return n

    def run_until_idle(self, max_ticks=100000):
        """Drive the engine synchronously (single-threaded use/tests).
        Raises if the auto_run loop is concurrently driving (see
        :meth:`step`'s single-driver contract)."""
        for _ in range(max_ticks):
            if not self.step():
                with self._lock:
                    if (not self._pending
                            and all(s.req is None for s in self._slots)):
                        # mirror the auto_run loop's idle-drain: a
                        # synchronously driven engine must not leave a
                        # forever-stale beacon 503ing /healthz?max_age
                        _tr.remove_beacon(f"serving.{self._engine_id}")
                return
        raise RuntimeError("engine did not drain in max_ticks")

    def drain(self, timeout=60.0):
        """Graceful removal, the half hard ``shutdown(timeout=)`` does
        not give: stop ADMITTING (``submit`` raises
        :class:`EngineDraining`), let queued + inflight requests run to
        completion, then drop the liveness beacon — the engine object
        stays constructed (introspection/metrics keep answering) until
        :meth:`shutdown` completes the teardown.  This is what a fleet
        router calls to remove a replica without failing a single
        request (``FleetRouter.drain``).

        A sync-driven engine (``auto_run=False``, or an auto_run engine
        whose loop has idled out) is driven to completion HERE — drain
        becomes the driver, honoring the single-driver contract (it
        only steps while the loop is not running).  Idempotent; raises
        ``TimeoutError`` if the backlog outlives ``timeout``, and
        ``RuntimeError`` (crash as ``__cause__``) if the engine's loop
        CRASHED instead of draining — the emptied slots/queue then
        mean the backlog was failed, not completed, and the pinned
        crash beacon is left alone (going stale IS the alert)."""
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                running = self._running
                crashed = self._crashed
                idle = (not self._pending
                        and all(s.req is None for s in self._slots)
                        and not self._inflight_live())
            if crashed is not None and not running:
                raise RuntimeError(
                    f"engine {self._engine_id} crashed while draining "
                    f"({type(crashed).__name__}): its queued + inflight "
                    f"requests were FAILED, not completed — this is not "
                    f"a clean removal") from crashed
            if idle and not running:
                with self._lock:
                    # graceful session eviction: a draining replica
                    # DONATES every retained chain to the prefix cache,
                    # so a conversation re-admitted elsewhere-then-back
                    # (or replayed by the router on a survivor) replays
                    # from cached pages instead of dying mid-dialogue
                    for sid in list(self._sessions):
                        if not self._sessions[sid].busy:
                            self._evict_session_locked(sid, donate=True)
                # same clean-drain contract as the loop's idle exit: a
                # DRAINED engine must not 503 /healthz?max_age forever
                _tr.remove_beacon(f"serving.{self._engine_id}")
                return
            if running:
                time.sleep(0.005)   # the auto_run loop is finishing it
            else:
                self.step()         # sync-driven: drain is the driver
        raise TimeoutError(
            f"engine {self._engine_id} did not drain in {timeout}s")

    def shutdown(self, timeout=60.0):
        """Wait for the background loop to drain and stop — call before
        interpreter exit so a daemon thread isn't killed mid-device-call
        (which aborts the process from PJRT's C++).  Also drops this
        engine's labelled series from the process-wide registry (engine
        churn must not grow it forever); ``self.stats`` holds its own
        counter handles, so it stays readable after shutdown."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not self._running:
                    self._registry.drop_labels(engine=self._engine_id)
                    _tr.unregister_introspection_source(self._engine_id)
                    # a shut-down engine must vanish from the router's
                    # /load poll (and the /debug mirror) immediately,
                    # not only when the weak refs die
                    _tr.unregister_load_source(self._engine_id)
                    _tr.unregister_introspection_source(
                        f"{self._engine_id}.load")
                    # clean shutdown: a gone engine must not leave a
                    # forever-stale beacon 503ing /healthz?max_age (a
                    # CRASHED loop keeps its beacon — stale IS the alert)
                    _tr.remove_beacon(f"serving.{self._engine_id}")
                    return
            time.sleep(0.005)
        raise TimeoutError("engine loop did not drain before timeout")
