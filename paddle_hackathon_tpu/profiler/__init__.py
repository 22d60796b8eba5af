"""paddle.profiler equivalent.

Ref ``python/paddle/profiler/profiler.py`` — ``Profiler`` (:271) with the
scheduler state machine (``ProfilerState`` :34, ``make_scheduler``),
``export_chrome_tracing`` (:158), ``RecordEvent`` instrumentation
(``platform/profiler/event_tracing.h``) and the statistics report
(``profiler_statistic.py``).

Host events come from a thread-local recorder (the ``HostEventRecorder``
analog, ``host_event_recorder.h``); device activity is captured by
``jax.profiler`` (XLA's tracer plays CUPTI's role) into a TensorBoard
trace directory next to the chrome JSON. Op-level instrumentation hooks
``core.autograd.apply_op`` the way the reference sprinkles RecordEvent
through its op layer.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
import time
from enum import Enum
from typing import Callable, Iterable, Optional

from ..core import autograd as _autograd
from ..observability.sanitizers import make_lock

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "make_scheduler",
           "export_chrome_tracing", "export_protobuf", "RecordEvent",
           "load_profiler_result", "SummaryView"]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # record and emit trace at this step


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Ref profiler.py make_scheduler — cyclic CLOSED/READY/RECORD windows."""
    period = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


# ---------------------------------------------------------------------------
# Host event recording
# ---------------------------------------------------------------------------

class _HostEvent:
    __slots__ = ("name", "start", "end", "tid", "event_type", "args")

    def __init__(self, name, start, end, tid, event_type="UserDefined",
                 args=None):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid
        self.event_type = event_type
        self.args = args  # chrome-trace args payload (span attrs)


class _Recorder:
    """Process-wide host event sink (ref HostEventRecorder)."""

    def __init__(self):
        self.events = []
        self.counters = []   # (name, labels_tuple, value, t_ns) samples
        # make_lock, not threading.Lock: the lock-order and race
        # sanitizers must see every lock in the process (PHT009 sweep)
        self._lock = make_lock("profiler.recorder")
        self.active = False

    def add(self, ev: _HostEvent):
        if not self.active:
            return
        with self._lock:
            self.events.append(ev)

    def add_counter(self, name, labels, value, t_ns):
        """Metric-update sample (armed into observability.metrics as the
        trace sink while recording) — lands as a chrome "ph":"C" counter
        event next to the spans."""
        if not self.active:
            return
        with self._lock:
            self.counters.append((name, labels, value, t_ns))

    def add_span(self, name, t0_ns, t1_ns, tid, attrs):
        """Finished observability.tracing span (armed as the span sink
        while recording) — a "ph":"X" duration event carrying its attrs
        (request id, slot, step …) as chrome-trace args."""
        if not self.active:
            return
        with self._lock:
            self.events.append(_HostEvent(name, t0_ns, t1_ns, tid, "Span",
                                          args=attrs))

    def drain(self):
        with self._lock:
            evs, self.events = self.events, []
        return evs

    def drain_counters(self):
        with self._lock:
            cs, self.counters = self.counters, []
        return cs


_recorder = _Recorder()


class RecordEvent:
    """Instrumentation scope (ref ``RecordEvent`` event_tracing.h; python
    ``paddle.profiler.RecordEvent``). Usable as context manager or
    begin()/end() pair."""

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        self._start = None

    def begin(self):
        self._start = time.perf_counter_ns()

    def end(self):
        if self._start is None:
            return
        _recorder.add(_HostEvent(self.name, self._start,
                                 time.perf_counter_ns(),
                                 threading.get_ident(), self.event_type))
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def _op_hook(name: str):
    """Installed into apply_op while a profiler records (the reference
    instruments every op launch)."""
    return RecordEvent(name, event_type="Operator")


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """Returns an on_trace_ready callback writing chrome://tracing JSON."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_step{prof.step_num}.json")
        events = []
        for ev in prof._events:
            row = {
                "name": ev.name, "ph": "X", "cat": ev.event_type,
                "pid": os.getpid(), "tid": ev.tid,
                "ts": ev.start / 1000.0,       # ns -> us
                "dur": (ev.end - ev.start) / 1000.0,
            }
            if getattr(ev, "args", None):
                row["args"] = ev.args   # span attrs (request id, step, …)
            events.append(row)
        # registry counters/gauges sampled while recording: chrome counter
        # rows ("ph":"C") on the same timeline as the spans.  Label sets
        # render into the event name so each series gets its own row;
        # the value rides args (chrome plots every args key as a series).
        for cname, labels, value, t_ns in getattr(prof, "_counter_events",
                                                  ()):
            if labels:
                cname = cname + "{" + ",".join(
                    f"{k}={v}" for k, v in labels) + "}"
            events.append({
                "name": cname, "ph": "C", "cat": "Metric",
                "pid": os.getpid(), "ts": t_ns / 1000.0,
                "args": {"value": value},
            })
        # compile spans ride a dedicated synthetic lane; name it so the
        # chrome/perfetto row reads "compiles", not a raw tid number
        # (cross_stack.merge_traces preserves tids, so merged traces keep
        # one named compiles lane per rank)
        from ..observability.programs import COMPILES_LANE_TID
        if any(e.get("tid") == COMPILES_LANE_TID for e in events):
            events.append({"name": "thread_name", "ph": "M",
                           "pid": os.getpid(), "tid": COMPILES_LANE_TID,
                           "args": {"name": "compiles"}})
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        prof._last_export = path
        return path

    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """Protobuf-analog exporter: pickled event list (the reference's
    serialization format is its own proto; the content parity is the event
    stream)."""

    def handler(prof: "Profiler"):
        import pickle
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_step{prof.step_num}.pb")
        with open(path, "wb") as f:
            pickle.dump([(e.name, e.start, e.end, e.tid, e.event_type)
                         for e in prof._events], f)
        prof._last_export = path
        return path

    return handler


def load_profiler_result(path: str):
    import pickle
    with open(path, "rb") as f:
        raw = pickle.load(f)
    return [_HostEvent(*r) for r in raw]


# ---------------------------------------------------------------------------
# Profiler
# ---------------------------------------------------------------------------

class Profiler:
    """Ref ``Profiler`` profiler.py:271. start/stop/step drive the scheduler
    state machine; on RECORD_AND_RETURN (or stop) the trace is handed to
    on_trace_ready."""

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, timer_only=False,
                 record_shapes=False, profile_memory=False,
                 with_flops=False, use_device_tracer: bool = True):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU,
                                                      ProfilerTarget.TPU]
        if scheduler is None:
            self._scheduler = _default_state_scheduler
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        else:
            self._scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._events = []
        self._counter_events = []
        self._last_export = None
        self._device_dir = None
        self._device_active = False
        self._use_device_tracer = use_device_tracer
        self._benchmark = _Timer()

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self._benchmark.begin()
        self.current_state = self._scheduler(self.step_num)
        self._transition(ProfilerState.CLOSED, self.current_state)

    def stop(self):
        self._benchmark.end()
        if not self.timer_only and self.current_state in (
                ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._stop_record()
            if self.on_trace_ready:
                self.on_trace_ready(self)
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        self._benchmark.step(num_samples)
        prev = self.current_state
        self.step_num += 1
        self.current_state = self._scheduler(self.step_num)
        self._transition(prev, self.current_state)

    def _transition(self, old: ProfilerState, new: ProfilerState):
        if self.timer_only:
            return
        recording_old = old in (ProfilerState.RECORD,
                                ProfilerState.RECORD_AND_RETURN)
        recording_new = new in (ProfilerState.RECORD,
                                ProfilerState.RECORD_AND_RETURN)
        if not recording_old and recording_new:
            self._start_record()
        elif recording_old and old == ProfilerState.RECORD_AND_RETURN:
            self._stop_record()
            if self.on_trace_ready:
                self.on_trace_ready(self)
            if recording_new:
                self._start_record()
        elif recording_old and not recording_new:
            self._stop_record()
            if self.on_trace_ready:
                self.on_trace_ready(self)

    def _start_record(self):
        _recorder.active = True
        _autograd._profiler_hook = _op_hook
        # mirror registry counter/gauge updates onto the trace timeline
        try:
            from ..observability import metrics as _metrics
            _metrics.set_trace_sink(_recorder.add_counter)
        except Exception:
            pass
        # arm event-level spans (observability.tracing): request/step
        # spans land as "ph":"X" events next to the counter rows.  A
        # user may have enabled tracing independently (flight-recorder
        # spans without a profiler) — remember and restore that.
        try:
            from ..observability import tracing as _tracing
            self._tracing_was_enabled = _tracing.tracing_enabled()
            _tracing.set_span_sink(_recorder.add_span)
            _tracing.enable_tracing()
        except Exception:
            pass
        # also arm the native host tracer (C++ workqueue/dataloader spans)
        try:
            from ..core import native as _native
            if _native.available():
                _native.trace_enable(True)
        except Exception:
            pass
        if self._use_device_tracer and ProfilerTarget.TPU in self.targets:
            try:
                import jax
                self._device_dir = os.path.join(
                    os.environ.get("PADDLE_PROFILER_DIR",
                                   tempfile.gettempdir()),
                    f"xla_trace_{os.getpid()}_{self.step_num}")
                jax.profiler.start_trace(self._device_dir)
                self._device_active = True
            except Exception:
                self._device_active = False

    def _stop_record(self):
        _autograd._profiler_hook = None
        _recorder.active = False
        try:
            from ..observability import metrics as _metrics
            _metrics.set_trace_sink(None)
        except Exception:
            pass
        try:
            from ..observability import tracing as _tracing
            _tracing.set_span_sink(None)
            if not getattr(self, "_tracing_was_enabled", False):
                _tracing.disable_tracing()
        except Exception:
            pass
        self._events = _recorder.drain()
        self._counter_events = _recorder.drain_counters()
        # drain native host-tracer events into the same stream
        try:
            from ..core import native as _native
            if _native.available():
                _native.trace_enable(False)
            if _native.available() and _native.trace_count():
                import tempfile
                with tempfile.NamedTemporaryFile("r", suffix=".json",
                                                 delete=False) as f:
                    path = f.name
                _native.trace_dump_chrome(path)
                _native.trace_clear()
                with open(path) as f:
                    for ev in json.load(f)["traceEvents"]:
                        start = int(ev["ts"] * 1000)
                        self._events.append(_HostEvent(
                            ev["name"], start, start + int(ev["dur"] * 1000),
                            ev["tid"], "Native"))
                os.unlink(path)
        except Exception:
            pass
        if self._device_active:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_active = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- reporting ---------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Aggregated per-op table (ref profiler_statistic.py)."""
        agg = {}
        for ev in self._events:
            dur = (ev.end - ev.start) / 1e6  # ms
            a = agg.setdefault(ev.name, [0, 0.0, float("inf"), 0.0])
            a[0] += 1
            a[1] += dur
            a[2] = min(a[2], dur)
            a[3] = max(a[3], dur)
        rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Min':>10}"
                 f"{'Max':>10}{'Avg':>10}"]
        for name, (calls, tot, mn, mx) in rows:
            lines.append(f"{name[:39]:<40}{calls:>8}{tot:>12.3f}{mn:>10.3f}"
                         f"{mx:>10.3f}{tot / calls:>10.3f}")
        report = "\n".join(lines)
        print(report)
        return agg

    @property
    def events(self):
        return list(self._events)

    @property
    def device_trace_dir(self):
        """Where the last recording's ``jax.profiler`` session wrote its
        ``.xplane.pb`` (device ops and the program's armed spans on one
        clock), or ``None`` before the first recording with the TPU
        target."""
        return self._device_dir

    def benchmark_summary(self):
        return self._benchmark.summary()


class _Timer:
    """Throughput benchmark (ref profiler/timer.py — ips/step stats)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = None
        self.step_times = []
        self.samples = []

    def begin(self):
        self._t0 = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t0 is not None:
            self.step_times.append(now - self._t0)
            self.samples.append(num_samples or 0)
        self._t0 = now

    def end(self):
        self._t0 = None

    def summary(self):
        if not self.step_times:
            return {}
        import numpy as np
        st = np.asarray(self.step_times)
        out = {"steps": len(st), "avg_step_s": float(st.mean()),
               "min_step_s": float(st.min()), "max_step_s": float(st.max())}
        total_samples = sum(self.samples)
        if total_samples:
            out["ips"] = total_samples / float(st.sum())
        return out


class SortedKeys(Enum):
    """Sort key for the stats report (ref profiler/profiler_statistic.py
    SortedKeys)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


__all__.append("SortedKeys")


from . import cross_stack  # noqa: E402,F401
from .cross_stack import merge_traces  # noqa: E402,F401
