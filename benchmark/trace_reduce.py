"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
time as the union of the intervals in which an operation runs, exclusive
("self") time by event name with nested events counted once, the ten ops
that took most time, and the longest idle gaps named by what the host was
doing.  Pure functions over ``(name, start_ns, duration_ns)`` lists, plus
one loader that needs nothing but jax.

How a v5e trace from jax 0.9.0 looks (read by hand, PR 25): one plane per
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO op or fusion, with control-flow bodies nested inside their
``while``/``conditional`` event on the same line; ``XLA Modules`` holds one
event per program run; host threads are lines of the plane ``/host:CPU``,
where ``jax.profiler.TraceAnnotation`` spans land under their own names.
"""

from __future__ import annotations

import glob
import math
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_planes(path: str) -> dict:
    """``{plane name: {line name: [(event name, start_ns, duration_ns)]}}``.
    Lines of one name within a plane are merged."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns),
                               float(ev.duration_ns)))
    return planes


def device_planes(planes: dict) -> dict:
    return {k: v for k, v in planes.items()
            if k.startswith(DEVICE_PLANE_PREFIX)}


def busy_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if cur_e is None or start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = start, end
        else:
            cur_e = max(cur_e, end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events):
    """``[(start_ns, duration_ns)]`` of the intervals between the first
    event's start and the last event's end in which no event runs."""
    out, cur_e = [], None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if cur_e is not None and start > cur_e:
            out.append((cur_e, start - cur_e))
        cur_e = start + dur if cur_e is None else max(cur_e, start + dur)
    return out


def self_time_by_name(events) -> dict:
    """Exclusive time by event name: an event nested inside another (a
    loop body inside its ``while``) is counted once, under its own name,
    and taken out of its parent's.  The values add up to ``busy_ns``
    where events nest properly."""
    out = {}
    stack = []  # [name, end, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own

    # parents before children at equal starts: longer first
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            # the child's time leaves the parent's own
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def own_name(name: str) -> str:
    """The instruction's own name: an ``XLA Ops`` event is named by the
    whole HLO text (``%fusion.7 = bf16[..] fusion(%kernel.3, ...)``), so
    an op that merely consumes a kernel's output carries the kernel's name
    among its operands.  Only the text before `` = `` is the op's."""
    return name.partition(" = ")[0]


def _is(name: str, names) -> bool:
    own = own_name(name)
    return any(n in own for n in names)


def time_of(events, names) -> float:
    """Summed duration (ns) of the events whose own name (``own_name``)
    contains one of ``names`` -- kernel time: a Mosaic call is a leaf,
    nothing nests in it."""
    return sum(dur for name, _, dur in events if _is(name, names))


def count_of(events, names) -> int:
    return sum(1 for name, _, _ in events if _is(name, names))


_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def op_kind(name: str) -> str:
    """An op's kind, for the breakdown: the HLO instruction's own name
    without its running number (``%jvp_flash_packed_fwd_.33 = ...`` ->
    ``jvp_flash_packed_fwd_``), and for a fusion the largest array it
    produces (``%fusion.189 = bf16[50304,1024]{...} fusion(...)`` ->
    ``fusion bf16[50304,1024]``), which tells the vocabulary head's
    fusions from a block's and a weight's gradient from an activation."""
    rest = name.partition(" = ")[2]
    base = own_name(name).lstrip("%").rstrip("0123456789").rstrip(".")
    if "fusion" in base and rest:
        arrays = _ARRAY.findall(rest.split(" fusion(")[0])
        if arrays:
            dtype, dims = max(arrays, key=lambda a: math.prod(
                int(d) for d in a[1].split(",") if d))
            return f"{base} {dtype}[{dims}]"
    return base[:120]


def by_kind(by_name: dict) -> dict:
    out = {}
    for name, t in by_name.items():
        k = op_kind(name)
        out[k] = out.get(k, 0.0) + t
    return out


def top(by_name: dict, n=10):
    return [[k, v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def name_gaps(gap_list, host_spans, n=10):
    """Name each idle gap by the host span (``[(name, start, dur)]``) that
    covers most of it, and sum by name: ``[[name, ns], ...]``, longest
    first.  A gap no span covers is ``"(no host span)"``."""
    out = {}
    for g_start, g_dur in gap_list:
        best, best_cover = "(no host span)", 0.0
        for name, s, d in host_spans:
            cover = min(g_start + g_dur, s + d) - max(g_start, s)
            if cover > best_cover:
                best, best_cover = name, cover
        out[best] = out.get(best, 0.0) + g_dur
    return top(out, n)


def reduce(planes: dict, host_span_names=()) -> dict:
    """The numbers the benchmark takes from one traced window.

    Per chip: the window is from the first op's start to the last op's
    end on ``XLA Ops``; busy is the union of the ops there.  ``busy_s``
    and ``window_s`` are averaged over the chips.  ``ops`` (the first
    chip's events, for kernel-time readers), ``device_ops`` (exclusive time by ``op_kind``) and
    ``idle_gaps`` (the breakdown) come with them.  Returns ``None`` where no device plane has an op."""
    per_chip = []
    for name in sorted(device_planes(planes)):
        ops = planes[name].get(OPS_LINE, [])
        if not ops:
            continue
        lo = min(s for _, s, _ in ops)
        hi = max(s + d for _, s, d in ops)
        per_chip.append({"ops": ops, "lo": lo, "hi": hi,
                         "busy_ns": busy_ns(ops)})
    if not per_chip:
        return None
    first = per_chip[0]
    host = [ev for pname, lines in planes.items()
            if pname.startswith(HOST_PLANE_PREFIX)
            for evs in lines.values() for ev in evs
            if ev[0] in host_span_names]
    return {
        "busy_s": sum(c["busy_ns"] for c in per_chip) / len(per_chip) / 1e9,
        "window_s": sum(c["hi"] - c["lo"] for c in per_chip)
        / len(per_chip) / 1e9,
        "ops": first["ops"],
        "device_ops": [[k, v / 1e9] for k, v in
                       top(by_kind(self_time_by_name(first["ops"])))],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      name_gaps(gaps(first["ops"]), host)],
    }
