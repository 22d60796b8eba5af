"""Program observatory: per-build compile telemetry, retrace-cause
forensics, and per-program HBM accounting.

``instrument_jit`` (and the ``jit/api.py`` to_static program cache) can
say *a* build happened; this module records *why*.  Every jit-build
site reports each trace+compile into the process-wide
:class:`ProgramRegistry` on the **build path only** — steady-state
calls never touch it — with:

- the site label, 1-based build index and compile wall time
  (also exported as the ``jit_compile_seconds{site}`` histogram, next
  to the existing ``jit_builds_total``), and where that wall went by
  jax's own build events: ``trace_s``, ``lower_s``,
  ``backend_compile_s`` and ``cache_hit`` (:func:`read_build_clock`);
  and ``kernel_facts``, what the kernels traced into the program said
  of themselves (:func:`note_kernel_fact`: the packed flash kernels'
  ``executed_score_share``);
- an abstract **call signature**: per-arg aval shape/dtype/weak_type,
  sharding spec when known, static-arg fingerprints and the donation
  map.  Signature capture is host-metadata-only (aval walks — never a
  device read), so instrumented hot paths stay PHT001-clean;
- on build N>1 at a site, the **retrace cause** — the signature diff
  rendered human-readable ("arg[2] `ids`: f32[8,512]→f32[8,640]",
  "static `spec_k`: 4→6", "dtype/weak_type flip", "new arg tree
  structure") — emitted as a ``program_build`` flight-recorder event
  and retained in a bounded per-site history;
- a compile span on the dedicated "compiles" chrome-trace lane
  (:data:`COMPILES_LANE_TID`; ``profiler/cross_stack.merge_traces``
  carries the lane through per rank), with the build clock's numbers
  as attributes;
- opt-in (``PHT_PROGRAM_ANALYSIS=1``, or :func:`program_analysis`,
  which ``bench.py``, ``chip_smoke.py`` and the benchmark's drivers
  put around their warm-up) per-program ``memory_analysis()`` bytes
  and ``cost_analysis()`` flops harvested through the AOT ``lower()``
  handle the wrappers preserve — exported as
  ``program_hbm_bytes{site,kind}`` / ``program_flops{site}`` gauges —
  plus two censuses of the compiled HLO text: the Mosaic kernels
  (:func:`mosaic_kernels`) and the phase census (:func:`phase_census`:
  every instruction that can run as a device op -> forward / backward
  / clip / update and the ``jax.named_scope`` component it sits
  under; the join key to a device trace's ``XLA Ops`` events), with
  what the compiler made itself and gave no name stack placed by the
  arrays it moves (:func:`placed_census`, the same parse).  The
  deeper pass asks jax to lower and compile the program once more per
  build; the build record times it (``analysis_s``,
  ``analysis_split``) — read that before paying it in a serving hot
  loop.

Surfaces: ``/debug/programs`` (``observability/server.py``), the
``programs`` summary in ``/debug/requests`` (registered via
``tracing.register_introspection_source``), ``tools/program_report.py``
(top compile-time sites, cause history, snapshot diffs), and the
``programs`` block bench rows embed for ``tools/perf_gate.py`` — a
build-growth gate failure prints the recorded causes.

Site labels are code-derived (call-site constants, layer class names),
never request-derived — the PHT005 label-boundedness contract.
Catalog and reading rules: ``docs/OBSERVABILITY.md``, "Program
observatory".
"""

from __future__ import annotations

import collections
import contextlib
import heapq
import inspect
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import tracing as _tracing
from .sanitizers import make_lock

__all__ = ["ProgramRegistry", "get_program_registry", "capture_signature",
           "diff_signatures", "signature_from_spec_key", "program_analysis",
           "mosaic_kernels", "phase_census", "phase_counts",
           "placed_census", "placed_counts", "PLACED_VIA",
           "PHASE_COMPONENTS", "PHASE_SUBCOMPONENTS", "PHASES", "start_build_clock",
           "read_build_clock", "BUILD_CLOCK_KEYS", "note_kernel_fact",
           "read_kernel_facts",
           "analysis_enabled", "observe_static_build",
           "observe_static_eviction", "COMPILES_LANE_TID",
           "HISTORY_PER_SITE"]

# Dedicated chrome-trace lane for compile spans: a fixed synthetic tid
# far outside both real thread idents' low range and the fleet's
# 2^20+fleet_rid lane space, so every build at every site lands on ONE
# "compiles" row (profiler.export_chrome_tracing names the lane;
# cross_stack.merge_traces preserves tids, so merged multi-rank traces
# keep one compiles lane per rank).
COMPILES_LANE_TID = 2 ** 21

# Bounded per-site build/cause history (the forensic window: recent
# retraces are the actionable ones; totals cover the rest).
HISTORY_PER_SITE = 16

_ENV_ANALYSIS = "PHT_PROGRAM_ANALYSIS"
_analysis_forced = 0

_DTYPE_SHORT = {"float32": "f32", "float64": "f64", "float16": "f16",
                "bfloat16": "bf16", "float8_e4m3fn": "f8e4m3",
                "float8_e5m2": "f8e5m2",
                "int64": "i64", "int32": "i32", "int16": "i16",
                "int8": "i8", "uint64": "u64", "uint32": "u32",
                "uint16": "u16", "uint8": "u8", "bool": "bool",
                "complex64": "c64", "complex128": "c128"}


def analysis_enabled() -> bool:
    """True when the deeper memory/cost harvest runs per build — the
    ``PHT_PROGRAM_ANALYSIS=1`` environment opt-in or an active
    :func:`program_analysis` context (bench.py arms the env form)."""
    return _analysis_forced > 0 \
        or os.environ.get(_ENV_ANALYSIS, "") not in ("", "0")


@contextlib.contextmanager
def program_analysis():
    """Force-enable the per-build memory/cost harvest for this block
    (test fixture path — no environment mutation, nests fine)."""
    global _analysis_forced
    _analysis_forced += 1
    try:
        yield
    finally:
        _analysis_forced -= 1


# ---------------------------------------------------------------------------
# Abstract call signatures (host metadata only — never a device read)
# ---------------------------------------------------------------------------

def _short_dtype(dt) -> str:
    name = getattr(dt, "name", None) or str(dt)
    return _DTYPE_SHORT.get(name, name)


def _sharding_str(x) -> Optional[str]:
    # .sharding/.spec are host metadata on a jax Array — reading them
    # never syncs; only a NamedSharding's spec is informative (every
    # single-device array would otherwise stamp identical noise)
    try:
        spec = getattr(getattr(x, "sharding", None), "spec", None)
        return str(spec) if spec is not None else None
    except Exception:  # noqa: BLE001 — signature capture is best-effort
        return None


def _static_fp(x) -> str:
    try:
        r = repr(x)
    except Exception:  # noqa: BLE001
        r = f"<unreprable {type(x).__name__}>"
    return r if len(r) <= 80 else r[:77] + "..."


def _leaf_entry(label: str, x) -> tuple:
    """One signature entry: ``("aval", label, shape, dtype, weak,
    sharding)`` for array-likes (aval metadata only), else
    ``("static", label, fingerprint)``."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None and not callable(shape):
        try:
            return ("aval", label, tuple(int(d) for d in shape),
                    _short_dtype(dtype), bool(getattr(x, "weak_type", False)),
                    _sharding_str(x))
        except Exception:  # noqa: BLE001 — fall through to the static path
            pass
    return ("static", label, _static_fp(x))


def _arg_names(fn, n: int) -> List[Optional[str]]:
    """Best-effort positional parameter names of the traced callable
    (``inspect.signature`` unwraps ``functools.wraps`` chains, so a
    jit/sanitizer wrapper still yields the user function's names)."""
    names: List[Optional[str]] = [None] * n
    if fn is None:
        return names
    try:
        params = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):
        return names
    for i in range(min(n, len(params))):
        if params[i].kind in (params[i].POSITIONAL_ONLY,
                              params[i].POSITIONAL_OR_KEYWORD):
            names[i] = params[i].name
    return names


def _tree_entries(label: str, tree) -> List[tuple]:
    try:
        import jax
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    except Exception:  # noqa: BLE001 — no-jax fallback: one opaque leaf
        return [_leaf_entry(label, tree)]
    out = []
    for path, leaf in flat:
        suffix = jax.tree_util.keystr(path) if path else ""
        out.append(_leaf_entry(label + suffix, leaf))
    return out


def capture_signature(args: Sequence = (), kwargs: Optional[dict] = None,
                      fn=None, donated=None) -> tuple:
    """The abstract signature of one call: a tuple of per-leaf entries
    over every positional/keyword arg's pytree.  Host-metadata-only by
    construction — aval walks (shape/dtype/weak_type/sharding spec) and
    ``repr`` of static python values; device buffers are never read, so
    the capture is PHT001-clean on any hot path that reaches it."""
    entries: List[tuple] = []
    names = _arg_names(fn, len(args))
    for i, a in enumerate(args):
        label = f"arg[{i}]" + (f" `{names[i]}`" if names[i] else "")
        entries.extend(_tree_entries(label, a))
    for k in sorted(kwargs or ()):
        entries.extend(_tree_entries(f"kw `{k}`", kwargs[k]))
    if donated:
        entries.append(("static", "donated", _static_fp(tuple(donated))))
    return tuple(entries)


def signature_from_spec_key(spec_key, training: bool) -> tuple:
    """Signature equivalent of ``jit/api.py``'s ``_spec_key`` tuples
    (the to_static program-cache key), so user-level retraces diff
    through the same taxonomy as instrument_jit sites."""
    entries: List[tuple] = []
    for i, part in enumerate(spec_key):
        label = f"arg[{i}]"
        if part[0] in ("T", "A"):
            entries.append(("aval", label, tuple(int(d) for d in part[1]),
                            _short_dtype(part[2]), False, None))
        elif part[0] == "S":
            entries.append(("static", label, _static_fp(part[1])))
        else:
            entries.append(("static", label, f"<{part[1]}>"))
    entries.append(("static", "training", repr(bool(training))))
    return tuple(entries)


def _fmt_aval(e: tuple) -> str:
    _, _, shape, dtype, weak, sharding = e
    s = f"{dtype}[{','.join(str(d) for d in shape)}]"
    if weak:
        s += "~w"
    if sharding:
        s += f"@{sharding}"
    return s


def _fmt_entry(e: tuple) -> str:
    if e[0] == "aval":
        return f"{e[1]}: {_fmt_aval(e)}"
    return f"static {e[1]}: {e[2]}"


def diff_signatures(prev: Optional[tuple], cur: tuple) -> List[str]:
    """Human-readable retrace causes, new signature vs the retained
    previous one.  Taxonomy (docs/OBSERVABILITY.md): tree-structure
    change, per-leaf shape change, dtype/weak_type flip, sharding
    change, static-value change — or, with an identical signature, a
    rebuild the signature cannot explain (cache eviction / flush)."""
    if prev is None:
        return []
    if [e[:2] for e in prev] != [e[:2] for e in cur]:
        return [f"new arg tree structure ({len(prev)}→{len(cur)} leaves)"]
    causes = []
    for pe, ce in zip(prev, cur):
        if pe == ce:
            continue
        label = pe[1]
        if pe[0] == "static":
            causes.append(f"static {label}: {pe[2]}→{ce[2]}")
        elif pe[3] != ce[3] or pe[4] != ce[4]:
            causes.append(f"{label}: dtype/weak_type flip "
                          f"{_fmt_aval(pe)}→{_fmt_aval(ce)}")
        elif pe[2] != ce[2]:
            causes.append(f"{label}: {_fmt_aval(pe)}→{_fmt_aval(ce)}")
        else:
            causes.append(f"{label}: sharding {pe[5]}→{ce[5]}")
    return causes or ["signature unchanged (program-cache eviction or "
                      "flush rebuilt an already-seen signature)"]


# ---------------------------------------------------------------------------
# AOT memory/cost harvest (the opt-in deeper pass)
# ---------------------------------------------------------------------------

_MEM_KINDS = (("args", "argument_size_in_bytes"),
              ("outputs", "output_size_in_bytes"),
              ("temp", "temp_size_in_bytes"),
              ("generated", "generated_code_size_in_bytes"))


_MOSAIC_CALL_RE = re.compile(r'custom_call_target="tpu_custom_call"')
# XLA:TPU prints a Mosaic call as
#   %quant_matmul.1 = ... custom-call(...), custom_call_target=
#   "tpu_custom_call", ..., metadata={op_name="jit(tick)/while/body/
#   quant_matmul/pallas_call" ...}
# — the pallas_call's name= is the path component before "/pallas_call",
# wrapped once per transform under autodiff:
#   op_name="jit(train_step)/transpose(jvp(flash_packed_bwd_dq))/pallas_call"
_KERNEL_SCOPE_RE = re.compile(r'op_name="[^"]*?([^/"]+)/pallas_call')
_IDENT_RE = re.compile(r'[A-Za-z_]\w*')


def mosaic_kernels(hlo_text: str) -> Dict[str, int]:
    """``{kernel name: count}`` of the Mosaic (Pallas TPU) custom calls
    in a COMPILED program's HLO text — evidence from the executable
    itself that a kernel ran compiled: an interpreted or bypassed kernel
    leaves no ``tpu_custom_call`` behind.  Names are the ``name=`` each
    ``pl.pallas_call`` passes (``flash_packed_fwd``, ``paged_decode``,
    ``quant_matmul``, ...); a call whose name the text does not carry
    counts under ``"?"``."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if not _MOSAIC_CALL_RE.search(line):
            continue
        m = _KERNEL_SCOPE_RE.search(line)
        idents = _IDENT_RE.findall(m.group(1)) if m else ()
        name = idents[-1] if idents else "?"   # innermost = the kernel
        out[name] = out.get(name, 0) + 1
    return out


# The train step's scopes (``parallel/api.py``, ``models/gpt.py``,
# ``models/qwen3_next.py``, ``models/bailing_hybrid.py``,
# ``models/keye_vl2.py``): the names a
# component can take in the phase census.  A scope of PHASE_SUBCOMPONENTS
# names a part of the component it is nested in (``gdn_rule`` inside
# ``gdn``, ``experts`` inside ``moe``): an instruction under both reads
# ``"gdn/gdn_rule"``, so a part's time can be told from its parent's, and a
# parent's is the sum over ``component.split("/")[0]``.  Parts are
# siblings, one level deep.  Where XLA merged instructions of two parts
# into one it joins their name stacks with ``;``: the instruction goes to
# the part listed first here, so the four parts PR 37 added (the mixers'
# projections and gates, which between them leave only a layer's norm and
# residual outside a part) stand last and take nothing from the parts that
# were there.
PHASE_COMPONENTS = ("embed", "attn", "mlp", "ln_f", "lm_head", "ce",
                    "clip", "update", "gdn", "moe", "kda", "mla", "dsa")
PHASE_SUBCOMPONENTS = ("gdn_conv", "gdn_rule", "router", "experts",
                       "shared_expert", "kda_conv", "kda_rule",
                       "gdn_proj", "gdn_gates", "kda_proj", "kda_gates",
                       "dsa_index", "dsa_select", "dsa_attn", "dsa_kl")
PHASES = ("fwd", "bwd", "clip", "update", "other")

_COMPUTATION_RE = re.compile(r'^(?:ENTRY )?%?([^\s(]+) \(.*\{$')
_INSTRUCTION_RE = re.compile(r'^\s+(?:ROOT )?%?(\S+) = (.*)$')
# the opcode is the first lower-case word followed by "(" after the
# result's type (a type's T(8,128) / S(1) tiles are upper-case)
_OPCODE_RE = re.compile(r'(?:^| )([a-z][\w\-]*)\(')
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLED_RE = re.compile(
    r'\b(?:calls|body|condition|to_apply|true_computation|'
    r'false_computation)=%?([^\s,)}]+)')
_BRANCHES_RE = re.compile(r'branch_computations=\{([^}]*)\}')
# jax wraps a scope's name once per transform: jvp(mlp),
# transpose(jvp(mlp)); jit(clip) is jnp.clip, not the scope
_WRAPPER_RE = re.compile(r'^(?:transpose|jvp|vmap|remat|checkpoint)'
                         r'\((.*)\)$')
_CONTROL_FLOW = ("while", "conditional", "call")


def _scopes(op_name: str) -> List[str]:
    """The name stack's elements with jax's transform wrappers taken
    off: ``jit(step)/transpose(jvp(mlp))/dot_general`` ->
    ``["jit(step)", "mlp", "dot_general"]``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPER_RE.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER_RE.match(part)
        out.append(part)
    return out


def _phase_of(op_name: str) -> Tuple[str, str]:
    """``(phase, component)`` of one instruction's ``op_name``."""
    scopes = _scopes(op_name)
    component = next((s for s in scopes if s in PHASE_COMPONENTS), "")
    if component:
        inner = scopes[scopes.index(component) + 1:]
        part = next((s for s in PHASE_SUBCOMPONENTS if s in inner), "")
        if part:
            component += "/" + part
    if "transpose(jvp(" in op_name:
        return "bwd", component
    if "jvp(" in op_name:
        return "fwd", component
    if "clip" in scopes:
        return "clip", component
    if "update" in scopes:
        return "update", component
    return "other", component


def _parse_hlo(hlo_text: str) -> Tuple[Dict[str, list], Optional[str]]:
    """``({computation: [(name, opcode, op_name, called, tail)]}, entry)``
    of an HLO module's text, the instructions of each computation in the
    text's order (a compiled module is scheduled: text order is run
    order); ``tail`` is the line after the opcode's ``(``: the operands
    by name, then the attributes."""
    comps: Dict[str, list] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION_RE.match(line)
            if m:
                cur = comps[m.group(1)] = []
                if line.startswith("ENTRY "):
                    entry = m.group(1)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION_RE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE_RE.search(rest)
        called = _CALLED_RE.findall(rest)
        for branches in _BRANCHES_RE.findall(rest):
            called += [b.strip().lstrip("%") for b in branches.split(",")]
        on = _OP_NAME_RE.search(rest)
        cur.append((name, op.group(1) if op else "",
                    on.group(1) if on else "", called,
                    rest[op.end():] if op else ""))
    return comps, entry


def _device_computations(comps: Dict[str, list], entry: Optional[str]):
    """The computations whose instructions can run as device ops: the
    entry and, through them, the bodies of its ``while`` / ``conditional``
    / ``call`` instructions."""
    todo, seen = [entry] if entry else [], {entry}
    while todo:
        comp = todo.pop()
        yield comp
        for _, opcode, _, called, _ in comps.get(comp, ()):
            if opcode in _CONTROL_FLOW:
                for c in called:
                    if c not in seen:
                        seen.add(c)
                        todo.append(c)


def phase_census(hlo_text: str) -> Dict[str, Tuple[str, str, bool]]:
    """``{instruction name: (phase, component, mixed)}`` over the
    instructions of a COMPILED program's HLO text that can run as device
    ops: the entry computation and, through them, the bodies of its
    ``while`` / ``conditional`` / ``call`` instructions -- not the
    insides of fused computations.  The name is the instruction's own
    (no leading ``%``): what a device trace's ``XLA Ops`` event carries
    before `` = ``.

    ``phase`` comes from the name stack jax wrote into ``op_name``:
    ``bwd`` where it holds ``transpose(jvp(``, else ``fwd`` where it
    holds ``jvp(``, else ``clip`` / ``update`` under those scopes, else
    ``other`` (no name stack: an instruction the compiler made itself,
    which :func:`placed_census` places by the arrays it moves; or a
    program without autodiff);
    ``component`` is the first of :data:`PHASE_COMPONENTS` on the stack
    (``"gdn/gdn_rule"`` where a scope of :data:`PHASE_SUBCOMPONENTS` is
    nested in it), else ``""``.  A fusion takes its own ``op_name``; where the
    instructions fused into it belong to more than one phase it is
    ``mixed``, and goes to the phase and component of the single
    ``convolution`` / ``dot`` inside it if there is exactly one (the
    matmul sets its time: a weight-gradient matmul that carries the
    clip's sum of squares is backward), else keeps its own."""
    return _census(*_parse_hlo(hlo_text))


def _census(comps, entry) -> Dict[str, Tuple[str, str, bool]]:
    def fused(comp, seen):
        """``(phase, component, is_matmul)`` of every instruction with
        metadata inside a fused computation, nested fusions included."""
        out = []
        for _, opcode, op_name, called, _ in comps.get(comp, ()):
            # an argument's op_name is its path (params['...']), no stack
            if "/" in op_name:
                out.append(_phase_of(op_name)
                           + (opcode in ("convolution", "dot"),))
            if opcode == "fusion":
                for c in called:
                    if c not in seen:
                        seen.add(c)
                        out += fused(c, seen)
        return out

    census: Dict[str, Tuple[str, str, bool]] = {}
    for comp in _device_computations(comps, entry):
        for name, opcode, op_name, called, _ in comps.get(comp, ()):
            phase, component = _phase_of(op_name)
            mixed = False
            if opcode == "fusion":
                inner = [x for c in called for x in fused(c, {c})]
                mixed = len({x[0] for x in inner}) > 1
                matmuls = [x for x in inner if x[2]]
                if mixed and len(matmuls) == 1:
                    phase, component = matmuls[0][:2]
            census[name] = (phase, component, mixed)
    return census


# The unnamed instructions that compute nothing and only hand an array
# on: the compiler's own copies between its memories and layouts (any
# asynchronous ``*-start`` / ``*-done`` pair among them) and the views
# (``ConcatBitcast`` joins a prefetch's slices back into their array).
# placed_census walks through them, and places them by who waits.
_MOVERS = ("bitcast", "get-tuple-element", "tuple", "copy", "reshape",
           "transpose")
_VIEW_CALL = 'custom_call_target="ConcatBitcast"'
# never a device op of their own: no entry in the placed map
_NEVER_RUN = ("parameter", "constant", "tuple", "get-tuple-element",
              "bitcast")
_OPERAND_RE = re.compile(r'%([^\s,(){}]+)')
PLACED_VIA = ("consumer", "producer", "unplaced")


def placed_census(hlo_text: str, census: Optional[dict] = None
                  ) -> Dict[str, Tuple[str, str, str]]:
    """``{instruction name: (phase, component, via)}`` over the
    instructions of :func:`phase_census` that have no name stack
    (``("other", "")`` there) and can run as a device op of their own:
    what the compiler made itself -- the asynchronous copies between its
    two memories (``copy-start`` / ``copy-done``, ``slice-start`` /
    ``slice-done``), layout copies, the kernels ``ragged_dot`` expands
    to -- placed by the arrays they move, within their own computation
    (a compiled module is scheduled: text order is run order):

    - an instruction that only moves an array (``copy``, ``reshape``,
      ``transpose``, an asynchronous pair, which is one: a ``*-done`` is
      placed as its ``*-start`` is) goes ``via="consumer"`` to the place
      of the first instruction in the computation's order that reads its
      result and has a place, looking through the unnamed instructions
      that hand the array on (``bitcast``, ``get-tuple-element``,
      ``tuple``, another unnamed mover, ``ConcatBitcast``): a prefetch's
      wait belongs to the op that waits for it; else ``via="producer"``
      to the nearest instruction with a place that made its operand,
      looked through in the same way;
    - an instruction that computes (a ``ragged-dot`` kernel, a fusion
      whose root lost its metadata) goes to its producer first, then to
      its consumer: its operands were made where the op it is a part of
      was named, while its first reader may be anything (a weight
      gradient's is the clip's sum of squares).  Once placed it is a
      place for the others, so the prefetch of an expert's weights goes
      with the kernel that reads them;
    - else ``("other", "", "unplaced")``: a parameter or the root ends
      the walk, which never leaves its computation.

    ``census`` is :func:`phase_census` of the same text where the caller
    has it; it is read, never changed."""
    comps, entry = _parse_hlo(hlo_text)
    return _placed(comps, entry,
                   _census(comps, entry) if census is None else census)


def _placed(comps, entry, census) -> Dict[str, Tuple[str, str, str]]:
    placed: Dict[str, Tuple[str, str, str]] = {}
    for comp in _device_computations(comps, entry):
        instrs = comps.get(comp, ())
        at = {ins[0]: i for i, ins in enumerate(instrs)}
        operands = [[at[o] for o in _OPERAND_RE.findall(
            ins[4][:max(ins[4].find(")"), 0)]) if o in at]
            for ins in instrs]
        users: List[list] = [[] for _ in instrs]
        for i, ops in enumerate(operands):
            for o in ops:
                users[o].append(i)
        # (phase, component) of the instructions that have one: by name
        # stack now, the unnamed that compute as they are placed
        place: List[Optional[tuple]] = [
            None if census[ins[0]][:2] == ("other", "")
            else census[ins[0]][:2] for ins in instrs]
        mover = [place[i] is None and (
            op in _MOVERS or op.endswith(("-start", "-done"))
            or (op == "custom-call" and _VIEW_CALL in tail))
            for i, (_, op, _, _, tail) in enumerate(instrs)]

        def walk(start, edges, sign):
            """The place nearest to ``start`` along ``edges`` in the
            computation's order, through the movers."""
            heap = [sign * j for j in edges[start]]
            heapq.heapify(heap)
            seen = set(heap)
            while heap:
                j = sign * heapq.heappop(heap)
                if place[j] is not None:
                    return place[j]
                if mover[j]:
                    for k in edges[j]:
                        if sign * k not in seen:
                            seen.add(sign * k)
                            heapq.heappush(heap, sign * k)
            return None

        def find(first, last, order):
            for via in order:
                found = walk(last, users, 1) if via == "consumer" \
                    else walk(first, operands, -1)
                if found is not None:
                    return found, via
            return None, "unplaced"

        todo = [i for i, ins in enumerate(instrs)
                if place[i] is None and ins[1] not in _NEVER_RUN]
        # what computes: producer first; again while a sweep places any,
        # since each one placed is a place for its neighbours
        computes = [i for i in todo if not mover[i]]
        while computes:
            left = []
            for i in computes:
                place[i], via = find(i, i, ("producer", "consumer"))
                if place[i] is None:
                    left.append(i)
                else:
                    placed[instrs[i][0]] = place[i] + (via,)
            if len(left) == len(computes):
                break
            computes = left
        for i in computes:
            placed[instrs[i][0]] = ("other", "", "unplaced")
        # what moves: the pair as one, consumer first
        for i in todo:
            name, opcode = instrs[i][:2]
            if not mover[i] or name in placed:
                continue
            pair = [i]
            if opcode.endswith("-start"):
                pair += [j for j in users[i]
                         if instrs[j][1] == opcode[:-5] + "done"]
            found, via = find(pair[0], pair[-1], ("consumer", "producer"))
            for j in pair:
                placed[instrs[j][0]] = (found or ("other", "")) + (via,)
    return placed


def placed_counts(placed: Dict[str, Tuple[str, str, str]]) -> Dict[str, int]:
    """Instructions of :func:`placed_census` by how they were placed:
    what the registry's snapshots carry in place of the names."""
    out = dict.fromkeys(PLACED_VIA, 0)
    for _, _, via in placed.values():
        out[via] += 1
    return out


def phase_counts(census: Dict[str, Tuple[str, str, bool]]) -> Dict[str, int]:
    """Instructions per phase, and how many of them are mixed fusions:
    what the registry's snapshots carry in place of the names."""
    out: Dict[str, int] = {}
    for phase, _, mixed in census.values():
        out[phase] = out.get(phase, 0) + 1
        if mixed:
            out["mixed"] = out.get("mixed", 0) + 1
    return out


def _harvest_analysis(fn, args, kwargs) -> Tuple[Optional[dict],
                                                Optional[dict],
                                                Optional[dict]]:
    """``(analysis, census, placed)``: per-program ``memory_analysis()``
    bytes, ``cost_analysis()`` flops, the Mosaic kernel census, the phase
    census and what it leaves unnamed placed by dataflow
    (:func:`phase_census`, :func:`placed_census`: one parse of the text;
    the analysis carries their counts, ``phases`` and ``placed``, and the
    seconds of both, ``census_s``; the maps themselves go to the
    registry) via the AOT ``lower()``
    handle (the ``parallel/planner.py`` harvesting shape).  Re-lowers
    and re-compiles once — the stated cost of ``PHT_PROGRAM_ANALYSIS``
    (with the persistent compile cache on, the re-compile is a cache
    hit; the build record's ``analysis_s`` and ``analysis_split`` say
    what it took) — and degrades to ``None`` on any backend that lacks
    the analyses."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None, None, None
    try:
        compiled = lower(*args, **(kwargs or {})).compile()
    except Exception:  # noqa: BLE001 — analysis is best-effort evidence
        return None, None, None
    out: Dict[str, Any] = {}
    census = placed = None
    try:
        text = compiled.as_text()
        out["mosaic_kernels"] = mosaic_kernels(text)
        t0 = time.perf_counter()
        comps, entry = _parse_hlo(text)
        census = _census(comps, entry) or None
        if census:
            placed = _placed(comps, entry, census)
            out["phases"] = phase_counts(census)
            out["placed"] = placed_counts(placed)
            out["census_s"] = round(time.perf_counter() - t0, 6)
    except Exception:  # noqa: BLE001
        pass
    try:
        mem = compiled.memory_analysis()
        for kind, attr in _MEM_KINDS:
            v = getattr(mem, attr, None)
            if v is not None:
                out[f"{kind}_bytes"] = int(v)
    except Exception:  # noqa: BLE001
        pass
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0)) if hasattr(ca, "get") else 0.0
        if flops:
            out["flops"] = flops
    except Exception:  # noqa: BLE001
        pass
    return out or None, census, placed


# ---------------------------------------------------------------------------
# The build clock: where a build's seconds went, by jax's own events
# ---------------------------------------------------------------------------

# jax.monitoring reports these on every build (dispatch.py's
# JAXPR_TRACE_EVENT, JAXPR_TO_MLIR_MODULE_EVENT, BACKEND_COMPILE_EVENT),
# each as a span on time.time().  The backend-compile span holds the
# persistent cache's retrieval when that hit.
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
BUILD_CLOCK_KEYS = tuple(_BUILD_EVENTS.values())

_build_tls = threading.local()
_build_listening = False
_build_listen_lock = make_lock("observability.programs.build_clock")


def _on_build_span(event, start, end, **_):
    kind = _BUILD_EVENTS.get(event)
    if kind is None:
        return
    spans = getattr(_build_tls, "spans", None)
    if spans is None:
        spans = _build_tls.spans = []
    spans.append((kind, start, end))


def _on_build_duration(event, duration, **_):
    if event == _CACHE_RETRIEVAL_EVENT:
        _build_tls.cache_hit = True


def _listen_to_builds() -> None:
    global _build_listening
    with _build_listen_lock:
        if _build_listening:
            return
        import jax.monitoring as monitoring
        monitoring.register_event_time_span_listener(_on_build_span)
        monitoring.register_event_duration_secs_listener(_on_build_duration)
        _build_listening = True


def start_build_clock() -> None:
    """Forget this thread's build events.  A wrapper calls it before a
    call that may build and :func:`read_build_clock` after one that
    did; a call that builds nothing raises no event, so it allocates
    nothing here and takes no lock (the listeners are registered once
    per process, at the first call)."""
    if not _build_listening:
        _listen_to_builds()
    _build_tls.spans = None
    _build_tls.cache_hit = False
    _build_tls.facts = None


def read_build_clock() -> dict:
    """``{"trace_s", "lower_s", "backend_compile_s", "cache_hit"}`` of
    this thread's events since :func:`start_build_clock`.  The three
    times are disjoint stretches of the wall clock: jax reports a trace
    event for every jitted function it inlines and compiles the small
    programs a trace runs eagerly, all inside the outer trace's span, so
    a span that starts inside a counted one is that one's time already
    and is left out."""
    out = dict.fromkeys(BUILD_CLOCK_KEYS, 0.0)
    covered = float("-inf")
    spans = getattr(_build_tls, "spans", None) or ()
    for kind, start, end in sorted(spans, key=lambda x: (x[1], -x[2])):
        if start < covered:
            continue
        out[kind] += end - start
        covered = end
    out = {k: round(v, 6) for k, v in out.items()}
    out["cache_hit"] = bool(getattr(_build_tls, "cache_hit", False))
    return out


def note_kernel_fact(key: str, value) -> None:
    """A static fact a kernel states about itself — what its plan makes
    it compute, decided from shapes — said from the kernel's wrapper
    while a program that holds it is traced.  The build record of that
    program carries it (``kernel_facts``, :func:`read_kernel_facts`).
    One set insertion a trace on this thread; nothing at run time."""
    facts = getattr(_build_tls, "facts", None)
    if facts is None:
        facts = _build_tls.facts = {}
    facts.setdefault(key, set()).add(value)


def read_kernel_facts() -> Dict[str, list]:
    """``{key: distinct values, sorted}`` of what this thread's kernels
    noted since :func:`start_build_clock`; ``{}`` where none did."""
    facts = getattr(_build_tls, "facts", None) or {}
    return {k: sorted(v) for k, v in facts.items()}


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

class _Site:
    __slots__ = ("kind", "builds", "evictions", "compile_seconds_total",
                 "signatures", "last_signature", "history", "last_ts",
                 "analysis", "phase_census", "placed_census")

    def __init__(self, kind: str, history: int):
        self.kind = kind
        self.builds = 0
        self.evictions = 0
        self.compile_seconds_total = 0.0
        self.signatures: set = set()
        self.last_signature: Optional[tuple] = None
        self.history: collections.deque = collections.deque(maxlen=history)
        self.last_ts = 0.0
        self.analysis: Optional[dict] = None
        self.phase_census: Optional[dict] = None
        self.placed_census: Optional[dict] = None


class ProgramRegistry:
    """Process-wide program-build ledger, one :class:`_Site` per site
    label.  Lock-disciplined (:func:`sanitizers.make_lock`; the lock is
    a leaf — flight/metrics/tracing emission happens outside it) and
    build-path-only: nothing here runs on a steady-state call but
    :meth:`note_counters`, which swaps one reference."""

    def __init__(self, history: int = HISTORY_PER_SITE):
        self._lock = make_lock("observability.programs")
        self._sites: Dict[str, _Site] = {}
        self._history = int(history)
        self._counters: Dict[str, Any] = {}

    # -- build-path reporting ----------------------------------------------

    def is_new_signature(self, site: str, signature: tuple) -> bool:
        """Membership probe for ``instrument_jit``'s ``_cache_size``-less
        fallback: a call whose abstract signature the site has not seen
        is a build (the old first-call-only heuristic missed every
        later retrace)."""
        with self._lock:
            rec = self._sites.get(site)
            return rec is None or tuple(signature) not in rec.signatures

    def record_build(self, site: str, *, args: Sequence = (),
                     kwargs: Optional[dict] = None, fn=None,
                     signature: Optional[tuple] = None,
                     compile_s: float = 0.0, t_end_ns: Optional[int] = None,
                     kind: str = "jit", registry=None,
                     labels: Optional[dict] = None,
                     donated=None,
                     build_clock: Optional[dict] = None,
                     kernel_facts: Optional[dict] = None) -> dict:
        """Record one trace+compile at ``site`` and return the build
        record.  Computes the signature (host metadata only) unless the
        caller already did, diffs it against the site's retained
        previous signature into a retrace cause, and emits the flight
        event / ``jit_compile_seconds`` observation / compile span —
        plus the AOT memory/cost harvest when :func:`analysis_enabled`.
        ``build_clock`` is the caller's :func:`read_build_clock` of the
        call that built: ``trace_s``, ``lower_s``, ``backend_compile_s``
        and ``cache_hit`` go into the record beside ``compile_s`` (the
        call's whole wall), and ``kernel_facts``, where the call's trace
        noted any (:func:`read_kernel_facts`), under that name beside the
        analysis.  The harvest is timed as ``analysis_s`` and
        its own trace/lower/compile events as ``analysis_split``: they
        are the analysis pass's cost, not the build's."""
        sig = tuple(signature) if signature is not None \
            else capture_signature(args, kwargs, fn=fn, donated=donated)
        analysis = census = placed = None
        timing = dict(build_clock or ())
        if analysis_enabled() and fn is not None:
            start_build_clock()
            t0 = time.perf_counter()
            analysis, census, placed = _harvest_analysis(fn, args, kwargs)
            timing["analysis_s"] = round(time.perf_counter() - t0, 6)
            timing["analysis_split"] = read_build_clock()
        now = time.time()
        with self._lock:
            rec = self._sites.get(site)
            if rec is None:
                rec = self._sites[site] = _Site(kind, self._history)
            rec.builds += 1
            n = rec.builds
            causes = diff_signatures(rec.last_signature, sig) if n > 1 else []
            cause = "; ".join(causes) if causes else None
            rec.last_signature = sig
            rec.signatures.add(sig)
            rec.compile_seconds_total += float(compile_s)
            rec.last_ts = now
            if analysis is not None:
                rec.analysis = analysis
                rec.phase_census = census
                rec.placed_census = placed
            record = {"build": n, "ts": now,
                      "compile_s": round(float(compile_s), 6), **timing,
                      "cause": cause, "analysis": analysis}
            if kernel_facts:
                record["kernel_facts"] = dict(kernel_facts)
            rec.history.append(record)
        self._emit(site, record, compile_s, t_end_ns, kind, registry, labels)
        return record

    def record_eviction(self, site: str, registry=None) -> None:
        """Count a program-cache eviction at ``site`` (the to_static
        cache's oldest-entry pop) — ``jit_cache_evictions_total{site}``
        plus a flight event; an evicted signature is forgotten so its
        inevitable rebuild diffs as a cause, not a silent no-op."""
        with self._lock:
            rec = self._sites.get(site)
            if rec is None:
                rec = self._sites[site] = _Site("to_static", self._history)
            rec.evictions += 1
            n = rec.evictions
        reg = self._metric_registry(registry)
        if reg is not None and reg.enabled:
            reg.counter(
                "jit_cache_evictions_total",
                "to_static program-cache evictions by site").labels(
                    site=site).inc()
        from . import flight as _flight
        _flight.get_flight_recorder().record("program_evict", site=site,
                                             evictions=n)

    # -- emission (outside the lock: the registry lock is a leaf) ----------

    @staticmethod
    def _metric_registry(registry):
        if registry is not None:
            return registry
        from . import metrics as _metrics
        return _metrics.get_registry()

    def _emit(self, site, record, compile_s, t_end_ns, kind, registry,
              labels):
        from . import flight as _flight
        _flight.get_flight_recorder().record(
            "program_build", site=site, build=record["build"], kind=kind,
            compile_ms=round(float(compile_s) * 1e3, 3),
            cause=record["cause"])
        reg = self._metric_registry(registry)
        if reg is not None and reg.enabled:
            reg.histogram(
                "jit_compile_seconds",
                "compile wall per program build, by jit-build site",
                unit="s").labels(site=site, **(labels or {})).observe(
                    float(compile_s))
            analysis = record["analysis"]
            if analysis:
                hbm = reg.gauge(
                    "program_hbm_bytes",
                    "per-program memory_analysis bytes by site and kind "
                    "(args/outputs/temp/generated)", unit="B")
                # kind is the literal 4-value enum (PHT005-bounded)
                for mkind in ("args", "outputs", "temp", "generated"):
                    v = analysis.get(mkind + "_bytes")
                    if v is not None:
                        hbm.labels(site=site, kind=mkind).set(v)
                if analysis.get("flops"):
                    reg.gauge("program_flops",
                              "per-program cost_analysis flops by site"
                              ).labels(site=site).set(analysis["flops"])
        if t_end_ns is None:
            t_end_ns = time.perf_counter_ns()
        attrs = {"site": site, "build": record["build"], "lane": "compiles"}
        if record["cause"]:
            attrs["cause"] = record["cause"]
        for k in BUILD_CLOCK_KEYS + ("analysis_s",):
            if k in record:
                attrs[k] = record[k]
        _tracing.add_span(f"compile:{site}",
                          int(t_end_ns - float(compile_s) * 1e9),
                          int(t_end_ns), _tid=COMPILES_LANE_TID, **attrs)

    # -- program counters --------------------------------------------------

    def note_counters(self, site: str, counters) -> None:
        """Keep what ``site``'s newest call returned beside its result: a
        pytree of device values that the program counted while it ran
        (an expert layer's routed rows).  The one steady-state call of
        this class: a reference is swapped, nothing is read and no lock
        is taken, so the step that hands it over stays asynchronous."""
        self._counters[site] = counters

    def counters(self, site: str):
        """The newest :meth:`note_counters` of ``site`` as host numbers
        (lists for vectors), read from the device now; ``None`` where the
        site has noted none."""
        tree = self._counters.get(site)
        if tree is None:
            return None
        import jax
        return jax.tree.map(lambda a: a.tolist(), jax.device_get(tree))

    # -- snapshots ---------------------------------------------------------

    def phase_census(self, site: str) -> Optional[dict]:
        """:func:`phase_census` of ``site``'s newest analysed build:
        ``{instruction name: (phase, component, mixed)}``, or ``None``
        where no build of the site was analysed.  The snapshots carry
        its per-phase counts only (``analysis["phases"]``)."""
        with self._lock:
            rec = self._sites.get(site)
            return rec.phase_census if rec is not None else None

    def placed_census(self, site: str) -> Optional[dict]:
        """:func:`placed_census` of ``site``'s newest analysed build:
        ``{instruction name: (phase, component, via)}`` over the
        instructions :meth:`phase_census` leaves ``("other", "")``, or
        ``None`` where no build of the site was analysed.  The snapshots
        carry its counts only (``analysis["placed"]``)."""
        with self._lock:
            rec = self._sites.get(site)
            return rec.placed_census if rec is not None else None

    def snapshot(self) -> dict:
        """JSON-able registry dump — the ``/debug/programs`` body and
        ``tools/program_report.py`` input."""
        with self._lock:
            sites = {}
            for name, rec in self._sites.items():
                sites[name] = {
                    "kind": rec.kind,
                    "builds": rec.builds,
                    "evictions": rec.evictions,
                    "compile_seconds_total":
                        round(rec.compile_seconds_total, 6),
                    "last_build_ts": rec.last_ts,
                    "signature": [_fmt_entry(e)
                                  for e in (rec.last_signature or ())],
                    "history": [dict(h) for h in rec.history],
                    "analysis": dict(rec.analysis) if rec.analysis else None,
                }
        return {"version": 1, "ts": time.time(),
                "builds_total": sum(s["builds"] for s in sites.values()),
                "compile_seconds_total": round(
                    sum(s["compile_seconds_total"] for s in sites.values()),
                    6),
                "sites": sites}

    def bench_block(self) -> dict:
        """The compact per-row evidence bench rows embed:
        ``compile_seconds_total`` plus per-site builds/evictions and the
        recent retrace causes ``perf_gate.suite_gate`` prints when the
        build-growth gate trips."""
        snap = self.snapshot()
        return {"compile_seconds_total": snap["compile_seconds_total"],
                "sites": {
                    name: {"builds": s["builds"],
                           "evictions": s["evictions"],
                           "compile_seconds_total":
                               s["compile_seconds_total"],
                           "causes": [f"build {h['build']}: {h['cause']}"
                                      for h in s["history"]
                                      if h.get("cause")][-4:],
                           **({"phases": s["analysis"]["phases"]}
                              if (s["analysis"] or {}).get("phases")
                              else {})}
                    for name, s in snap["sites"].items()}}

    def introspect_requests(self) -> dict:
        """Compact table for ``/debug/requests`` (the registry is also
        a ``tracing.register_introspection_source`` source); the full
        forensic dump lives at ``/debug/programs``."""
        snap = self.snapshot()
        return {"builds_total": snap["builds_total"],
                "compile_seconds_total": snap["compile_seconds_total"],
                "sites": {
                    name: {"builds": s["builds"],
                           "evictions": s["evictions"],
                           "compile_seconds_total":
                               s["compile_seconds_total"],
                           "last_cause": next(
                               (h["cause"] for h in reversed(s["history"])
                                if h.get("cause")), None)}
                    for name, s in snap["sites"].items()}}

    def reset(self) -> None:
        """Drop every site (test isolation)."""
        with self._lock:
            self._sites.clear()
        self._counters.clear()


# ---------------------------------------------------------------------------
# Default (process-wide) registry + the to_static reporting hooks
# ---------------------------------------------------------------------------

_default_registry = ProgramRegistry()
# weakly held by tracing; this module's strong ref keeps it live
_tracing.register_introspection_source("programs", _default_registry)


def get_program_registry() -> ProgramRegistry:
    """The process-wide registry every built-in jit-build site reports
    into (``instrument_jit`` and the to_static program cache)."""
    return _default_registry


def observe_static_build(site: str, cache_key, compile_s: float) -> None:
    """Report one to_static program build (``jit/api.py`` cache-miss
    path): counts ``jit_builds_total{site}`` / ``jit_build_seconds``
    like an instrument_jit site and records the spec-key signature so
    user-level retraces get cause forensics too."""
    from . import metrics as _metrics
    reg = _metrics.get_registry()
    if not reg.enabled:
        return
    reg.counter("jit_builds_total",
                "program trace+compile events per jit-build site").labels(
                    site=site).inc()
    reg.histogram("jit_build_seconds",
                  "wall time of calls that trace+compile a new program",
                  unit="s").labels(site=site).observe(float(compile_s))
    spec_key, training = cache_key
    _default_registry.record_build(
        site, signature=signature_from_spec_key(spec_key, training),
        compile_s=compile_s, kind="to_static", registry=reg)


def observe_static_eviction(site: str) -> None:
    """Report one to_static program-cache eviction (``jit/api.py``)."""
    from . import metrics as _metrics
    reg = _metrics.get_registry()
    if not reg.enabled:
        return
    _default_registry.record_eviction(site, registry=reg)
