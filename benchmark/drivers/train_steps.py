"""Traffic kind ``train_steps``: a closed loop of train steps on one
compiled program, fed from a pool of distinct batches made from the seed.

The timed entry is the program's own ``step(state, ids, labels, key)`` as
``parallel.make_sharded_train_step`` returns it.  Set-up builds that one
object, drives it through its first three steps (the warm-up, on the
window's own call and feed), reads what the correctness check compares,
and hands the same object to the window.  The window keeps two steps queued
behind the one running and stamps the host clock at every step's end.
After the window: the memory peak is read, the program's state is freed,
and the plain reference follows the same three steps from the same seed.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import importlib
import math
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmark import trace_reduce, weights

HOST_SPANS = ("bench.dispatch", "bench.wait")
TRACED_STEPS = 5
QUEUE_DEPTH = 2
# a leaf whose reference gradient is under this share of the median leaf's
# has no gradient to rounding: Adam moves it by round-off alone
ZERO_GRAD_LEAF_SHARE = 1e-3


def _resolve(path: str):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def _site(name: str) -> dict:
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    return get_program_registry().snapshot()["sites"].get(name) \
        or {"builds": 0, "history": []}


def build_program(cell, params):
    """The program under test: model, mesh and the compiled-step builder,
    as ``chip_smoke.py phase_trainer`` calls them, with the benchmark's
    weights put in before the builder lays the state out.  Returns
    ``(step, state, model)``."""
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    cfg, train = cell.config, cell.config["training"]
    prog = cfg["program"]
    paddle.seed(cell.seed % (2 ** 31))
    model = _resolve(prog["model"])(_resolve(prog["config"])(
        **{k: cfg[k] for k in prog["config_keys"]}))
    named = dict(model.named_parameters())
    if {k: tuple(p.shape) for k, p in named.items()} != \
            {k: tuple(v.shape) for k, v in params.items()}:
        raise RuntimeError(
            "the program's parameter tree is not the one the reference "
            "states: " + str(sorted(set(named) ^ set(params))[:8]))
    for k, p in named.items():
        p._set_value(params[k])
    mesh_dims = dict(cfg["deployment"]["mesh"])
    n_dev = math.prod(mesh_dims.values())
    mesh = parallel.create_mesh(mesh_dims, devices=jax.devices()[:n_dev])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=_resolve(prog["sharding_rule"]),
        learning_rate=train["learning_rate"],
        param_dtype=jnp.dtype(train["param_dtype"]),
        moment_dtype=jnp.dtype(train["moment_dtype"]),
        grad_clip_norm=train["grad_clip_norm"],
        optimizer=train["optimizer"],
        master_weights=train["master_weights"],
        optimizer_kwargs={k: train[k] for k in ("beta1", "beta2", "epsilon")})
    return step, state, model


class Loop:
    """The one call and feed that warm-up, window and traced steps share."""

    def __init__(self, step, state, batches, keys):
        self.step, self.state = step, state
        self.batches, self.keys = batches, keys
        self.i = 0

    def dispatch(self):
        ids, labels = self.batches[self.i % len(self.batches)]
        key = self.keys[self.i % len(self.keys)]
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.state, loss = self.step(self.state, ids, labels, key)
        self.i += 1
        return loss

    def run(self, stop):
        """Steps until ``stop(n_done, t)``: ``QUEUE_DEPTH`` steps stay
        queued behind the one running (a host stall shorter than that many
        steps leaves the device busy); each step's loss is awaited in turn
        and the clock stamped.  The steps still queued at the end are
        awaited too, and not counted."""
        ends, losses = [], []
        queue = collections.deque(self.dispatch()
                                  for _ in range(QUEUE_DEPTH))
        while True:
            queue.append(self.dispatch())
            cur = queue.popleft()
            with jax.profiler.TraceAnnotation("bench.wait"):
                cur.block_until_ready()
            ends.append(time.perf_counter())
            losses.append(cur)
            if stop(len(ends), ends[-1]):
                break
        for loss in queue:
            loss.block_until_ready()
        return ends, losses


def _norms(tree, segments):
    segs = tuple(sorted((k, segments.get(k, 1)) for k in tree))
    return _norms_jit(tree, segs)


@functools.partial(jax.jit, static_argnames=("segs",))
def _norms_jit(tree, segs):
    return {k: weights.segment_norms(tree[k], n) for k, n in segs}


def program_readings(loop, cell, spec, segments, steps):
    """Drive the first ``steps`` steps one at a time and read each loss,
    the first gradient's per-leaf norm as Adam got it (its first moment
    after one step over 1 - beta1), and the per-leaf norm of the
    parameters' change after the last."""
    beta1 = cell.config["training"]["beta1"]
    losses, grad_norm = [], None
    for n in range(steps):
        loss = loop.dispatch()
        if n == 0:
            grad_norm = _norms({k: st["m"] for k, st in
                                loop.state["opt_state"].items()}, segments)
        losses.append(float(loss))
    grad_norm = {k: v / (1.0 - beta1) for name, values in grad_norm.items()
                 for k, v in weights.by_segment(name, values).items()}
    change = weights.change_norms(cell.seed, spec, loop.state["params"],
                                  segments)
    return {"losses": losses, "grad_norm": grad_norm, "change_norm": change}


def compare(prog, ref, zero_share):
    """The numbers of the check: per step the loss's gap as a share of the
    reference's; for the first gradient and for the parameters' change the
    worst leaf's gap between the two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger.  Leaves whose
    reference gradient is under ``zero_share`` of the median leaf's (a
    key's bias under softmax) are left out of both: what the program
    reads there is round-off, and Adam moves them by round-off alone."""
    out, worst = {}, {}
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_gap_{i}"] = abs(p - r) / abs(r) if math.isfinite(p) \
            else float("inf")
    rg = ref["grad_norm"]
    live = [k for k in rg
            if rg[k] >= zero_share * statistics.median(rg.values())]
    for key in ("grad_norm", "change_norm"):
        r_all, p_all = ref[key], prog[key]
        med = statistics.median(r_all[k] for k in live)
        gaps = {k: abs(p_all[k] - r_all[k]) / max(r_all[k], med)
                for k in live}
        gaps = {k: g if math.isfinite(g) else float("inf")
                for k, g in gaps.items()}
        k = max(gaps, key=gaps.get)
        out[f"{key}_gap"] = gaps[k]
        worst[key] = {"leaf": k, "program": p_all[k], "reference": r_all[k],
                      "median_leaf": med}
    worst["leaves_left_out"] = len(rg) - len(live)
    return out, worst


def checks_of(gaps, limits, exact=()):
    """``[(name, value, limit)]``: the gaps the cell's file gives a limit
    (a number it gives none is read and not compared), then the exact
    checks, whose limit is 0."""
    return [(k, v, limits[k]) for k, v in gaps.items() if k in limits] \
        + [(k, v, 0) for k, v in exact]


def reference_readings(cell, steps, quant=None, rows=None):
    """The plain reference over the first ``steps`` batches of the seed."""
    ref = cell.config_module("reference", "reference")
    traffic, cfg = cell.workload["traffic"], cell.config
    spec = ref.param_spec(cfg)
    batches = weights.make_batches(cell.seed, steps, traffic["batch"],
                                   traffic["seqlen"], cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        return ref.train_readings(
            cfg, cfg["training"],
            lambda: weights.make_params(cell.seed, spec,
                                        cfg["training"]["param_dtype"]),
            batches, quant=quant, rows=rows)


def kernels_missing(required: dict, census: dict) -> int:
    """Calls of Mosaic kernels that the executable has to hold
    (``program.mosaic_kernels`` of the configuration: name -> calls) and
    ``census`` (name -> calls found) does not: 0 where every kernel
    stands as often as required or more."""
    return sum(max(0, n - census.get(k, 0)) for k, n in required.items())


def _free(*trees):
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()


def run(cell) -> dict:
    cfg, wl = cell.config, cell.workload
    traffic, check = wl["traffic"], wl["check"]
    prog = cfg["program"]
    batch, seqlen = traffic["batch"], traffic["seqlen"]
    device = jax.devices()[0]

    # ------------------------------------------------------------ set-up --
    marks = [("start", cell.t_start), ("driver", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    ref = cell.config_module("reference", "reference")
    flops_per_token = cell.config_module(
        "op_count", "op_counts").train_flops_per_token(cfg, seqlen)
    cell.note(f"operations a token at s{seqlen}: {flops_per_token!r} "
              f"(op_counts/{cfg['op_count']}.py)")
    spec = ref.param_spec(cfg)
    params = weights.make_params(cell.seed, spec,
                                 cfg["training"]["param_dtype"])
    batches = weights.make_batches(cell.seed, traffic["pool"], batch, seqlen,
                                   cfg["vocab_size"])
    keys = [jax.random.fold_in(weights.seed_key(cell.seed), i)
            for i in range(traffic["pool"])]
    jax.block_until_ready(params)
    mark("weights+batches")
    from paddle_hackathon_tpu.observability.programs import program_analysis
    site = prog["jit_site"]
    builds_before = _site(site)["builds"]
    step, state, model = build_program(cell, params)
    del params
    mark("program built")
    loop = Loop(step, state, batches, keys)
    del state
    # the kernels' census needs the AOT pass of the program observatory;
    # its compile is a hit in the persistent cache
    with program_analysis() if not cell.rehearsal \
            else contextlib.nullcontext():
        prog_read = program_readings(loop, cell, spec,
                                     ref.leaf_segments(cfg), check["steps"])
    mark("first steps + readings")
    cell.note("set-up phases (s): " + ", ".join(
        f"{name} {t - t0:.1f}" for (_, t0), (name, t) in
        zip(marks, marks[1:])))
    built = _site(site)
    builds_setup = built["builds"] - builds_before
    missing = 0
    if not cell.rehearsal:
        census = {}
        for h in built["history"]:
            if h["build"] > builds_before:
                for k, n in ((h.get("analysis") or {})
                             .get("mosaic_kernels") or {}).items():
                    census[k] = max(census.get(k, 0), n)
        missing = kernels_missing(prog["mosaic_kernels"], census)
        cell.note(f"mosaic kernels in the step: {census}, required "
                  f"{prog['mosaic_kernels']}; builds in set-up: "
                  f"{builds_setup}")

    # ------------------------------------------------------------ window --
    t_open = time.perf_counter()
    setup_s = t_open - cell.t_start
    ends, losses = loop.run(lambda n, t: t - t_open >= cell.seconds)
    window_s = ends[-1] - t_open
    builds_window = _site(site)["builds"] - built["builds"]
    stats = device.memory_stats() or {}
    # The TPU runtime counts a running program's temporaries as "reserved"
    # (read on the chip, PR 25: peak_bytes_reserved equals the step's AOT
    # temp_size_in_bytes in both cells) apart from the buffers "in use"
    # (state, batches).  While a step runs the chip holds both, so the
    # peak is their sum -- an upper bound, as the two peaks need not fall
    # together; it read 1.4 % over the AOT total at 1.3B.  Both parts are
    # in the notes on standard error.
    in_use = int(stats.get("peak_bytes_in_use", 0))
    reserved = int(stats.get("peak_bytes_reserved", 0))
    memory_peak = in_use + reserved

    # ------------------------------------------- traced steps (own window) --
    traced = None
    if cell.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(trace_dir)
            try:
                loop.run(lambda n, t: n >= TRACED_STEPS)
            finally:
                jax.profiler.stop_trace()
            traced = trace_reduce.reduce(
                trace_reduce.load_planes(trace_reduce.find_xplane(trace_dir)),
                HOST_SPANS)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    loss_values = [float(x) for x in losses]
    failed = sum(not math.isfinite(x) for x in loss_values)

    # ------------------------------------- free the program, then compare --
    _free(loop.state, batches, keys, losses)
    for _, p in model.named_parameters():
        _free(p._value)
    del loop, step, model, batches, keys, losses
    gc.collect()
    jax.clear_caches()
    cell.note(f"set-up {setup_s:.1f} s, window {window_s:.2f} s, "
              f"{len(ends)} steps; memory peak {memory_peak} = in use {in_use} "
              f"+ reserved {reserved}; memory_stats after the window "
              f"{stats}; "
              "bytes in use after freeing the program: "
              f"{(device.memory_stats() or {}).get('bytes_in_use')}")
    t_ref = time.perf_counter()
    ref_read = reference_readings(cell, check["steps"])
    cell.note(f"reference took {time.perf_counter() - t_ref:.1f} s")
    gaps, worst = compare(prog_read, ref_read, ZERO_GRAD_LEAF_SHARE)
    cell.note(f"program losses {prog_read['losses']} reference losses "
              f"{ref_read['losses']} worst leaves {worst}")
    limits = check["limits"]
    cell.note("read, not compared: " + str(
        {k: v for k, v in gaps.items() if k not in limits}))
    checks = checks_of(gaps, limits, (
        ("builds_in_window", builds_window),
        ("mosaic_kernels_missing", missing),
        ("nonfinite_losses", failed)))

    rate = len(ends) * batch * seqlen / window_s
    step_gaps = [b - a for a, b in zip([t_open] + ends[:-1], ends)]
    typical = statistics.median(step_gaps)
    cell.note(f"step gaps (ms): min {1e3 * min(step_gaps):.1f} median "
              f"{1e3 * typical:.1f} max {1e3 * max(step_gaps):.1f}; "
              f"{sum(g > 1.2 * typical for g in step_gaps)} over 1.2 x the "
              "median")
    return {
        "attempted": len(ends), "failed": failed,
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "checks": checks,
        "facts": {"batch": batch, "seqlen": seqlen, "tokens_per_s": rate,
                  "train_flops_per_token": flops_per_token,
                  "step_gaps_s": step_gaps,
                  "memory_peak_bytes": memory_peak, "traced": traced},
    }
