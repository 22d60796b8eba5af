"""The readings that the limits of a training cell's check are set from,
taken on the chip at the cell's own size, many seeds in one process:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,...  --control-seeds 1,2,3

For every seed of ``--seeds`` (the lower readings): the cell's compiled
step -- built once, as a run builds it -- is given a fresh state from the
seed, driven through the check's steps and compared with the plain
reference, exactly as a run does after its window.  For every seed of
``--control-seeds`` (the upper readings): the reference is put in the
program's place, once computed in float8 (the control: the nearest
precision below the configuration's bfloat16) and once with half of the
batch left out (a fault), and each is compared with the float32 reference
by the same numbers.  A state left unchanged reads 1 by the measure and
needs no run.  Prints one JSON line per comparison, each with the verdict
``correct`` that the harness gives those numbers under the cell's own
limits (``run.verdict``, the one a run prints): the program's lines have to
say true, the control's and the fault's false.  Exits 1 where one does
not.  The benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import run as harness
    from benchmark import weights
    from benchmark.drivers import train_steps

    workload = harness.load_json("workloads", args.workload)
    config = harness.load_json("configs", workload["config"])
    if not workload.get("rehearsal"):
        if jax.devices()[0].platform != "tpu":
            print("control: needs the chip", file=sys.stderr)
            return harness.EXIT_NO_CHIP
        harness.enable_compile_cache()

    def cell_for(seed):
        return harness.Cell(workload, config, seed)

    check, traffic = workload["check"], workload["traffic"]
    surprises = []

    def emit(kind, seed, gaps, worst, losses, **more):
        """One line; ``kind`` "program" has to be correct, any other not."""
        checks = train_steps.checks_of(gaps, check["limits"])
        correct = harness.verdict(checks)
        if correct != (kind == "program"):
            surprises.append((kind, seed))
        print(json.dumps({
            "kind": kind, "workload": args.workload, "seed": seed,
            "correct": correct,
            "checks": {k: {"value": v, "limit": lim} for k, v, lim in checks},
            "gaps": gaps, "worst": worst, "losses": losses, **more}),
            flush=True)

    ref_mod = harness.config_module(config, "reference", "reference")
    spec = ref_mod.param_spec(config)
    segments = ref_mod.leaf_segments(config)
    dtype = config["training"]["param_dtype"]
    refs = {}

    def reference(seed):
        if seed not in refs:
            t0 = time.perf_counter()
            refs[seed] = train_steps.reference_readings(
                cell_for(seed), check["steps"])
            print(f"control: reference seed {seed} "
                  f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
        return refs[seed]

    if args.seeds:
        first = cell_for(args.seeds[0])
        step, state, model = train_steps.build_program(
            first, weights.make_params(first.seed, spec, dtype))
        layout = jax.tree.map(lambda a: (a.shape, a.dtype, a.sharding), state)
        for seed in args.seeds:
            cell = cell_for(seed)
            if state is None:
                # the same compiled step, a fresh state from this seed
                params = weights.make_params(seed, spec, dtype)
                state = jax.tree.map(
                    lambda l: jax.device_put(jnp.zeros(l[0], l[1]), l[2]),
                    layout, is_leaf=lambda l: isinstance(l, tuple))
                state["params"] = {
                    k: jax.device_put(v, layout["params"][k][2])
                    for k, v in params.items()}
                del params
            batches = weights.make_batches(
                seed, check["steps"], traffic["batch"], traffic["seqlen"],
                config["vocab_size"])
            keys = [jax.random.fold_in(weights.seed_key(seed), i)
                    for i in range(check["steps"])]
            loop = train_steps.Loop(step, state, batches, keys)
            state = None
            prog = train_steps.program_readings(loop, cell, spec, segments,
                                                check["steps"])
            train_steps._free(loop.state)
            del loop
            gaps, worst = train_steps.compare(
                prog, reference(seed), train_steps.ZERO_GRAD_LEAF_SHARE)
            emit("program", seed, gaps, worst, prog["losses"])
        for _, p in model.named_parameters():
            train_steps._free(p._value)
        del step, model

    for seed in args.control_seeds:
        ref = reference(seed)
        half = slice(0, traffic["batch"] // 2)
        for kind, kw in (("control_fp8", {"quant": "fp8"}),
                         ("fault_half_batch", {"rows": half})):
            t0 = time.perf_counter()
            got = train_steps.reference_readings(
                cell_for(seed), check["steps"], **kw)
            gaps, worst = train_steps.compare(
                got, ref, train_steps.ZERO_GRAD_LEAF_SHARE)
            emit(kind, seed, gaps, worst, got["losses"],
                 seconds=time.perf_counter() - t0)
    for kind, seed in surprises:
        print(f"control: {kind} seed {seed} came out "
              f"{'not ' if kind == 'program' else ''}correct under the "
              "cell's limits", file=sys.stderr)
    return 1 if surprises else 0


if __name__ == "__main__":
    sys.exit(main())
