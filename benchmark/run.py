"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It holds no table of cells, configurations, drivers or metrics.  The cell
is ``workloads/<cell>.json``; that file names its configuration
(``configs/<config>.json``) and its driver (``drivers/<driver>.py``); the
metrics are those ``BENCHMARK.json`` lists for the cell, each per-layer
metric read by ``layer_metrics/<metric>.py``.  The last line of standard
output is one JSON object (README.md gives its keys).
"""

import time

T_START = time.perf_counter()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 3


class Cell:
    """What a driver is handed: the cell's files, the arguments, and the
    harness's two services (find a module that the configuration names;
    keep a note)."""

    def __init__(self, workload, config, seed, seconds=0.0, trace=False,
                 t_start=0.0):
        self.name = workload["name"]
        self.workload, self.config = workload, config
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.rehearsal = bool(workload.get("rehearsal"))
        self.t_start = t_start
        self.notes = []

    def note(self, text):
        self.notes.append(text)

    def config_module(self, key, kind):
        return config_module(self.config, key, kind)


def load_json(kind, name):
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.json")
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    modname = f"benchmark.{kind}.{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def config_module(config, key, kind):
    """The module of ``<kind>/`` that a configuration's file names under
    ``key``: its ``reference`` (``reference/``), its ``op_count``
    (``op_counts/``).  There is no default: a file that names none ends
    the run as a name without a file does."""
    if not config.get(key):
        raise SystemExit(f"benchmark: configs/{config['name']}.json names "
                         f"no {key}: no {kind}/<name>.py")
    return load_module(kind, config[key])


def enable_compile_cache():
    """The program's own placed cache (``JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<checkout>/.jax_compile_cache``), with every program
    admitted, so that only a checkout's first run of a cell compiles."""
    import jax
    from paddle_hackathon_tpu.core.compile_cache import \
        enable_compile_cache as place
    place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def verdict(checks) -> bool:
    """``correct``: every ``(name, value, limit)`` finite and within its
    limit."""
    return all(math.isfinite(v) and v <= lim for _, v, lim in checks)


def cell_metrics(manifest, cell_name):
    """The end-to-end and per-layer metrics ``BENCHMARK.json`` gives this
    cell.  A metric without ``workloads`` belongs to every cell that
    reports the end-to-end metric it moves (``setup_s``: every cell)."""
    e2e = [m for m in manifest["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if cell_name in m.get("workloads", [cell_name])
             and m["moves"] in names]
    return e2e, layer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    workload = load_json("workloads", args.workload)
    config = load_json("configs", workload["config"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = Cell(workload, config, args.seed, args.seconds, args.trace,
                T_START)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    from benchmark import peaks as peaks_table
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if cell.rehearsal:
        # a CPU rehearsal of the harness: counts only, never a device metric
        peaks = None
    else:
        if platform != "tpu" or len(devices) < workload["chips"]:
            print(f"benchmark: cell {cell.name} needs {workload['chips']} "
                  f"TPU chip(s); jax found {len(devices)} x {platform}",
                  file=sys.stderr)
            return EXIT_NO_CHIP
        peaks = peaks_table.load_peaks(kind)  # unknown kind: an error
        enable_compile_cache()

    out = load_module("drivers", workload["driver"]).run(cell)

    e2e, layer = cell_metrics(manifest, cell.name) if not cell.rehearsal \
        else (manifest["end_to_end"], manifest["per_layer"])
    run = {"config": config, "workload": workload, "peaks": peaks,
           "chips": workload["chips"], "facts": out["facts"],
           "notes": cell.notes}
    metrics = {}
    if cell.trace:
        for m in layer:
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}

    checks = {k: {"value": v, "limit": lim} for k, v, lim in out["checks"]}
    correct = verdict(out["checks"])
    device = {"platform": platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["facts"]["memory_peak_bytes"]}
    traced = out["facts"].get("traced")
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if cell.rehearsal:
        # numbers of a CPU run never go under a device metric's name
        result["rehearsal"] = {f"cpu_rehearsal.{k}": v["value"]
                               for k, v in metrics.items()}
        metrics = {}
    result["metrics"] = metrics
    if traced is not None:
        device["busy_s"], device["window_s"] = \
            traced["busy_s"], traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["device"] = device
    result["checks"] = checks

    for note in cell.notes:
        print("benchmark:", note, file=sys.stderr)
    for k, c in checks.items():
        print(f"benchmark: check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
