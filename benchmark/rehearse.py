"""Does this cell's step compile for the chip, and does its batch fit?

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <cell> [--batch N]

Compiles the cell's train step ahead of time for a described (not
attached) TPU v5e chip and prints ``memory_analysis()``: what the chip's
compiler would refuse (a kernel's tiling, a program over 16 GB) is refused
here, at no chip time.  A scratch check for this PR and for every later PR
that adds a cell -- nothing runs, so it says nothing about results or
times, and it is never reported as a chip run.

The program places its own parameters with ``jax.device_put``, which a
described device cannot hold; for the length of the build that call is
stood in for by one that returns the array's shape, dtype and sharding; and
the kernels ask ``jax.default_backend()`` whether to interpret themselves,
so that answer is stood in for while the step is traced.
"""

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None,
                    help="try another batch than the cell's")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import run as harness
    from benchmark.drivers import train_steps

    workload = harness.load_json("workloads", args.workload)
    config = harness.load_json("configs", workload["config"])
    batch = args.batch or workload["traffic"]["batch"]
    seqlen = workload["traffic"]["seqlen"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)

    cell = harness.Cell(workload, config, seed=0)
    ref = harness.config_module(config, "reference", "reference")
    spec = ref.param_spec(config)
    dtype = jnp.dtype(config["training"]["param_dtype"])
    params = {k: jnp.zeros(shape, dtype) for k, shape in spec.items()}

    real_put, real_devices = jax.device_put, jax.devices
    real_backend = jax.default_backend

    def described_put(x, device=None, **kw):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=device)

    t0 = time.perf_counter()
    jax.device_put = described_put
    jax.devices = lambda *a, **k: list(topo.devices)
    try:
        step, state, _ = train_steps.build_program(cell, params)
    finally:
        jax.device_put, jax.devices = real_put, real_devices
    mesh = jax.tree.leaves(state["params"])[0].sharding.mesh
    # the kernels ask jax.default_backend() whether to interpret
    # themselves; here it says "cpu", so the chip's answer is stood in for
    # while the step is traced and lowered
    jax.default_backend = lambda: "tpu"
    tokens = jax.ShapeDtypeStruct((batch, seqlen), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp")))
    key = jax.eval_shape(lambda: jax.random.key(0))
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    with jax.set_mesh(mesh):
        lowered = step._jitted.lower(state["params"], state["opt_state"],
                                     state["step"], (tokens, tokens), key, lr)
    jax.default_backend = real_backend
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    gib = 2.0 ** 30
    print(f"{args.workload} batch {batch} x {seqlen} for {args.topology}, "
          f"one device: trace+lower {t1 - t0:.1f} s, compile {t2 - t1:.1f} s")
    for name in ("argument_size_in_bytes", "output_size_in_bytes",
                 "alias_size_in_bytes", "temp_size_in_bytes",
                 "generated_code_size_in_bytes"):
        print(f"  {name}: {getattr(mem, name) / gib:.3f} GiB")
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"  arguments + outputs - aliased + temporaries: {live / gib:.3f}"
          " GiB of the chip's 16")
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    required = config["program"]["mosaic_kernels"]
    found = {k: sum(k in line for line in calls) for k in required}
    for k, n in required.items():
        print(f"  {k}: {found[k]} Mosaic calls in the compiled HLO, "
              f"{n} required")
    missing = train_steps.kernels_missing(required, found)
    print(f"  mosaic_kernels_missing: {missing} ({len(calls)} Mosaic "
          "calls in all)")
    return 1 if missing else 0


if __name__ == "__main__":
    sys.exit(main())
