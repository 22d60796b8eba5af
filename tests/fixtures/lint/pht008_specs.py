"""Seeded PHT008 sharding-spec drift violations — `# expect:` comments
are the exact-line assertions.

Negative shapes asserted clean by the same comparison: specs whose axes
match the mesh, arity in agreement, meshes whose axes are NOT statically
known (a function parameter) are skipped entirely.  Never executed.
"""
import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from paddle_hackathon_tpu.parallel._smap import run_shard_map
from paddle_hackathon_tpu.parallel.api import create_mesh

AXES = ("dp", "mp")

mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), AXES)
mesh_api = create_mesh({"dp": 2, "mp": 4})


def renamed_axis_sharding(arr):
    return jax.device_put(arr, NamedSharding(mesh2, P("tp")))  # expect: PHT008


def good_sharding(arr):
    return jax.device_put(arr, NamedSharding(mesh2, P("dp", "mp")))


def spec_axis_drift(x):
    def body(xl, yl):
        return xl + yl
    return run_shard_map(body, mesh_api,               # expect: PHT008
                         in_specs=(P("dp"), P("data")),
                         out_specs=P("dp"), manual_axes={"dp"},
                         args=(x, x), cache_key=("drift",))


def body_arity_drift(x, y):
    def body(xl, yl, zl):                 # grew an argument...
        return xl + yl + zl
    return run_shard_map(body, mesh_api,               # expect: PHT008
                         in_specs=(P("dp"), P("dp")),  # ...specs did not
                         out_specs=P("dp"), manual_axes={"dp"},
                         args=(x, y), cache_key=("arity",))


def args_arity_drift(x, y, z):
    def body(xl, yl):
        return xl + yl
    return run_shard_map(body, mesh_api,               # expect: PHT008
                         in_specs=(P("dp"), P("dp")),
                         out_specs=P("dp"), manual_axes={"dp"},
                         args=(x, y, z), cache_key=("args",))


def manual_axis_drift(x):
    def body(xl):
        return xl
    return run_shard_map(body, mesh2,                  # expect: PHT008
                         in_specs=(P("dp"),), out_specs=P("dp"),
                         manual_axes={"sharding"},
                         args=(x,), cache_key=("manual",))


def shard_map_axis_drift(x):
    def body(xl):
        return xl
    sm = shard_map(body, mesh=mesh2, in_specs=(P("sp"),),  # expect: PHT008
                   out_specs=P("dp"), axis_names=("dp",))
    return sm(x)


def unknown_mesh_is_skipped(x, mesh):
    # the mesh's axes are not statically known here: no axis check (a
    # guess would false-positive), arity still applies and matches
    def body(xl):
        return xl
    return run_shard_map(body, mesh, in_specs=(P("anything"),),
                         out_specs=P("anything"), manual_axes={"a"},
                         args=(x,), cache_key=("unknown",))


def matching_specs_ok(x, y):
    def body(xl, yl):
        return xl + yl
    return run_shard_map(body, mesh_api, in_specs=(P("dp"), P("mp")),
                         out_specs=P("dp"), manual_axes={"dp", "mp"},
                         args=(x, y), cache_key=("ok",))
