"""The delta rule's triangular inverse as one Pallas kernel
(``incubate/nn/kernels/delta_rule_inverse.py``), interpreted on the CPU,
against the log-doubling it replaced and against a triangular solve, on
systems drawn as both rules draw them: the scalar rule's with decays near
0 and near -0.7 a token, the channel-decay rule's near -2.5 a token and
channel; one and two chunks, and head counts whose matrices fill the
kernel's blocks of 128 and do not."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import test_gated_delta_rule as scalar_case  # noqa: E402
import test_kimi_delta_rule as channel_case  # noqa: E402
from paddle_hackathon_tpu.incubate.nn.kernels import \
    delta_rule_inverse as kernel  # noqa: E402

scalar_rule = importlib.import_module(
    "paddle_hackathon_tpu.incubate.nn.functional.gated_delta_rule")
channel_rule = importlib.import_module(
    "paddle_hackathon_tpu.incubate.nn.functional.kimi_delta_rule")
C = scalar_rule.CHUNK


def _doubling(a):
    """The parent's ``_unit_lower_inverse``: with ``b = -a`` nilpotent,
    ``(I - b)^-1 = (I + b)(I + b^2)(I + b^4)...``, ten float32 products."""
    power = -a
    inv = jnp.eye(C, dtype=a.dtype) + power
    span = 2
    while span < C:
        power = power @ power
        inv = inv + inv @ power
        span *= 2
    return inv


def _system(kind, chunks, heads):
    """Every chunk's strictly lower-triangular float32 ``a`` (chunks, b,
    heads, C, C), from the rule's own ``_chunk_system`` on its tests'
    inputs."""
    case, rule, g_mean = {
        "scalar-0.01": (scalar_case, scalar_rule, -0.01),
        "scalar-0.7": (scalar_case, scalar_rule, -0.7),
        "channel-2.5": (channel_case, channel_rule, -2.5)}[kind]
    _, k, _, g, beta = case._inputs(chunks * C, g_mean, h=heads)
    b = k.shape[0]

    def chunked(x):     # (b, n*c, h, ...) -> (n, b, h, c, ...)
        x = x.reshape((b, chunks, C) + x.shape[2:])
        return jnp.moveaxis(x, (1, 3), (0, 2))
    return rule._chunk_system(chunked(k), chunked(g.astype(jnp.float32)),
                              chunked(beta))


@pytest.mark.parametrize("heads", [3, 32])      # 2 * 32 * 2 = 128 a block
@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("kind", ["scalar-0.01", "scalar-0.7",
                                  "channel-2.5"])
def test_the_kernel_inverts_every_chunks_system(kind, chunks, heads):
    a = _system(kind, chunks, heads)
    assert a.shape == (chunks, 2, heads, C, C)
    assert bool((jnp.triu(a) == 0).all())
    with jax.default_matmul_precision("highest"):
        got = kernel.inverse(a)
        doubled = _doubling(a)
        eye = jnp.eye(C, dtype=jnp.float32)
        solved = jax.scipy.linalg.solve_triangular(
            eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
            unit_diagonal=True)
        residual = jnp.einsum("...ij,...jk->...ik", eye + a, got) - eye
    assert got.shape == a.shape and got.dtype == jnp.float32
    # a few float32 units in the last place of the largest entry
    bound = 8 * float(np.spacing(np.float32(jnp.abs(doubled).max())))
    assert float(jnp.abs(got - doubled).max()) <= bound
    assert float(jnp.abs(got - solved).max()) <= bound
    assert float(jnp.abs(residual).max()) <= bound
    # unit lower-triangular, as the inverse of a unit lower-triangular
    assert bool((jnp.diagonal(got, axis1=-2, axis2=-1) == 1).all())
    assert bool((jnp.triu(got, 1) == 0).all())


def test_the_kernel_takes_only_square_float32_chunks():
    with pytest.raises(ValueError, match="float32"):
        kernel.inverse(jnp.zeros((2, C, C), jnp.bfloat16))
    with pytest.raises(ValueError, match="multiple of 8"):
        kernel.inverse(jnp.zeros((2, 12, 12), jnp.float32))


@pytest.mark.parametrize("axes", [("dp", "mp"), ("dp", "sp")])
def test_on_a_mesh_every_device_inverts_its_own_matrices(axes):
    """On a mesh that splits the batch and the heads the kernel runs per
    shard, and the shards reassemble to the one-device inverse exactly; a
    mesh ``kernels/mesh.py`` does not cover ('sp') takes XLA's triangular
    solve, within the same few units in the last place."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    a = _system("scalar-0.7", 2, 4)
    want = kernel.inverse(a)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), axes)
    sharded = jax.device_put(a, NamedSharding(mesh, P(None, *axes)))
    with jax.set_mesh(mesh):
        got = jax.jit(kernel.inverse)(sharded)
    if "mp" in axes:
        assert jnp.array_equal(want, got)
    else:
        bound = 8 * float(np.spacing(np.float32(jnp.abs(want).max())))
        assert float(jnp.abs(got - want).max()) <= bound
