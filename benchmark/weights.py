"""Weights and token batches made on the device from ``--seed``.

The benchmark owns these generators: the program is handed the weights as
values for the state its own builder laid out, and the plain reference
draws the same values again from the seed after the program's state is
gone, so neither takes anything the other made.  Every leaf has its own
key (``fold_in`` by the leaf's place among the sorted names), so one leaf
can be drawn again alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02
_BATCH_STREAM = 0x5EED


def seed_key(seed: int):
    return jax.random.key(int(seed))


def _is_gain(name: str, shape) -> bool:
    """A normalisation's scale: one-dimensional ``.weight``."""
    return len(shape) == 1 and name.endswith(".weight")


@functools.partial(jax.jit, static_argnames=("shape", "gain", "dtype"))
def _leaf(key, idx, shape, gain, dtype):
    """One leaf.  Always this one compiled program for a shape: drawn
    inside a larger program the same formula is fused otherwise and rounds
    a few values in a hundred to the neighbouring bfloat16 (seen on the
    chip, PR 25), and the change of a leaf is measured against it."""
    z = STD * jax.random.normal(jax.random.fold_in(key, idx), shape,
                                jnp.float32)
    return (1.0 + z if gain else z).astype(dtype)


def _spec_tuple(spec: dict):
    return tuple((k, tuple(spec[k])) for k in sorted(spec))


def make_params(seed: int, spec: dict, dtype) -> dict:
    """Every leaf of ``spec`` (``{name: shape}``) on the device: N(0, 0.02)
    for matrices, embeddings and biases, 1 + N(0, 0.02) for normalisation
    scales, rounded to ``dtype``.  One compiled program per distinct
    shape, dispatched once per leaf."""
    key, dtype = seed_key(seed), jnp.dtype(dtype)
    return {name: _leaf(key, i, shape, _is_gain(name, shape), dtype)
            for i, (name, shape) in enumerate(_spec_tuple(spec))}


def segment_sumsq(x, n):
    """Sum of squares of each of ``n`` equal parts of ``x``'s last axis."""
    sq = jnp.square(x.astype(jnp.float32))
    return jnp.sum(sq.reshape(-1, n, x.shape[-1] // n), axis=(0, 2))


def segment_norms(x, n):
    """The norm of each of ``n`` equal parts of ``x``'s last axis."""
    return jnp.sqrt(segment_sumsq(x, n))


def by_segment(name, values) -> dict:
    """``{name: v}`` for one segment, ``{name#i: v_i}`` for several."""
    values = [float(v) for v in values]
    return {name: values[0]} if len(values) == 1 else \
        {f"{name}#{i}": v for i, v in enumerate(values)}


@functools.partial(jax.jit, static_argnames=("n",))
def _change_norm(now, start, n):
    return segment_norms(
        now.astype(jnp.float32) - start.astype(jnp.float32), n)


def change_norms(seed: int, spec: dict, params: dict, segments: dict) -> dict:
    """Per leaf (per segment where ``segments`` splits one)
    ``|params - make_params(seed)|``, drawing each starting leaf again
    instead of keeping a copy of the whole tree."""
    key = seed_key(seed)
    out = [(name, _change_norm(
        params[name],
        _leaf(key, i, shape, _is_gain(name, shape), params[name].dtype),
        segments.get(name, 1)))
        for i, (name, shape) in enumerate(_spec_tuple(spec))]
    return {k: v for name, values in out
            for k, v in by_segment(name, values).items()}


@functools.partial(jax.jit, static_argnames=("n", "batch", "seqlen", "vocab"))
def _pool(key, n, batch, seqlen, vocab):
    stream = jax.random.fold_in(key, _BATCH_STREAM)
    return jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(stream, i), (batch, seqlen + 1), 0, vocab,
        jnp.int32))(jnp.arange(n))


def make_batches(seed: int, n: int, batch: int, seqlen: int, vocab: int):
    """The first ``n`` batches of the seed's stream (batch i is the same
    whatever ``n``), uniform tokens: each sequence is
    ``seqlen + 1`` tokens, ``ids`` its first ``seqlen`` and ``labels`` its
    last ``seqlen`` (the program's loss does not shift)."""
    pool = _pool(seed_key(seed), n, batch, seqlen, vocab)
    return [(pool[i, :, :-1], pool[i, :, 1:]) for i in range(n)]
