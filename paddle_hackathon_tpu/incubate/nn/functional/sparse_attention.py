"""DeepSeek Sparse Attention (DeepSeek-V3.2-Exp): a lightning indexer
picks ``topk`` keys for every query, softmax attention runs over those
keys only, and the indexer learns from a KL loss against the attention's
own probabilities.

For queries ``t`` and keys ``j <= t``:

- indexer: ``I[t, j] = sum_h w[t, h] relu(qi[t, h] . ki[j])``, float32;
- selection: ``S_t`` = the ``topk`` keys ``j <= t`` of largest ``I[t, j]``
  (all of them where ``t < topk``), ties to the earlier key, as
  ``jax.lax.top_k`` chooses them; no gradient;
- attention: ``o[t, h] = sum_{j in S_t} softmax_{S_t}(q_h . k_j * scale)
  v_j``, ``heads / kv_heads`` query heads to a KV head;
- indexer loss: ``p[t, j] = mean_h softmax_{S_t}(q_h . k_j * scale)``
  (a target: no gradient), ``KL_t = sum_{j in S_t} p log(p /
  softmax_{S_t}(I[t, .]))``; its gradient reaches ``qi``, ``ki`` and ``w``
  alone.

The computation (kernels in ``incubate/nn/kernels/dsa_attention.py``):
the scores in blocks of 512 queries under a ``lax.scan`` (scope
``dsa_index``), each block's threshold a row (a kernel that counts, where
XLA's ``top_k`` sorts every row) and its bits in the selection's words
(``dsa_select``), the masked attention forward and backward
(``dsa_attn``), the KL and its gradient (``dsa_kl``).  Nothing holds an
(s, s) array: a block's scores are (512, s) float32 and the selection is
one bit a pair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import dsa_attention as K
from ..kernels import mesh as kmesh

SELECT_BLOCK = 512
# what ``dsa_counters`` counts, in order
DSA_COUNTERS = ("selected_pairs", "causal_tiles", "live_tiles")


def select_keys(qi, w, ki, topk: int):
    """The selection of every query: ``(words, lse_i)``.

    ``qi`` (b, ih, s, id), ``w`` (b, s, ih) float32, ``ki`` (b, s, id).
    ``words`` (b, s, ``K.word_columns(s)``) int32 holds one bit a (query,
    key) pair as ``dsa_attention.py`` lays them out; ``lse_i`` (b, 1, s)
    is the log-sum-exp of each query's scores over its selected keys."""
    b, _, s, _ = qi.shape
    block = K.blocks(s, SELECT_BLOCK)
    take = min(topk, s)

    def one_block(words, j):
        row0 = j * block
        with jax.named_scope("dsa_index"):
            scores = K.index_scores(
                row0, jax.lax.dynamic_slice_in_dim(qi, row0, block, 2),
                jax.lax.dynamic_slice_in_dim(w, row0, block, 1), ki)
        with jax.named_scope("dsa_select"):
            thr, cut, lse_i = K.select_threshold(scores, take, row0)
            key = K.order_keys(scores)
            rows = row0 + jnp.arange(block, dtype=jnp.int32)[:, None]
            cols = jnp.arange(s, dtype=jnp.int32)[None, :]
            sel = (cols <= rows) & ((key > thr)
                                    | ((key == thr) & (cols <= cut)))
            planes = sel.reshape(b, block // K.LANES, K.LANES, s) \
                .astype(jnp.int32)
            bit0 = (row0 % K.GROUP_QUERIES) // K.LANES
            packed = planes[:, 0] << bit0
            for i in range(1, block // K.LANES):
                packed = packed | (planes[:, i] << (bit0 + i))
            col0 = (row0 // K.GROUP_QUERIES) * K.LANES
            old = jax.lax.dynamic_slice_in_dim(words, col0, K.LANES, 2)
            words = jax.lax.dynamic_update_slice_in_dim(
                words, old | jnp.swapaxes(packed, 1, 2), col0, 2)
            return words, lse_i[..., 0]

    words = jnp.zeros((b, s, K.word_columns(s)), jnp.int32)
    words, lse_i = jax.lax.scan(one_block, words,
                                jnp.arange(s // block, dtype=jnp.int32))
    return words, jnp.moveaxis(lse_i, 0, 1).reshape(b, 1, s)


def selection_mask(words, s: int):
    """The (b, s queries, s keys) boolean selection that ``words`` packs:
    for tests and small sizes."""
    t = np.arange(s)
    col = (t // K.GROUP_QUERIES) * K.LANES + t % K.LANES
    bit = (t % K.GROUP_QUERIES) // K.LANES
    per_key = (words[:, :, col] >> bit) & 1            # (b, keys, queries)
    return jnp.swapaxes(per_key, 1, 2) != 0


def dsa_counters(words):
    """:data:`DSA_COUNTERS` of a selection, float32: the selected (query,
    key) pairs, the causal (q tile, kv tile) cells the attention kernels
    run, and those among them that hold a selected key."""
    b, s, cols = words.shape
    bq = bk = K.blocks(s)
    pairs = jnp.sum(jax.lax.population_count(words))
    groups = cols // K.LANES
    orv = jax.lax.reduce(words.reshape(b, s // bk, bk, groups, K.LANES),
                         np.int32(0), jax.lax.bitwise_or, (2, 4))
    qt = np.arange(s // bq)
    group_of = qt * bq // K.GROUP_QUERIES
    bit0 = (qt * bq % K.GROUP_QUERIES) // K.LANES
    planes = (1 << (bq // K.LANES)) - 1
    live = ((orv[:, :, group_of] >> bit0) & planes) != 0
    return jnp.stack([pairs.astype(jnp.float32),
                      jnp.float32(b * K.causal_tiles(s)),
                      jnp.sum(live).astype(jnp.float32)])


def _local(q, k, v, qi, ki, w, *, heads, topk, scale):
    words, lse_i = select_keys(*map(jax.lax.stop_gradient, (qi, w, ki)),
                               topk)
    with jax.named_scope("dsa_attn"):
        o, lse = K.masked_attention(q, k, v, words, heads, scale)
    with jax.named_scope("dsa_kl"):
        wt = jnp.swapaxes(w, 1, 2)[:, :, None, :]
        kl = K.indexer_kl(
            jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
            jax.lax.stop_gradient(lse), qi, ki, wt, words, lse_i, heads,
            scale)
    return o, kl[:, 0], jax.lax.stop_gradient(dsa_counters(words))


def sparse_attention(q, k, v, qi, ki, w, *, heads: int, topk: int,
                     scale: float):
    """DSA over ``q`` (b, s, heads * d), ``k``, ``v`` (b, s, kv_heads * d),
    the indexer's ``qi`` (b, ih, s, id), ``ki`` (b, s, id) and head
    weights ``w`` (b, s, ih) float32 -> ``(o, kl, counters)``: ``o`` like
    ``q``, ``kl`` (b, s) float32 each query's indexer loss, ``counters``
    :data:`DSA_COUNTERS`.  ``o`` has gradients for ``q``, ``k``, ``v``;
    ``kl`` for ``qi``, ``ki``, ``w``.

    Under a mesh that shards the batch the kernels run on each device's
    own rows (``kernels/mesh.py``: jax will not partition a Mosaic call);
    a mesh that module does not cover is refused."""
    fn = functools.partial(_local, heads=heads, topk=topk, scale=scale)
    p = kmesh.plan(q.shape[0],
                   kmesh.partitioned_axes().get(kmesh.HEAD_AXIS, 1))
    if p is None:
        raise NotImplementedError(
            "sparse attention under this mesh: only the batch axes "
            f"{kmesh.BATCH_AXES} and {kmesh.HEAD_AXIS!r} may be split")
    if not p.partitioned:
        return fn(q, k, v, qi, ki, w)
    from jax.sharding import PartitionSpec as P
    rows = P(p.batch_axes)

    def per_shard(*args):
        o, kl, counters = fn(*args)
        total = counters
        for a in p.batch_axes:
            total = jax.lax.psum(total, a)
        return o, kl, total

    return jax.shard_map(per_shard, in_specs=(rows,) * 6,
                         out_specs=(rows, rows, P()),
                         check_vma=False)(q, k, v, qi, ki, w)
