"""Mixture-of-Experts with expert parallelism.

Ref ``python/paddle/incubate/distributed/models/moe/moe_layer.py:244``
(``MoELayer``), gates ``moe/gate/{naive,gshard,switch}_gate.py``, dispatch
via the ``global_scatter``/``global_gather`` CUDA all-to-all ops
(``operators/collective/global_scatter_op.cc:20``) and MoE-aware grad clip
(``moe/grad_clip.py``).

TPU-native design (GShard): dispatch is expressed as dense einsums with a
static per-expert ``capacity`` — no ragged a2a, no dynamic shapes (XLA
requirement). Expert weights carry a leading expert dim sharded over the
'ep' (or 'mp') mesh axis; with tokens batch-sharded and experts
expert-sharded, XLA lowers the dispatch/combine einsums to exactly the
all_to_all pair ``global_scatter``/``global_gather`` implement by hand
(:func:`moe_all_to_all` is the same exchange written explicitly through
the ``parallel/_smap.py`` shard_map helper, for manual-collective
schedules and as executable documentation of what GSPMD inserts).
The full forward is one taped op (``apply_op``) so eager autograd flows
through routing, dispatch and the expert FFNs.

Two scaling/correctness properties of the dispatch (PR 9):

- **Grouped dispatch.**  The one-hot dispatch tensor is ``(tokens, E,
  capacity)`` — O(n^2) in tokens for fixed ``capacity_factor``, which is
  fine at layer-test sizes and catastrophic at pretraining sizes (32k
  tokens/step would build a multi-TB dispatch tensor).  Tokens therefore
  regroup to ``(groups, group_size)`` and capacity applies PER GROUP —
  exactly the GShard formulation (groups are the capacity domains) —
  bounding the dispatch tensor at ``group_size`` x ``E`` x ``C`` per
  group.  The group size is the largest divisor of the token count not
  exceeding a cap (``group_size`` when set, else 512): one group at
  decode/layer-test sizes, bounded groups at pretraining sizes, and a
  training-tuned cap still serves (decode ticks route far fewer tokens
  than any training group — the cap is an upper bound, never a
  divisibility requirement).

- **Dropless eval.**  In eval the per-group capacity is the group size
  itself: an expert can appear at most once in one token's top-k, so
  ``C = S`` can never drop a token.  Token dropping is a TRAINING
  regularizer; at serving time a drop would make a token's output depend
  on which other requests share its tick batch (capacity is assigned by
  intra-batch cumsum), breaking the engine's token-exactness contract
  against ``generate`` under continuous batching.  With zero drops the
  combine is a per-token function, so slot composition cannot change any
  request's tokens.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import apply_op
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.parameter import ParamAttr


def _one_hot(idx, n, dtype=jnp.float32):
    return jax.nn.one_hot(idx, n, dtype=dtype)


def softmax_topk(logits, topk: int, renormalize: bool):
    """The router's choice, shared by every expert layer of this file:
    ``probs`` = softmax over all experts in the logits' dtype (a caller
    that wants a float32 router hands float32 logits), the ``topk``
    largest and their indices, renormalised to sum 1 where asked.
    Returns ``(probs, gate_vals (n, k), idx (n, k))``."""
    return router_topk(logits, topk, renormalize)


def router_topk(logits, topk: int, renormalize: bool,
                score_function: str = "softmax", bias=None, n_group: int = 1,
                topk_group: int = 1, scaling: float = 1.0):
    """A top-k router's choice from ``logits`` (n, experts), in their
    dtype: ``scores`` = softmax over the experts or an elementwise
    sigmoid (``score_function``); the selection by ``scores + bias``
    where a ``bias`` (experts,) is given (DeepSeek-V3's ``noaux_tc``: it
    steers the load and never weighs an output) and, where ``n_group`` >
    1, among the experts of the ``topk_group`` best of ``n_group`` equal
    groups only, a group scored by the sum of its two largest; the chosen
    experts' *unbiased* scores, renormalised to sum 1 where asked, times
    ``scaling``.  Returns ``(scores, gate_vals (n, k), idx (n, k))``."""
    if score_function not in ("softmax", "sigmoid"):
        raise ValueError(f"score_function {score_function!r}: "
                         "'softmax' or 'sigmoid'")
    scores = jax.nn.softmax(logits, axis=-1) \
        if score_function == "softmax" else jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias
    if n_group > 1:
        n, experts = choice.shape
        grouped = choice.reshape(n, n_group, experts // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
        _, kept = jax.lax.top_k(group_score, topk_group)
        kept = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(n, experts)
    if choice is scores:
        gate_vals, idx = jax.lax.top_k(scores, topk)
    else:
        _, idx = jax.lax.top_k(choice, topk)
        gate_vals = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalize:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    if scaling != 1.0:
        gate_vals = gate_vals * scaling
    return scores, gate_vals, idx


def _balance_loss(probs, idx, num_experts):
    """GShard/Switch load-balance aux: E * sum_e mean(gate_e) * frac_e."""
    me = probs.mean(0)
    ce = _one_hot(idx[:, 0], num_experts).mean(0)
    return num_experts * jnp.sum(me * ce)


class NaiveGate(Layer):
    """Plain top-k softmax gate (ref ``moe/gate/naive_gate.py``)."""

    aux = False

    def __init__(self, d_model: int, num_experts: int, topk: int = 2):
        super().__init__()
        self.num_experts, self.topk = num_experts, topk
        self.weight = self.create_parameter(
            [d_model, num_experts],
            attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))

    def route(self, logits, noise=None):
        """Pure routing: logits (n, E) -> (gate_vals (n,k), idx (n,k), aux).

        top-k > 1 renormalizes the kept gates to sum to 1 (GShard).
        top-1 keeps the RAW softmax probability as the combine weight —
        the Switch formulation, where multiplying the expert output by
        the router prob is what makes routing differentiable; a top-1
        renormalization would pin the weight at 1.0 and starve the
        router of any gradient except the aux loss (PR 9 fix, pinned by
        tests/test_moe.py::test_top1_router_gradient_flows)."""
        probs, gate_vals, idx = softmax_topk(logits, self.topk,
                                             self.topk > 1)
        aux = (_balance_loss(probs, idx, self.num_experts) if self.aux
               else jnp.zeros((), jnp.float32))
        return gate_vals, idx, aux

    def forward(self, x):
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        return self.route(xv @ self.weight._value)


class GShardGate(NaiveGate):
    """Top-2 gate with load-balance aux loss and randomized second-expert
    dispatch (ref ``gshard_gate.py``; Lepikhin et al.: route to the 2nd
    expert only with probability proportional to its gate weight)."""

    aux = True

    def __init__(self, d_model, num_experts, topk: int = 2,
                 random_routing: bool = True):
        super().__init__(d_model, num_experts, topk)
        self.random_routing = random_routing

    def route(self, logits, noise=None):
        gate_vals, idx, aux = super().route(logits)
        if noise is not None and self.random_routing and self.topk >= 2:
            keep2 = noise < 2.0 * gate_vals[:, 1]
            gate_vals = gate_vals.at[:, 1].multiply(
                keep2.astype(gate_vals.dtype))
        return gate_vals, idx, aux


class SwitchGate(NaiveGate):
    """Top-1 switch gate with input jitter (ref ``switch_gate.py``;
    Fedus et al.). Jitter noise is sampled by the MoELayer and multiplied
    into the gate input during training."""

    aux = True

    def __init__(self, d_model, num_experts, jitter: float = 0.01):
        super().__init__(d_model, num_experts, topk=1)
        self.jitter = jitter


GATES = {"naive": NaiveGate, "gshard": GShardGate, "switch": SwitchGate}


class MoELayer(Layer):
    """Expert-parallel FFN block (ref ``moe_layer.py:244``).

    Expert weights are stacked (E, ...) with pspec ('ep', ...) so the expert
    dim shards over the 'ep' mesh axis; capacity-based einsum dispatch keeps
    all shapes static. The aux (load-balance) loss lands in ``self.l_aux``
    after each forward, mirroring the reference.
    """

    # when True, forward additionally computes per-layer router stats
    # (mean routing entropy, per-expert dispatched-token fractions) and
    # leaves them on ``self.router_stats`` — the ServingEngine flips this
    # on so its tick programs can return them with the sampled tokens
    # (one fetch; docs/OBSERVABILITY.md moe_router_entropy/moe_expert_load)
    collect_router_stats = False

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate: str = "gshard", topk: int = 2,
                 capacity_factor: float = 1.25,
                 act: Optional[Callable] = None,
                 group_size: Optional[int] = None):
        super().__init__()
        self.d_model, self.d_hidden = d_model, d_hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.group_size = group_size
        # raw (jax-level) activation — runs inside the taped op
        self.act = act or (lambda a: jax.nn.gelu(a, approximate=True))
        if isinstance(gate, str):
            kwargs = {"topk": topk} if gate != "switch" else {}
            gate = GATES[gate](d_model, num_experts, **kwargs)
        self.gate = gate
        init = ParamAttr(initializer=I.Normal(0.0, 0.02))
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden],
                                        attr=init)
        self.b1 = self.create_parameter([num_experts, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model],
                                        attr=init)
        self.b2 = self.create_parameter([num_experts, d_model], is_bias=True)
        for p, spec in ((self.w1, ("ep", None, "mp")),
                        (self.b1, ("ep", "mp")),
                        (self.w2, ("ep", "mp", None)),
                        (self.b2, ("ep", None))):
            p.pspec = spec
            p.is_distributed = True
        self.l_aux = None
        self.router_stats = None

    def capacity(self, group_size: int) -> int:
        """Per-GROUP expert capacity for the TRAINING dispatch (eval is
        dropless — see the module docstring)."""
        k = self.gate.topk
        return max(4, int(math.ceil(
            k * group_size * self.capacity_factor / self.num_experts)))

    def _group_size(self, n: int) -> int:
        """Static token-group size for the dispatch (module docstring):
        the largest divisor of the token count that does not exceed the
        cap — ``group_size`` when set, else 512.  One group at
        layer-test/decode sizes (n <= cap), bounded groups at
        pretraining sizes so the (S, E, C) dispatch tensor stays
        O(cap * capacity), never O(tokens^2).

        ``group_size`` is an UPPER BOUND, not an exact size: a config
        tuned for training (e.g. 512) must still serve — decode ticks
        route n = batch tokens and prefill chunks n = batch * chunk,
        neither of which the training group divides.  Awkward token
        counts (prime n) degrade to small groups, never to an error and
        never past the cap."""
        cap = 512 if self.group_size is None else int(self.group_size)
        if cap < 1:
            raise ValueError(f"group_size must be >= 1, got {cap}")
        if n <= cap:
            return n
        for g in range(cap, 0, -1):
            if n % g == 0:
                return g
        return n  # unreachable (g=1 always divides); keeps mypy honest

    # pht-lint: hot-root (MoE dispatch/combine — every routed block's
    # train step and every MoE decode tick runs this body)
    def forward(self, x):
        xt = x if isinstance(x, Tensor) else Tensor(x)
        orig_shape = tuple(xt._value.shape)
        d = orig_shape[-1]
        n = int(np.prod(orig_shape[:-1]))
        E, K = self.num_experts, self.gate.topk
        S = self._group_size(n)
        G = n // S
        # eval capacity = S (dropless): an expert appears at most once in
        # a token's top-k, so <= S tokens per group can ever want it —
        # no drops, and therefore no dependence of one token's output on
        # the other rows sharing its (serving) batch
        C = self.capacity(S) if self.training else S
        route, act = self.gate.route, self.act
        collect = self.collect_router_stats

        # stateful randomness is sampled OUTSIDE the pure taped fn
        # (jax.vjp would bake a constant key otherwise)
        jitter_noise = route_noise = None
        if self.training:
            from ..core import random as core_random
            if isinstance(self.gate, SwitchGate) and self.gate.jitter > 0:
                j = self.gate.jitter
                jitter_noise = jax.random.uniform(
                    core_random.split_key(), (n, d), xt._value.dtype,
                    1 - j, 1 + j)
            elif (isinstance(self.gate, GShardGate)
                  and self.gate.random_routing):
                route_noise = jax.random.uniform(
                    core_random.split_key(), (n,), jnp.float32)

        def moe_fn(tokens_in, gate_w, w1, b1, w2, b2):
            tokens = tokens_in.reshape(n, d)
            gate_in = (tokens * jitter_noise if jitter_noise is not None
                       else tokens)
            logits = gate_in @ gate_w
            gate_vals, idx, aux = route(logits, route_noise)

            # position of each (token, k) slot in its expert's capacity
            # queue, counted WITHIN its group (groups are the capacity
            # domains — the GShard formulation)
            oh = _one_hot(idx.reshape(G, S * K), E)         # (G, S*K, E)
            pos = (jnp.cumsum(oh, axis=1) - 1.0) * oh
            pos = pos.sum(-1).astype(jnp.int32).reshape(G, S, K)
            keep = pos < C                                  # overflow drop
            gate_g = (gate_vals.reshape(G, S, K)
                      * keep.astype(gate_vals.dtype))

            # GShard dispatch/combine tensors (G, S, E, C)
            slot = _one_hot(jnp.where(keep, pos, C), C + 1)[..., :C]
            sel = _one_hot(idx.reshape(G, S, K), E)         # (G, S, K, E)
            disp = (sel[..., None] * slot[..., None, :]).sum(2)
            comb = (gate_g[..., None, None] * sel[..., None]
                    * slot[..., None, :]).sum(2)

            tok_g = tokens.reshape(G, S, d)
            expert_in = jnp.einsum("gsec,gsd->gecd",
                                   disp.astype(tokens.dtype), tok_g)
            h = act(jnp.einsum("gecd,edh->gech", expert_in, w1)
                    + b1[None, :, None])
            expert_out = (jnp.einsum("gech,ehd->gecd", h, w2)
                          + b2[None, :, None])
            y = jnp.einsum("gsec,gecd->gsd", comb.astype(expert_out.dtype),
                           expert_out)
            out = y.reshape(orig_shape)
            if not collect:
                return out, aux
            # router stats (serving observability), PER TOKEN so the
            # consumer can mask rows that are padding/inactive-slot
            # scratch in a serving tick batch: routing entropy (n,) and
            # kept (dispatched) slot counts per expert (n, E);
            # stop_gradient so the side channel can never grow the
            # backward
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            ent = -(probs * jnp.log(probs + 1e-9)).sum(-1)
            load = disp.astype(jnp.float32).sum(-1).reshape(n, E)
            return (out, aux, jax.lax.stop_gradient(ent),
                    jax.lax.stop_gradient(load))

        args = [xt, self.gate.weight, self.w1, self.b1, self.w2, self.b2]
        if collect:
            y, aux, ent, load = apply_op("moe_layer", moe_fn, args,
                                         n_outputs=4)
            self.router_stats = (ent, load)
        else:
            y, aux = apply_op("moe_layer", moe_fn, args, n_outputs=2)
            self.router_stats = None
        self.l_aux = aux
        return y


# ---------------------------------------------------------------------------
# Dropless top-k experts, told which experts this chip holds
# ---------------------------------------------------------------------------

# what a DroplessMoELayer's forward leaves on ``layer_counters``, in order
ROUTER_COUNTERS = ("rows_routed_here", "row_bound", "rows_largest_expert",
                   "rows_mean_expert")


def _narrow(dtype):
    """The package's default matmul precision is "highest" (float32 means
    float32); operands narrower than that have one pass to take, and the
    chip's grouped-matmul kernel refuses to be asked for more."""
    return None if jnp.dtype(dtype).itemsize >= 4 \
        else jax.lax.Precision.DEFAULT


def _row_slice(n, k, count, num_experts):
    """Rows the grouped products take at a time: twice the even share.
    A router whose ``n * k`` choices fall evenly on ``num_experts`` sends
    ``count`` of them ``n k count / num_experts`` rows, give or take a
    few per cent (5,120 for 8,192 tokens, 10 choices and 32 of 512
    experts; a router drawn N(0, 0.02) read 4,674-5,324 a layer on the
    chip, ``PERF.md`` PR 33); systems that do drop size an expert's
    buffer at 1.25 (this file's ``MoELayer``, Switch) to 2 (GShard)
    times the even share, and a chip's load is the mean of ``count``
    such experts'.  So one slice holds what a chip is sent under any
    routing such a system would accept; whatever is beyond it runs as
    further slices, each at a slice's whole cost.  In whole tiles of the
    grouped-matmul kernel, never more than can be routed here."""
    even = -(-n * k * count // num_experts)
    return min(-(-2 * even // 512) * 512, n * min(k, count))


def _every_token(n, k, count, num_experts):
    """Whether the held experts run on every token under its gate rather
    than on the sorted rows.  The sorted rows take between one slice
    and ``slices`` of :func:`_row_slice`, as many as the routing fills;
    every token through every held expert is a plain product, no gather,
    mask or scatter, a row of it a third to a quarter of a sorted row's
    cost (forward and backward at 16,384 tokens, 2,048 wide, 16 of 128
    experts held on a v5e: 53.6 ms every token, 20.1-26.3 ms one slice
    of 32,768 sorted rows as the routed rows fill it, 103 ms every slice
    with every row in a group).  So where the routing
    can fill more than one slice and every token is at most eight
    slices' rows -- about what two slices cost -- every token it is:
    the layer's time stops following the routing, at about twice a
    balanced router's.  Otherwise the sorted rows."""
    step = _row_slice(n, k, count, num_experts)
    return n * min(k, count) > step and count * n <= 8 * step


def held_expert_outputs(tokens, gate_vals, idx, w_gate_up, w_down, first,
                        num_experts):
    """What the experts ``[first, first + count)`` of ``num_experts`` add
    to every token, and the layer's counters.  ``tokens`` (n, d);
    ``gate_vals`` / ``idx`` (n, k) the router's weights and choices over
    ALL experts; ``w_gate_up`` (count, d, 2 * width) = [gate | up],
    ``w_down`` (count, width, d).

    Dropless and exact for any routing.  The (token, slot) pairs are
    sorted by held expert (pairs of absent experts last); their rows are
    gathered, go through the grouped products (``jax.lax.ragged_dot``),
    are weighted by their pair's gate and summed into their tokens
    (``segment_sum``, in float32).  All shapes are static: as many as
    ``n * min(k, count)`` rows can be routed here (an expert stands at
    most once among a token's k), sixteen times the usual traffic where a
    chip holds a sixteenth of the experts, and a row costs its gather and
    its masks whether routed or not.  So the sorted rows are taken a
    slice at a time (:func:`_row_slice`) under a ``lax.scan`` over the
    worst case's slices, and a slice that starts past the last routed row
    is skipped (``lax.cond``): usually one slice runs.  No capacity, no
    drop; what the absent experts would add is left out.

    Rows past the last group belong to no expert: the grouped kernels do
    not write them (forward or transposed), so each product's input and
    output is masked to the rows routed -- the mask's transpose keeps
    what the transposed kernels left there out of the gradients.

    Where the rows routed here can fill more than one slice and every
    token through every held expert is at most eight slices' rows
    (:func:`_every_token`), the part is laid out the other way
    (:func:`_every_token_outputs`): the same work for any routing."""
    n, d = tokens.shape
    k, count = idx.shape[1], w_gate_up.shape[0]
    if _every_token(n, k, count, num_experts):
        return _every_token_outputs(tokens, gate_vals, idx, w_gate_up,
                                    w_down, first)
    width = w_down.shape[1]
    local = idx.reshape(-1) - first
    here = (local >= 0) & (local < count)
    key = jnp.where(here, local, count)          # absent experts sort last
    order = jnp.argsort(key)                     # stable: pairs keep order
    group_sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], 0,
                          dtype=jnp.int32)
    ends = jnp.cumsum(group_sizes)
    routed = ends[-1]
    pair_weights = jnp.where(here, gate_vals.reshape(-1), 0.0)
    precision = _narrow(tokens.dtype)
    step = _row_slice(n, k, count, num_experts)
    slices = -(-n * min(k, count) // step)
    order = jnp.pad(order, (0, max(0, slices * step - order.shape[0])))

    def rows_from(lo):
        """The sorted rows [lo, lo + step) through the experts."""
        sel = jax.lax.dynamic_slice(order, (lo,), (step,))
        tok = sel // k
        valid = (lo + jnp.arange(step) < routed)[:, None]
        # each expert's rows that fall inside this slice
        sizes = jnp.diff(jnp.clip(ends, lo, lo + step), prepend=lo)
        rows = jnp.where(valid, jnp.take(tokens, tok, axis=0), 0)
        hidden = jnp.where(valid, jax.lax.ragged_dot(
            rows, w_gate_up, sizes, precision=precision), 0)
        act = jax.nn.silu(hidden[:, :width]) * hidden[:, width:]
        out = jnp.where(valid, jax.lax.ragged_dot(
            act, w_down, sizes, precision=precision), 0)
        out = out.astype(jnp.float32) * jnp.take(pair_weights, sel)[:, None]
        return jax.ops.segment_sum(out, tok, num_segments=n) \
            .astype(tokens.dtype)

    # Under ``jax.checkpoint``, the ``cond`` inside it: the backward
    # rebuilds a slice from the layer's inputs.  (The other way round the
    # ``cond``'s operands -- the tokens, both weight stacks -- would be
    # kept once a slice by the scan.)
    @jax.checkpoint
    def slice_or_nothing(lo):
        return jax.lax.cond(lo < routed, rows_from,
                            lambda lo: jnp.zeros_like(tokens), lo)

    def further_slices():
        return jax.lax.scan(
            lambda total, lo: (total + slice_or_nothing(lo), None),
            jnp.zeros_like(tokens),
            step * jnp.arange(1, slices, dtype=jnp.int32))[0]

    # The first slice always runs.  The others stand behind one more
    # ``cond``: a skipped slice still costs its backward a zero gradient
    # for both weight stacks, written and added; with the scan as a whole
    # skipped that is paid once a layer and not once a slice.
    combined = jax.checkpoint(rows_from)(jnp.int32(0))
    if slices > 1:
        combined = combined + jax.checkpoint(lambda: jax.lax.cond(
            routed > step, further_slices,
            lambda: jnp.zeros_like(tokens)))()
    spanned = step * jnp.maximum(1, -(-routed // step))
    counters = jnp.stack([
        routed.astype(jnp.float32), spanned.astype(jnp.float32),
        jnp.max(group_sizes).astype(jnp.float32),
        routed.astype(jnp.float32) / count])
    return combined, jax.lax.stop_gradient(counters)


def _every_token_outputs(tokens, gate_vals, idx, w_gate_up, w_down, first):
    """The held experts' part laid out the other way: every held expert
    on every token, weighted by the token's gate for it (0 where the token
    did not choose it), one expert at a time under a ``lax.scan``.  The
    counters are :data:`ROUTER_COUNTERS`, the rows spanned being every
    token for every held expert."""
    n = tokens.shape[0]
    count, width = w_down.shape[0], w_down.shape[1]
    precision = _narrow(tokens.dtype)
    numbers = first + jnp.arange(count)

    @jax.checkpoint
    def one(total, expert):
        w_gu, w_d, number = expert
        weight = jnp.sum(jnp.where(idx == number, gate_vals, 0.0), -1)
        hidden = jnp.matmul(tokens, w_gu, precision=precision)
        act = jax.nn.silu(hidden[:, :width]) * hidden[:, width:]
        out = jnp.matmul(act, w_d, precision=precision)
        return total + out.astype(jnp.float32) * weight[:, None], None

    combined, _ = jax.lax.scan(one, jnp.zeros(tokens.shape, jnp.float32),
                               (w_gate_up, w_down, numbers))
    per_expert = jnp.sum(idx[..., None] == numbers, (0, 1))
    routed = jnp.sum(per_expert)
    counters = jnp.stack([
        routed.astype(jnp.float32), jnp.float32(n * count),
        jnp.max(per_expert).astype(jnp.float32),
        routed.astype(jnp.float32) / count])
    return combined.astype(tokens.dtype), jax.lax.stop_gradient(counters)


class DroplessMoELayer(Layer):
    """Top-k mixture of SwiGLU experts beside one shared expert (none where
    ``shared_hidden`` is 0: no ``shared_*`` leaves, no ``shared_expert``
    scope), for a chip
    that holds ``experts_held = (first, count)`` of the ``num_experts``
    the router chooses among (expert parallelism's layer on one chip: the
    router keeps its full width, the chip computes its own experts' part
    for the tokens routed to them, without the exchange).

    ``y = sum_{e in top-k(x), e held} p_e(x) expert_e(x)
    + sigmoid(w_s . x) shared(x)``, with ``p = softmax(x W_r)`` over all
    experts in float32, the top-k renormalised to sum 1 where
    ``norm_topk_prob``.  The router's other forms are
    :func:`router_topk`'s, each a constructor argument that a model's
    config fills: ``score_function`` "sigmoid", a selection among the
    ``topk_group`` best of ``n_group`` groups, a ``selection_bias``
    (``router_bias``, one an expert, added for the selection only: no
    gradient ever reaches it; its deployment moves it by the experts'
    load), ``routed_scaling_factor``; ``shared_gated=False`` adds the
    shared expert without its gate.

    *The router's gradient.*  It comes from the k returns of every token.
    A chip that holds all the experts has them and trains its router.  A
    chip that holds a part, without the exchange, has only its own
    experts' returns: that part alone says "my experts help", Adam gives
    it the step of the whole, and the router sends this chip twice its
    share within fifty steps and most of the tokens soon after
    (``PERF.md``, PR 33).  So where ``count < num_experts`` the weights
    ``p_e`` are constants to the backward pass and the router's weight
    gets no gradient from this layer; the exchange, when it comes
    (ROADMAP B11), brings the other returns and the gradient with them.

    After each forward ``layer_counters`` holds :data:`ROUTER_COUNTERS`
    as a float32 vector (the rows the grouped products were routed, the
    rows of the slices they ran, the busiest held expert's rows and the
    mean): ``parallel/layer_outputs.py collect_layer_counters`` hands them
    to the train step, which returns them with the loss."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 topk: int, experts_held, shared_hidden: int,
                 norm_topk_prob: bool = True,
                 score_function: str = "softmax", n_group: int = 1,
                 topk_group: int = 1, routed_scaling_factor: float = 1.0,
                 selection_bias: bool = False, shared_gated: bool = True):
        super().__init__()
        from ..nn.layers.common import Linear
        first, count = experts_held
        if not (0 <= first and count > 0 and first + count <= num_experts):
            raise ValueError(f"experts_held={experts_held} is no range of "
                             f"the {num_experts} experts")
        if num_experts % n_group or not 0 < topk_group <= n_group \
                or topk > topk_group * (num_experts // n_group):
            raise ValueError(
                f"{topk} of {num_experts} experts cannot be chosen among "
                f"{topk_group} of {n_group} equal groups")
        self.num_experts, self.topk = num_experts, topk
        self.experts_held = (int(first), int(count))
        self.norm_topk_prob = norm_topk_prob
        self.router_form = dict(
            score_function=score_function, n_group=n_group,
            topk_group=topk_group, scaling=float(routed_scaling_factor))
        self.selection_bias = selection_bias
        init = ParamAttr(initializer=I.Normal(0.0, 0.02))
        self.router = Linear(d_model, num_experts, weight_attr=init,
                             bias_attr=False)
        self.experts_gate_up = self.create_parameter(
            [count, d_model, 2 * d_hidden], attr=init)
        self.experts_down = self.create_parameter(
            [count, d_hidden, d_model], attr=init)
        for p in (self.experts_gate_up, self.experts_down):
            p.pspec = ("ep", None, None)
            p.is_distributed = True
        self.shared_hidden = shared_hidden
        if shared_hidden:
            self.shared_gate_up = Linear(d_model, 2 * shared_hidden,
                                         weight_attr=init, bias_attr=False)
            self.shared_down = Linear(shared_hidden, d_model,
                                      weight_attr=init, bias_attr=False)
        self.shared_gated = bool(shared_hidden) and shared_gated
        if self.shared_gated:
            self.shared_gate = Linear(d_model, 1, weight_attr=init,
                                      bias_attr=False)
        if selection_bias:
            self.router_bias = self.create_parameter(
                [num_experts], default_initializer=I.Constant(0.0))
        self.layer_counters = None

    def forward(self, x):
        xt = x if isinstance(x, Tensor) else Tensor(x)
        shape = tuple(xt._value.shape)
        topk, norm = self.topk, self.norm_topk_prob
        (first, count), shared = self.experts_held, self.shared_hidden
        num_experts = self.num_experts
        form = self.router_form
        gated, biased = self.shared_gated, self.selection_bias
        # the leaves the shared expert and a router's form may add, in the
        # order moe_fn reads them
        optional = ([self.shared_gate_up.weight, self.shared_down.weight]
                    if shared else []) \
            + ([self.shared_gate.weight] if gated else []) \
            + ([self.router_bias] if biased else [])

        def moe_fn(x_in, router_w, w_gate_up, w_down, *rest):
            gate_up, down = rest[:2] if shared else (None, None)
            gate = rest[2] if gated else None
            bias = rest[-1] if biased else None
            tokens = x_in.reshape(-1, shape[-1])
            with jax.named_scope("router"):
                logits = jnp.matmul(tokens.astype(jnp.float32),
                                    router_w.astype(jnp.float32))
                if bias is not None:
                    bias = jax.lax.stop_gradient(bias.astype(jnp.float32))
                _, gate_vals, idx = router_topk(logits, topk, norm,
                                                bias=bias, **form)
                if count < num_experts:
                    gate_vals = jax.lax.stop_gradient(gate_vals)
            with jax.named_scope("experts"):
                out, counters = held_expert_outputs(
                    tokens, gate_vals, idx, w_gate_up, w_down, first,
                    num_experts)
            if not shared:
                return out.reshape(shape), counters
            with jax.named_scope("shared_expert"):
                h = tokens @ gate_up
                y = (jax.nn.silu(h[:, :shared]) * h[:, shared:]) @ down
                if gate is None:
                    out = out + y
                else:
                    # one output column: float32 like the router costs
                    # nothing and keeps 2,048-long sums out of bfloat16
                    opened = jax.nn.sigmoid(jnp.matmul(
                        tokens.astype(jnp.float32), gate.astype(jnp.float32)))
                    out = out + (y.astype(jnp.float32)
                                 * opened).astype(out.dtype)
            return out.reshape(shape), counters

        y, counters = apply_op("dropless_moe_layer", moe_fn, [
            xt, self.router.weight, self.experts_gate_up, self.experts_down,
            *optional], n_outputs=2)
        self.layer_counters = counters
        return y


def moe_all_to_all(x, mesh, axis: str = "ep", split_axis: int = 0,
                   concat_axis: int = 1):
    """The expert-parallel dispatch exchange, written EXPLICITLY through
    the ``parallel/_smap.py`` shard_map helper — the collective the
    reference implements by hand as ``global_scatter``/``global_gather``
    (``operators/collective/global_scatter_op.cc:20``) and that GSPMD
    inserts automatically around the capacity einsums when tokens are
    batch-sharded and experts 'ep'-sharded.

    ``x`` is a GLOBAL array whose ``concat_axis`` dim is sharded over
    mesh axis ``axis`` (the per-source-rank dim); each device's local
    block is exchanged with ``jax.lax.all_to_all(tiled=True)`` over
    ``split_axis``.  In the global view the VALUES are unchanged — the
    result is ``x`` resharded from ``concat_axis`` onto ``split_axis``
    (dispatch: token-sharded -> expert-sharded; run it with the axes
    swapped for the combine/gather direction).  That identity is the
    whole point: the hand-written a2a pair IS a reshard, which is why
    the einsum formulation needs no explicit collective.  Programs that
    schedule collectives manually (full-manual 'ep' regions) use this
    helper; the ``MoELayer`` forward itself stays on the GSPMD lowering."""
    from jax.sharding import PartitionSpec as P

    from ._smap import run_shard_map
    if x.ndim <= max(split_axis, concat_axis):
        raise ValueError(
            f"moe_all_to_all needs ndim > {max(split_axis, concat_axis)}, "
            f"got shape {tuple(x.shape)}")
    in_spec = [None] * x.ndim
    in_spec[concat_axis] = axis
    out_spec = [None] * x.ndim
    out_spec[split_axis] = axis

    def exchange(local):
        return jax.lax.all_to_all(local, axis, split_axis, concat_axis,
                                  tiled=True)

    return run_shard_map(
        exchange, mesh, in_specs=(P(*in_spec),), out_specs=P(*out_spec),
        manual_axes={axis}, args=(x,),
        cache_key=("moe_all_to_all", axis, split_axis, concat_axis))


def moe_active_params(model) -> tuple:
    """(active, total) parameter counts for an MoE model: ``total`` is
    every parameter; ``active`` counts each :class:`MoELayer`'s expert
    stacks at ``topk / num_experts`` of their size (the params one token
    actually exercises) — the denominator for "tokens/s/chip at matched
    ACTIVE params" bench comparisons (ROADMAP item 5)."""
    total = sum(int(p.size) for p in model.parameters())
    inactive = 0
    for layer in model.sublayers(include_self=True):
        if isinstance(layer, MoELayer):
            E, k = layer.num_experts, layer.gate.topk
            expert = sum(int(p.size) for p in
                         (layer.w1, layer.b1, layer.w2, layer.b2))
            inactive += int(round(expert * (E - min(k, E)) / E))
    return total - inactive, total


def collect_router_stats(model):
    """Layer-averaged PER-TOKEN router stats — ``(entropy (n,),
    kept-slot counts (n, E))`` — over every :class:`MoELayer` whose
    ``collect_router_stats`` flag armed the side channel in the forward
    just traced (the ``collect_moe_aux`` pattern); None when no layer
    left stats.  Per token, not pre-reduced: a serving tick batch mixes
    live rows with inactive-slot scratch and prefill padding, and only
    the ENGINE knows which is which — it masks rows host-side before
    observing the histograms.  Raw jax values: the tick returns them as
    program outputs so they ride the tick's single designed fetch."""
    ents, loads = [], []
    for layer in model.sublayers(include_self=True):
        st = getattr(layer, "router_stats", None)
        if st is None:
            continue
        e, l = st
        ents.append(e._value if isinstance(e, Tensor) else e)
        loads.append(l._value if isinstance(l, Tensor) else l)
    if not ents:
        return None
    inv = 1.0 / len(ents)
    ent = sum(ents[1:], ents[0]) * inv
    load = sum(loads[1:], loads[0]) * inv
    return ent, load


def moe_aux_weight(model) -> float:
    """The load-balance aux-loss weight for ``model`` — the config knob
    (``GPTConfig.moe_aux_weight``), overridable by an explicit
    ``_aux_weight`` attribute (the PipelineLayer convention).  Single
    owner: the sharded train step, the compiled hapi trainer and the
    eager ``train_batch`` all resolve the weight here."""
    w = getattr(model, "_aux_weight", None)
    if w is None:
        w = getattr(getattr(model, "config", None), "moe_aux_weight", 0.01)
    return float(w)


def collect_moe_aux(model, tensors: bool = False, weight=None):
    """The auxiliary losses the forward just run left on ``model``'s
    layers (``l_aux``), as the loss takes them: a layer with its own
    ``aux_weight`` (the sparse attention's indexer KL, 1) adds its value
    times that; the others (the MoE load balance) add their sum times
    ``weight``, by default :func:`moe_aux_weight` of ``model``, applied
    once.  None where no layer left one.  ``tensors=True`` keeps the
    eager autograd Tensors ON the tape (the eager ``train_batch`` path
    must backprop through the aux term); the default strips to raw jax
    values for traced/functional consumers.  Single owner of the
    ``l_aux`` side-channel walk."""
    shared, own = None, None
    for layer in model.sublayers(include_self=True):
        aux = getattr(layer, "l_aux", None)
        if aux is None:
            continue
        if tensors:
            v = aux if isinstance(aux, Tensor) else Tensor(aux)
        else:
            v = aux._value if isinstance(aux, Tensor) else aux
        w = getattr(layer, "aux_weight", None)
        if w is None:
            shared = v if shared is None else shared + v
        else:
            own = w * v if own is None else own + w * v
    if shared is None:
        return own
    shared = (moe_aux_weight(model) if weight is None else weight) * shared
    return shared if own is None else shared + own
