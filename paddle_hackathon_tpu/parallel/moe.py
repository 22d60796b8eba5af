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
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, self.topk)
        if self.topk > 1:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)
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
    just traced (the ``_collect_moe_aux`` pattern); None when no layer
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


def collect_moe_aux(model, tensors: bool = False):
    """Sum of the trace-fresh MoE load-balance aux values left on
    MoELayer instances by the forward just run (None when none).
    ``tensors=True`` keeps the eager autograd Tensors ON the tape (the
    eager ``train_batch`` path must backprop through the aux term);
    the default strips to raw jax values for traced/functional
    consumers.  Single owner of the ``l_aux`` side-channel walk."""
    total = None
    for layer in model.sublayers(include_self=True):
        aux = getattr(layer, "l_aux", None)
        if aux is None:
            continue
        if tensors:
            v = aux if isinstance(aux, Tensor) else Tensor(aux)
        else:
            v = aux._value if isinstance(aux, Tensor) else aux
        total = v if total is None else total + v
    return total
