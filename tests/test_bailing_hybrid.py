"""``BailingHybridForCausalLM`` and the grouped sigmoid router on the CPU at
a toy size, held to the benchmark's plain float32 reference
(``benchmark/reference/ling3_f32.py``, which imports nothing of the
program): logits, loss and every leaf's gradient; latent attention through
the packed flash kernel with ``v`` padded against the reference's blocked
softmax with ``v`` as it is; the grouped selection against a sort; the
shares of a cut expert layer add up to the uncut layer; the layer rule from
the config; the dropless layer with another model's arguments unchanged;
the census knows the new scopes; what a KDA layer keeps for its backward and
how often that backward builds the rule's inverse."""

import contextlib
import functools
import io
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_hackathon_tpu as paddle  # noqa: E402
from benchmark import weights  # noqa: E402
from benchmark.reference import ling3_f32 as ref  # noqa: E402
from paddle_hackathon_tpu.models import (BailingHybridConfig,  # noqa: E402
                                         BailingHybridForCausalLM)
from paddle_hackathon_tpu.models import bailing_hybrid as prog  # noqa: E402
from paddle_hackathon_tpu.nn.layer import functional_call  # noqa: E402
from paddle_hackathon_tpu.parallel import moe  # noqa: E402
from test_gated_delta_rule import (_inverse_products, _kernel_calls,  # noqa: E402
                                   rule)

TINY = "ling3-tiny-rehearsal"


def _cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", TINY + ".json")) as f:
        return json.load(f)


def _model(cfg):
    from benchmark.program_configs import ling3 as factory
    keys = cfg["program"]["config_keys"]
    return BailingHybridForCausalLM(factory.config(**{k: cfg[k]
                                                      for k in keys}))


def _setup(seed=7, batch=2, seqlen=80):
    cfg = _cfg()
    params = weights.make_params(seed, ref.param_spec(cfg), jnp.float32)
    (ids, labels), = weights.make_batches(seed, 1, batch, seqlen,
                                          cfg["vocab_size"])
    model = _model(cfg)
    assert {k: tuple(p.shape) for k, p in model.named_parameters()} == \
        {k: tuple(v.shape) for k, v in params.items()}
    return cfg, params, ids, labels, model


def _ce(logits, labels):
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def test_logits_equal_the_reference():
    cfg, params, ids, _, model = _setup(batch=1, seqlen=70)
    for k, p in model.named_parameters():
        p._set_value(params[k])
    got = np.asarray(model(paddle.to_tensor(np.asarray(ids)))._value)
    want = np.asarray(ref.logits_fn(params, ids, cfg))
    assert got.shape == (1, 70, cfg["vocab_size"])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() + 1e-6


def test_loss_and_every_leafs_gradient_equal_the_reference():
    cfg, params, ids, labels, model = _setup()
    got_loss, got = jax.value_and_grad(lambda p: _ce(functional_call(
        model, p, (paddle.to_tensor(ids),)), labels))(params)
    want_loss, want = jax.value_and_grad(
        lambda p: _ce(ref.logits_fn(p, ids, cfg), labels))(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    assert set(got) == set(want)
    for k in want:
        scale = float(jnp.abs(want[k]).max())
        if k.endswith(("router.weight", "router_bias")):
            # 8 of the 16 experts are held: no gradient from a part of a
            # token's returns; the selection bias never has one
            assert scale == 0.0 == float(jnp.abs(got[k]).max()), k
            continue
        assert scale > 0, f"{k} has no gradient"
        assert float(jnp.abs(got[k] - want[k]).max()) < 2e-4 * scale, k


def test_the_layer_rule_comes_from_the_config():
    """``(l + 1) % layer_group_size``, the dense layers first: the cell's
    seven layers are KDA + dense, four KDA + experts, MLA + experts, KDA +
    experts; the published 42 hold 7 MLA layers and 2 dense ones."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash.json")) as f:
        cell = json.load(f)
    c = BailingHybridConfig(
        num_hidden_layers=cell["num_hidden_layers"],
        layer_group_size=cell["layer_group_size"],
        first_k_dense_replace=cell["first_k_dense_replace"])
    kinds = [("mla" if c.layer_is_mla(i) else "kda",
              "mlp" if c.layer_is_dense(i) else "moe") for i in range(7)]
    assert kinds == [("kda", "mlp")] + [("kda", "moe")] * 4 \
        + [("mla", "moe"), ("kda", "moe")]
    whole = BailingHybridConfig()
    assert [i for i in range(42) if whole.layer_is_mla(i)] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert [i for i in range(42) if whole.layer_is_dense(i)] == [0, 1]
    model = _model(_cfg())            # the toy: period 3, one dense layer
    assert [(layer.mla, layer.dense) for layer in model.layers] == [
        (False, True), (False, False), (True, False), (False, False)]


def test_rotary_turns_adjacent_pairs():
    x = jax.random.normal(jax.random.key(1), (1, 6, 2, 8))
    got = prog._rotate_pairs(x, 10000.0)
    assert float(jnp.abs(got - ref.rotary_pairs(x, 10000.0)).max()) < 1e-6
    assert float(jnp.abs(got[:, 0] - x[:, 0]).max()) < 1e-7   # position 0
    t, i = 5, 3                                  # pair (x_6, x_7) of token 5
    angle = t * 10000.0 ** (-2 * i / 8)
    a, b = x[0, t, 1, 2 * i], x[0, t, 1, 2 * i + 1]
    assert abs(float(got[0, t, 1, 2 * i])
               - float(a * np.cos(angle) - b * np.sin(angle))) < 1e-6
    assert abs(float(got[0, t, 1, 2 * i + 1])
               - float(b * np.cos(angle) + a * np.sin(angle))) < 1e-6


def _latent_layer(dtype):
    """One MLA mixer at the published head dims (2 heads x 192 / 128) on a
    narrow hidden, with the reference's sizes and seeded leaves."""
    c = BailingHybridConfig(hidden_size=64, num_attention_heads=2,
                            kv_lora_rank=32)
    layer = prog.BailingLatentAttention(c)
    sizes = {"heads": 2, "nope": 128, "rope": 64, "v_dim": 128, "latent": 32,
             "theta": c.rope_theta, "eps": c.rms_norm_eps}
    spec = {k: tuple(p.shape) for k, p in layer.named_parameters()}
    leaves = weights.make_params(5, spec, dtype)
    for k, p in layer.named_parameters():
        p._set_value(leaves[k])
    return layer, sizes, leaves


def test_latent_attention_equals_the_reference():
    layer, sizes, leaves = _latent_layer(jnp.float32)
    x = jax.random.normal(jax.random.key(2), (2, 70, 64))
    got = layer(paddle.to_tensor(np.asarray(x)))._value
    want = ref.mla(leaves, x, sizes)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def test_latent_attention_through_the_flash_kernel_with_v_padded():
    """From ``flash_attention_min_seqlen`` on the mixer packs ``[q | k | v
    padded to 192]`` for the kernels (interpreted here) and drops the
    output's padding: values and every leaf's gradient against the
    reference's blocked softmax over 192-wide keys and 128-wide values."""
    layer, sizes, leaves = _latent_layer(jnp.bfloat16)
    x = jax.random.normal(jax.random.key(3), (1, 256, 64)) \
        .astype(jnp.bfloat16)
    f32 = {k: v.astype(jnp.float32) for k, v in leaves.items()}

    def through_the_kernel(p, x_):
        return functional_call(layer, p, (paddle.to_tensor(x_),))

    old = paddle.get_flags("flash_attention_min_seqlen")
    paddle.set_flags({"flash_attention_min_seqlen": 256})
    try:
        got, vjp = jax.vjp(through_the_kernel, leaves, x)
        got_g, got_dx = vjp(jnp.ones_like(got))
    finally:
        paddle.set_flags(old)
    want, vjp = jax.vjp(lambda p, x_: ref.mla(p, x_, sizes), f32,
                        x.astype(jnp.float32))
    want_g, want_dx = vjp(jnp.ones_like(want))
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.03 * scale
    for k in want_g:
        err = jnp.abs(got_g[k].astype(jnp.float32) - want_g[k]).max()
        assert float(err) < 0.05 * float(jnp.abs(want_g[k]).max()), k
    err = jnp.abs(got_dx.astype(jnp.float32) - want_dx).max()
    assert float(err) < 0.05 * float(jnp.abs(want_dx).max())


def _kda_layer(b=1, s=128, hidden=32, heads=2, head_dim=16):
    """One KDA mixer's function under the layer's own checkpoint, its nine
    bfloat16 arguments (the normed input first) and the same function with
    no checkpoint around it; two chunks of the rule."""
    c = BailingHybridConfig(hidden_size=hidden, num_attention_heads=heads,
                            head_dim=head_dim)
    layer = prog.BailingKimiDeltaAttention(c)
    names = ("in_proj_qkv.weight", "in_proj_fg.weight", "in_proj_b.weight",
             "conv", "A_log", "dt_bias", "norm.weight", "o_proj.weight")
    spec = {k: tuple(p.shape) for k, p in layer.named_parameters()}
    assert sorted(spec) == sorted(names)
    leaves = weights.make_params(11, spec, jnp.bfloat16)
    x = jax.random.normal(jax.random.key(6), (b, s, hidden)) \
        .astype(jnp.bfloat16)
    plain = functools.partial(
        prog._kimi_delta_attention, heads=heads, head_dim=head_dim,
        floor=float(c.kda_lower_bound), eps=c.rms_norm_eps)
    return layer._core, plain, (x,) + tuple(leaves[k] for k in names)


def _summed(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))


def test_the_kda_layers_backward_does_not_rebuild_the_inverse():
    """Under the layer's checkpoint the gradient program calls the
    inverse's kernel once, the forward's, beside the two products of the
    inverse's own rule: the policy keeps the value that rule reads.  A
    second call (what the layer made while the name sat on the rule's
    output alone) would be the inverse and the system in front of it built
    again in the backward."""
    core, plain, args = _kda_layer()
    assert _kernel_calls(core, args) == 1
    assert _inverse_products(core, args) == 0
    every = tuple(range(len(args)))
    for fn in (core, plain):
        grad = jax.grad(_summed(fn), every)
        assert _kernel_calls(grad, args) == 1
        assert _inverse_products(grad, args) == 2


def test_what_a_kda_layer_keeps_for_its_backward():
    """The residuals of the layer's function: its nine arguments, the
    float32 inverse (chunks, b, h, 64, 64) = 256 B a token and head, and
    the two wide projections' outputs, [q | k | v] at 6 B and [f | gate]
    at 4 B a token and channel.  At the cell's 32 heads x 128: 8 + 24 + 16
    = 48 KB a token.  Nothing else: not ``g``'s float32 (b, s, h, dk), no
    other input of the rule, no state a chunk."""
    b, s, heads, head_dim = 1, 128, 2, 16
    core, plain, args = _kda_layer(b, s, heads=heads, head_dim=head_dim)
    width, chunk = heads * head_dim, rule.CHUNK

    def kept(fn):
        """(shape, short dtype) of every line ``print_saved_residuals``
        prints, as ``bf16[1,128,32] from the argument x``."""
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            jax.ad_checkpoint.print_saved_residuals(fn, *args)
        lines = [re.match(r"(\w+)\[([\d,]*)\] ", line)
                 for line in out.getvalue().splitlines()]
        return sorted((tuple(int(n) for n in m.group(2).split(",")),
                       m.group(1)) for m in lines)
    arguments = [(a.shape, "bf16") for a in args]
    inverse = ((s // chunk, b, heads, chunk, chunk), "f32")
    projections = [((b, s, 3 * width), "bf16"), ((b, s, 2 * width), "bf16")]
    kept_by_the_layer = kept(core)
    assert kept_by_the_layer == sorted(arguments + [inverse] + projections)
    a_token = sum(int(np.prod(shape)) * {"f32": 4, "bf16": 2}[dt]
                  for shape, dt in [inverse] + projections) // (b * s)
    assert a_token == heads * (4 * chunk + 10 * head_dim)
    assert 32 * (4 * chunk + 10 * 128) == 48 * 1024   # the cell's heads
    assert ((b, s, heads, head_dim), "f32") not in kept_by_the_layer
    # the names are what keeps them: a policy without them keeps the
    # arguments, and the backward builds the inverse again
    bare = jax.checkpoint(
        plain, policy=jax.checkpoint_policies.save_only_these_names())
    assert kept(bare) == sorted(arguments)
    grad = jax.grad(_summed(bare), tuple(range(len(args))))
    assert _kernel_calls(grad, args) == 2
    assert _inverse_products(grad, args) == 2


def test_the_kda_layers_gradients_equal_those_without_a_checkpoint():
    core, plain, args = _kda_layer()
    every = tuple(range(len(args)))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(_summed(core), every)(*args)
        want = jax.grad(_summed(plain), every)(*args)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype == jnp.bfloat16
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        scale = float(jnp.abs(w).max())
        assert scale > 0, i
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, i


GROUPED = {"groups": 8, "groups_kept": 4, "topk": 8, "renorm": True,
           "scaling": 2.5}


def _scores(seed, n=64, experts=512):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return (jax.random.normal(k1, (n, experts)),
            0.3 * jax.random.normal(k2, (experts,)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_grouped_sigmoid_selection_equals_a_sort(seed):
    logits, bias = _scores(seed)
    _, got_w, got_i = moe.router_topk(
        logits, 8, True, score_function="sigmoid", bias=bias, n_group=8,
        topk_group=4, scaling=2.5)
    want_w, want_i = ref.grouped_choice(jax.nn.sigmoid(logits), bias, GROUPED)
    assert (np.sort(np.asarray(got_i), -1)
            == np.sort(np.asarray(want_i), -1)).all()
    order = np.argsort(np.asarray(want_i), -1)
    got_order = np.argsort(np.asarray(got_i), -1)
    assert np.abs(np.take_along_axis(np.asarray(got_w), got_order, -1)
                  - np.take_along_axis(np.asarray(want_w), order, -1)
                  ).max() < 1e-6
    assert np.abs(np.asarray(got_w).sum(-1) - 2.5).max() < 1e-5
    # a token never takes an expert of a dropped group: by hand, the four
    # groups with the largest sums of their two best biased scores
    biased = np.asarray(jax.nn.sigmoid(logits) + bias).reshape(64, 8, 64)
    best_two = np.sort(biased, -1)[..., -2:].sum(-1)
    kept = np.argsort(-best_two, -1)[:, :4]
    for token in range(64):
        assert set(np.asarray(got_i)[token] // 64) <= set(kept[token])


def test_the_bias_moves_the_selection_and_not_the_weights():
    logits, bias = _scores(3)
    pick = dict(score_function="sigmoid", n_group=8, topk_group=4,
                scaling=2.5)
    scores, w_with, i_with = moe.router_topk(logits, 8, True, bias=bias,
                                             **pick)
    _, _, i_without = moe.router_topk(logits, 8, True, bias=None, **pick)
    assert (np.sort(np.asarray(i_with), -1)
            != np.sort(np.asarray(i_without), -1)).any()
    chosen = jnp.take_along_axis(scores, i_with, -1)
    want = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    assert float(jnp.abs(w_with - want).max()) < 1e-6


def _expert_layer(first, count, d=16, width=8, num=32, k=4):
    """A grouped-router layer that holds ``count`` of ``num`` experts from
    ``first`` and the whole layer's leaves (the held experts cut out)."""
    spec = {"router.weight": (d, num), "router_bias": (num,),
            "experts_gate_up": (num, d, 2 * width),
            "experts_down": (num, width, d),
            "shared_gate_up.weight": (d, 2 * width),
            "shared_down.weight": (width, d)}
    whole = weights.make_params(21, spec, jnp.float32)
    whole["router.weight"] = whole["router.weight"] * 40    # spread scores
    whole["router_bias"] = whole["router_bias"] * 5
    layer = moe.DroplessMoELayer(
        d, width, num, k, experts_held=(first, count), shared_hidden=width,
        score_function="sigmoid", n_group=4, topk_group=2,
        routed_scaling_factor=2.5, selection_bias=True, shared_gated=False)
    cut = dict(whole)
    for name in ("experts_gate_up", "experts_down"):
        cut[name] = whole[name][first:first + count]
    assert {k_: tuple(p.shape) for k_, p in layer.named_parameters()} \
        == {k_: tuple(v.shape) for k_, v in cut.items()}
    for name, p in layer.named_parameters():
        p._set_value(cut[name])
    sizes = {"topk": k, "renorm": True, "first": first, "count": count,
             "groups": 4, "groups_kept": 2, "scaling": 2.5}
    return layer, whole, cut, sizes


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold 8 of 32 experts each under the grouped router: the
    routed parts their layers give, with the shared expert (which every
    chip computes alike, ungated) counted once, add up to what the uncut
    reference gives for the whole layer."""
    x = jax.random.normal(jax.random.key(9), (3, 20, 16))
    _, whole, _, sizes = _expert_layer(0, 32)
    uncut = ref.experts(whole, x, sizes)
    shared_only = None
    total = jnp.zeros_like(x)
    for first in (0, 8, 16, 24):
        layer, _, cut, sizes = _expert_layer(first, 8)
        got = layer(paddle.to_tensor(np.asarray(x)))._value
        # this share against the reference given the same share
        assert float(jnp.abs(got - ref.experts(cut, x, sizes)).max()) < 1e-5
        shared = ref.experts(cut, x, sizes) \
            - ref.experts(cut, x, sizes, shared=False)
        shared_only = shared if shared_only is None else shared_only
        total = total + (got - shared)
    assert float(jnp.abs(total + shared_only - uncut).max()) < 2e-5
    # every token's 4 choices lie in 2 of the 4 groups of 8
    _, idx = ref.router_choice(whole, x.reshape(-1, 16), sizes)
    assert all(len(set(row // 8)) <= 2 for row in np.asarray(idx))


def test_another_models_arguments_give_the_function_they_gave():
    """``DroplessMoELayer`` as ``Qwen3NextDecoderLayer`` builds it (softmax
    top-k, renormalised, a gated shared expert; none of the router's new
    arguments): the layer's output is that model's reference's, and its
    leaves are the ones it had."""
    from benchmark.reference import qwen3_next_f32 as other
    d, width, num, k = 16, 8, 32, 4
    layer = moe.DroplessMoELayer(d, width, num, k, experts_held=(8, 8),
                                 shared_hidden=width, norm_topk_prob=True)
    assert sorted(n for n, _ in layer.named_parameters()) == sorted([
        "router.weight", "experts_gate_up", "experts_down",
        "shared_gate_up.weight", "shared_down.weight", "shared_gate.weight"])
    leaves = weights.make_params(
        33, {n: tuple(p.shape) for n, p in layer.named_parameters()},
        jnp.float32)
    leaves["router.weight"] = leaves["router.weight"] * 40
    for n, p in layer.named_parameters():
        p._set_value(leaves[n])
    x = jax.random.normal(jax.random.key(4), (2, 24, d))
    got = layer(paddle.to_tensor(np.asarray(x)))._value
    want = other.experts(leaves, x, {"topk": k, "renorm": True, "first": 8,
                                     "count": 8})
    assert float(jnp.abs(got - want).max()) < 1e-6
    # and the shared choice function is the one softmax_topk always was
    logits = jax.random.normal(jax.random.key(5), (40, num))
    probs, vals, idx = moe.softmax_topk(logits, k, True)
    want_vals, want_idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert float(jnp.abs(vals - want_vals / want_vals.sum(-1, keepdims=True)
                         ).max()) == 0.0
    assert float(jnp.abs(probs - jax.nn.softmax(logits, -1)).max()) == 0.0


def test_a_router_form_that_cannot_choose_is_refused():
    with pytest.raises(ValueError, match="cannot be chosen"):
        moe.DroplessMoELayer(8, 4, 32, 9, experts_held=(0, 32),
                             shared_hidden=4, n_group=8, topk_group=2)
    with pytest.raises(ValueError, match="score_function"):
        moe.router_topk(jnp.zeros((2, 8)), 2, True, score_function="tanh")


def test_scopes_nested_in_kda_read_apart_from_it():
    from paddle_hackathon_tpu.observability.programs import _phase_of
    stack = "jit(train_step)/transpose(jvp(kda))/kda_rule/while/body/dot"
    assert _phase_of(stack) == ("bwd", "kda/kda_rule")
    assert _phase_of("jit(train_step)/jvp(kda)/kda_conv/mul") == \
        ("fwd", "kda/kda_conv")
    assert _phase_of("jit(train_step)/jvp(kda)/dot_general") == ("fwd", "kda")
    assert _phase_of("jit(train_step)/jvp(mla)/concatenate") == ("fwd", "mla")
    # a part's name outside its parent names nothing
    assert _phase_of("jit(train_step)/jvp(kda_rule)/dot") == ("fwd", "")


def test_trains_through_the_sharded_step_and_hands_over_its_counters():
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import bailing_hybrid_sharding_spec
    from paddle_hackathon_tpu.observability.programs import (
        get_program_registry, program_analysis)
    cfg = _cfg()
    paddle.seed(3)
    model = _model(cfg)
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=bailing_hybrid_sharding_spec, learning_rate=3e-3)
    (ids, labels), = weights.make_batches(3, 1, 4, 64, cfg["vocab_size"])
    losses = []
    with program_analysis():          # the build keeps its phase census
        for i in range(6):
            state, loss = step(state, ids, labels, jax.random.key(i))
            losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    # the expert layers' counters come back beside the loss: every layer
    # but the leading dense one
    counters = get_program_registry().counters("parallel.sharded_train_step")
    assert sorted(counters) == [f"layers.{i}.mlp" for i in (1, 2, 3)]
    for rows, bound, largest, mean in counters.values():
        assert 0 < rows <= bound == 4 * 64 * 2
    site = get_program_registry().phase_census("parallel.sharded_train_step")
    components = {c for _, c, _ in site.values()}
    assert {"kda", "kda/kda_conv", "kda/kda_rule", "mla", "mlp", "moe/router",
            "moe/experts", "moe/shared_expert"} <= components
