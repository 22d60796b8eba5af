"""``Qwen3NextForCausalLM`` and the dropless expert layer on the CPU at a
toy size, held to the benchmark's plain float32 reference
(``benchmark/reference/qwen3_next_f32.py``, which imports nothing of the
program): logits, loss and every leaf's gradient; rotary on part of the
head; the shares of a cut expert layer add up to the uncut layer; nothing
routed to a held expert is dropped under any skew; the packed flash
kernel's plans for the shapes the other cells run stay what they were; the
phase census of a GPT step is unchanged by the new scopes."""

import json
import os
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
from benchmark.reference import qwen3_next_f32 as ref  # noqa: E402
from paddle_hackathon_tpu.models import (Qwen3NextConfig,  # noqa: E402
                                         Qwen3NextForCausalLM)
from paddle_hackathon_tpu.nn.layer import functional_call  # noqa: E402
from paddle_hackathon_tpu.parallel import moe  # noqa: E402

TINY = "qwen3-next-tiny-rehearsal"


def _cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", TINY + ".json")) as f:
        return json.load(f)


def _model(cfg):
    from benchmark.program_configs import qwen3_next as factory
    keys = cfg["program"]["config_keys"]
    return Qwen3NextForCausalLM(factory.config(**{k: cfg[k] for k in keys}))


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
    cfg, params, ids, _, model = _setup()
    for k, p in model.named_parameters():
        p._set_value(params[k])
    got = np.asarray(model(paddle.to_tensor(np.asarray(ids)))._value)
    want = np.asarray(ref.logits_fn(params, ids, cfg))
    assert got.shape == (2, 80, cfg["vocab_size"])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() + 1e-6


def test_loss_and_every_leafs_gradient_equal_the_reference():
    cfg, params, ids, labels, model = _setup()

    def program_loss(p):
        return _ce(functional_call(model, p, (paddle.to_tensor(ids),)),
                   labels)

    got_loss, got = jax.value_and_grad(program_loss)(params)
    want_loss, want = jax.value_and_grad(
        lambda p: _ce(ref.logits_fn(p, ids, cfg), labels))(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    assert set(got) == set(want)
    for k in want:
        scale = float(jnp.abs(want[k]).max())
        if k.endswith("router.weight"):
            # 4 of the 8 experts are held: no gradient from a part of
            # a token's returns, on either side
            assert scale == 0.0 == float(jnp.abs(got[k]).max()), k
            continue
        assert scale > 0, f"{k} has no gradient"
        assert float(jnp.abs(got[k] - want[k]).max()) < 2e-4 * scale, k


def test_rotary_turns_a_quarter_of_the_head_and_leaves_the_rest():
    from paddle_hackathon_tpu.models import qwen3_next as prog
    x = jax.random.normal(jax.random.key(1), (2, 11, 3, 64))
    got = prog._rotate(x, 16, 1e7)
    assert float(jnp.abs(got - ref.rotary(x, 16, 1e7)).max()) < 1e-6
    assert np.array_equal(got[..., 16:], x[..., 16:])
    assert np.allclose(got[:, 0], x[:, 0])            # position 0: no turn
    assert not np.allclose(got[:, 5, :, :16], x[:, 5, :, :16])
    # a rotation: the turned part keeps its length
    assert np.allclose(jnp.linalg.norm(got[..., :16], axis=-1),
                       jnp.linalg.norm(x[..., :16], axis=-1), rtol=1e-5)


def _expert_layer(first, count, num=32, d=16, width=8, k=4, seed=5):
    """A DroplessMoELayer holding ``[first, first + count)`` of ``num``
    experts, its weights cut from one uncut set made from the seed."""
    key = jax.random.key(seed)
    ks = jax.random.split(key, 6)
    whole = {
        "router.weight": jax.random.normal(ks[0], (d, num)),
        "experts_gate_up": 0.3 * jax.random.normal(ks[1], (num, d, 2 * width)),
        "experts_down": 0.3 * jax.random.normal(ks[2], (num, width, d)),
        "shared_gate_up.weight": 0.3 * jax.random.normal(ks[3], (d, 2 * width)),
        "shared_down.weight": 0.3 * jax.random.normal(ks[4], (width, d)),
        "shared_gate.weight": jax.random.normal(ks[5], (d, 1))}
    layer = moe.DroplessMoELayer(d, width, num, k, experts_held=(first, count),
                                 shared_hidden=width)
    cut = dict(whole)
    for name in ("experts_gate_up", "experts_down"):
        cut[name] = whole[name][first:first + count]
    for name, p in layer.named_parameters():
        p._set_value(cut[name])
    sizes = {"topk": k, "renorm": True, "first": first, "count": count}
    return layer, whole, cut, sizes


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold 8 of 32 experts each: the routed parts their layers
    give, with the shared expert (which every chip computes alike) counted
    once, add up to what the uncut reference gives for the whole layer."""
    x = jax.random.normal(jax.random.key(9), (3, 20, 16))
    _, whole, _, _ = _expert_layer(0, 32)
    uncut = ref.experts(whole, x, {"topk": 4, "renorm": True, "first": 0,
                                   "count": 32})
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


def _forced(layer, experts_per_token):
    """Route every token to the given experts by the router's weights."""
    d, num = layer.router.weight.shape
    w = np.full((d, num), 0.0, np.float32)
    for rank, e in enumerate(experts_per_token):
        w[:, e] = 5.0 - rank
    layer.router.weight._set_value(jnp.asarray(w))


def test_dropless_when_every_token_chooses_one_held_expert():
    layer, _, cut, sizes = _expert_layer(8, 8)
    _forced(layer, (11, 3, 20, 30))       # one held (11), three absent
    x = jnp.abs(jax.random.normal(jax.random.key(2), (2, 50, 16))) + 0.1
    got = layer(paddle.to_tensor(np.asarray(x)))._value
    cut = dict(cut, **{"router.weight": layer.router.weight._value})
    assert float(jnp.abs(got - ref.experts(cut, x, sizes)).max()) < 1e-5
    counters = dict(zip(moe.ROUTER_COUNTERS,
                        np.asarray(layer.layer_counters._value)))
    # all 100 tokens' pairs for expert 11 were computed: none dropped
    assert counters["rows_routed_here"] == 100
    assert counters["rows_largest_expert"] == 100
    assert counters["row_bound"] == 100 * 4
    assert counters["rows_mean_expert"] == 100 / 8


def _forced_rows_against_the_reference(num):
    """2,000 tokens, top-4 of ``num`` with 8 held, every token forced onto
    two held experts (4,000 rows here): the layer's value and gradients
    against the reference's, and its counters."""
    layer, _, cut, sizes = _expert_layer(8, 8, num=num)
    _forced(layer, (9, 14, 40, 50))
    x = jnp.abs(jax.random.normal(jax.random.key(4), (2, 1000, 16))) + 0.1
    params = {k: p._value for k, p in layer.named_parameters()}
    cut = dict(cut, **{"router.weight": params["router.weight"]})

    def program(p, x):
        return jnp.sum(jnp.sin(functional_call(layer, p,
                                               (paddle.to_tensor(x),))))

    def reference(p, x):
        return jnp.sum(jnp.sin(ref.experts(p, x, sizes)))

    got, (gp, gx) = jax.value_and_grad(program, argnums=(0, 1))(params, x)
    want, (wp, wx) = jax.value_and_grad(reference, argnums=(0, 1))(cut, x)
    assert abs(float(got) - float(want)) < 1e-3 * abs(float(want))
    for name in ("experts_gate_up", "experts_down", "shared_down.weight"):
        scale = float(jnp.abs(wp[name]).max())
        assert float(jnp.abs(gp[name] - wp[name]).max()) < 1e-4 * scale, name
    assert float(jnp.abs(gx - wx).max()) < 1e-4 * float(jnp.abs(wx).max())
    return dict(zip(moe.ROUTER_COUNTERS,
                    np.asarray(layer.layer_counters._value)))


def test_more_rows_than_the_usual_slice_take_more_slices_and_stay_exact():
    """Top-4 of 128 with 8 held: an even router sends 500 rows here, a
    slice is 1,024 (twice that, in whole tiles), the worst case eight
    slices.  The 4,000 rows take four slices, the last not full, and
    value and gradients are the reference's."""
    counters = _forced_rows_against_the_reference(128)
    assert counters["rows_routed_here"] == 4000
    assert counters["row_bound"] == 4 * 1024


def test_every_token_where_it_costs_no_more_than_eight_slices_rows():
    """Top-4 of 64 with 8 held: a slice is 2,048 rows, the worst case
    four, and every token through every held expert 16,000 rows, under
    eight slices': the held experts run on every token under its gate,
    whatever the routing, and value and gradients are the reference's."""
    assert moe._every_token(2000, 4, 8, 64)
    assert not moe._every_token(2000, 4, 8, 128)
    counters = _forced_rows_against_the_reference(64)
    assert counters["rows_routed_here"] == 4000
    assert counters["row_bound"] == 2000 * 8


def test_a_step_with_no_token_routed_here_is_finite():
    layer, _, cut, sizes = _expert_layer(8, 8)
    _forced(layer, (0, 1, 2, 3))          # all absent
    x = jnp.abs(jax.random.normal(jax.random.key(2), (2, 10, 16))) + 0.1
    params = {k: p._value for k, p in layer.named_parameters()}

    def loss(p, x):
        return jnp.sum(functional_call(layer, p, (paddle.to_tensor(x),)) ** 2)

    val, (gp, gx) = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert np.isfinite(float(val))
    for g in list(gp.values()) + [gx]:
        assert bool(jnp.isfinite(g).all())
    assert float(jnp.abs(gp["experts_down"]).max()) == 0.0
    assert np.asarray(layer.layer_counters._value)[0] == 0


@pytest.mark.parametrize("first, count", [(0, 32), (8, 8)])
def test_the_router_trains_where_all_the_experts_are_held(first, count):
    """All k returns of a token make the router's gradient.  A layer that
    holds every expert has them: its router's gradient is the
    reference's.  A layer that holds a part has a part: the k weights are
    constants to its backward pass and the router gets none, while every
    other leaf and the input get theirs."""
    layer, whole, cut, sizes = _expert_layer(first, count)
    x = jax.random.normal(jax.random.key(6), (2, 40, 16))
    params = {k: p._value for k, p in layer.named_parameters()}

    def program(p, x):
        return jnp.sum(jnp.sin(functional_call(layer, p,
                                               (paddle.to_tensor(x),))))

    gp, gx = jax.grad(program, argnums=(0, 1))(params, x)
    wp, wx = jax.grad(lambda p, x: jnp.sum(jnp.sin(ref.experts(
        p, x, sizes))), argnums=(0, 1))(cut, x)
    for name in wp:
        scale = float(jnp.abs(wp[name]).max())
        assert (scale > 0) == (count == 32 or name != "router.weight"), name
        assert float(jnp.abs(gp[name] - wp[name]).max()) <= 1e-4 * scale, name
    assert float(jnp.abs(gx - wx).max()) < 1e-4 * float(jnp.abs(wx).max())


def test_experts_held_has_to_be_a_range_of_the_experts():
    with pytest.raises(ValueError):
        moe.DroplessMoELayer(8, 4, 16, 2, experts_held=(12, 8),
                             shared_hidden=4)


@pytest.mark.parametrize("shape, plan", [
    ((1024, 16, 128), (512, 512, 4, 128)),     # gpt3-1.3b.train-s1024
    ((1024, 16, 64), (512, 512, 8, 128)),      # gpt2-medium.train-s1024
    ((4096, 16, 256), (512, 512, 2, 128)),     # this model's attention layer
])
def test_packed_flash_plans_of_the_cells(shape, plan):
    from paddle_hackathon_tpu.incubate.nn.kernels import \
        flash_attention_packed as fap
    s, heads, head_dim = shape
    assert fap._plan(s, s, heads, head_dim, jnp.bfloat16) == plan


def test_the_new_scopes_leave_a_gpt_steps_census_as_it_was(monkeypatch):
    """The census of the tiny GPT's compiled train step, with the scopes
    this model added and without them (the parent's tuple): the same map."""
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)
    from paddle_hackathon_tpu.observability import programs
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec)
    ids = jnp.zeros((2, 16), jnp.int32)
    with jax.set_mesh(mesh):
        text = step._jitted.lower(
            state["params"], state["opt_state"], state["step"], (ids, ids),
            jax.random.key(0), jnp.float32(1e-4)).compile().as_text()
    now = programs.phase_census(text)
    monkeypatch.setattr(programs, "PHASE_COMPONENTS", (
        "embed", "attn", "mlp", "ln_f", "lm_head", "ce", "clip", "update"))
    monkeypatch.setattr(programs, "PHASE_SUBCOMPONENTS", ())
    before = programs.phase_census(text)
    assert now == before and len(now) > 20
    assert {c for _, c, _ in now.values()} >= {"attn", "mlp", "lm_head",
                                               "update"}


def test_a_nested_scope_reads_apart_from_its_parent():
    from paddle_hackathon_tpu.observability.programs import _phase_of
    stack = "jit(train_step)/transpose(jvp(gdn))/gdn_rule/while/body/dot"
    assert _phase_of(stack) == ("bwd", "gdn/gdn_rule")
    assert _phase_of("jit(train_step)/jvp(moe)/experts/sort") == \
        ("fwd", "moe/experts")
    assert _phase_of("jit(train_step)/jvp(gdn)/dot_general") == ("fwd", "gdn")
    # a part's name outside its parent names nothing
    assert _phase_of("jit(train_step)/jvp(router)/dot") == ("fwd", "")


def test_trains_through_the_sharded_step_and_hands_over_its_counters():
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import qwen3_next_sharding_spec
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    cfg = _cfg()
    paddle.seed(3)
    model = _model(cfg)
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=qwen3_next_sharding_spec, learning_rate=3e-3)
    (ids, labels), = weights.make_batches(3, 1, 4, 64, cfg["vocab_size"])
    losses = []
    for i in range(6):
        state, loss = step(state, ids, labels, jax.random.key(i))
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    counters = get_program_registry().counters("parallel.sharded_train_step")
    assert sorted(counters) == [f"layers.{i}.mlp" for i in range(4)]
    for rows, bound, largest, mean in counters.values():
        assert 0 < rows <= bound == 4 * 64 * 2
        assert largest >= mean == rows / 4
