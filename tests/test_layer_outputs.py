"""What a layer hands the train step beside its output
(``parallel/layer_outputs.py``, ``parallel/moe.py collect_moe_aux``):
the default loss of
``make_sharded_train_step`` adds every layer's weighted auxiliary loss and
returns every layer's counters, and for the models that had them before
(a dense GPT, the capacity-MoE GPT, the dropless expert layers) what it
adds and returns is what it was: the mean cross-entropy alone, plus the
model's MoE weight times the sum of the load-balance terms, and the expert
layers' routing counters under their paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import parallel
from paddle_hackathon_tpu.core.tensor import Tensor
from paddle_hackathon_tpu.models import GPTConfig, GPTForCausalLM
from paddle_hackathon_tpu.nn.functional.loss import fused_softmax_ce_rows
from paddle_hackathon_tpu.nn.layer import Layer
from paddle_hackathon_tpu.parallel.layer_outputs import collect_layer_counters
from paddle_hackathon_tpu.parallel.moe import collect_moe_aux


def _gpt(**moe):
    paddle.seed(3)
    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0, **moe))


def _first_loss(model, ids):
    """The loss the compiled step returns for its first batch."""
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=lambda name, shape: (None,) * len(shape),
        learning_rate=1e-3)
    _, loss = step(state, ids, ids, jax.random.key(0))
    return float(loss)


def _by_hand(model, ids):
    """Mean cross-entropy of the eager forward, and the layers' l_aux."""
    logits = model(Tensor(ids))._value
    ce = float(jnp.mean(fused_softmax_ce_rows(logits, ids)))
    aux = [float(layer.l_aux._value) for layer in model.sublayers()
           if getattr(layer, "l_aux", None) is not None]
    return ce, aux


@pytest.mark.parametrize("moe", [{}, {"moe_num_experts": 4,
                                      "moe_aux_weight": 0.05}],
                         ids=["dense", "capacity_moe"])
def test_the_default_loss_of_the_gpt_models_is_unchanged(moe):
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)),
                      jnp.int32)
    model = _gpt(**moe)
    ce, aux = _by_hand(model, ids)
    assert bool(aux) == bool(moe)
    want = ce + moe.get("moe_aux_weight", 0.0) * sum(aux)
    assert _first_loss(model, ids) == pytest.approx(want, rel=1e-5)
    # layers without a weight of their own share the model's, applied once
    total = collect_moe_aux(model)
    if moe:
        assert float(total) == pytest.approx(0.05 * sum(aux), rel=1e-6)
    else:
        assert total is None
    assert collect_layer_counters(model) == {}


class _Aux(Layer):
    def __init__(self, value, weight=None):
        super().__init__()
        self.l_aux = jnp.float32(value)
        if weight is not None:
            self.aux_weight = weight
        self.layer_counters = jnp.arange(3.0) + value


def test_a_layer_with_its_own_weight_beside_the_moe_ones():
    model = _Aux(0.0)
    model.a = _Aux(2.0)                 # the model's MoE weight, 0.01
    model.b = _Aux(3.0, weight=1.0)     # its own
    model.c = _Aux(5.0, weight=0.5)
    assert float(collect_moe_aux(model)) == pytest.approx(
        0.01 * (0.0 + 2.0) + 3.0 + 2.5)
    counters = collect_layer_counters(model)
    assert sorted(counters) == ["", "a", "b", "c"]
    assert list(np.asarray(counters["c"])) == [5.0, 6.0, 7.0]
    model.l_aux = model.a.l_aux = None
    assert float(collect_moe_aux(model)) == pytest.approx(5.5)


def test_the_expert_layers_counters_reach_the_program_observatory():
    from paddle_hackathon_tpu.models import Qwen3NextConfig, \
        Qwen3NextForCausalLM
    from paddle_hackathon_tpu.observability.programs import \
        get_program_registry
    paddle.seed(4)
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        linear_key_head_dim=8, linear_value_head_dim=8,
        linear_num_key_heads=2, linear_num_value_heads=2, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, experts_held=(0, 4)))
    ids = jnp.zeros((1, 16), jnp.int32)
    _first_loss(model, ids)
    counters = get_program_registry().counters("parallel.sharded_train_step")
    assert sorted(counters) == ["layers.0.mlp", "layers.1.mlp"]
    assert all(len(v) == 4 for v in counters.values())
