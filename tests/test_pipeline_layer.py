"""Generic pipeline segmentation (parallel.PipelineLayer) tests.

The reference's ``PipelineLayer`` segments ANY LayerDesc list across
stages (``parallel_layers/pp_layers.py:162``, shared weights ``:77``).
These tests prove the TPU-native equivalent is a framework feature:
BERT/ERNIE — never hand-wired for pp — pipelines through the generic
desc-list path, composes with dp/mp/ZeRO on the virtual mesh, and matches
the single-device loss trajectory (the reference's hybrid-parallel parity
pattern, ``test_dist_base.py:786``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import parallel
from paddle_hackathon_tpu.core.tensor import Tensor
from paddle_hackathon_tpu.models import (BertForPretraining, bert_config,
                                         bert_mlm_pipeline,
                                         bert_param_sharding_spec)
from paddle_hackathon_tpu.parallel import (LayerDesc, PipelineLayer,
                                           SharedLayerDesc)


def _tiny_cfg(**kw):
    base = dict(num_layers=4, hidden_size=64, num_heads=4, vocab_size=128,
                max_position_embeddings=32, hidden_dropout_prob=0.0,
                attention_dropout_prob=0.0, use_flash_attention=False)
    base.update(kw)
    return bert_config("bert-base-uncased", **base)


def _mlm_data(batch=8, seq=16, vocab=128):
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, vocab, (batch, seq)), jnp.int32)
    raw = r.randint(0, vocab, (batch, seq))
    labels = np.where(r.rand(batch, seq) < 0.15, raw, -100)
    return ids, jnp.asarray(labels, jnp.int32)


def test_segmentation_structure():
    pipe = bert_mlm_pipeline(_tiny_cfg())
    assert len(pipe.pre) == 1          # shared embeddings
    assert len(pipe.blocks) == 4       # the homogeneous encoder run
    assert len(pipe.post) == 2         # mlm transform + vocab bias
    # the tied decode position reuses the pre.0 module (SharedLayerDesc)
    prefixes = [p for p, _, _ in pipe._positions]
    assert prefixes.count("pre.0.") == 2
    spec = pipe.pipeline_stage_spec()
    assert spec["block_prefix"] == "blocks."
    assert spec["num_layers"] == 4


def test_no_homogeneous_run_raises():
    from paddle_hackathon_tpu.nn.layers.common import Linear
    with pytest.raises(ValueError, match="homogeneous"):
        PipelineLayer([LayerDesc(Linear, 4, 8), LayerDesc(Linear, 8, 2)])


def test_forward_matches_bert_pretraining_head():
    """Independent check of the position machinery incl. the tied decode:
    copy the pipeline's params into a BertForPretraining and compare MLM
    logits computed by the two entirely separate forward paths."""
    cfg = _tiny_cfg()
    paddle.seed(5)
    pipe = bert_mlm_pipeline(cfg)
    paddle.seed(99)
    bert = BertForPretraining(cfg)

    mapping = dict(pipe.named_parameters())
    targets = dict(bert.named_parameters())

    def copy(src, dst):
        targets[dst]._set_value(mapping[src]._value)

    for rel in ("word_embeddings.weight", "position_embeddings.weight",
                "token_type_embeddings.weight", "layer_norm.weight",
                "layer_norm.bias"):
        copy(f"pre.0.{rel}", f"bert.embeddings.{rel}")
    for i in range(cfg.num_layers):
        for name in mapping:
            if name.startswith(f"blocks.{i}."):
                copy(name, f"bert.encoder.{i}." + name[len(f"blocks.{i}."):])
    for rel in ("transform.weight", "transform.bias", "layer_norm.weight",
                "layer_norm.bias"):
        copy(f"post.0.{rel}", f"cls.{rel}")
    copy("post.1.bias", "cls.decoder_bias")

    ids, _ = _mlm_data()
    pipe.eval(), bert.eval()
    out_pipe = pipe(Tensor(ids))
    out_bert, _ = bert(Tensor(ids))
    np.testing.assert_allclose(np.asarray(out_pipe._value),
                               np.asarray(out_bert._value),
                               rtol=1e-5, atol=1e-5)


_PP_BASELINE = {}


@pytest.mark.parametrize("mesh_dims,zero", [
    ({"pp": 2, "dp": 2, "mp": 2}, 0),     # the 4-D hybrid composition
    ({"pp": 2, "sharding": 2, "dp": 2}, 3),  # pp x ZeRO-3
    # pp x sp: ring attention runs INSIDE each pipeline stage of the
    # desc-built BERT (the region is manual over pp+sp; the attention
    # mixin detects the already-manual axis)
    ({"pp": 2, "sp": 2, "mp": 2}, 0),
])
def test_bert_pipeline_matches_single_device(mesh_dims, zero):
    """BERT (never hand-wired for pp) pipelines via the generic desc path
    and matches the single-device loss trajectory."""
    ids, labels = _mlm_data()

    def run(md, zs):
        paddle.seed(123)
        pipe = bert_mlm_pipeline(_tiny_cfg())
        n = int(np.prod(list(md.values())))
        mesh = parallel.create_mesh(md, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            pipe, mesh, rule=bert_param_sharding_spec, learning_rate=1e-3,
            zero_stage=zs, grad_clip_norm=None,
            loss_fn=pipe.make_loss_fn() if md.get("pp", 1) == 1 else None)
        out = []
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out

    if "base" not in _PP_BASELINE:
        _PP_BASELINE["base"] = run({"dp": 1}, 0)
    single = _PP_BASELINE["base"]
    pp = run(mesh_dims, zero)
    np.testing.assert_allclose(pp, single, rtol=2e-3)


def test_shared_desc_builds_one_module():
    from paddle_hackathon_tpu.models.bert import BertEmbeddings, BertLayer
    cfg = _tiny_cfg()
    pipe = PipelineLayer([
        SharedLayerDesc("e", BertEmbeddings, cfg),
        LayerDesc(BertLayer, cfg),
        LayerDesc(BertLayer, cfg),
        SharedLayerDesc("e", BertEmbeddings, cfg,
                        forward_func=lambda mod, x: x),
    ])
    # one embedding module registered once; reuse position points at it
    names = [n for n, _ in pipe.named_parameters()]
    assert sum("word_embeddings" in n for n in names) == 1
    assert pipe._positions[0][1] is pipe._positions[-1][1]



def test_pipeline_layer_moe_aux_flows():
    """A desc-built pipeline whose blocks carry an l_aux side channel
    (MoE) feeds the pipeline aux accumulator — the aux term must reach
    the objective (aux_weight=0 gives a different loss)."""
    from paddle_hackathon_tpu.models.gpt import GPTBlock, GPTConfig
    from paddle_hackathon_tpu.nn.functional.loss import fused_softmax_ce_rows
    from paddle_hackathon_tpu.nn.layers.common import Embedding, Linear

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=16,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False, moe_num_experts=4,
                    moe_gate="gshard")

    def ce(logits, labels):
        return jnp.mean(fused_softmax_ce_rows(
            logits.reshape(-1, logits.shape[-1]), labels.reshape(-1)))

    def build(w):
        paddle.seed(7)
        return PipelineLayer([
            LayerDesc(Embedding, 64, 32),
            LayerDesc(GPTBlock, cfg), LayerDesc(GPTBlock, cfg),
            LayerDesc(Linear, 32, 64),
        ], loss_fn=ce, aux_weight=w)

    pipe = build(0.05)
    spec = pipe.pipeline_stage_spec()
    assert spec["layer_aux"] is True

    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 64, (8, 8)), jnp.int32)
    labels = jnp.asarray(r.randint(0, 64, (8, 8)), jnp.int32)

    def first_loss(w):
        pipe = build(w)
        mesh = parallel.create_mesh({"pp": 2, "ep": 2, "mp": 2})
        try:
            step, state = parallel.make_sharded_train_step(
                pipe, mesh, rule=None, learning_rate=1e-3,
                grad_clip_norm=None)
            losses = []
            for i in range(2):
                state, loss = step(state, ids, labels, jax.random.key(0))
                losses.append(float(loss))
        finally:
            parallel.set_mesh(None)
        return losses

    with_aux = first_loss(0.05)
    without = first_loss(0.0)
    assert all(np.isfinite(with_aux)) and with_aux[-1] < with_aux[0]
    assert abs(with_aux[0] - without[0]) > 1e-5   # aux reached the loss
