"""PP-YOLOE detector tests (BASELINE.json driver config #5: conv-heavy
static-graph model; ref PaddleDetection PP-YOLOE, built on the reference's
vision ops — yolo ops / nms in python/paddle/vision/ops.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu.core.tensor import Tensor
from paddle_hackathon_tpu.models.ppyoloe import (PPYOLOE, PPYOLOEConfig,
                                                 ppyoloe_s)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    # small multipliers keep the CPU-side test fast but exercise every
    # block type (CSP backbone stages, PAN neck, decoupled head, DFL)
    return PPYOLOE(PPYOLOEConfig(num_classes=6, depth_mult=0.33,
                                 width_mult=0.25))


def _images(b=2, size=64):
    rng = np.random.RandomState(0)
    return Tensor(jnp.asarray(rng.rand(b, 3, size, size), jnp.float32))


def test_forward_shapes(tiny_model):
    m = tiny_model
    cls_logits, reg_dists = m(_images(2, 64))
    assert len(cls_logits) == len(m.head.strides) == 3
    for lvl, (cl, rd) in enumerate(zip(cls_logits, reg_dists)):
        stride = m.head.strides[lvl]
        h = w = 64 // stride
        assert list(cl.shape) == [2, 6, h, w]
        assert list(rd.shape) == [2, 4 * m.config.reg_max, h, w]


def test_loss_decreases_under_sgd(tiny_model):
    m = tiny_model
    m.train()
    imgs = _images(2, 64)
    gt_boxes = Tensor(jnp.asarray(
        [[[4.0, 4.0, 30.0, 30.0], [10.0, 20.0, 50.0, 60.0]],
         [[8.0, 8.0, 40.0, 40.0], [0.0, 0.0, 0.0, 0.0]]], jnp.float32))
    gt_labels = Tensor(jnp.asarray([[1, 3], [5, 0]], jnp.int32))

    from paddle_hackathon_tpu import optimizer
    opt = optimizer.SGD(learning_rate=0.01, parameters=m.parameters())
    losses = []
    for _ in range(4):
        loss = m.loss(imgs, gt_boxes, gt_labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_gradients_reach_all_submodules(tiny_model):
    m = tiny_model
    m.train()
    imgs = _images(1, 64)
    gt_boxes = Tensor(jnp.asarray([[[4.0, 4.0, 30.0, 30.0]]], jnp.float32))
    gt_labels = Tensor(jnp.asarray([[2]], jnp.int32))
    for p in m.parameters():
        p.clear_grad()
    m.loss(imgs, gt_boxes, gt_labels).backward()
    groups = {"backbone": 0, "neck": 0, "head": 0}
    for name, p in m.named_parameters():
        if p.grad is not None and float(jnp.sum(jnp.abs(p._grad_value))) > 0:
            for g in groups:
                if name.startswith(g):
                    groups[g] += 1
    assert all(v > 0 for v in groups.values()), groups


def test_predict_decodes_and_nms(tiny_model):
    m = tiny_model
    out = m.predict(_images(2, 64), score_threshold=0.0, top_k=10)
    assert len(out) == 2
    for boxes, scores, labels in out:
        n = boxes.shape[0]
        assert n <= 10
        assert list(scores.shape) == [n]
        assert list(labels.shape) == [n]
        if n:
            bv = np.asarray(boxes._value)
            assert (bv[:, 2] >= bv[:, 0]).all()
            assert (bv[:, 3] >= bv[:, 1]).all()


def test_jit_static_forward_matches_eager(tiny_model):
    """The driver config is 'via jit/static path' — compiled forward must
    agree with eager."""
    from paddle_hackathon_tpu import jit
    m = tiny_model
    m.eval()
    imgs = _images(1, 64)
    eager_cls, eager_reg = m(imgs)
    static_forward = jit.to_static(m.forward)
    static_cls, static_reg = static_forward(imgs)
    for a, b in zip(eager_cls, static_cls):
        np.testing.assert_allclose(np.asarray(a._value),
                                   np.asarray(b._value), rtol=1e-4,
                                   atol=1e-5)


def test_ppyoloe_s_factory():
    m = ppyoloe_s(num_classes=3)
    assert m.config.num_classes == 3
    assert m.config.width_mult == 0.50
