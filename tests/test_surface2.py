"""Second surface batch: viterbi, PyLayer, incubate graph/segment ops,
distribution wrappers, detection ops, transforms, hermitian FFT."""

import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu.autograd import PyLayer


def test_viterbi_matches_kernel_port():
    def ref_viterbi(pot, trans, lens, bos_eos):
        B, L, n = pot.shape
        scores = np.zeros(B)
        paths = np.zeros((B, L), np.int64)
        for b in range(B):
            ln = lens[b]
            alpha = pot[b, 0].copy()
            if bos_eos:
                alpha = alpha + trans[n - 1]
                if ln == 1:
                    alpha = alpha + trans[n - 2]
            hist = []
            for i in range(1, ln):
                ts = alpha[:, None] + trans
                hist.append(np.argmax(ts, 0))
                alpha = np.max(ts, 0) + pot[b, i]
                if bos_eos and i == ln - 1:
                    alpha = alpha + trans[n - 2]
            scores[b] = alpha.max()
            cur = int(alpha.argmax())
            path = [cur]
            for h in reversed(hist):
                cur = int(h[cur])
                path.append(cur)
            paths[b, :ln] = path[::-1]
        return scores, paths

    rng = np.random.RandomState(7)
    for bos in (True, False):
        B, L, n = 3, 5, 4
        pot = rng.rand(B, L, n).astype(np.float32)
        trans = rng.rand(n, n).astype(np.float32)
        lens = rng.randint(1, L + 1, B).astype(np.int64)
        s, path = paddle.text.viterbi_decode(
            paddle.to_tensor(pot), paddle.to_tensor(trans),
            paddle.to_tensor(lens), bos)
        rs, rp = ref_viterbi(pot, trans, lens, bos)
        np.testing.assert_allclose(s.numpy(), rs, rtol=1e-5)
        np.testing.assert_array_equal(path.numpy(), rp)


def test_pylayer_custom_grad():
    class CubeHalf(PyLayer):
        @staticmethod
        def forward(ctx, x, scale):
            ctx.save_for_backward(x)
            ctx.scale = scale
            return x * x * x * scale

        @staticmethod
        def backward(ctx, gy):
            (x,) = ctx.saved_tensor()
            return gy * 3.0 * x * x * ctx.scale

    x = paddle.to_tensor([2.0, -1.0], stop_gradient=False)
    y = CubeHalf.apply(x, 0.5)
    np.testing.assert_allclose(y.numpy(), [4.0, -0.5])
    (y * paddle.to_tensor([1.0, 2.0])).sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [6.0 * 0.5 * 2, 2 * 3 * 0.5])


def test_pylayer_multi_output():
    class Split(PyLayer):
        @staticmethod
        def forward(ctx, x):
            return x * 2, x * 3

        @staticmethod
        def backward(ctx, g1, g2):
            return g1 * 2 + g2 * 3

    a = paddle.to_tensor([1.0], stop_gradient=False)
    u, v = Split.apply(a)
    (u + 2 * v).sum().backward()
    np.testing.assert_allclose(a.grad.numpy(), [8.0])  # 1*2 + 2*3


def test_segment_ops():
    inc = paddle.incubate
    d = paddle.to_tensor(np.arange(8, dtype=np.float32).reshape(4, 2),
                         stop_gradient=False)
    ids = paddle.to_tensor(np.array([0, 0, 1, 2]))
    np.testing.assert_allclose(inc.segment_sum(d, ids).numpy(),
                               [[2, 4], [4, 5], [6, 7]])
    np.testing.assert_allclose(inc.segment_mean(d, ids).numpy(),
                               [[1, 2], [4, 5], [6, 7]])
    np.testing.assert_allclose(inc.segment_max(d, ids).numpy(),
                               [[2, 3], [4, 5], [6, 7]])
    out = inc.segment_sum(d, ids)
    out.sum().backward()
    np.testing.assert_allclose(d.grad.numpy(), np.ones((4, 2)))


def test_graph_send_recv_pools():
    inc = paddle.incubate
    x = paddle.to_tensor(np.array([[1.0], [2.0], [3.0]], np.float32))
    src = paddle.to_tensor(np.array([0, 1, 2, 0]))
    dst = paddle.to_tensor(np.array([1, 2, 1, 0]))
    np.testing.assert_allclose(
        inc.graph_send_recv(x, src, dst, "sum").numpy(), [[1], [4], [2]])
    np.testing.assert_allclose(
        inc.graph_send_recv(x, src, dst, "mean").numpy(), [[1], [2], [2]])
    np.testing.assert_allclose(
        inc.graph_send_recv(x, src, dst, "max").numpy(), [[1], [3], [2]])


def test_softmax_mask_fuse_upper_triangle_is_causal():
    inc = paddle.incubate
    x = paddle.to_tensor(np.zeros((1, 1, 3, 3), np.float32))
    out = inc.softmax_mask_fuse_upper_triangle(x).numpy()[0, 0]
    np.testing.assert_allclose(out[0], [1, 0, 0], atol=1e-6)
    np.testing.assert_allclose(out[2], [1 / 3] * 3, atol=1e-6)


def test_distribution_independent_and_transformed():
    D = paddle.distribution
    base = D.Normal(paddle.to_tensor([0.0, 0.0]), paddle.to_tensor([1.0, 1.0]))
    ind = D.Independent(base, 1)
    lp = ind.log_prob(paddle.to_tensor([0.5, -0.5]))
    ref = -np.log(2 * np.pi) - 0.25
    np.testing.assert_allclose(float(lp.numpy()), ref, rtol=1e-5)

    td = D.TransformedDistribution(
        D.Normal(paddle.to_tensor([0.0]), paddle.to_tensor([1.0])),
        [D.AffineTransform(paddle.to_tensor([1.0]), paddle.to_tensor([2.0]))])
    lp2 = td.log_prob(paddle.to_tensor([1.0]))
    np.testing.assert_allclose(float(lp2.numpy()),
                               -np.log(2) - 0.5 * np.log(2 * np.pi), rtol=1e-5)


def test_deform_conv2d_zero_offset_equals_conv():
    torch = pytest.importorskip("torch")
    import torch.nn.functional as TF
    V = paddle.vision.ops
    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, 8, 8).astype(np.float32)
    w = rng.randn(6, 4, 3, 3).astype(np.float32)
    off = np.zeros((2, 18, 6, 6), np.float32)
    out = V.deform_conv2d(paddle.to_tensor(x), paddle.to_tensor(off),
                          paddle.to_tensor(w))
    ref = TF.conv2d(torch.tensor(x), torch.tensor(w)).numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-3, atol=1e-4)
    xt = paddle.to_tensor(x, stop_gradient=False)
    wt = paddle.to_tensor(w, stop_gradient=False)
    V.deform_conv2d(xt, paddle.to_tensor(off), wt).sum().backward()
    assert xt.grad is not None and wt.grad is not None


def test_yolo_box_and_loss_shapes():
    V = paddle.vision.ops
    rng = np.random.RandomState(0)
    xb = rng.randn(2, 27, 4, 4).astype(np.float32)
    boxes, scores = V.yolo_box(
        paddle.to_tensor(xb),
        paddle.to_tensor(np.array([[64, 64], [32, 32]], np.int32)),
        [10, 13, 16, 30, 33, 23], 4, 0.01, 16)
    assert boxes.shape == [2, 48, 4] and scores.shape == [2, 48, 4]
    gtb = np.array([[[0.5, 0.5, 0.3, 0.4], [0, 0, 0, 0]]] * 2, np.float32)
    gtl = np.array([[1, 0]] * 2, np.int64)
    loss = V.yolo_loss(paddle.to_tensor(xb), paddle.to_tensor(gtb),
                       paddle.to_tensor(gtl), [10, 13, 16, 30, 33, 23],
                       [0, 1, 2], 4, 0.7, 16)
    assert loss.shape == [2] and np.isfinite(loss.numpy()).all()


def test_generate_and_distribute_proposals():
    V = paddle.vision.ops
    rng = np.random.RandomState(0)
    sc = rng.rand(1, 3, 4, 4).astype(np.float32)
    bd = rng.randn(1, 12, 4, 4).astype(np.float32) * 0.1
    anchors = rng.rand(48, 4).astype(np.float32) * 16
    anchors[:, 2:] += 16
    var = np.ones((48, 4), np.float32)
    rois, rscores, nums = V.generate_proposals(
        paddle.to_tensor(sc), paddle.to_tensor(bd),
        paddle.to_tensor(np.array([[64.0, 64.0]], np.float32)),
        paddle.to_tensor(anchors), paddle.to_tensor(var),
        return_rois_num=True)
    assert int(nums.numpy()[0]) == rois.shape[0] > 0
    outs, restore, nums2 = V.distribute_fpn_proposals(rois, 2, 5, 4, 224)
    assert sum(o.shape[0] for o in outs) == rois.shape[0]
    # restore index is a permutation
    assert sorted(restore.numpy().tolist()) == list(range(rois.shape[0]))


def test_random_transforms_preserve_shape():
    T = paddle.vision.transforms
    img = (np.random.RandomState(0).rand(16, 16, 3) * 255).astype(np.uint8)
    np.random.seed(0)
    for t in [T.BrightnessTransform(0.4), T.ContrastTransform(0.4),
              T.SaturationTransform(0.4), T.HueTransform(0.2),
              T.RandomAffine(15, translate=(0.1, 0.1)),
              T.RandomErasing(prob=1.0), T.RandomPerspective(prob=1.0)]:
        assert np.asarray(t(img)).shape == (16, 16, 3)
    ident = T.affine(img, 0, (0, 0), 1.0, 0)
    np.testing.assert_array_equal(ident, img)


def test_hermitian_fft_roundtrip():
    x = np.random.RandomState(0).randn(4, 4).astype(np.float32)
    ih = paddle.fft.ihfft2(paddle.to_tensor(x)).numpy()
    h = paddle.fft.hfft2(paddle.to_tensor(ih.astype(np.complex64))).numpy()
    np.testing.assert_allclose(h, x, rtol=1e-4, atol=1e-5)


def test_flash_dispatch_is_seqlen_aware():
    import jax.numpy as jnp
    from paddle_hackathon_tpu.nn.functional.attention import (
        scaled_dot_product_attention)
    rng = np.random.RandomState(0)
    q = paddle.to_tensor(rng.randn(1, 64, 2, 8).astype(np.float32))
    # short seq (auto) must take the XLA path and still be correct
    out = scaled_dot_product_attention(q, q, q, is_causal=True)
    assert out.shape == [1, 64, 2, 8]


def test_model_zoo_surface_complete():
    import ast
    try:
        tree = ast.parse(open(
            "/root/reference/python/paddle/vision/models/__init__.py").read())
    except OSError:
        pytest.skip("reference not mounted")
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", None) == "__all__":
                    names = [ast.literal_eval(e) for e in node.value.elts]
    missing = [n for n in names if not hasattr(paddle.vision.models, n)]
    assert missing == []


# one case per model: eager construction and forward compile every
# layer shape by itself (densenet121 alone is a minute on a quiet
# machine), and as one test the four were the suite's longest by far
@pytest.mark.parametrize("ctor", ["densenet121", "squeezenet1_1",
                                  "shufflenet_v2_x0_25", "MobileNetV3Small"])
def test_new_models_forward(ctor):
    paddle.seed(0)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(1, 3, 64, 64).astype(np.float32))
    out = getattr(paddle.vision.models, ctor)(num_classes=7)(x)
    assert out.shape == [1, 7]


def test_static_namespace_surface_complete():
    import ast
    import paddle_hackathon_tpu.static as st
    for path, mod in [("static/__init__.py", st), ("static/nn/__init__.py",
                                                   st.nn)]:
        try:
            tree = ast.parse(open(
                f"/root/reference/python/paddle/{path}").read())
        except OSError:
            pytest.skip("reference not mounted")
        names = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if getattr(t, "id", None) == "__all__":
                        names = [ast.literal_eval(e) for e in node.value.elts]
        assert [n for n in names if not hasattr(mod, n)] == []


def test_utils_dlpack_roundtrip():
    from paddle_hackathon_tpu.utils import dlpack
    t = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
    cap = dlpack.to_dlpack(t)
    back = dlpack.from_dlpack(t._value)  # jax arrays carry __dlpack__
    np.testing.assert_array_equal(back.numpy(), t.numpy())
    with pytest.raises(TypeError):
        dlpack.to_dlpack(np.zeros(3))
    assert cap is not None


def test_utils_unique_name():
    from paddle_hackathon_tpu.utils import unique_name
    a, b = unique_name.generate("fc"), unique_name.generate("fc")
    assert a != b and a.startswith("fc_")
    with unique_name.guard():
        inner = unique_name.generate("fc")
    assert inner == "fc_0"
    with unique_name.guard("pre_"):
        assert unique_name.generate("fc").startswith("pre_fc")


def test_utils_download_local(tmp_path, monkeypatch):
    from paddle_hackathon_tpu.utils import download
    monkeypatch.setattr(download, "WEIGHTS_HOME", str(tmp_path))
    assert download.is_url("https://host/m.pdparams")
    (tmp_path / "m.pdparams").write_bytes(b"weights")
    p = download.get_weights_path_from_url("https://host/m.pdparams")
    assert p.endswith("m.pdparams")
    with pytest.raises(FileNotFoundError):
        download.get_weights_path_from_url("https://host/missing.pdparams")


def test_spectral_norm_power_iteration():
    from paddle_hackathon_tpu import nn
    lin = nn.Linear(8, 5)
    nn.utils.spectral_norm(lin, dim=1)
    x = paddle.to_tensor(np.random.randn(3, 8).astype("float32"))
    for _ in range(25):
        lin(x)
    sigma = np.linalg.svd(lin.weight.numpy(), compute_uv=False)[0]
    np.testing.assert_allclose(sigma, 1.0, atol=1e-3)
    # still trainable through the reparam
    xg = paddle.to_tensor(np.random.randn(3, 8).astype("float32"))
    lin(xg).sum().backward()
    assert lin.weight_orig.grad is not None


def test_static_amp_namespace():
    import paddle_hackathon_tpu.static.amp as samp
    lists = samp.AutoMixedPrecisionLists(custom_white_list=["foo_op"],
                                         custom_black_list=["bar_op"])
    assert "foo_op" in lists.white_list and "bar_op" in lists.black_list
    assert samp.CustomOpLists is samp.AutoMixedPrecisionLists
    with samp.fp16_guard():
        pass
    lin = paddle.nn.Linear(4, 4)
    samp.cast_model_to_fp16(lin)
    assert str(lin.weight.dtype) == "float16"
    assert samp.bf16.decorate_bf16 is not None


def test_fleet_utils_namespace():
    from paddle_hackathon_tpu.distributed import fleet
    assert fleet.utils.recompute is not None
    assert fleet.utils.LocalFS is not None and fleet.utils.HDFSClient is not None
