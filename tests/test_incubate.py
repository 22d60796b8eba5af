"""incubate surface: Pallas flash attention (interpret mode on CPU), fused
layers, ASP n:m sparsity, functional autograd, LookAhead/ModelAverage.

Mirrors the reference's test style: fused results checked against the
plain composition (ref test_fused_attention_op.py pattern — fused vs
separate-op numerics).
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import incubate, nn, optimizer
from paddle_hackathon_tpu.core.tensor import Tensor


def _sdpa_ref(q, k, v, causal):
    qh = np.swapaxes(q, 1, 2).astype(np.float32)
    kh = np.swapaxes(k, 1, 2).astype(np.float32)
    vh = np.swapaxes(v, 1, 2).astype(np.float32)
    s = np.einsum("bhsd,bhtd->bhst", qh, kh) / np.sqrt(q.shape[-1])
    if causal:
        m = np.tril(np.ones(s.shape[-2:], bool))
        s = np.where(m, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bhst,bhtd->bhsd", p, vh)
    return np.swapaxes(o, 1, 2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    rng = np.random.RandomState(0)
    b, s, h, d = 1, 256, 2, 32
    q = rng.randn(b, s, h, d).astype(np.float32) * 0.3
    k = rng.randn(b, s, h, d).astype(np.float32) * 0.3
    v = rng.randn(b, s, h, d).astype(np.float32)
    out = incubate.nn.functional.flash_attention_bshd(
        Tensor(q), Tensor(k), Tensor(v), causal=causal)
    ref = _sdpa_ref(q, k, v, causal)
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,tol", [("float32", 3e-3), ("bfloat16", 0.1)])
def test_flash_attention_grad_matches_xla(dtype, tol):
    # bf16 runs the kernels' real TPU path (DEFAULT-precision bf16 dots +
    # the p/ds downcasts) which the f32 (HIGHEST-precision) run never
    # executes numerically
    rng = np.random.RandomState(1)
    b, s, h, d = 1, 128, 2, 16
    q0 = rng.randn(b, s, h, d).astype(np.float32) * 0.3
    k0 = rng.randn(b, s, h, d).astype(np.float32) * 0.3
    v0 = rng.randn(b, s, h, d).astype(np.float32)

    grads = {}
    for use_flash in (True, False):
        q = Tensor(jnp.asarray(q0, dtype), stop_gradient=False)
        k = Tensor(jnp.asarray(k0, dtype), stop_gradient=False)
        v = Tensor(jnp.asarray(v0, dtype), stop_gradient=False)
        if use_flash:
            out = incubate.nn.functional.flash_attention_bshd(
                q, k, v, causal=True)
        else:
            out = nn.functional.scaled_dot_product_attention(
                q, k, v, is_causal=True, use_flash=False)
        outf = out.astype("float32")
        (outf * outf).sum().backward()
        grads[use_flash] = tuple(
            np.asarray(t.grad._value, np.float32) for t in (q, k, v))

    for gf, gx in zip(grads[True], grads[False]):
        np.testing.assert_allclose(gf, gx, rtol=tol, atol=tol)


def test_sdpa_routes_to_flash():
    # default flags: use_fused_kernels=True, no mask, no dropout -> flash
    rng = np.random.RandomState(2)
    x = rng.randn(1, 128, 2, 16).astype(np.float32)
    out = nn.functional.scaled_dot_product_attention(
        Tensor(x), Tensor(x), Tensor(x), is_causal=True)
    ref = _sdpa_ref(x, x, x, True)
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)


def test_fused_layer_norm_matches_composition():
    rng = np.random.RandomState(3)
    x = rng.randn(2, 8, 16).astype(np.float32)
    res = rng.randn(2, 8, 16).astype(np.float32)
    bias = rng.randn(16).astype(np.float32)
    w = rng.rand(16).astype(np.float32) + 0.5
    b = rng.randn(16).astype(np.float32)
    out, res_out = incubate.nn.functional.fused_layer_norm(
        Tensor(x), Tensor(w), Tensor(b), residual=Tensor(res),
        bias=Tensor(bias), dropout_rate=0.0)
    h = x + bias + res
    mu = h.mean(-1, keepdims=True)
    var = h.var(-1, keepdims=True)
    ref = (h - mu) / np.sqrt(var + 1e-5) * w + b
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(res_out.numpy(), h, rtol=1e-6, atol=1e-6)


def test_fused_encoder_layer_runs_and_backprops():
    layer = incubate.nn.FusedTransformerEncoderLayer(
        d_model=32, nhead=4, dim_feedforward=64, dropout_rate=0.0)
    x = Tensor(np.random.randn(2, 16, 32).astype(np.float32),
               stop_gradient=False)
    out = layer(x)
    assert out.shape == [2, 16, 32]
    out.sum().backward()
    for _, p in layer.named_parameters():
        assert p.grad is not None


def test_fused_multi_transformer():
    m = incubate.nn.FusedMultiTransformer(32, 4, 64, num_layers=2)
    x = Tensor(np.random.randn(2, 8, 32).astype(np.float32))
    assert m(x).shape == [2, 8, 32]


def test_asp_prune_and_decorate():
    lin = nn.Linear(16, 8)
    incubate.asp.prune_model(lin, n=2, m=4)
    w = lin.weight.numpy()
    # every group of 4 along the last axis has exactly 2 zeros
    g = w.reshape(16, 2, 4)
    nz = (g != 0).sum(-1)
    assert (nz <= 2).all()
    assert abs(incubate.asp.calculate_density(lin.weight) - 0.5) < 1e-6

    opt = incubate.asp.decorate(
        optimizer.SGD(learning_rate=0.1, parameters=lin.parameters()))
    x = Tensor(np.random.randn(4, 16).astype(np.float32))
    lin(x).sum().backward()
    opt.step()
    w2 = lin.weight.numpy()
    assert (w2[w == 0] == 0).all()  # pruned entries stayed zero
    assert (w2 != w).any()          # but training actually moved weights


def test_functional_jvp_vjp():
    def f(x):
        return (x * x).sum()

    x = Tensor(np.arange(4, dtype=np.float32))
    _, tangent = incubate.autograd.jvp(f, [x])
    assert float(tangent.numpy()) == pytest.approx(2 * (0 + 1 + 2 + 3))
    _, grads = incubate.autograd.vjp(f, [x])
    np.testing.assert_allclose(grads.numpy(), 2 * np.arange(4), rtol=1e-6)


def test_jacobian_hessian():
    def f(x):
        return x * x

    x = Tensor(np.array([1.0, 2.0, 3.0], np.float32))
    J = incubate.autograd.Jacobian(f, [x])
    np.testing.assert_allclose(np.asarray(J[:].numpy()),
                               np.diag([2.0, 4.0, 6.0]), rtol=1e-6)

    def g(x):
        return (x * x * x).sum()

    H = incubate.autograd.Hessian(g, [x])
    np.testing.assert_allclose(np.asarray(H[:].numpy()),
                               np.diag([6.0, 12.0, 18.0]), rtol=1e-6)


def test_lookahead_and_model_average():
    lin = nn.Linear(4, 2)
    inner = optimizer.SGD(learning_rate=0.1, parameters=lin.parameters())
    opt = incubate.LookAhead(inner, alpha=0.5, k=2)
    x = Tensor(np.ones((2, 4), np.float32))
    for _ in range(4):
        lin(x).sum().backward()
        opt.step()
        opt.clear_grad()

    ma = incubate.ModelAverage(parameters=lin.parameters())
    w_before = lin.weight.numpy().copy()
    ma.step()
    lin.weight._set_value(lin.weight._value + 1.0)
    ma.step()
    with ma.apply():
        np.testing.assert_allclose(lin.weight.numpy(), w_before + 0.5,
                                   rtol=1e-6)
    np.testing.assert_allclose(lin.weight.numpy(), w_before + 1.0, rtol=1e-6)


def test_flash_attention_dropout():
    """In-kernel attention dropout: deterministic per seed, unbiased vs the
    no-dropout output, and the backward regenerates the identical mask
    (finite-difference check through the custom_vjp)."""
    import jax
    from paddle_hackathon_tpu.incubate.nn.kernels import flash_attention as fa

    rng = np.random.RandomState(0)
    bh, s, d = 2, 128, 16
    q = jnp.asarray(rng.randn(bh, s, d) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(bh, s, d) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(bh, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    seed1 = jnp.asarray([7], jnp.int32)
    seed2 = jnp.asarray([8], jnp.int32)
    o1 = fa.flash_attention_bhd(q, k, v, True, scale, 0.2, seed1)
    o1b = fa.flash_attention_bhd(q, k, v, True, scale, 0.2, seed1)
    o2 = fa.flash_attention_bhd(q, k, v, True, scale, 0.2, seed2)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() > 1e-4

    base = np.asarray(fa.flash_attention_bhd(q, k, v, True, scale))
    acc = np.zeros_like(base)
    n_seeds = 24
    for i in range(n_seeds):
        acc += np.asarray(fa.flash_attention_bhd(
            q, k, v, True, scale, 0.2, jnp.asarray([i], jnp.int32)))
    # dropout is unbiased on the attention average
    err = np.abs(acc / n_seeds - base).mean() / (np.abs(base).mean() + 1e-9)
    assert err < 0.15, f"dropout bias too large: {err}"

    # fwd/bwd mask consistency: analytic grad == finite differences
    def loss(q_, k_, v_):
        o = fa.flash_attention_bhd(q_, k_, v_, True, scale, 0.3, seed1)
        return jnp.sum(o * o)

    g_q, g_k, g_v = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    eps = 1e-3
    for (arr, g, name) in ((q, g_q, "q"), (k, g_k, "k"), (v, g_v, "v")):
        idx = (1, 64, 3)
        pert = np.zeros(arr.shape, np.float32)
        pert[idx] = eps
        f1 = float(loss(jnp.asarray(np.asarray(arr) + pert), k, v)) \
            if name == "q" else \
            float(loss(q, jnp.asarray(np.asarray(arr) + pert), v)) \
            if name == "k" else \
            float(loss(q, k, jnp.asarray(np.asarray(arr) + pert)))
        f0 = float(loss(q, k, v))
        fd = (f1 - f0) / eps
        np.testing.assert_allclose(float(g[idx]), fd, rtol=0.05, atol=0.05)


def test_flash_dropout_mask_decorrelated_across_heads():
    """Masks must differ across the batch*head index even at shifted
    positions (a mixing bug once made head b row r equal head b+1 row
    r-1)."""
    from paddle_hackathon_tpu.incubate.nn.kernels.flash_attention import (
        _dropout_keep)
    import jax.numpy as jnp2

    seed = jnp2.asarray([123], jnp2.int32)[0]
    n = 64
    q = jnp2.arange(n, dtype=jnp2.int32)[:, None] * jnp2.ones(
        (1, n), jnp2.int32)
    k = jnp2.arange(n, dtype=jnp2.int32)[None, :] * jnp2.ones(
        (n, 1), jnp2.int32)
    m0 = np.asarray(_dropout_keep(seed, jnp2.int32(0), q, k, 0.5))
    m1 = np.asarray(_dropout_keep(seed, jnp2.int32(1), q, k, 0.5))
    assert (m0 != m1).mean() > 0.3          # independent-ish
    assert (m0[1:, :] != m1[:-1, :]).mean() > 0.3  # not a shifted copy


def test_kernel_autotune_cache():
    """incubate.autotune kernel tuning: candidates measured once, winner
    cached and used by _block_sizes (ref phi/kernels/autotune)."""
    from paddle_hackathon_tpu.core import autotune as at
    from paddle_hackathon_tpu.incubate.nn.kernels import flash_attention as fa

    at.kernel_cache.clear()
    ret = incubate.autotune({"kernel": {"enable": True,
                                        "tuning_range": [0, 100]}})
    assert ret is None  # reference parity: set_config returns None
    st = incubate.autotune_status()
    assert st["config"]["kernel"]["enable"]

    calls = []

    def measure(cand):
        calls.append(cand)
        return 0.5 if cand == (256, 256) else 1.0

    best = at.tune(("k", 1), [(512, 512), (256, 256), (128, 128)], measure)
    assert best == (256, 256) and len(calls) == 3
    # second lookup: cache hit, no re-measure
    best2 = at.tune(("k", 1), [(512, 512)], measure)
    assert best2 == (256, 256) and len(calls) == 3

    # a cached winner overrides _block_sizes for that signature
    at.kernel_cache.put(fa._tune_key(512, 512, jnp.float32), (128, 128))
    assert fa._block_sizes(512, 512, jnp.float32) == (128, 128)
    # other signatures keep the default
    assert fa._block_sizes(1024, 1024, jnp.bfloat16) == (1024, 1024)

    # failing candidates are skipped; default wins when all fail
    def boom(c):
        raise RuntimeError("no")
    assert at.tune(("k", 2), [(1, 1)], boom, default=(9, 9)) == (9, 9)

    incubate.autotune({"kernel": {"enable": False}})
    at.kernel_cache.clear()


def test_autotune_eager_window(monkeypatch):
    """maybe_autotune gating: no-op under the interpreter / outside the
    tuning window; enabling tuning resets the step counter (so enabling
    mid-training still opens a window)."""
    from paddle_hackathon_tpu.core import autotune as at
    from paddle_hackathon_tpu.incubate.nn.kernels import flash_attention as fa

    at.kernel_cache.clear()
    monkeypatch.setattr(fa, "_interpret", lambda: True)  # any backend
    incubate.autotune({"kernel": {"enable": True, "tuning_range": [0, 2]}})
    q = jnp.ones((2, 128, 16), jnp.float32)
    fa.maybe_autotune(q, q, q, True, 0.25)   # interpreter -> no measuring
    assert at.kernel_cache.size() == 0
    for _ in range(5):
        at.step()
    assert not at.in_tuning_window()
    # re-enabling resets the counter: the window reopens
    incubate.autotune({"kernel": {"enable": True, "tuning_range": [0, 2]}})
    assert at.in_tuning_window()
    incubate.autotune({"kernel": {"enable": False}})


def test_flash_attention_causal_cross_lengths():
    """skv != sq with causal=True: the diagonal-clamped index maps must stay
    in range (regression: the q-block map could run past n_q for long kv)."""
    from paddle_hackathon_tpu.incubate.nn.kernels import flash_attention as fa
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    bh, sq, skv, d = 2, 256, 512, 32
    q = jnp.asarray(rng.randn(bh, sq, d), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(bh, skv, d), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(bh, skv, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def ref(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        mask = (jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :])
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v)

    out = fa.flash_attention_bhd(q, k, v, True, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    g1 = jax.grad(loss(lambda q, k, v: fa.flash_attention_bhd(
        q, k, v, True, scale)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
    # keys past the causal horizon get exactly zero grad
    assert float(jnp.max(jnp.abs(g1[1][:, sq:, :]))) == 0.0


class TestPackedFlashAttention:
    """flash_attention_packed: the projection-native (b, s, 3*H*D) kernel
    family (no head split/merge copies; ~17% e2e on gpt2-small-class
    training vs the bhd kernels)."""

    def _ref(self, qkv, H, causal=True):
        import jax
        b, s, hd3 = qkv.shape
        hd = hd3 // 3
        D = hd // H
        x = np.asarray(qkv, np.float32)
        q, k, v = x[..., :hd], x[..., hd:2 * hd], x[..., 2 * hd:]
        q = q.reshape(b, s, H, D).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, H, D).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, H, D).transpose(0, 2, 1, 3)
        sc = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        if causal:
            sc = np.where(np.tril(np.ones((s, s), bool)), sc, -1e30)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        o = np.einsum("bhqk,bhkd->bhqd", p, v)
        return o.transpose(0, 2, 1, 3).reshape(b, s, hd)

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        rng = np.random.RandomState(0)
        B, S, H, D = 2, 256, 4, 32
        qkv = jnp.asarray(rng.randn(B, S, 3 * H * D) * 0.3, jnp.bfloat16)
        out = fap.flash_attention_packed(qkv, H, causal, 1.0 / np.sqrt(D))
        ref = self._ref(qkv, H, causal)
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=0.05, atol=0.02)

    def test_grad_matches_reference(self):
        import jax
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        rng = np.random.RandomState(1)
        B, S, H, D = 1, 256, 4, 32
        qkv = jnp.asarray(rng.randn(B, S, 3 * H * D) * 0.3, jnp.bfloat16)

        def ref_j(a):
            b, s, hd3 = a.shape
            hd = hd3 // 3
            x = a.astype(jnp.float32)
            q, k, v = x[..., :hd], x[..., hd:2 * hd], x[..., 2 * hd:]
            q = q.reshape(B, S, H, D).transpose(0, 2, 1, 3)
            k = k.reshape(B, S, H, D).transpose(0, 2, 1, 3)
            v = v.reshape(B, S, H, D).transpose(0, 2, 1, 3)
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
            sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -1e30)
            import jax as _j
            o = jnp.einsum("bhqk,bhkd->bhqd", _j.nn.softmax(sc, -1), v)
            return o.transpose(0, 2, 1, 3).reshape(B, S, hd)

        g1 = jax.grad(lambda a: jnp.sum(fap.flash_attention_packed(
            a, H, True, 1.0 / np.sqrt(D)).astype(jnp.float32) ** 2))(qkv)
        g2 = jax.grad(lambda a: jnp.sum(
            ref_j(a).astype(jnp.float32) ** 2))(qkv)
        np.testing.assert_allclose(np.asarray(g1, np.float32),
                                   np.asarray(g2, np.float32),
                                   rtol=0.1, atol=0.05)

    def test_gpt_attention_packed_matches_bhd_path(self):
        """The GPT attention fast path must agree with the (b,s,h,d)
        composition it replaces."""
        from paddle_hackathon_tpu.models.gpt import GPTAttention, GPTConfig
        paddle.seed(0)
        cfg = GPTConfig(hidden_size=128, num_heads=4, num_layers=1,
                        max_position_embeddings=1024,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        attn = GPTAttention(cfg)
        attn.eval()
        x = Tensor(jnp.asarray(
            np.random.RandomState(0).randn(2, 1024, 128) * 0.3,
            jnp.bfloat16))
        # force both paths on the same weights
        attn.use_flash = True
        assert attn._packed_flash_ok(Tensor(jnp.zeros(
            (2, 1024, 384), jnp.bfloat16)), 1024)
        out_fast = attn(x)
        attn.use_flash = False
        out_ref = attn(x)
        np.testing.assert_allclose(
            np.asarray(out_fast._value, np.float32),
            np.asarray(out_ref._value, np.float32), rtol=0.1, atol=0.05)

    def test_dropout_deterministic_and_backward_consistent(self):
        import jax
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        rng = np.random.RandomState(2)
        B, S, H, D = 1, 128, 4, 32
        qkv = jnp.asarray(rng.randn(B, S, 3 * H * D) * 0.3, jnp.bfloat16)
        seed = jnp.asarray([1234], jnp.int32)
        o1 = fap.flash_attention_packed(qkv, H, True, 0.18, 0.3, seed)
        o2 = fap.flash_attention_packed(qkv, H, True, 0.18, 0.3, seed)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        o3 = fap.flash_attention_packed(qkv, H, True, 0.18, 0.3,
                                        jnp.asarray([99], jnp.int32))
        assert np.abs(np.asarray(o1, np.float32)
                      - np.asarray(o3, np.float32)).max() > 0
        # grad executes (mask regenerated in backward, not stored)
        g = jax.grad(lambda a: jnp.sum(fap.flash_attention_packed(
            a, H, True, 0.18, 0.3, seed).astype(jnp.float32) ** 2))(qkv)
        assert np.isfinite(np.asarray(g, np.float32)).all()

    @staticmethod
    def _with_plan(monkeypatch, plan):
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        monkeypatch.setattr(fap, "_plan", lambda *a, **k: plan)
        return fap

    @staticmethod
    def _out_and_grad(fap, qkv, H, scale, dropout=0.0, seed=None):
        import jax

        def loss(a):
            o = fap.flash_attention_packed(a, H, True, scale, dropout, seed)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        (_, out), grad = jax.value_and_grad(loss, has_aux=True)(qkv)
        return (np.asarray(out, np.float32), np.asarray(grad, np.float32))

    # (s, H, D, block, strip): diagonal cells with 1, 2 and 3 kv blocks a
    # row, strips of a half and of a quarter of the block, both head dims
    @pytest.mark.parametrize("S,H,D,block,strip", [
        (128, 2, 64, 128, 32),
        (512, 1, 64, 256, 128),     # lane-tile strips: the folded reductions
        (384, 1, 128, 128, 32),
    ])
    def test_strips_match_whole_tile_and_reference(self, monkeypatch, S, H,
                                                   D, block, strip):
        """A diagonal cell run as trapezoid strips leaves out exactly
        what the causal mask zeroes: forward and gradient agree with the
        whole-tile masked body to rounding, and with float32 attention."""
        import jax
        rng = np.random.RandomState(S + D)
        qkv = jnp.asarray(rng.randn(1, S, 3 * H * D) * 0.3, jnp.bfloat16)
        scale = 1.0 / np.sqrt(D)
        no_seed = jnp.zeros((1,), jnp.int32)
        fap = self._with_plan(monkeypatch, (block, block, H, strip))
        out, grad = self._out_and_grad(fap, qkv, H, scale)
        _, lse = fap._fwd(qkv, no_seed, heads=H, causal=True, sm_scale=scale,
                          dropout_p=0.0, plan=(block, block, H, strip),
                          interpret=True)
        fap = self._with_plan(monkeypatch, (block, block, H, 0))
        out_w, grad_w = self._out_and_grad(fap, qkv, H, scale)
        _, lse_w = fap._fwd(qkv, no_seed, heads=H, causal=True, sm_scale=scale,
                            dropout_p=0.0, plan=(block, block, H, 0),
                            interpret=True)
        # float32 statistics to float32 rounding; bf16 results to an ulp
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_w),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out, out_w, rtol=2 ** -7, atol=1e-3)
        np.testing.assert_allclose(grad, grad_w, rtol=2 ** -7, atol=2e-3)
        np.testing.assert_allclose(out, self._ref(qkv, H), rtol=0.05,
                                   atol=0.02)

        def ref_loss(a):
            x = a.astype(jnp.float32)
            q, k, v = (x[..., i * H * D:(i + 1) * H * D].reshape(
                1, S, H, D).transpose(0, 2, 1, 3) for i in range(3))
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -1e30)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
            return jnp.sum(o ** 2)
        np.testing.assert_allclose(
            grad, np.asarray(jax.grad(ref_loss)(qkv), np.float32),
            rtol=0.1, atol=0.05)

    @staticmethod
    def _q_major_forward(qkv, H, scale, causal, block):
        """The row-major forward this kernel replaced (PR 27's body),
        restated in plain jnp: score tiles (q, kv), row maximum and row
        sum across the kv axis, the accumulator (q, D), block by block
        with the kernel's roundings (q scaled in bf16, float32 scores and
        statistics, p cast to bf16 for the PV product).  ``(out, lse)``
        with lse (b, H, s)."""
        b, s, hd3 = qkv.shape
        D = hd3 // 3 // H
        q, k, v = (qkv[..., i * H * D:(i + 1) * H * D].reshape(
            b, s, H, D).transpose(0, 2, 1, 3) for i in range(3))
        q = q * jnp.asarray(scale, q.dtype)
        m = jnp.full((b, H, s, 1), -1e30, jnp.float32)
        l = jnp.zeros((b, H, s, 1), jnp.float32)
        acc = jnp.zeros((b, H, s, D), jnp.float32)
        q_pos = jnp.arange(s)[:, None]
        for k0 in range(0, s, block):
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k[:, :, k0:k0 + block],
                            preferred_element_type=jnp.float32)
            if causal:
                sc = jnp.where(q_pos >= k0 + jnp.arange(block)[None, :],
                               sc, -1e30)
            m_next = jnp.maximum(m, sc.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_next)
            p = jnp.exp(sc - m_next)
            if causal:          # the kernel skips cells above the diagonal
                p = jnp.where(q_pos >= k0, p, 0.0)
                m_next = jnp.where(q_pos >= k0, m_next, m)
                alpha = jnp.where(q_pos >= k0, alpha, 1.0)
            l = l * alpha + p.sum(-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(qkv.dtype),
                v[:, :, k0:k0 + block], preferred_element_type=jnp.float32)
            m = m_next
        out = (acc / l).astype(qkv.dtype).transpose(0, 2, 1, 3)
        return (np.asarray(out.reshape(b, s, H * D), np.float32),
                np.asarray((m + jnp.log(l))[..., 0]))

    def _ref_lse(self, qkv, H, scale, causal):
        b, s, hd3 = qkv.shape
        D = hd3 // 3 // H
        x = np.asarray(qkv, np.float32)
        q, k = (x[..., i * H * D:(i + 1) * H * D].reshape(
            b, s, H, D).transpose(0, 2, 1, 3) for i in range(2))
        sc = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
        top = sc.max(-1)
        return top + np.log(np.exp(sc - top[..., None]).sum(-1))

    # (s, H, D, plan or None for ``_plan``'s own, causal): 1, 2 and 3 kv
    # blocks a row, both head dims, strips and whole tiles, the plans the
    # benchmark's cells run (512-edge, four 128-row strips) and a 256-edge
    # plan (whole-tile diagonal body)
    @pytest.mark.parametrize("S,H,D,plan,causal", [
        (128, 2, 64, (128, 128, 2, 32), True),
        (512, 2, 64, (256, 256, 2, 128), True),
        (384, 1, 128, (128, 128, 1, 32), True),
        (512, 2, 64, None, True),           # (512, 512, 2, 128): one cell
        (1024, 1, 128, None, True),         # a whole cell under the diagonal
        (768, 2, 64, None, True),           # (256, 256, 2, 0), 3 kv blocks
        (256, 2, 64, (128, 128, 2, 0), False),
        (512, 1, 128, None, False),         # one whole 512-edge tile
    ], ids=["strips-1kv-d64", "strips-2kv-d64", "strips-3kv-d128",
            "cell-plan-1kv-d64", "cell-plan-2kv-d128", "edge256-3kv-d64",
            "noncausal-2kv-d64", "noncausal-1kv-d128"])
    def test_kv_major_forward_matches_row_major_and_reference(
            self, monkeypatch, S, H, D, plan, causal):
        """The forward's scores are kv-major and its accumulator
        transposed; ``out`` AND ``lse`` (the backward kernels read it)
        equal the row-major body's to rounding and float32 attention's."""
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        if plan is None:
            plan = fap._plan(S, S, H, D, jnp.bfloat16)
        assert plan[0] == plan[1] and S % plan[0] == 0
        rng = np.random.RandomState(S + D + H)
        qkv = jnp.asarray(rng.randn(1, S, 3 * H * D) * 0.5, jnp.bfloat16)
        scale = 1.0 / np.sqrt(D)
        out, lse = fap._fwd(qkv, jnp.zeros((1,), jnp.int32), heads=H,
                            causal=causal, sm_scale=scale, dropout_p=0.0,
                            plan=plan, interpret=True)
        out, lse = np.asarray(out, np.float32), np.asarray(lse)
        assert lse.shape == (1, H, 8, S)        # the stored layout
        assert (lse == lse[:, :, :1]).all()
        out_q, lse_q = self._q_major_forward(qkv, H, scale, causal, plan[1])
        np.testing.assert_allclose(lse[:, :, 0], lse_q, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out, out_q, rtol=2 ** -7, atol=1e-3)
        np.testing.assert_allclose(out, self._ref(qkv, H, causal),
                                   rtol=0.05, atol=0.02)
        np.testing.assert_allclose(
            lse[:, :, 0], self._ref_lse(qkv, H, scale, causal), atol=0.03)

    def test_kv_major_strips_keep_statistics_finite(self):
        """The q columns at the head of a diagonal cell are outside every
        strip but the first, and logits of +-60 put every masked and many
        live entries beyond exp's range: no maximum, sum or alpha may
        turn NaN or inf, in the first kv block of a row or a later one."""
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        S, H, D = 256, 2, 64
        rng = np.random.RandomState(11)
        qkv = np.asarray(rng.randn(1, S, 3 * H * D) * 3.0, np.float32)
        qkv[0, :, :8] = 30.0                    # one outlier direction
        qkv = jnp.asarray(qkv, jnp.bfloat16)
        out, lse = fap._fwd(qkv, jnp.zeros((1,), jnp.int32), heads=H,
                            causal=True, sm_scale=0.125, dropout_p=0.0,
                            plan=(128, 128, H, 32), interpret=True)
        out, lse = np.asarray(out, np.float32), np.asarray(lse)
        assert np.isfinite(out).all() and np.isfinite(lse).all()
        np.testing.assert_allclose(
            lse[:, :, 0], self._ref_lse(qkv, H, 0.125, True),
            rtol=2e-3, atol=0.05)
        # row 0 sees key 0 alone: its output is v[0], its lse the one logit
        v0 = np.asarray(qkv, np.float32)[0, 0, 2 * H * D:]
        np.testing.assert_allclose(out[0, 0], v0, rtol=2 ** -7)

    @staticmethod
    def _backward_with_xla_delta(qkv, out, lse, do, H, scale, causal,
                                 dropout, seed):
        """The backward this PR's parent ran, restated whole in plain
        jnp with the kernels' roundings: ``delta`` by the formula that
        stood in ``_bwd`` (float32 reshape, product, sum over head dim),
        p from the stored lse, q scaled in the operand dtype, p and ds
        cast to it for the accumulating products.  ``(dqkv, delta)`` with
        delta (b, H, s)."""
        from paddle_hackathon_tpu.incubate.nn.kernels.flash_attention \
            import _dropout_keep
        b, s, hd3 = qkv.shape
        D = hd3 // 3 // H
        f32 = jnp.float32

        def heads(x):
            return x.reshape(b, s, H, D).transpose(0, 2, 1, 3)
        q, k, v = (heads(qkv[..., i * H * D:(i + 1) * H * D])
                   for i in range(3))
        do_h, out_h = heads(do), heads(out)
        delta = jnp.sum(do_h.astype(f32) * out_h.astype(f32), axis=-1)
        qs = q * jnp.asarray(scale, q.dtype)
        st = jnp.einsum("bhqd,bhkd->bhqk", qs, k, preferred_element_type=f32)
        p = jnp.exp(st - lse[:, :, 0, :, None])
        if causal:
            p = jnp.where(jnp.tril(jnp.ones((s, s), bool)), p, 0.0)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_h, v,
                        preferred_element_type=f32)
        p_v = p
        if dropout:
            bh = (jnp.arange(b)[:, None] * H + jnp.arange(H)[None, :])
            keep = _dropout_keep(
                seed[0], bh[:, :, None, None].astype(jnp.int32),
                jnp.arange(s, dtype=jnp.int32)[None, None, :, None],
                jnp.arange(s, dtype=jnp.int32)[None, None, None, :],
                1.0 - dropout)
            p_v = jnp.where(keep, p / (1.0 - dropout), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout), 0.0)
        ds = (p * (dp - delta[..., None])).astype(q.dtype)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p_v.astype(q.dtype), do_h,
                        preferred_element_type=f32)
        dq = jnp.einsum("bhqk,bhkd->bhqd", ds,
                        k * jnp.asarray(scale, k.dtype),
                        preferred_element_type=f32)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qs,
                        preferred_element_type=f32)
        dqkv = jnp.concatenate(
            [x.astype(qkv.dtype).transpose(0, 2, 1, 3).reshape(b, s, H * D)
             for x in (dq, dk, dv)], axis=-1)
        return np.asarray(dqkv, np.float32), np.asarray(delta)

    # (s, H, D, plan or None for ``_plan``'s own, causal, dropout, dtype):
    # the dq kernel computes delta in the first step of a q row from the
    # dO and O blocks it holds and every cell of the row's sweep (and the
    # dkdv kernel) reads it back, so the cases span 1, 2 and 3 kv blocks
    # a row
    @pytest.mark.parametrize("S,H,D,plan,causal,dropout,dtype", [
        (1024, 2, 64, None, True, 0.0, jnp.bfloat16),
        (1024, 1, 128, None, True, 0.0, jnp.bfloat16),
        (768, 2, 64, None, True, 0.0, jnp.bfloat16),
        (256, 2, 64, (128, 128, 2, 0), False, 0.0, jnp.bfloat16),
        (256, 2, 64, (128, 128, 2, 32), True, 0.3, jnp.bfloat16),
        (256, 6, 64, None, True, 0.0, jnp.bfloat16),
        (256, 2, 64, None, True, 0.0, jnp.float16),
    ], ids=["strips-d64", "strips-d128", "edge256-whole-tile", "noncausal",
            "dropout", "group6", "float16"])
    def test_delta_is_computed_in_the_dq_kernel(self, monkeypatch, S, H, D,
                                                plan, causal, dropout, dtype):
        """delta = rowsum(dO * O) is born inside the dq kernel, in the
        statistics' layout: a float32 sum of float32 products of the
        operands, to float32 rounding (not to bf16), and the gradients
        through ``flash_attention_packed`` are the ones the parent's
        XLA-side formula gave."""
        import jax
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        if plan is None:
            plan = fap._plan(S, S, H, D, dtype)
        rng = np.random.RandomState(S + H + D)
        qkv = jnp.asarray(rng.randn(2, S, 3 * H * D) * 0.5, dtype)
        do = jnp.asarray(rng.randn(2, S, H * D) * 0.5, dtype)
        scale = 1.0 / np.sqrt(D)
        seed = jnp.asarray([77], jnp.int32)
        statics = dict(heads=H, causal=causal, sm_scale=scale,
                       dropout_p=dropout, plan=plan, interpret=True)
        out, lse = fap._fwd(qkv, seed, **statics)
        dqkv, delta = fap._bwd(qkv, out, lse, seed, do, **statics)
        want_dqkv, want_delta = self._backward_with_xla_delta(
            qkv, out, lse, do, H, scale, causal, dropout, seed)
        delta = np.asarray(delta)
        assert delta.shape == (2, H, 8, S) and delta.dtype == np.float32
        assert (delta == delta[:, :, :1]).all()
        # 64 or 128 products of magnitude 0.05 a row: float32 rounding of
        # the sum is 1e-7; a bf16 product or sum would miss by 1e-3
        np.testing.assert_allclose(delta[:, :, 0], want_delta,
                                   rtol=1e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(dqkv, np.float32), want_dqkv,
                                   rtol=2 ** -7, atol=2e-3)

        # and through the custom_vjp, for the cotangent of sum(o * w)
        self._with_plan(monkeypatch, plan)
        grad = jax.grad(lambda a: jnp.sum(
            fap.flash_attention_packed(a, H, causal, scale, dropout, seed)
            .astype(jnp.float32) * do.astype(jnp.float32)))(qkv)
        np.testing.assert_allclose(np.asarray(grad, np.float32), want_dqkv,
                                   rtol=2 ** -7, atol=2e-3)

    def test_forward_tile_helpers(self):
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        a = jnp.arange(8.0).reshape(1, 8)
        b = jnp.full((1, 4), 10.0)
        assert fap._join_trailing(None, a, jnp.add) is a
        np.testing.assert_array_equal(
            fap._join_trailing(a, b, jnp.add),
            [[0, 1, 2, 3, 14, 15, 16, 17]])
        np.testing.assert_array_equal(
            fap._join_trailing(a, a, jnp.maximum), a)
        x = jnp.asarray(np.random.RandomState(0).randn(16, 8), jnp.float32)
        y = jnp.asarray(np.random.RandomState(1).randn(16, 4), jnp.float32)
        np.testing.assert_allclose(fap._dot(x, y, 0, a_dim=0),
                                   np.asarray(x).T @ y, rtol=1e-5, atol=1e-5)

    def test_strips_keep_dropout_masks_in_step(self, monkeypatch):
        """With strips the three kernels still draw one mask from (seed,
        head, global q, global k): every result equals the whole-tile
        body's, and the v-gradient is the forward's own linear map (the
        output is linear in v, so <dv, e> = <w, out(v + e) - out(v)>)."""
        rng = np.random.RandomState(7)
        S, H, D = 256, 2, 64
        qkv = jnp.asarray(rng.randn(1, S, 3 * H * D) * 0.3, jnp.bfloat16)
        seed = jnp.asarray([4321], jnp.int32)
        fap = self._with_plan(monkeypatch, (128, 128, H, 32))
        out, grad = self._out_and_grad(fap, qkv, H, 0.125, 0.3, seed)
        e = np.zeros(qkv.shape, np.float32)
        e[..., 2 * H * D:] = rng.randint(-1, 2, (1, S, H * D)) * 0.25
        shifted = (qkv.astype(jnp.float32) + e).astype(jnp.bfloat16)
        out_e = np.asarray(fap.flash_attention_packed(
            shifted, H, True, 0.125, 0.3, seed), np.float32)
        fap = self._with_plan(monkeypatch, (128, 128, H, 0))
        out_w, grad_w = self._out_and_grad(fap, qkv, H, 0.125, 0.3, seed)
        np.testing.assert_allclose(out, out_w, rtol=2 ** -7, atol=1e-3)
        np.testing.assert_allclose(grad, grad_w, rtol=2 ** -7, atol=4e-3)
        assert (out != np.asarray(fap.flash_attention_packed(
            qkv, H, True, 0.125), np.float32)).mean() > 0.5  # masks drawn
        # d/dv of sum(out^2) along e, by the chain rule through out
        np.testing.assert_allclose(
            np.sum(grad * e), np.sum(2 * out * (out_e - out)), rtol=0.03)

    def test_layers_share_one_trace_of_each_kernel(self, monkeypatch):
        """The kernel bodies are Python-unrolled over heads and strips:
        a stack of layers must trace each once, not once a layer."""
        import jax
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        calls = {}
        for name in ("_fwd_kernel", "_bwd_dkdv_kernel", "_bwd_dq_kernel"):
            def counted(*a, _real=getattr(fap, name), _name=name, **k):
                calls[_name] = calls.get(_name, 0) + 1
                return _real(*a, **k)
            monkeypatch.setattr(fap, name, counted)
        H, D = 2, 64
        scale = 0.1237          # a scale no other test's trace has cached

        def loss(x):
            for _ in range(4):
                o = fap.flash_attention_packed(x, H, True, scale)
                x = jnp.concatenate([o, o, o], -1)
            return jnp.sum(x.astype(jnp.float32))
        qkv = jax.ShapeDtypeStruct((1, 256, 3 * H * D), jnp.bfloat16)
        jax.jit(jax.grad(loss)).trace(qkv)
        assert calls == {"_fwd_kernel": 1, "_bwd_dkdv_kernel": 1,
                         "_bwd_dq_kernel": 1}
        # and one lowered function of each serves the four layers; the
        # same shapes and statics lower compiled once the backend's
        # answer changes (it is part of the trace's key)
        monkeypatch.setattr(fap, "_interpret", lambda: False)
        text = jax.jit(jax.grad(loss)).trace(qkv).lower(
            lowering_platforms=("tpu",)).as_text()
        import re
        assert sorted(re.findall(r'kernel_name = "(\w+)"', text)) == [
            "flash_packed_bwd_dkdv", "flash_packed_bwd_dq",
            "flash_packed_fwd"]

    def test_executed_score_share(self, monkeypatch):
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        bf16 = jnp.bfloat16
        # both benchmark cells: 3 whole tiles of 4 before the strips; two
        # of the three are diagonal and run (4 + 1) / 8 of their tile
        for heads, d in ((16, 64), (16, 128)):
            assert fap._plan(1024, 1024, heads, d, bf16)[:2] == (512, 512)
            assert fap.executed_score_share(
                1024, 1024, heads, d, bf16, True) == 0.5625
            assert fap.executed_score_share(
                1024, 1024, heads, d, bf16, False) == 1.0
        assert fap.executed_score_share(512, 512, 12, 64, bf16, True) \
            == 0.625                                    # all diagonal
        assert fap.executed_score_share(4096, 4096, 12, 64, bf16, True) \
            == (28 + 8 * 0.625) / 64
        # a plan the strips do not cover reads what the whole-tile body
        # runs: every cell the diagonal touches, whole
        assert fap._score_share(1024, 1024, (512, 512, 8, 0), True) == 0.75
        assert fap._score_share(1024, 1024, (256, 512, 8, 0), True) == 0.75
        assert fap._score_share(1024, 1024, (512, 256, 8, 0), True) == 0.75
        assert fap._strip_rows(512, 256) == 0 == fap._strip_rows(128, 128)
        assert fap._strip_rows(256, 256) == 0       # under four strips
        assert fap._strip_rows(512, 512) == 128
        # the autotune cache's three-number override gets its strip rows
        # from the same rule
        from paddle_hackathon_tpu.core import autotune as at
        monkeypatch.setattr(at, "enabled", lambda: True)
        monkeypatch.setattr(at, "kernel_cache", at.AutoTuneCache())
        at.kernel_cache.put(fap._tune_key(1024, 1024, 16, bf16),
                            (256, 512, 8))
        assert fap._plan(1024, 1024, 16, 64, bf16) == (256, 512, 8, 0)
        assert fap.executed_score_share(1024, 1024, 16, 64, bf16, True) \
            == 0.75
        # a shape no plan covers has no share to state
        assert not fap.supported(1000, 1000, 3, 8, bf16)
        with pytest.raises(ValueError, match="no packed flash plan"):
            fap.executed_score_share(1000, 1000, 3, 8, bf16, True)

    def test_supported_gates(self):
        from paddle_hackathon_tpu.incubate.nn.kernels import (
            flash_attention_packed as fap)
        assert fap.supported(1024, 1024, 12, 64, jnp.bfloat16)
        assert not fap.supported(1024, 1024, 12, 64, jnp.float32)  # VMEM
        assert not fap.supported(1003, 1003, 12, 64, jnp.bfloat16)  # divis
        assert not fap.supported(1024, 1024, 3, 20, jnp.bfloat16)  # lanes
