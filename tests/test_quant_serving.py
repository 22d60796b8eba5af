"""Weight-only int8/fp8 quantized inference path (PR 8).

Covers the fused dequant Pallas GEMM (`incubate/nn/kernels/quant_matmul`)
against its jnp oracle in interpreter mode, the post-training quantizer
and QAT export (`nn/quant/weight_only`), the quantize-at-load artifact
round trip (`save_for_serving(quant=)` / `load_for_serving` /
`Predictor`), the int8-vs-bf16 logit-error bound, and — slow-marked —
token-exact engine parity on the quantized model (dense + paged) and the
mp-sharded path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu.core.tensor import Tensor
from paddle_hackathon_tpu.incubate.nn.kernels import quant_matmul as qm
from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_hackathon_tpu.nn.quant import weight_only as wo


def _gpt(num_layers=2, hidden=64, vocab=128):
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=num_layers, num_heads=4,
                    max_position_embeddings=128, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _bf16(model):
    for _, p in model.named_parameters():
        if jnp.issubdtype(p._value.dtype, jnp.floating):
            p._set_value(p._value.astype(jnp.bfloat16))
    return model


def _kernel(x, w, s, **kw):
    qm.FORCE_KERNEL = True   # run the Pallas kernel under the interpreter
    try:
        return qm.quant_matmul(x, w, s, **kw)
    finally:
        qm.FORCE_KERNEL = None


@pytest.fixture(scope="module")
def quant_artifact(tmp_path_factory):
    """One shared int8 artifact (bf16 source model, saved dir, reloaded
    quantized model) — the forward-only tests reuse it instead of each
    paying the save/load again."""
    from paddle_hackathon_tpu.inference.serving import (load_for_serving,
                                                        save_for_serving)

    m = _bf16(_gpt())
    d = str(tmp_path_factory.mktemp("artifact") / "q")
    save_for_serving(m, d, quant="int8")
    return m, d, load_for_serving(d)


# ---------------------------------------------------------------- kernel
def test_kernel_matches_ref_bf16_ulp():
    """Interpreter-mode kernel vs the jnp oracle at GPT-2 projection
    shapes, bf16 activations (the serving dtype): blocking only M and N
    keeps each output element's contraction one dot, so any difference
    is CPU-XLA dot reassociation — bounded at one bf16 output ulp."""
    rng = np.random.RandomState(0)
    for m, k, n in ((1, 128, 128), (5, 256, 384), (8, 768, 2304),
                    (200, 384, 256)):
        x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        w = jnp.asarray(rng.randint(-127, 128, (k, n)), jnp.int8)
        s = jnp.asarray(rng.rand(n) * 0.01 + 1e-4, jnp.float32)
        ref = np.asarray(qm.quant_matmul_ref(x, w, s), np.float32)
        ker = np.asarray(_kernel(x, w, s), np.float32)
        # 1 bf16 ulp = 2^-8 relative
        np.testing.assert_allclose(ker, ref, rtol=2 ** -8, atol=1e-6,
                                   err_msg=f"{(m, k, n)}")


def test_kernel_matches_ref_f32_reassociation_tolerance():
    """f32 activations agree to dot-reassociation tolerance (CPU XLA
    picks a K-tiling per output shape, so bitwise equality is not the
    contract off the serving dtype)."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 768), jnp.float32)
    w = jnp.asarray(rng.randint(-127, 128, (768, 2304)), jnp.int8)
    s = jnp.asarray(rng.rand(2304) * 0.01 + 1e-4, jnp.float32)
    np.testing.assert_allclose(np.asarray(_kernel(x, w, s)),
                               np.asarray(qm.quant_matmul_ref(x, w, s)),
                               rtol=2e-3, atol=1e-4)


def test_kernel_fp8_bias_and_3d():
    rng = np.random.RandomState(2)
    s = jnp.asarray(rng.rand(256) * 0.01 + 1e-4, jnp.float32)
    b = jnp.asarray(rng.randn(256), jnp.float32)
    x = jnp.asarray(rng.randn(4, 128), jnp.bfloat16)
    w = jnp.asarray(rng.randn(128, 256), jnp.float8_e4m3fn)
    np.testing.assert_array_equal(
        np.asarray(_kernel(x, w, s), np.float32),
        np.asarray(qm.quant_matmul_ref(x, w, s), np.float32))
    # 3-D activations (B, S, K) flatten through the same kernel; bias is
    # added identically on both paths
    x3 = jnp.asarray(rng.randn(2, 3, 128), jnp.bfloat16)
    w8 = jnp.asarray(rng.randint(-127, 128, (128, 256)), jnp.int8)
    got = _kernel(x3, w8, s, bias=b)
    assert got.shape == (2, 3, 256)
    want = qm.quant_matmul_ref(x3.reshape(-1, 128), w8, s).reshape(
        2, 3, 256) + b.astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_kernel_rejects_unsupported_geometry():
    """The kernel refuses non-lane-aligned N loudly — a grid floor
    division would otherwise leave the tail columns unwritten (silent
    garbage); FORCE_KERNEL bypasses dispatch but not this guard."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(4, 128), jnp.bfloat16)
    w = jnp.asarray(rng.randint(-127, 128, (128, 300)), jnp.int8)
    s = jnp.ones((300,), jnp.float32)
    with pytest.raises(ValueError, match="lane-aligned"):
        _kernel(x, w, s)


def test_kernel_dispatch_uses_ref_off_tpu():
    """Without FORCE_KERNEL the CPU backend dispatches the reference
    (supported() geometry notwithstanding) — same contract as
    paged_attention."""
    assert not qm.use_kernel(128, 128, jnp.int8)
    assert qm.supported(128, 128, jnp.int8)
    assert not qm.supported(120, 128, jnp.int8)      # lane-misaligned K
    assert not qm.supported(128, 128, jnp.float32)   # not a quant dtype


# ------------------------------------------------------------- quantizer
def test_quantize_array_error_bound_and_dead_channels():
    rng = np.random.RandomState(3)
    w = rng.randn(64, 96).astype(np.float32) * 0.1
    w[:, 7] = 0.0   # dead output channel: absmax 0 must not divide-by-0
    q, scale = wo.quantize_array(jnp.asarray(w), "int8")
    assert q.dtype == jnp.int8 and scale.shape == (96,)
    deq = np.asarray(q, np.float32) * np.asarray(scale)[None, :]
    # symmetric absmax grid: per-element error is at most half a step
    assert np.abs(deq - w).max() <= np.asarray(scale).max() / 2 + 1e-7
    np.testing.assert_array_equal(deq[:, 7], 0.0)


def test_quantize_weights_predicate_and_manifest():
    rng = np.random.RandomState(4)
    params = {
        "gpt.blocks.0.attn.qkv_proj.weight": jnp.asarray(
            rng.randn(8, 24), jnp.bfloat16),
        "gpt.wte.weight": jnp.asarray(rng.randn(16, 8), jnp.bfloat16),
        "gpt.ln_f.weight": jnp.ones((8,), jnp.bfloat16),
        "gpt.blocks.0.attn.qkv_proj.bias": jnp.zeros((24,), jnp.bfloat16),
    }
    out, manifest = wo.quantize_weights(params, "int8")
    assert manifest == ["gpt.blocks.0.attn.qkv_proj.weight"]
    assert out["gpt.blocks.0.attn.qkv_proj.weight"].dtype == jnp.int8
    assert out["gpt.blocks.0.attn.qkv_proj.weight_scale"].shape == (24,)
    # embeddings / 1-D params untouched; re-quantizing is a no-op
    assert out["gpt.wte.weight"].dtype == jnp.bfloat16
    out2, manifest2 = wo.quantize_weights(out, "int8")
    assert manifest2 == []


def test_fp8_scheme_resolution():
    assert wo.resolve_scheme("fp8") == "fp8-e4m3"
    with pytest.raises(ValueError):
        wo.resolve_scheme("int4")


def test_apply_weight_only_live_path_respects_embedding_names():
    """The live (names=None) path feeds the predicate REAL dotted paths,
    so an embedding-like projection implemented as a plain Linear (e.g.
    an untied embed_out head) is excluded by name exactly as it is in
    the save_for_serving(quant=) param-dict path."""
    from paddle_hackathon_tpu.nn.layer import Layer
    from paddle_hackathon_tpu.nn.layers.common import Linear

    class Net(Layer):
        def __init__(self):
            super().__init__()
            self.proj = Linear(16, 32)
            self.embed_out = Linear(16, 32)

        def forward(self, x):
            return self.embed_out(self.proj(x))

    net = Net()
    assert wo.apply_weight_only(net) == 1
    assert type(net.proj).__name__ == "WeightOnlyLinear"
    assert type(net.embed_out).__name__ == "Linear"


def test_convert_to_weight_only_uses_learned_scales():
    """QAT export: the serving layer must quantize on the grid training
    simulated — scale == learned_absmax / 127 for a channel-wise
    quantizer, the scalar absmax broadcast per channel for the default
    per-tensor one (the (1,) scale must NOT land in the per-channel
    weight_scale slot — it broke the artifact round trip) — and the
    dequantized weight equals the fake-quant layer's dequant output."""
    from paddle_hackathon_tpu.nn.layer import Layer
    from paddle_hackathon_tpu.nn.layers.common import Linear
    from paddle_hackathon_tpu.nn.quant.quant_layers import QuantizedLinear

    class Net(Layer):
        def __init__(self):
            super().__init__()
            # the two QAT weight-quantizer flavors
            self.fc = QuantizedLinear(
                Linear(32, 48),
                weight_quantize_type="channel_wise_abs_max")
            self.head = QuantizedLinear(Linear(48, 48))  # per-tensor

        def forward(self, x):
            return self.head(self.fc(x))

    paddle.seed(0)
    net = Net()
    x = Tensor(jnp.asarray(np.random.RandomState(0).randn(4, 32),
                           jnp.float32))
    net.train()
    net(x)   # one forward populates the learned absmax observers
    learned = np.asarray(net.fc._fake_quant_weight.scale._value).copy()
    scalar = np.asarray(net.head._fake_quant_weight.scale._value).copy()
    w = np.asarray(net.fc.weight._value).copy()
    assert wo.convert_to_weight_only(net) == 2
    fc, head = net.fc, net.head
    assert type(fc).__name__ == "WeightOnlyLinear"
    np.testing.assert_allclose(np.asarray(fc.weight_scale._value),
                               learned / 127.0, rtol=1e-6)
    assert scalar.shape == (1,)
    assert head.weight_scale._value.shape == (48,)   # broadcast, not (1,)
    np.testing.assert_allclose(np.asarray(head.weight_scale._value),
                               np.full(48, scalar[0] / 127.0), rtol=1e-6)
    # same grid as _ste_quant_dequant: round(w / absmax * 127) steps
    deq = (np.asarray(fc.weight._value, np.float32)
           * np.asarray(fc.weight_scale._value)[None, :])
    want = np.clip(np.round(w / (learned[None, :] / 127.0)),
                   -127, 127) * (learned[None, :] / 127.0)
    np.testing.assert_allclose(deq, want, atol=1e-6)
    # params now expose the serving layout for functional paths
    params, _ = net.functional_state()
    assert params["fc.weight"].dtype == jnp.int8
    assert "fc.weight_scale" in params


def test_convert_rejects_per_in_channel_qat_scales():
    """Per-IN-channel QAT scales (weight_quant_axis=0) cannot commute
    out of the GEMM as a per-output epilogue; conversion must refuse
    with the remedy, not shape-sniff (undetectably wrong for square
    weights)."""
    from paddle_hackathon_tpu.nn.layers.common import Linear
    from paddle_hackathon_tpu.nn.quant.quant_layers import QuantizedLinear

    paddle.seed(0)
    q = QuantizedLinear(Linear(32, 32),
                        weight_quantize_type="channel_wise_abs_max",
                        weight_quant_axis=0)
    q(Tensor(jnp.asarray(np.random.RandomState(0).randn(2, 32),
                         jnp.float32)))
    with pytest.raises(ValueError, match="weight_quant_axis"):
        wo.WeightOnlyLinear.from_qat(q)


# ------------------------------------------------- artifact + logit bound
def test_int8_artifact_weight_bytes_ratio(tmp_path):
    """Acceptance bound: on a projection-dominated shape (every real LLM
    — vocab small next to 12*h^2*L) the int8 artifact holds <= 0.55x the
    bf16 artifact's weight bytes, scales included."""
    from paddle_hackathon_tpu.inference.serving import save_for_serving

    m = _bf16(_gpt(num_layers=3, hidden=128, vocab=128))
    d_bf16, d_int8 = str(tmp_path / "bf16"), str(tmp_path / "int8")
    save_for_serving(m, d_bf16)
    save_for_serving(m, d_int8, quant="int8")

    def artifact_bytes(d):
        z = np.load(d + "/params.npz")
        return sum(z[k].nbytes for k in z.files)

    ratio = artifact_bytes(d_int8) / artifact_bytes(d_bf16)
    assert ratio <= 0.55, ratio


def test_logit_error_bound_int8_vs_bf16(quant_artifact):
    """int8-vs-bf16 max-abs logit error on a seeded GPT layer stack
    stays under a fixed tolerance (weight-only PTQ: activations bf16,
    per-channel scales — the quality-survives claim, pinned)."""
    m, _, mq = quant_artifact
    ids = Tensor(jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (1, 12)), jnp.int32))
    lg = np.asarray(m(ids).numpy(), np.float32)
    lq = np.asarray(mq(ids).numpy(), np.float32)
    err = np.abs(lg - lq).max()
    # measured 0.008 at this seed/shape; 0.05 gives headroom without
    # letting a broken scale path (errors O(|logits|) ~ 0.7) through
    assert err < 0.05, err


def test_quantized_artifact_roundtrip_dtypes(quant_artifact):
    _, _, mq = quant_artifact
    blk = mq.gpt.blocks[0]
    for lay in (blk.attn.qkv_proj, blk.attn.out_proj,
                blk.mlp.fc_in, blk.mlp.fc_out):
        assert type(lay).__name__ == "WeightOnlyLinear"
        assert lay.weight._value.dtype == jnp.int8
        assert lay.weight_scale._value.dtype == jnp.float32
        assert lay.bias._value.dtype == jnp.bfloat16
    # embeddings / layernorms / tied logits head stay bf16
    assert mq.gpt.wte.weight._value.dtype == jnp.bfloat16
    assert mq.gpt.ln_f.weight._value.dtype == jnp.bfloat16


def test_fp8_artifact_roundtrip(tmp_path):
    fp8 = jnp.float8_e4m3fn
    import json

    from paddle_hackathon_tpu.inference.serving import (load_for_serving,
                                                        save_for_serving)

    m = _bf16(_gpt())
    d = str(tmp_path / "q8")
    save_for_serving(m, d, quant="fp8")
    with open(d + "/config.json") as f:
        assert json.load(f)["quant"]["scheme"] == "fp8-e4m3"
    mq = load_for_serving(d)
    blk = mq.gpt.blocks[0]
    assert blk.attn.qkv_proj.weight._value.dtype == fp8
    assert blk.attn.qkv_proj.weight_scale._value.dtype == jnp.float32
    # fp8 GEMM numerics are covered at the kernel level
    # (test_kernel_fp8_bias_and_3d); here the artifact contract is the
    # point: scheme recorded, shells installed, narrow dtype loaded


def test_predictor_serves_quantized_dir(quant_artifact):
    """Predictor loads the serving-directory artifact and its jitted
    forward routes through the fused-GEMM layers — logits match the
    model's own forward."""
    from paddle_hackathon_tpu.inference import Config, create_predictor

    _, d, mq = quant_artifact
    cfg = Config()
    cfg.set_model(d)
    pred = create_predictor(cfg)
    assert pred.get_input_names() == ["input_ids"]
    ids = np.random.RandomState(0).randint(0, 128, (2, 8)).astype(np.int32)
    (logits,) = pred.run([ids])
    want = np.asarray(mq(Tensor(jnp.asarray(ids))).numpy())
    # jitted-fused vs eager per-op forward: bf16 rounding differs at the
    # ulp level; the bound is well under the int8-vs-bf16 logit budget
    assert np.abs(np.asarray(logits, np.float32)
                  - np.asarray(want, np.float32)).max() < 0.02


# --------------------------------------------- tick trim (host-side unit)
def test_sampling_vectors_cache_invalidation():
    """Tick-dispatch trim: the per-slot sampling vectors are computed
    once and reused until admission changes membership (no per-tick
    restaging); admitting an overriding request invalidates and the
    rebuilt vectors carry the override."""
    from paddle_hackathon_tpu.inference.serving import ServingEngine

    eng = ServingEngine(_gpt(), max_slots=4, max_len=64, chunk=4,
                        auto_run=False)
    s1 = eng._sampling_vectors()
    assert eng._sampling_vectors() is s1        # cached
    assert s1[0] is False                        # scalar program flavor
    eng.submit(np.arange(5, dtype=np.int32), 4, temperature=0.7, top_k=3)
    with eng._lock:
        eng._admit()
    assert eng._sampling_cache is None           # membership invalidated
    s2 = eng._sampling_vectors()
    assert s2[0] == (True, False)                # top-k live, top-p off
    assert s2[1][0] == np.float32(0.7) and s2[2][0] == 3
    # device staging happens lazily, once per rebuild
    d1 = eng._sampling_dev3(s2)
    assert eng._sampling_dev3(s2) is d1


# ----------------------------------------------------- engine (slow) ----
@pytest.mark.slow
def test_int8_engine_parity_dense_paged_and_spec(tmp_path):
    """The quantized engine is token-exact against the quantized model's
    own greedy generate() in dense, paged and speculative modes (the
    engine's exactness contract is unchanged by the fused GEMM)."""
    from paddle_hackathon_tpu.inference.serving import (ServingEngine,
                                                        load_for_serving,
                                                        save_for_serving)

    m = _bf16(_gpt())
    d = str(tmp_path / "q")
    save_for_serving(m, d, quant="int8")
    mq = load_for_serving(d)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 128, (n,)).astype(np.int32)
               for n in (6, 9, 5)]
    refs = [np.asarray(mq.generate(Tensor(jnp.asarray(p[None, :])),
                                   max_new_tokens=8,
                                   temperature=0.0).numpy())[0]
            for p in prompts]
    for kw in (dict(),
               dict(cache_mode="paged", page_size=8),
               dict(spec_k=3)):
        eng = ServingEngine(mq, max_slots=4, max_len=64, chunk=4, **kw)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        assert all(r.wait(300) for r in reqs)
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.result(), ref, err_msg=str(kw))
        eng.shutdown()


@pytest.mark.slow
def test_int8_mp_sharded_generate_parity(tmp_path):
    """Quantized weights + scales place onto an mp mesh (scales follow
    the projections' out-feature partitioning) and sharded greedy decode
    matches the unsharded quantized model token-for-token."""
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.inference.serving import (load_for_serving,
                                                        save_for_serving)
    from paddle_hackathon_tpu.models.gpt import param_sharding_spec

    m = _bf16(_gpt())
    d = str(tmp_path / "q")
    save_for_serving(m, d, quant="int8")
    mq = load_for_serving(d)
    p = np.random.RandomState(5).randint(0, 128, (7,)).astype(np.int32)
    ids = Tensor(jnp.asarray(p[None, :]))
    ref = np.asarray(mq.generate(ids, max_new_tokens=8,
                                 temperature=0.0).numpy())
    mq2 = load_for_serving(d)
    mesh = parallel.create_mesh({"mp": 2}, devices=jax.devices()[:2])
    parallel.shard_params(mq2, mesh, rule=param_sharding_spec)
    got = np.asarray(mq2.generate(ids, max_new_tokens=8,
                                  temperature=0.0).numpy())
    np.testing.assert_array_equal(got, ref)
