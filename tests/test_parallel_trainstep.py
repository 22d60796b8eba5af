"""Sharded train-step tests on the virtual 8-device CPU mesh (SURVEY §4's
multi-process-on-one-host pattern, realised as a multi-device mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import parallel
from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                         param_sharding_spec)




def _tiny(**kw):
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
               max_position_embeddings=32, hidden_dropout_prob=0.0,
               attention_dropout_prob=0.0, use_flash_attention=False)
    cfg.update(kw)
    return GPTConfig(**cfg)


def _data(batch=8, seq=16, vocab=128):
    r = np.random.RandomState(0)
    return (jnp.asarray(r.randint(0, vocab, (batch, seq)), jnp.int32),
            jnp.asarray(r.randint(0, vocab, (batch, seq)), jnp.int32))


def test_create_mesh_axis_order_and_validation():
    mesh = parallel.create_mesh({"dp": 2, "mp": 4})
    assert mesh.axis_names == ("dp", "mp")
    assert parallel.get_mesh() is mesh
    with pytest.raises(ValueError):
        parallel.create_mesh({"dp": 3, "mp": 4})


def test_dp_only_train_step_decreases_loss():
    paddle.seed(0)
    model = GPTForCausalLM(_tiny())
    mesh = parallel.create_mesh({"dp": 8})
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-3)
    ids, labels = _data()
    losses = []
    for i in range(5):
        state, loss = step(state, ids, labels, jax.random.key(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_hybrid_dp_sharding_mp_matches_single_device():
    """Parity check in the spirit of the reference's hybrid-parallel tests
    (TP layers == single-card, ``hybrid_parallel_mp_layers.py``)."""
    ids, labels = _data(batch=4)

    def run(mesh_dims, zero_stage):
        paddle.seed(123)
        model = GPTForCausalLM(_tiny())
        n = int(np.prod(list(mesh_dims.values())))
        mesh = parallel.create_mesh(mesh_dims, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            zero_stage=zero_stage, grad_clip_norm=None)
        out = []
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out

    single = run({"dp": 1}, 0)
    hybrid = run({"dp": 2, "sharding": 2, "mp": 2}, 3)
    np.testing.assert_allclose(hybrid, single, rtol=2e-4)


_SP_BASELINE_CACHE = {}


@pytest.mark.parametrize("mesh_dims,zero", [
    ({"dp": 2, "sp": 2, "mp": 2}, 0),
    ({"sharding": 2, "sp": 2, "mp": 2}, 3),   # sp composes with ZeRO-3
])
def test_hybrid_sp_matches_single_device(mesh_dims, zero):
    """Sequence parallelism composed INSIDE the one-program step (the seq
    dim shards on 'sp', attention runs the ring schedule) must match the
    single-device loss — SURVEY §5.7, beyond-reference capability."""
    ids, labels = _data(batch=4)

    def run(md, zs):
        paddle.seed(123)
        model = GPTForCausalLM(_tiny())
        n = int(np.prod(list(md.values())))
        mesh = parallel.create_mesh(md, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            zero_stage=zs, grad_clip_norm=None)
        out = []
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out

    if "base" not in _SP_BASELINE_CACHE:   # shared across parametrizations
        _SP_BASELINE_CACHE["base"] = run({"dp": 1}, 0)
    single = _SP_BASELINE_CACHE["base"]
    sp = run(mesh_dims, zero)
    np.testing.assert_allclose(sp, single, rtol=2e-3)


@pytest.mark.parametrize("mesh_dims,zero,sp_mode", [
    ({"pp": 2, "sp": 2, "mp": 2}, 0, "ring"),       # sp x pp composes
    ({"dp": 2, "pp": 2, "sp": 2}, 1, "ulysses"),    # ulysses as the sp mode
    ({"dp": 2, "sp": 2, "mp": 2}, 0, "ulysses"),    # ulysses without pp
])
def test_hybrid_sp_pp_matches_single_device(mesh_dims, zero, sp_mode):
    """sp composes with pp INSIDE the one-program step (the pipeline
    region goes manual over both axes; ring/ulysses run their per-device
    bodies directly — VERDICT r3 missing #3), and ulysses_attention is
    selectable as the sp mode."""
    ids, labels = _data(batch=4)

    def run(md, zs, mode):
        paddle.seed(123)
        model = GPTForCausalLM(_tiny())
        n = int(np.prod(list(md.values())))
        mesh = parallel.create_mesh(md, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            zero_stage=zs, grad_clip_norm=None, sp_mode=mode)
        out = []
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out

    if "base" not in _SP_BASELINE_CACHE:
        _SP_BASELINE_CACHE["base"] = run({"dp": 1}, 0, "auto")
    single = _SP_BASELINE_CACHE["base"]
    got = run(mesh_dims, zero, sp_mode)
    np.testing.assert_allclose(got, single, rtol=2e-3)


def test_bert_sequence_parallel_matches_single_device():
    """BERT — no model-specific sp hook — trains under sp2 via the generic
    attention-module switch (VERDICT r3 weak #5): bidirectional ring/
    ulysses attention, MLM loss parity vs single device."""
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.models import (BertForPretraining, bert_config,
                                             bert_param_sharding_spec,
                                             masked_mlm_loss)
    from paddle_hackathon_tpu.nn.layer import functional_call

    cfg = bert_config(
        "bert-base-uncased", num_layers=2, hidden_size=64, num_heads=4,
        vocab_size=128, max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0, use_flash_attention=False)
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 128, (4, 16)), jnp.int32)
    raw = r.randint(0, 128, (4, 16))
    labels = jnp.asarray(
        np.where(r.rand(4, 16) < 0.15, raw, -100), jnp.int32)

    def mlm_loss(model, params, buffers, batch, rng):
        b_ids, b_labels = batch
        pred, _ = functional_call(model, params, (Tensor(b_ids),),
                                  buffers=buffers)
        return masked_mlm_loss(pred, b_labels)

    def run(md, mode):
        paddle.seed(123)
        model = BertForPretraining(cfg)
        n = int(np.prod(list(md.values())))
        mesh = parallel.create_mesh(md, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=bert_param_sharding_spec, learning_rate=1e-3,
            grad_clip_norm=None, loss_fn=mlm_loss, sp_mode=mode)
        out = []
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out

    single = run({"dp": 1}, "auto")
    for mode in ("ring", "ulysses"):
        got = run({"sp": 2, "mp": 2}, mode)
        np.testing.assert_allclose(got, single, rtol=2e-3, err_msg=mode)


def test_zero3_actually_shards_params():
    paddle.seed(0)
    model = GPTForCausalLM(_tiny())
    mesh = parallel.create_mesh({"sharding": 4, "mp": 2})
    parallel.shard_params(model, mesh, rule=param_sharding_spec, zero_stage=3)
    p = dict(model.named_parameters())["gpt.blocks.0.attn.qkv_proj.weight"]
    spec = p._value.sharding.spec
    assert "mp" in spec and "sharding" in spec
    # per-device memory is 1/8 of the full tensor
    shard_size = p._value.addressable_shards[0].data.size
    assert shard_size == p.size // 8


def test_tp_sharding_spec_rules():
    assert param_sharding_spec("gpt.blocks.0.attn.qkv_proj.weight",
                               (64, 192)) == (None, "mp")
    assert param_sharding_spec("gpt.blocks.0.attn.out_proj.weight",
                               (64, 64)) == ("mp", None)
    assert param_sharding_spec("gpt.wte.weight", (128, 64)) == (
        ("mp", "sharding"), None)
    assert param_sharding_spec("gpt.wpe.weight", (32, 64)) == (
        "sharding", None)
    assert param_sharding_spec("gpt.ln_f.weight", (64,)) == (None,)


def test_graft_entry_contract():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", "/root/repo/__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 256
    mod.dryrun_multichip(8)


def test_train_step_accepts_pytree_batch():
    """Batch slots may be pytrees (ernie feeds (ids, masked_positions));
    1-D leaves shard on the data axes truncated to their rank."""
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.models import GPTForCausalLM, gpt_config
    from paddle_hackathon_tpu.nn.layer import functional_call

    paddle.seed(0)
    cfg = gpt_config("gpt2-small-en", num_layers=2, hidden_size=64,
                     num_heads=2, vocab_size=256,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    mesh = parallel.create_mesh({"dp": 2}, devices=jax.devices()[:2])

    def loss_fn(model, params, buffers, batch_, rng):
        (ids, pos), labels = batch_
        logits = functional_call(model, params, (Tensor(ids),),
                                 buffers=dict(buffers))
        lg = logits._value if isinstance(logits, Tensor) else logits
        flat = lg.reshape(-1, lg.shape[-1])[pos]
        onehot = jax.nn.one_hot(labels, lg.shape[-1])
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(flat) * onehot, -1))

    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=None, learning_rate=1e-3, zero_stage=0,
        loss_fn=loss_fn)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32)
    pos = jnp.asarray(rng.randint(0, 4 * 16, (8,)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, 256, (8,)), jnp.int32)
    key = jax.random.key(0)
    l0 = l1 = None
    for i in range(3):
        state, loss = step(state, (ids, pos), labels,
                           jax.random.fold_in(key, i))
        l0 = l0 if l0 is not None else float(loss)
        l1 = float(loss)
    assert np.isfinite(l1) and l1 < l0   # actually trains


def test_bench_script_output_format(tmp_path):
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the CPU is asked for explicitly: without JAX_PLATFORMS=cpu (and
    # without a chip) bench.py exits non-zero (tests/test_chip_smoke.py)
    env["JAX_PLATFORMS"] = "cpu"
    # a cache placed from outside: keeps this CPU run out of the
    # in-checkout cache directory (and never hits — a fresh directory)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        capture_output=True, text=True, env=env, timeout=600)
    lines = [l for l in out.stdout.strip().splitlines() if l.startswith("{")]
    assert out.returncode == 0 and lines, out.stderr[-2000:]
    rec = json.loads(lines[-1])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
    assert rec["value"] > 0
    # the requested-CPU shape must never masquerade as a chip headline
    assert rec["metric"].endswith("cpu_smoke")


def test_gpt_kv_cache_matches_full_forward():
    """Incremental decode with cache == full causal forward (last position)."""
    paddle.seed(5)
    model = GPTForCausalLM(_tiny())
    model.eval()
    ids, _ = _data(batch=2, seq=8)
    from paddle_hackathon_tpu.core.tensor import Tensor
    full_logits = model(Tensor(ids)).numpy()

    # prefill 5 tokens, then decode 3 one at a time
    caches = model.gpt.gen_empty_caches(2)
    logits, caches = model(Tensor(ids[:, :5]), caches=caches)
    np.testing.assert_allclose(logits.numpy(), full_logits[:, :5], atol=2e-4)
    for t in range(5, 8):
        logits, caches = model(Tensor(ids[:, t:t + 1]), caches=caches)
        np.testing.assert_allclose(logits.numpy()[:, 0], full_logits[:, t],
                                   atol=2e-4)


def test_gpt_generate_static_matches_concat():
    """jit_decode=True (two compiled programs, static cache) must produce
    token-for-token the same greedy output as the growing-concat path."""
    import jax.numpy as jnp

    from paddle_hackathon_tpu.core.tensor import Tensor

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 128, (2, 5)),
                      jnp.int32)
    new = m.generate(Tensor(ids), max_new_tokens=6, temperature=0.0)
    old = m.generate(Tensor(ids), max_new_tokens=6, temperature=0.0,
                     jit_decode=False)
    np.testing.assert_array_equal(np.asarray(new.numpy()),
                                  np.asarray(old.numpy()))


def test_gpt_generate():
    paddle.seed(6)
    model = GPTForCausalLM(_tiny())
    from paddle_hackathon_tpu.core.tensor import Tensor
    ids, _ = _data(batch=2, seq=4)
    out = model.generate(Tensor(ids), max_new_tokens=3, temperature=0.0)
    assert out.shape == [2, 7]
    np.testing.assert_allclose(out.numpy()[:, :4], np.asarray(ids))
    # max_new_tokens=0 returns the prompt unchanged on BOTH paths (the
    # jit path used to crash building a (b, 0) outbuf — advisor r3)
    for jd in (True, False):
        same = model.generate(Tensor(ids), max_new_tokens=0,
                              temperature=0.0, jit_decode=jd)
        np.testing.assert_array_equal(np.asarray(same.numpy()),
                                      np.asarray(ids))


def test_moe_pipeline_matches_ep_only():
    """pp x ep: MoE blocks pipeline — the per-layer load-balance aux is
    accumulated INSIDE the stage scan (pipeline_apply with_aux; the side
    channel collect_moe_aux reads cannot escape lax.scan) with
    per-microbatch semantics (the reference's gradient-accumulation
    behavior). Trajectory matches the ep-only composition."""
    cfg = _tiny(moe_num_experts=4, moe_gate="naive")
    ids, labels = _data()

    def run(md):
        paddle.seed(123)
        model = GPTForCausalLM(cfg)
        n = int(np.prod(list(md.values())))
        mesh = parallel.create_mesh(md, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            grad_clip_norm=None)
        out = []
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out

    base = run({"ep": 2, "mp": 2, "dp": 2})
    ppep = run({"pp": 2, "ep": 2, "mp": 2})
    assert ppep[-1] < ppep[0]
    np.testing.assert_allclose(ppep, base, rtol=2e-2)


def test_gpt_generate_mp_sharded_matches_single_device():
    """TP-sharded one-program decode (VERDICT r3 missing #2): a model
    placed on a dp x mp mesh generates the SAME greedy tokens as the
    single-device program — GSPMD inserts the out_proj psum and
    vocab-parallel argmax collectives inside the decode loop (the
    reference's fused_multi_transformer in-decode allreduce)."""
    from paddle_hackathon_tpu.core.tensor import Tensor

    paddle.seed(3)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = jnp.asarray(np.random.RandomState(5).randint(0, 128, (4, 6)),
                      jnp.int32)
    single = np.asarray(
        model.generate(Tensor(ids), max_new_tokens=8,
                       temperature=0.0).numpy())

    mesh = parallel.create_mesh({"dp": 2, "mp": 2},
                                devices=jax.devices()[:4])
    try:
        parallel.shard_params(model, mesh, rule=param_sharding_spec)
        assert model._param_mesh() is not None
        sharded = np.asarray(
            model.generate(Tensor(ids), max_new_tokens=8,
                           temperature=0.0).numpy())
    finally:
        parallel.set_mesh(None)
    np.testing.assert_array_equal(sharded, single)


@pytest.mark.parametrize("mesh_dims", [
    {"pp": 2, "dp": 2, "mp": 2},
    {"pp": 4, "dp": 2},
])
def test_gpt_generate_pp_sharded_matches_single_device(mesh_dims):
    """Pipeline-sharded decode: block params stacked on 'pp', each token
    crosses the stages sequentially inside ONE compiled program
    (pipeline_decode_apply); greedy tokens must be bit-identical to the
    single-device program."""
    from paddle_hackathon_tpu.core.tensor import Tensor

    paddle.seed(3)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = jnp.asarray(np.random.RandomState(5).randint(0, 128, (4, 6)),
                      jnp.int32)
    single = np.asarray(
        model.generate(Tensor(ids), max_new_tokens=8,
                       temperature=0.0).numpy())
    n = int(np.prod(list(mesh_dims.values())))
    parallel.create_mesh(mesh_dims, devices=jax.devices()[:n])
    try:
        pp_out = np.asarray(
            model.generate(Tensor(ids), max_new_tokens=8,
                           temperature=0.0).numpy())
    finally:
        parallel.set_mesh(None)
    np.testing.assert_array_equal(pp_out, single)


def test_jit_save_dynamic_batch(tmp_path):
    from paddle_hackathon_tpu import jit, nn
    model = nn.Sequential(nn.Linear(4, 8), nn.GELU(), nn.Linear(8, 2))
    model.eval()
    p = jit.save(model, str(tmp_path / "dyn"),
                 input_spec=[jit.InputSpec([None, 4])])
    loaded = jit.load(p)
    for b in (1, 3, 7):
        x = paddle.randn([b, 4])
        np.testing.assert_allclose(loaded(x).numpy(), model(x).numpy(),
                                   atol=1e-5)


class TestPipelineComposition:
    """VERDICT r2 #1: pp composed with dp/sharding/mp in ONE program
    (ref 4-axis hybrid: fleet_base.py:381-408 topology +
    pipeline_parallel.py:82-152 1F1B + hybrid_parallel_optimizer.py:172)."""

    def _run(self, mesh_dims, zero_stage, steps=3, **kw):
        ids, labels = _data(batch=16)
        paddle.seed(123)
        model = GPTForCausalLM(_tiny(num_layers=4))
        n = int(np.prod(list(mesh_dims.values())))
        mesh = parallel.create_mesh(mesh_dims, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            zero_stage=zero_stage, grad_clip_norm=None, **kw)
        out = []
        for i in range(steps):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out, step, state, model

    def test_dp_pp_mp_matches_single_device(self):
        single, *_ = self._run({"dp": 1}, 0)
        hybrid, *_ = self._run({"dp": 2, "pp": 2, "mp": 2}, 0)
        np.testing.assert_allclose(hybrid, single, rtol=2e-4)

    def test_dp_pp_sharding_zero3_matches_single_device(self):
        single, *_ = self._run({"dp": 1}, 0)
        hybrid, *_ = self._run({"dp": 2, "pp": 2, "sharding": 2}, 3,
                               pp_microbatches=2)
        np.testing.assert_allclose(hybrid, single, rtol=2e-4)

    def test_pp_stacked_params_actually_pipeline_sharded(self):
        _, step, state, model = self._run({"pp": 2, "mp": 2}, 0, steps=1)
        k = "gpt.blocks.$stacked.attn.qkv_proj.weight"
        arr = state["params"][k]
        assert arr.shape[0] == 4      # stacked layer dim
        spec = arr.sharding.spec
        assert spec[0] == "pp" and "mp" in spec
        # per-device shard is 1/4 of the stacked tensor (pp2 x mp2)
        assert arr.addressable_shards[0].data.size == arr.size // 4

    def test_pp_sync_model_restores_per_layer_params(self):
        _, step, state, model = self._run({"pp": 2, "dp": 2}, 0, steps=2)
        step.sync_model(state)
        k = "gpt.blocks.$stacked.attn.qkv_proj.weight"
        stacked = np.asarray(state["params"][k])
        live = dict(model.named_parameters())
        for i in range(4):
            np.testing.assert_allclose(
                np.asarray(live[f"gpt.blocks.{i}.attn.qkv_proj.weight"]._value),
                stacked[i])

    def test_pp_with_dropout_trains(self):
        """rng threading through the pipeline scan (fold_in per layer)."""
        ids, labels = _data(batch=8)
        paddle.seed(7)
        model = GPTForCausalLM(_tiny(num_layers=4, hidden_dropout_prob=0.1,
                                     attention_dropout_prob=0.0))
        mesh = parallel.create_mesh({"pp": 2, "dp": 2},
                                    devices=jax.devices()[:4])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3)
        losses = []
        for i in range(4):
            state, loss = step(state, ids, labels, jax.random.key(i))
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_pp_microbatch_divisibility_error(self):
        with pytest.raises(ValueError, match="divide"):
            self._run({"dp": 4, "pp": 2}, 0, pp_microbatches=8)


def test_fleet_pipeline_distributed_model_train_batch():
    """fleet wiring (ref fleet_base.py:1073-): a pp-axis mesh makes
    distributed_model return the PipelineParallel wrapper whose train_batch
    runs the one-program 4-axis hybrid step."""
    from paddle_hackathon_tpu.distributed import fleet
    paddle.seed(0)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                               "pp_degree": 2, "sharding_degree": 2}
    strategy.pipeline = True
    strategy.pipeline_configs = {"accumulate_steps": 2}
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 3}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        model = GPTForCausalLM(_tiny(num_layers=4))
        model = fleet.distributed_model(model)
        assert isinstance(model, parallel.PipelineParallel)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        opt = fleet.distributed_optimizer(opt)
        r = np.random.RandomState(0)
        losses = []
        for i in range(4):
            ids = paddle.to_tensor(r.randint(0, 128, (8, 16)).astype("int32"))
            labels = paddle.to_tensor(
                r.randint(0, 128, (8, 16)).astype("int32"))
            loss = model.train_batch([ids, labels], opt)
            losses.append(float(loss.numpy()))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        model.sync_model()  # stacked params restored into the live layers
    finally:
        parallel.set_mesh(None)


def _pp_params(optimizer, steps=3, via_train_batch=True, **step_kw):
    """Three pipelined steps on a pp2 x dp2 mesh, through the fleet wrapper
    (``optimizer`` an object) or through the builder; the live model's
    parameters after ``sync_model``."""
    paddle.seed(0)
    model = GPTForCausalLM(_tiny())
    mesh = parallel.create_mesh({"pp": 2, "dp": 2},
                                devices=jax.devices()[:4])
    try:
        ids, labels = _data()
        if via_train_batch:
            pipe = parallel.PipelineParallel(model, mesh,
                                             rule=param_sharding_spec)
            opt = optimizer(model.parameters())
            for _ in range(steps):
                loss = pipe.train_batch([paddle.to_tensor(np.asarray(ids)),
                                         paddle.to_tensor(np.asarray(labels))],
                                        opt)
            pipe.sync_model()
        else:
            step, state = parallel.make_sharded_train_step(
                model, mesh, rule=param_sharding_spec, zero_stage=0,
                optimizer=optimizer, **step_kw)
            for i in range(steps):
                state, loss = step(state, ids, labels, jax.random.key(i))
            step.sync_model(state)
        assert np.isfinite(float(np.asarray(loss)))
        return {k: np.asarray(p._value) for k, p in model.named_parameters()}
    finally:
        parallel.set_mesh(None)


@pytest.mark.parametrize("case", ["adamw_decays", "lamb_hyperparameters"])
def test_train_batch_takes_the_users_optimizer_as_it_is(case):
    """``PipelineParallel.train_batch`` hands the optimizer object to the
    builder: an ``AdamW``'s decoupled decay reaches the compiled program
    (it was dropped: the pipeline trained it as plain Adam), and a
    ``Lamb``'s hyper-parameters arrive without ``pipeline.py`` reading a
    private attribute of it."""
    import inspect
    import re

    from paddle_hackathon_tpu.parallel import pipeline
    assert not re.search(r"\b(optimizer|opt)\._",
                         inspect.getsource(pipeline.PipelineParallel))
    if case == "adamw_decays":
        got = _pp_params(lambda ps: paddle.optimizer.AdamW(
            learning_rate=1e-2, beta2=0.95, weight_decay=0.5, parameters=ps))
        plain = _pp_params("adam", via_train_batch=False,
                           learning_rate=1e-2, grad_clip_norm=None)
        k = "gpt.blocks.1.mlp.fc_in.weight"
        # three steps at lr 1e-2 and decay 0.5 shrink a weight by 1.5 %
        shrink = np.linalg.norm(got[k]) / np.linalg.norm(plain[k])
        assert 0.98 < shrink < 0.99, shrink
        return
    hp = dict(beta1=0.7, beta2=0.9, epsilon=1e-5, lamb_weight_decay=0.3)
    got = _pp_params(lambda ps: paddle.optimizer.Lamb(
        learning_rate=1e-2, parameters=ps, **hp))
    want = _pp_params("lamb", via_train_batch=False, learning_rate=1e-2,
                      grad_clip_norm=None, optimizer_kwargs=hp)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def _ce_loss(model, params, buffers, batch, rng):
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.nn.layer import functional_call
    ids, labels = batch
    logits = functional_call(model, params, (Tensor(ids),), buffers=buffers)
    logits = logits._value if isinstance(logits, Tensor) else logits
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


_SHORTHAND_CASES = {
    "adam": ("Adam", dict(beta1=0.8, beta2=0.95, epsilon=1e-6)),
    "lamb": ("Lamb", dict(beta1=0.8, lamb_weight_decay=0.02)),
    "lars": ("Lars", dict(momentum=0.8, lars_coeff=0.01,
                          lars_weight_decay=0.001)),
}


@pytest.mark.parametrize("kind", sorted(_SHORTHAND_CASES))
def test_optimizer_shorthand_is_the_class(kind):
    """``optimizer="adam" | "lamb" | "lars"`` with ``optimizer_kwargs`` and
    the class of ``optimizer/`` built with the same values are one update:
    bitwise the same params and opt_state after three steps, dp2, clip on."""
    from paddle_hackathon_tpu import nn
    cls, hp = _SHORTHAND_CASES[kind]
    ids, labels = _data()

    def run(as_instance):
        paddle.seed(0)
        model = GPTForCausalLM(_tiny())
        mesh = parallel.create_mesh({"dp": 2}, devices=jax.devices()[:2])
        if as_instance:
            kw = dict(optimizer=getattr(paddle.optimizer, cls)(
                learning_rate=1e-2, parameters=model.parameters(),
                grad_clip=nn.ClipGradByGlobalNorm(1.0), **hp))
        else:
            kw = dict(optimizer=kind, optimizer_kwargs=hp,
                      learning_rate=1e-2, grad_clip_norm=1.0)
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, **kw)
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(i))
        return jax.tree.map(np.asarray, (state["params"],
                                         state["opt_state"]))

    try:
        a, b = run(False), run(True)
    finally:
        parallel.set_mesh(None)
    assert set(next(iter(a[1].values()))) == \
        ({"m"} if kind == "lars" else {"m", "v"})
    jax.tree.map(np.testing.assert_array_equal, a, b)


def test_sharded_step_takes_adamw_and_matches_eager():
    """``make_sharded_train_step(optimizer=AdamW(...))``: three compiled
    steps against three eager ``AdamW.step()`` calls fed the gradients the
    step saw, decay mask and global-norm clip included; the masked leaves
    (biases, LayerNorm) are left undecayed."""
    from paddle_hackathon_tpu import nn
    ids, labels = _data(batch=4)
    lr, wd = 1e-2, 0.5

    def twin(weight_decay):
        paddle.seed(0)
        model = GPTForCausalLM(_tiny())
        undecayed = {p.name for k, p in model.named_parameters()
                     if k.endswith("bias") or ".ln_" in k}
        return model, undecayed, paddle.optimizer.AdamW(
            learning_rate=lr, weight_decay=weight_decay,
            parameters=model.parameters(),
            apply_decay_param_fun=lambda n: n not in undecayed,
            grad_clip=nn.ClipGradByGlobalNorm(1.0))

    model, undecayed, opt = twin(wd)
    eager, _, eager_opt = twin(wd)
    nodecay, _, nodecay_opt = twin(0.0)
    mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    try:
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, optimizer=opt,
            loss_fn=_ce_loss)
        _, buffers = model.functional_state()
        grads_of = jax.jit(jax.grad(lambda p: _ce_loss(
            model, p, buffers, (ids, labels), None)))
        for i in range(3):
            grads = grads_of(state["params"])
            state, _ = step(state, ids, labels, jax.random.key(i))
            for m, o in ((eager, eager_opt), (nodecay, nodecay_opt)):
                for k, p in m.named_parameters():
                    p._grad_value = grads[k]
                o.step()
    finally:
        parallel.set_mesh(None)
    names = dict(model.named_parameters())
    decayed_moved = 0
    for k, p in eager.named_parameters():
        got = np.asarray(state["params"][k])
        np.testing.assert_allclose(got, np.asarray(p._value), rtol=2e-5,
                                   atol=2e-7, err_msg=k)
        free = np.asarray(dict(nodecay.named_parameters())[k]._value)
        if names[k].name in undecayed:
            np.testing.assert_allclose(got, free, rtol=2e-5, atol=2e-7,
                                       err_msg=k)
        else:
            decayed_moved += np.abs(got - free).max() > 1e-4
    assert decayed_moved == len(names) - len(undecayed) == 10


class TestExpertParallelComposition:
    """VERDICT r2 #8: MoE expert parallelism INSIDE the sharded train step
    — 'ep' mesh axis, experts sharded, dispatch/combine lowered by GSPMD
    to the all_to_all pair the reference implements by hand
    (operators/collective/global_scatter_op.cc:20)."""

    def _run(self, mesh_dims, zero_stage=0, experts=4):
        ids_labels = _data(batch=16)
        paddle.seed(3)
        model = GPTForCausalLM(_tiny(
            num_layers=2, moe_num_experts=experts, moe_gate="naive"))
        n = int(np.prod(list(mesh_dims.values())))
        mesh = parallel.create_mesh(mesh_dims, devices=jax.devices()[:n])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            zero_stage=zero_stage, grad_clip_norm=None)
        out = []
        for i in range(3):
            state, loss = step(state, *ids_labels, jax.random.key(0))
            out.append(float(loss))
        return out, state

    def test_ep_composition_matches_single_device(self):
        single, _ = self._run({"dp": 1})
        hybrid, state = self._run({"dp": 2, "ep": 2, "mp": 2})
        np.testing.assert_allclose(hybrid, single, rtol=2e-4)
        spec = state["params"]["gpt.blocks.0.mlp.w1"].sharding.spec
        assert spec[0] == "ep" and "mp" in spec

    def test_ep_with_zero3_sharding(self):
        single, _ = self._run({"dp": 1})
        hybrid, _ = self._run({"ep": 2, "sharding": 2, "mp": 2},
                              zero_stage=3)
        np.testing.assert_allclose(hybrid, single, rtol=2e-4)

    def test_moe_dense_parity_single_expert_topk1(self):
        """A 1-expert top-1 MoE routes every token to the one expert —
        training must behave like a dense FFN of the same shape (the
        reference's global_scatter degenerate case)."""
        ids, labels = _data(batch=8)
        paddle.seed(5)
        model = GPTForCausalLM(_tiny(num_layers=2, moe_num_experts=1,
                                     moe_topk=1, moe_gate="naive",
                                     moe_capacity_factor=8.0))
        mesh = parallel.create_mesh({"dp": 2}, devices=jax.devices()[:2])
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3)
        losses = []
        for i in range(4):
            state, loss = step(state, ids, labels, jax.random.key(i))
            losses.append(float(loss))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]

    def test_moe_aux_loss_included(self):
        """The composed loss must include the load-balance aux term."""
        ids, labels = _data(batch=8)

        def loss_with(gate):
            paddle.seed(5)
            model = GPTForCausalLM(_tiny(num_layers=2, moe_num_experts=4,
                                         moe_gate=gate))
            mesh = parallel.create_mesh({"dp": 1}, devices=jax.devices()[:1])
            step, state = parallel.make_sharded_train_step(
                model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
                grad_clip_norm=None)
            _, loss = step(state, ids, labels, jax.random.key(0))
            return float(loss)

        # gshard gate has aux=True; naive gate contributes zero aux —
        # identical init => any difference is exactly the aux term
        assert loss_with("gshard") > loss_with("naive")


class TestMultisliceDesign:
    """VERDICT r2 missing #4 (heterogeneous comm tier): the DCN x ICI
    placement rule as mesh geometry — the ProcessGroupHeter analog
    (ProcessGroupHeter.cc: slow tier for gradient traffic across
    clusters, fast tier inside). Emulated as 2 'slices' x 4 devices."""

    def test_dcn_axis_outermost_and_ici_axes_guarded(self):
        mesh = parallel.create_multislice_mesh(
            2, {"sharding": 2, "mp": 2}, devices=jax.devices()[:8])
        try:
            assert mesh.axis_names[0] == "dp"      # DCN axis outermost
            assert mesh.shape["dp"] == 2
            assert parallel.dcn_traffic_axes(mesh) == ("dp",)
            with pytest.raises(ValueError, match="ICI|activation"):
                parallel.create_multislice_mesh(
                    2, {"dp": 4}, dcn_axis="mp",
                    devices=jax.devices()[:8])
        finally:
            parallel.set_mesh(None)

    def test_train_step_over_emulated_two_slice_mesh(self):
        """Full hybrid step on the 2-slice mesh: grad psum rides the DCN
        axis, TP/ZeRO collectives stay in-slice; loss matches the
        single-device run exactly (geometry changes placement, not
        math)."""
        ids, labels = _data(batch=16)

        def run(mesh):
            paddle.seed(11)
            model = GPTForCausalLM(_tiny(num_layers=2))
            step, state = parallel.make_sharded_train_step(
                model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
                zero_stage=3, grad_clip_norm=None)
            out = []
            for i in range(3):
                state, loss = step(state, ids, labels, jax.random.key(0))
                out.append(float(loss))
            return out

        try:
            two_slice = run(parallel.create_multislice_mesh(
                2, {"sharding": 2, "mp": 2}, devices=jax.devices()[:8]))
            single = run(parallel.create_mesh(
                {"dp": 1}, devices=jax.devices()[:1]))
            np.testing.assert_allclose(two_slice, single, rtol=2e-4)
        finally:
            parallel.set_mesh(None)
