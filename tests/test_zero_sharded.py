"""ZeRO-sharded optimizer state (ROADMAP item 4's training half).

`zero_stage>=1` shards every optimizer moment (and the optional f32
master copy) 1/dp over the mesh's 'sharding'/'dp' axis in BOTH
one-program trainers — `make_sharded_train_step` / `auto_parallel.Engine`
and the hapi `Model.fit` donated K-step scan — via the shard-aware
`Optimizer.functional_update` path: grads constraint-pinned onto the
moment sharding (the pending dp psum fuses into a reduce-scatter),
shard-local update, per-tensor param all-gathers.

Parity contract pinned here:
- the UPDATE MATH is bit-exact sharded-vs-replicated on identical
  gradient inputs (elementwise rules slice/gather transparently); the
  global-norm clip's scale is the one reduction in it, summed shard by
  shard under jax 0.9.0's partitioner, and is held to a stated tolerance;
- end-to-end fit series match the replicated update to a stated f32
  tolerance: the reduce-scatter changes the grad-psum summation order
  by design (~1 ulp/step reassociation), which is the only difference —
  pinned by comparing against the SAME program with the sharding specs
  neutralized (moments replicated), where the first several steps stay
  bit-identical;
- the sharded state flows through `parallel/checkpointing.py`
  UNCHANGED: `restore_like` re-shards a dp=4-written ZeRO checkpoint
  onto a dp=2 resume for free.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import hapi, io, nn, parallel
from paddle_hackathon_tpu import optimizer as optim
from paddle_hackathon_tpu.parallel.sharding import (ZeroShardInfo,
                                                    state_bytes,
                                                    zero_data_axis)


@pytest.fixture(autouse=True)
def _restore_mesh():
    from paddle_hackathon_tpu.parallel import api as mesh_api
    prev = mesh_api.get_mesh()
    yield
    mesh_api._current_mesh = prev


def _mlp(seed=7):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 2))


class _DS(io.Dataset):
    def __init__(self, n=64, d=16, seed=0):
        r = np.random.RandomState(seed)
        self.x = r.randn(n, d).astype(np.float32)
        self.y = (self.x.sum(1) > 0).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _fit(zero_stage=0, k=4, master=False, dp=4, epochs=1, seed=7,
         checkpoint=None, num_iters=None, log_freq=4, zero_offload=False):
    parallel.create_mesh({"dp": dp}, devices=jax.devices()[:dp])
    np.random.seed(0)
    net = _mlp(seed)
    m = hapi.Model(net)
    m.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                   parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    losses = []

    class Rec(hapi.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            losses.append(float(logs["loss"]))

    m.fit(_DS(), epochs=epochs, batch_size=8, verbose=0, shuffle=False,
          jit_compile=True, steps_per_execution=k, log_freq=log_freq,
          callbacks=[Rec()], zero_stage=zero_stage, master_weights=master,
          checkpoint=checkpoint, num_iters=num_iters,
          zero_offload=zero_offload)
    assert m._fit_used_compiled
    return losses, m


# ---------------------------------------------------------------------------
# fast: spec/update units (host-light)
# ---------------------------------------------------------------------------


def test_zero_data_axis_and_moment_spec():
    """'sharding' wins over 'dp'; dp-only meshes shard over dp (the old
    behavior replicated there); specs extend the param's TP dims and
    skip indivisible shapes."""
    assert zero_data_axis(None) is None
    mesh_dp = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    assert zero_data_axis(mesh_dp) == "dp"
    mesh_sh = parallel.create_mesh({"sharding": 2, "dp": 2},
                                   devices=jax.devices()[:4])
    assert zero_data_axis(mesh_sh) == "sharding"
    mesh_mp = parallel.create_mesh({"mp": 4}, devices=jax.devices()[:4])
    assert zero_data_axis(mesh_mp) is None

    si = ZeroShardInfo(mesh=mesh_dp, axis="dp")
    assert si.moment_spec((32, 8)) == ("dp", None)
    # nothing divisible -> replicated moment (graceful per-param)
    assert si.moment_spec((3,)) == (None,)
    # absent mesh axes are filtered out of an existing spec
    assert si.moment_spec((32, 8), existing=(None, "mp")) == ("dp", None)
    # TP dim preserved, ZeRO axis lands on the next divisible dim
    mesh_mix = parallel.create_mesh({"dp": 2, "mp": 2},
                                    devices=jax.devices()[:4])
    si2 = ZeroShardInfo(mesh=mesh_mix, axis="dp")
    assert si2.moment_spec((32, 8), existing=("mp", None)) == ("mp", "dp")


@pytest.mark.parametrize("clip", [False, True], ids=["no_clip", "clip"])
def test_functional_update_sharded_is_bit_exact_and_sharded(clip):
    """The shard-aware `Optimizer.functional_update` path on identical
    grad inputs, while the new moments come back on their 1/dp slices
    (the constraint pins keep the partitioner from re-replicating them).

    The UPDATE MATH is bitwise the replicated path's: asserted with the
    clip off.  With the global-norm clip on, jax 0.9.0's partitioner
    (Shardy) propagates the moment sharding back through the clip's
    multiply and sums the squares shard by shard, so the clip SCALE is
    the same sum in another order; everything after it inherits that
    reassociation (measured: params within 8 ulp, moments within 5) and
    is held to 1e-6 relative / 1e-8 absolute."""
    mesh = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    net = _mlp()
    plist = net.parameters()
    opt = optim.Adam(learning_rate=1e-2, parameters=plist,
                     grad_clip=nn.ClipGradByGlobalNorm(1.0) if clip
                     else None)
    vals = [p._value for p in plist]
    rng = np.random.RandomState(0)
    grads = [jnp.asarray(rng.randn(*v.shape).astype(np.float32))
             for v in vals]
    states = opt.functional_state(plist)
    si = ZeroShardInfo(mesh=mesh, axis="dp").with_param_specs(
        [(None,) * v.ndim for v in vals])

    def upd(shard_info):
        return jax.jit(lambda v, g, s: opt.functional_update(
            v, g, s, jnp.float32(1e-2), jnp.int32(1), params=plist,
            shard_info=shard_info))(vals, grads, states)

    def same(a, b):
        if clip:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    nv_r, ns_r = upd(None)
    nv_s, ns_s = upd(si)
    for a, b in zip(nv_r, nv_s):
        same(a, b)
    for s_r, s_s in zip(ns_r, ns_s):
        for key in s_r:
            same(s_r[key], s_s[key])
    # the (16, 32) fc1 weight's moments own a 1/4 slice each; the
    # replicated path's stay whole
    m0 = ns_s[0]["moment1"]
    assert m0.sharding.spec[0] == "dp"
    assert m0.addressable_shards[0].data.shape == (4, 32)
    assert ns_r[0]["moment1"].addressable_shards[0].data.shape == (16, 32)
    logical, per_dev = state_bytes(ns_s)
    assert per_dev < logical  # genuinely sharded somewhere


def test_master_weights_slot_updates_in_f32():
    """`master_weights=True`: the f32 master slot advances and the new
    param is exactly its cast — bf16 compute params, f32 accumulation."""
    mesh = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    net = _mlp()
    plist = net.parameters()
    for p in plist:
        p._set_value(p._value.astype(jnp.bfloat16))
    opt = optim.Adam(learning_rate=1e-2, parameters=plist)
    vals = [p._value for p in plist]
    rng = np.random.RandomState(0)
    grads = [jnp.asarray(rng.randn(*v.shape).astype(np.float32))
             for v in vals]
    si = ZeroShardInfo(mesh=mesh, axis="dp", master_weights=True
                       ).with_param_specs([(None,) * v.ndim for v in vals])
    states = []
    for p, st in zip(plist, opt.functional_state(plist)):
        st = dict(st)
        st["master"] = jnp.copy(p._value.astype(jnp.float32))
        states.append(st)
    nv, ns = jax.jit(lambda v, g, s: opt.functional_update(
        v, g, s, jnp.float32(1e-2), jnp.int32(1), params=plist,
        shard_info=si))(vals, grads, states)
    for p, new_p, st in zip(plist, nv, ns):
        assert new_p.dtype == jnp.bfloat16
        assert st["master"].dtype == jnp.float32
        # the bf16 param IS the cast of the f32 master (no second rule)
        np.testing.assert_array_equal(
            np.asarray(new_p),
            np.asarray(st["master"].astype(jnp.bfloat16)))
        # master moved away from the (bf16-castable) start value
        assert not (np.asarray(st["master"])
                    == np.asarray(p._value.astype(jnp.float32))).all()


def test_sharded_step_state_bytes_and_gauge():
    """`make_sharded_train_step(zero_stage=1)` on a dp-only mesh places
    the moments 1/dp (the old code replicated there) and sets the
    `train_opt_state_bytes{path,sharded}` gauge pair — placement only,
    no program compile."""
    mesh = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    model = _mlp()

    def loss_fn(model, params, buffers, batch, rng):
        return jnp.float32(0)

    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=None, zero_stage=1, loss_fn=loss_fn)
    logical, per_dev = state_bytes(state["opt_state"])
    # <= (1/dp + eps): everything shards 1/4 except the indivisible
    # (2,)-shaped fc2 bias moments (16 replicated bytes)
    assert per_dev <= logical / 4 + 16
    from paddle_hackathon_tpu.observability import get_registry
    fam = get_registry().get("train_opt_state_bytes")
    vals = {dict(c.labels)["sharded"]: c.value for c in fam.children()
            if dict(c.labels).get("path") == "sharded_step"
            and "sharded" in dict(c.labels)}
    assert vals["false"] == logical and vals["true"] == per_dev
    # the placement split (PR 18): everything device-resident here
    pl = {dict(c.labels)["placement"]: c.value for c in fam.children()
          if dict(c.labels).get("path") == "sharded_step"
          and "placement" in dict(c.labels)}
    assert pl["device"] == per_dev and pl["host"] == 0


def test_compiled_trainer_zero_state_flows_through_checkpoint_flat():
    """The hapi trainer's ZeRO state (sharded moments + master) keeps
    the UNCHANGED flat checkpoint namespace (`opt::i::slot`), so
    `parallel/checkpointing.py` persists and re-shards it with zero new
    code — build-only, the donated program is never run."""
    parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    net = _mlp()
    m = hapi.Model(net)
    m.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                   parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    from paddle_hackathon_tpu.hapi.compiled import CompiledTrainer
    tr = CompiledTrainer(m, zero_stage=1, master_weights=True)
    assert tr._zero is not None and tr._zero.axis == "dp"
    flat = tr.checkpoint_flat()
    assert "opt::0::master" in flat and "opt::0::moment1" in flat
    mom = flat["opt::0::moment1"]
    assert "dp" in tuple(mom.sharding.spec)
    from paddle_hackathon_tpu.parallel.checkpointing import (
        flatten_train_state, unflatten_train_state)
    params, opt_states, step = unflatten_train_state(flat)
    assert sorted(opt_states[0]) == ["master", "moment1", "moment2"]
    again = flatten_train_state(params, opt_states, step)
    assert set(again) == set(flat)


def test_eager_group_sharded_os_matches_plain_adam():
    """The eager `group_sharded_parallel` 'os' path now runs the SAME
    functional sharded update the compiled trainers compile (not just
    sharded placement): accumulators live 1/N-sharded and the weights
    stay bitwise equal to plain Adam."""
    parallel.create_mesh({"sharding": 4}, devices=jax.devices()[:4])
    from paddle_hackathon_tpu.core.tensor import Tensor
    rng = np.random.RandomState(0)
    x = Tensor(rng.randn(8, 16).astype(np.float32))
    y = Tensor(rng.randn(8, 2).astype(np.float32))

    def train(shard_level):
        net = _mlp(3)
        opt = optim.Adam(learning_rate=1e-2, parameters=net.parameters())
        if shard_level:
            net, opt, _ = parallel.group_sharded_parallel(
                net, opt, level=shard_level)
        for _ in range(3):
            loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
        return net, opt

    net_a, opt_a = train("os")
    net_b, _ = train(None)
    wa = {k: np.asarray(v.numpy()) for k, v in net_a.state_dict().items()}
    wb = {k: np.asarray(v.numpy()) for k, v in net_b.state_dict().items()}
    for k in wa:
        np.testing.assert_array_equal(wa[k], wb[k])
    acc = opt_a._accumulators[id(net_a.parameters()[0])]
    assert "sharding" in tuple(acc["moment1"].sharding.spec)


def test_sharded_step_hlo_gathers_params_per_tensor():
    """The compiled ZeRO step must contain the param all-gathers (the
    update really runs on 1/dp slices) as INDEPENDENT per-tensor ops —
    one fused gather would serialize step k+1's forward on the whole
    update.  (The grad reduce-scatter lowers as reduce-scatter on TPU;
    this jaxlib's CPU backend decomposes it to all-to-all+all-reduce, so
    the assert accepts either spelling.)"""
    mesh = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    model = _mlp()

    def loss_fn(model, params, buffers, batch, rng):
        from paddle_hackathon_tpu.core.tensor import Tensor
        from paddle_hackathon_tpu.nn.layer import functional_call
        ids, labels = batch
        out = functional_call(model, params, (Tensor(ids),),
                              buffers=buffers)
        lg = out._value if hasattr(out, "_value") else out
        return jnp.mean((lg - labels) ** 2)

    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=None, zero_stage=1, loss_fn=loss_fn)
    x = jnp.zeros((8, 16), jnp.float32)
    y = jnp.zeros((8, 2), jnp.float32)
    compiled = step._jitted.lower(
        state["params"], state["opt_state"], state["step"], (x, y),
        jax.random.key(0), jnp.float32(1e-2)).compile()
    text = compiled.as_text()
    from paddle_hackathon_tpu.parallel.planner import \
        collective_bytes_from_hlo
    coll = collective_bytes_from_hlo(text)
    assert coll.get("all-gather", 0) > 0
    assert (coll.get("reduce-scatter", 0) > 0
            or coll.get("all-to-all", 0) > 0
            or coll.get("all-reduce", 0) > 0)
    # per-tensor gathers: at least one all-gather per weight matrix
    # (4 params in the MLP; >= 2 distinct gather ops proves no single
    # fused barrier gather)
    n_gathers = sum(1 for line in text.splitlines()
                    if "all-gather(" in line or "all-gather-start(" in line)
    assert n_gathers >= 2, text[:2000]


def test_zero_ragged_batch_trains_replicated_and_warns():
    """A batch that cannot shard over the data axes (the ragged final
    batch under the default drop_last=False, or a plain indivisible
    batch size) must NOT crash the fit — and must not be swallowed by
    the trace-failure fallback into silent eager training either: the
    trainer selects a replicated-batch program flavor (same update, no
    dp compute scaling for that superstep) and warns once."""
    parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    np.random.seed(0)
    net = _mlp()
    m = hapi.Model(net)
    m.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                   parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    with pytest.warns(RuntimeWarning, match="REPLICATED batch"):
        logs = m.fit(_DS(n=18), epochs=1, batch_size=6, verbose=0,
                     shuffle=False, jit_compile=True, zero_stage=1)
    assert m._fit_used_compiled
    assert np.isfinite(logs["loss"])
    assert m._optimizer._step_count == 3
    # the moments still live sharded — only the batch replicated
    acc = m._optimizer._accumulators[id(m._optimizer._parameter_list[0])]
    assert "dp" in tuple(acc["moment1"].sharding.spec)


def test_perf_gate_zero_sharding_evidence():
    """compare_zero_sharding fails vacuous ZeRO rows (single-device run,
    or an unshrunk opt-state ratio) and passes real evidence."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from perf_gate import compare_zero_sharding
    good = {"metric": "hapi_fit_zero1_tokens_per_sec", "zero_stage": 1,
            "dp": 8, "opt_state_bytes_vs_replicated": 0.125}
    single = {"metric": "z1", "zero_stage": 1, "dp": 1,
              "opt_state_bytes_vs_replicated": 1.0}
    unshrunk = {"metric": "z2", "zero_stage": 1, "dp": 8,
                "opt_state_bytes_vs_replicated": 1.0}
    dense = {"metric": "hapi_fit_tokens_per_sec", "zero_stage": 0,
             "opt_state_bytes_vs_replicated": 1.0}
    assert compare_zero_sharding([good, dense]) == []
    bad = compare_zero_sharding([good, single, unshrunk, dense])
    assert [m for m, _ in bad] == ["z1", "z2"]


# ---------------------------------------------------------------------------
# slow: end-to-end fit drills on the CPU mesh
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_model_fit_zero1_matches_replicated_update(monkeypatch):
    """`Model.fit(zero_stage=1)` vs the IDENTICAL program with the
    sharding specs neutralized (moments replicated): same mesh, same
    batch sharding, so the only delta is the ZeRO pins.  The update is
    elementwise — the loss series stays bit-identical until the grad
    reduce-scatter's reassociation drifts it at the f32 ulp level; pin
    the head exactly and the whole series to 1e-5."""
    l_sh, m_sh = _fit(zero_stage=1)

    import paddle_hackathon_tpu.parallel.sharding as shmod
    orig = shmod._shard_spec_for
    monkeypatch.setattr(
        shmod, "_shard_spec_for",
        lambda shape, mesh, axis="sharding", existing=None:
        tuple(existing) if existing else (None,) * len(shape))
    l_rep, m_rep = _fit(zero_stage=1)
    monkeypatch.setattr(shmod, "_shard_spec_for", orig)

    assert l_sh[:2] == l_rep[:2]
    np.testing.assert_allclose(l_sh, l_rep, rtol=1e-5)
    w_sh = {k: np.asarray(v.numpy())
            for k, v in m_sh.network.state_dict().items()}
    w_rep = {k: np.asarray(v.numpy())
             for k, v in m_rep.network.state_dict().items()}
    for k in w_sh:
        np.testing.assert_allclose(w_sh[k], w_rep[k], rtol=1e-4,
                                   atol=1e-6)
    # the real run's moments are genuinely dp-sharded, 1/4 per chip
    p0 = m_sh._optimizer._parameter_list[0]
    acc = m_sh._optimizer._accumulators[id(p0)]
    assert "dp" in tuple(acc["moment1"].sharding.spec)
    logical, per_dev = state_bytes(
        [m_sh._optimizer._accumulators[id(p)]
         for p in m_sh._optimizer._parameter_list])
    assert per_dev <= logical / 4 + 64  # <= (1/dp + eps) of replicated


@pytest.mark.slow
def test_model_fit_zero1_master_weights_bf16():
    """bf16 compute params + sharded f32 masters: the series tracks the
    all-f32 ZeRO run to bf16 tolerance (the accumulation dtype is the
    stated difference) and params stay bf16 end to end."""
    parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    np.random.seed(0)
    net = _mlp()
    for p in net.parameters():
        p._set_value(p._value.astype(jnp.bfloat16))
    m = hapi.Model(net)
    m.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                   parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    losses = []

    class Rec(hapi.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            losses.append(float(logs["loss"]))

    m.fit(_DS(), epochs=1, batch_size=8, verbose=0, shuffle=False,
          jit_compile=True, steps_per_execution=4, log_freq=4,
          callbacks=[Rec()], zero_stage=1, master_weights=True)
    assert m._fit_used_compiled
    l_f32, _ = _fit(zero_stage=1)
    np.testing.assert_allclose(losses, l_f32, rtol=0.05, atol=0.02)
    for p in net.parameters():
        assert p._value.dtype == jnp.bfloat16
    acc = m._optimizer._accumulators[id(net.parameters()[0])]
    assert acc["master"].dtype == jnp.float32
    assert "dp" in tuple(acc["master"].sharding.spec)


@pytest.mark.slow
def test_engine_zero1_bit_exact_vs_replicated():
    """`Engine.fit` with Strategy(sharding=True, sharding_stage=1) on a
    dp x mp mesh: bit-identical loss series to the unsharded strategy
    (same mesh, same program shape — the Engine feeds the update
    already-reduced grads, so even the pins reassociate nothing)."""
    from paddle_hackathon_tpu.parallel.auto_parallel import (Engine,
                                                             ProcessMesh,
                                                             Strategy)

    def run(sharding):
        np.random.seed(11)
        paddle.seed(3)
        net = _mlp(3)
        pm = ProcessMesh([[0, 1], [2, 3]], dim_names=["dp", "mp"])
        eng = Engine(net, loss=nn.CrossEntropyLoss(),
                     optimizer=optim.Adam(learning_rate=1e-2,
                                          parameters=net.parameters()),
                     process_mesh=pm,
                     strategy=Strategy(sharding=sharding,
                                       sharding_stage=1))
        hist = eng.fit(_DS(), epochs=1, batch_size=8, verbose=0)
        return hist["loss"], eng

    l_rep, _ = run(False)
    l_sh, eng = run(True)
    assert l_sh == l_rep
    st = eng._state["opt_states"][0]
    assert "dp" in tuple(st["moment1"].sharding.spec)
    logical, per_dev = state_bytes(eng._state["opt_states"])
    assert per_dev < logical


@pytest.mark.slow
def test_zero_checkpoint_resumes_across_changed_dp(tmp_path):
    """The PR 11 crash-drill shape on ZeRO state: a dp=4 fit checkpoints
    mid-run through `parallel/checkpointing.py` UNCHANGED; a dp=2 fit
    resumes from it — `restore_like` places every sharded moment (and
    the step/cursor/RNG) with the NEW mesh's shardings.  The restored
    state is bitwise the checkpointed bytes; the continued series tracks
    an uninterrupted dp=2 run to f32 reassociation tolerance (dp=4's
    first half sums grads in a different order than dp=2's)."""
    ckdir = tmp_path / "zck"
    # half run on dp=4 (saves at the log_freq fetches + final flush)
    l_head, _ = _fit(zero_stage=1, dp=4, checkpoint=str(ckdir),
                     num_iters=4, k=2, log_freq=2)
    from paddle_hackathon_tpu.parallel.checkpointing import load_latest
    flat_host, manifest = load_latest(str(ckdir))
    assert manifest["step"] == 4 and "opt::0::moment1" in flat_host

    # resume on dp=2: placement must be bitwise the checkpoint...
    parallel.create_mesh({"dp": 2}, devices=jax.devices()[:2])
    net = _mlp(7)
    m = hapi.Model(net)
    m.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                   parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    from paddle_hackathon_tpu.hapi.compiled import CompiledTrainer
    tr = CompiledTrainer(m, zero_stage=1)
    from paddle_hackathon_tpu.parallel.checkpointing import restore_like
    placed, _ = restore_like(str(ckdir), tr.checkpoint_flat())
    mom = placed["opt::0::moment1"]
    assert tuple(mom.sharding.mesh.axis_names) == ("dp",)
    assert mom.sharding.mesh.devices.size == 2
    np.testing.assert_array_equal(np.asarray(mom),
                                  flat_host["opt::0::moment1"])

    # ...and the resumed fit continues the series
    l_resumed, _ = _fit(zero_stage=1, dp=2, checkpoint=str(ckdir),
                        num_iters=8, k=2, log_freq=2)
    l_full, _ = _fit(zero_stage=1, dp=2, num_iters=8, k=2, log_freq=2)
    assert len(l_resumed) == 4  # steps 4..7 only; 0..3 fast-forwarded
    np.testing.assert_allclose(l_resumed, l_full[4:], rtol=1e-4)


@pytest.mark.slow
def test_zero_offload_checkpoint_resumes_across_changed_dp(tmp_path):
    """The PR 11 crash-drill shape on OFFLOADED ZeRO state: a dp=4
    `Model.fit(zero_stage=1, zero_offload=True)` checkpoints its host
    numpy moments through the UNCHANGED flat namespace
    (`opt::i::slot`); a dp=2 offloaded trainer resumes from it —
    `restore_like` keeps numpy likes on the host (bitwise the
    checkpointed bytes, no device placement), and the continued series
    tracks an uninterrupted dp=2 offloaded run."""
    ckdir = tmp_path / "zoffck"
    l_head, _ = _fit(zero_stage=1, zero_offload=True, dp=4,
                     checkpoint=str(ckdir), num_iters=4, k=2, log_freq=2)
    from paddle_hackathon_tpu.parallel.checkpointing import load_latest
    flat_host, manifest = load_latest(str(ckdir))
    assert manifest["step"] == 4 and "opt::0::moment1" in flat_host

    # resume on dp=2: the offloaded trainer's checkpoint template offers
    # numpy likes, so restore_like must hand back HOST numpy bitwise
    parallel.create_mesh({"dp": 2}, devices=jax.devices()[:2])
    net = _mlp(7)
    m = hapi.Model(net)
    m.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                   parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    from paddle_hackathon_tpu.hapi.compiled import CompiledTrainer
    tr = CompiledTrainer(m, zero_stage=1, zero_offload=True)
    flat = tr.checkpoint_flat()
    assert isinstance(flat["opt::0::moment1"], np.ndarray)
    from paddle_hackathon_tpu.parallel.checkpointing import restore_like
    placed, _ = restore_like(str(ckdir), flat)
    mom = placed["opt::0::moment1"]
    assert isinstance(mom, np.ndarray) and not isinstance(mom, jax.Array)
    np.testing.assert_array_equal(mom, flat_host["opt::0::moment1"])

    # ...and the resumed offloaded fit continues the series
    l_resumed, _ = _fit(zero_stage=1, zero_offload=True, dp=2,
                        checkpoint=str(ckdir), num_iters=8, k=2,
                        log_freq=2)
    l_full, _ = _fit(zero_stage=1, zero_offload=True, dp=2, num_iters=8,
                     k=2, log_freq=2)
    assert len(l_resumed) == 4  # steps 4..7 only; 0..3 fast-forwarded
    np.testing.assert_allclose(l_resumed, l_full[4:], rtol=1e-4)


@pytest.mark.slow
def test_zero_fit_clean_under_donation_sanitizer():
    """The Pre-ZeRO checklist's dynamic backstop as a repeatable test:
    one `Model.fit(zero_stage=1)` superstep and one sharded `Engine.fit`
    epoch run clean under the donation sanitizer — no read of a donated
    buffer anywhere in the new reduce-scatter/update/gather flow."""
    from paddle_hackathon_tpu.observability import sanitizers
    with sanitizers.donation_sanitizer():
        _fit(zero_stage=1, num_iters=4, k=4)
        from paddle_hackathon_tpu.parallel.auto_parallel import (
            Engine, ProcessMesh, Strategy)
        np.random.seed(11)
        net = _mlp(3)
        pm = ProcessMesh([0, 1, 2, 3], dim_names=["dp"])
        eng = Engine(net, loss=nn.CrossEntropyLoss(),
                     optimizer=optim.Adam(learning_rate=1e-2,
                                          parameters=net.parameters()),
                     process_mesh=pm,
                     strategy=Strategy(sharding=True, sharding_stage=1))
        eng.fit(_DS(), epochs=1, batch_size=8, verbose=0)
