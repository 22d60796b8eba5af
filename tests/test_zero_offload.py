"""ZeRO-offload + backward/reduce-scatter overlap (PR 18 tentpole).

Contract pinned here:

- **Offload is bit-exact per update.**  `zero_offload=True` splits the
  step into a grads-only device program (forward + backward + the SAME
  replicated global clip preamble as the resident path) and a per-tensor
  streamed update (h2d -> the SAME pinned update body -> d2h through
  `io.TransferRing`).  On identical gradient inputs the update math is
  bitwise the resident ZeRO step's (pinned with the clip off);
  opt-state device bytes drop to ~0 while `placement=host` carries the
  footprint.  (With the clip on, the split program materializes the
  all-reduced gradient at the program boundary and sums its squares
  whole, where the fused one reduce-scatters and sums slices: the clip
  scale is reassociated — stated, tested at tolerance.)
- **Overlap is explicit emission, series-tolerance numerics.**
  `grad_overlap=True` pins each gradient to its moment sharding straight
  after the backward (BEFORE the clip): the traced program carries the
  per-tensor sharding constraints ahead of the clip reduction, the
  compiled module schedules >=2 independent (distinct-channel)
  grad-shaped scatter collectives ahead of the clip scale, and the loss
  series matches the fused order to f32 reassociation tolerance.
- **ZeRO x pp composes.**  `zero_stage>=1` with a 'pp' axis shards the
  stacked per-stage moments over BOTH pp (the stage dim) and the data
  axis; offloaded composed state lives in host numpy; dp-reshard resume
  round-trips the composed state bitwise through `restore_like`.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import hapi, io, nn, parallel
from paddle_hackathon_tpu import optimizer as optim



@pytest.fixture(autouse=True)
def _restore_mesh():
    from paddle_hackathon_tpu.parallel import api as mesh_api
    prev = mesh_api.get_mesh()
    yield
    mesh_api._current_mesh = prev


def _mlp(seed=7):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 2))


def _loss_fn(model, params, buffers, batch, rng):
    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.nn.layer import functional_call
    ids, labels = batch
    out = functional_call(model, params, (Tensor(ids),), buffers=buffers)
    lg = out._value if hasattr(out, "_value") else out
    return jnp.mean((lg - labels) ** 2)


_rng = np.random.RandomState(0)
_X = _rng.randn(8, 16).astype(np.float32)
_Y = _rng.randn(8, 2).astype(np.float32)


def _run_sharded(nsteps=2, mesh=None, **kw):
    mesh = mesh or parallel.create_mesh({"dp": 4},
                                        devices=jax.devices()[:4])
    model = _mlp()
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=None, zero_stage=1, loss_fn=_loss_fn, **kw)
    losses = []
    for _ in range(nsteps):
        state, loss = step(state, jnp.asarray(_X), jnp.asarray(_Y),
                           jax.random.key(0), lr=1e-2)
        losses.append(float(loss))
    return losses, state, step


# ---------------------------------------------------------------------------
# fast: TransferRing units (pure host)
# ---------------------------------------------------------------------------


def test_transfer_ring_depth_semantics():
    """depth-bounded FIFO: push returns the oldest entry once more than
    `depth` are in flight; depth=0 is fully synchronous; drain yields
    the in-flight tail in order."""
    ring = io.TransferRing(depth=1)  # classic double-buffer
    assert ring.push("a") is None
    assert ring.push("b") == "a"
    assert ring.push("c") == "b"
    assert list(ring.drain()) == ["c"]
    assert len(ring) == 0

    sync = io.TransferRing(depth=0)
    assert sync.push(1) == 1            # nothing ever stays in flight
    assert list(sync.drain()) == []

    deep = io.TransferRing(depth=3)
    assert [deep.push(i) for i in range(5)] == [None, None, None, 0, 1]
    assert list(deep.drain()) == [2, 3, 4]


def test_transfer_ring_d2h_roundtrip_bitwise():
    """start_d2h/finish_d2h: async-copy hints + np materialization keep
    bytes bitwise; non-array leaves pass through untouched."""
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16), "n": 7}}
    staged = io.start_d2h(tree)
    out = io.finish_d2h(staged)
    assert isinstance(out["a"], np.ndarray)
    np.testing.assert_array_equal(out["a"],
                                  np.arange(12, dtype=np.float32)
                                  .reshape(3, 4))
    assert out["b"]["c"].dtype == jnp.bfloat16  # dtype preserved
    assert out["b"]["n"] == 7


def test_device_prefetch_rides_the_ring():
    """`io.device_prefetch` (the double-buffer the offload pipe
    generalizes) still yields every batch exactly once, in order."""
    batches = [np.full((2,), i, np.float32) for i in range(5)]
    for size in (1, 2, 3):
        got = list(io.device_prefetch(iter(batches), size=size))
        assert len(got) == 5
        for i, b in enumerate(got):
            np.testing.assert_array_equal(np.asarray(b), batches[i])


# ---------------------------------------------------------------------------
# fast: offload update bitwise + placement evidence
# ---------------------------------------------------------------------------


def _entry(compiled_text):
    """The compiled entry computation's instructions, in schedule order."""
    body = re.search(r"^ENTRY [^\n]*\n(.*?)^}", compiled_text,
                     re.S | re.M).group(1)
    return [l.strip() for l in body.splitlines() if " = " in l]


def _clip_reads_slices(entry):
    """How many of the clip's instructions read only this device's own
    slice of a gradient (`partition-id` is the slice's offset)."""
    return sum(1 for l in entry if "/clip/" in l and "%partition-id" in l)


@pytest.mark.parametrize("clip", [None, 1.0], ids=["no_clip", "clip"])
def test_sharded_step_offload_bitwise_and_host_placement(clip):
    """Two full steps against the resident ZeRO run; the offloaded state
    is host numpy; the placement gauge reports device 0 / host > 0.

    Update math and transport are BITWISE: with the clip off, params,
    moments and losses are equal bit for bit.  With the clip on, the
    two compiled programs sum the squares in different orders: the
    resident step's clip reads each device's slice of the reduced
    gradients (jax 0.9.0's partitioner propagates the moment sharding
    back through the clip's multiply), the offload path's grads-only
    program hands whole gradients over its boundary and sums those.
    The scale differs in its last bit and the state after two steps
    by what that reassociation gives: measured 3.0e-8 absolute on the
    params, 9.1e-7 relative on the moments; held to 1e-7 / 1e-5."""
    l_res, s_res, step_res = _run_sharded(2, grad_clip_norm=clip)
    l_off, s_off, step_off = _run_sharded(2, grad_clip_norm=clip,
                                          zero_offload=True)

    def same(a, b):
        if clip is None:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)

    same(np.asarray(l_res), np.asarray(l_off))
    for k in s_res["params"]:
        same(np.asarray(s_res["params"][k]), np.asarray(s_off["params"][k]))
        for sl, v in s_off["opt_state"][k].items():
            assert isinstance(v, np.ndarray) and not isinstance(
                v, jax.Array)
            same(np.asarray(s_res["opt_state"][k][sl]), v)
            assert s_res["opt_state"][k][sl].sharding.memory_kind == "device"
    # the resident moments sit on the device, each on its 1/dp slice
    m = s_res["opt_state"]["0.weight"]["m"]
    assert m.sharding.spec[0] == "dp"
    assert m.addressable_shards[0].data.shape == (4, 32)
    if clip is not None:
        batch = (jnp.asarray(_X), jnp.asarray(_Y))
        key, lr = jax.random.key(0), jnp.float32(1e-2)
        resident = step_res._jitted.lower(
            s_res["params"], s_res["opt_state"], s_res["step"], batch, key,
            lr).compile().as_text()
        grads_only = step_off._jitted.lower(
            s_off["params"], s_off["step"], batch, key,
            lr).compile().as_text()
        # 3 of the MLP's 4 tensors split over dp=4 (the (2,) bias cannot)
        assert _clip_reads_slices(_entry(resident)) >= 3
        assert _clip_reads_slices(_entry(grads_only)) == 0
    from paddle_hackathon_tpu.observability import get_registry
    fam = get_registry().get("train_opt_state_bytes")
    pl = {dict(c.labels)["placement"]: c.value for c in fam.children()
          if dict(c.labels).get("path") == "sharded_step"
          and "placement" in dict(c.labels)}
    assert pl["device"] == 0 and pl["host"] > 0
    # the replicated baseline still counts the offloaded slots: the
    # shrink ratio the bench derives stays ~0, never vacuous 0/0
    sh = {dict(c.labels)["sharded"]: c.value for c in fam.children()
          if dict(c.labels).get("path") == "sharded_step"
          and "sharded" in dict(c.labels)}
    assert sh["false"] >= pl["host"] and sh["true"] == 0


def test_sharded_step_offload_master_weights_bitwise():
    """f32 masters ride the same host slots: series parity holds and the
    master slot exists host-side."""
    l_res, _, _ = _run_sharded(2, master_weights=True)
    l_off, s_off, _ = _run_sharded(2, master_weights=True,
                                   zero_offload=True)
    assert l_res == l_off
    assert all("master" in s_off["opt_state"][k]
               and isinstance(s_off["opt_state"][k]["master"], np.ndarray)
               for k in s_off["opt_state"])


def test_offload_inert_warns():
    """`zero_offload=True` with no active ZeRO axis warns and keeps the
    state device-resident (never a silent no-op)."""
    mesh = parallel.create_mesh({"mp": 4}, devices=jax.devices()[:4])
    with pytest.warns(RuntimeWarning, match="device-resident"):
        _, state, _ = _run_sharded(0, mesh=mesh, zero_offload=True)
    assert all(isinstance(v, jax.Array)
               for st in state["opt_state"].values() for v in st.values())


def test_group_sharded_offload_flag_warns():
    """The eager wrapper's reference `offload=True` flag points at the
    compiled offload path instead of silently accepting."""
    parallel.create_mesh({"sharding": 4}, devices=jax.devices()[:4])
    net = _mlp(3)
    opt = optim.Adam(learning_rate=1e-2, parameters=net.parameters())
    with pytest.warns(UserWarning, match="zero_offload=True"):
        parallel.group_sharded_parallel(net, opt, level="os", offload=True)


# ---------------------------------------------------------------------------
# fast: overlap evidence (lowering order + compiled collectives)
# ---------------------------------------------------------------------------


def _traced(overlap):
    mesh = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
    model = _mlp()
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=None, zero_stage=1, loss_fn=_loss_fn,
        grad_clip_norm=1.0, grad_overlap=overlap)
    return step._jitted.trace(
        state["params"], state["opt_state"], state["step"],
        (jnp.asarray(_X), jnp.asarray(_Y)), jax.random.key(0),
        jnp.float32(1e-2))


_GRAD_SHAPES = ("f32[32,16]", "f32[2,32]")  # the MLP weight grads


def test_grad_overlap_emits_scatters_before_clip():
    """The flag is the emission order: under overlap the traced program
    pins each gradient to its moment sharding BEFORE the global-norm
    clip's sqrt (each tensor's reduce-scatter is independent of the clip
    scalar, so XLA may start it during the remaining backward); the
    fused order emits no pin before the clip.

    And the compiled overlap program keeps that order: every grad-shaped
    collective is scheduled before the instruction that turns the summed
    squares into the clip scale, and the clip's sums read the device's
    own slices: the scatter has happened by then.  (On jax 0.9.0's CPU
    backend the fused order compiles to the same schedule, because the
    partitioner propagates the moment sharding backwards by itself; the
    traced programs differ, the flag is what guarantees the order.)"""
    def pins_before_clip(traced):
        names = [e.primitive.name for e in traced.jaxpr.eqns]
        return names[:names.index("sqrt")].count("sharding_constraint")
    assert pins_before_clip(_traced(False)) == 0
    overlap = _traced(True)
    # one pin per MLP tensor (2 weights + 2 biases)
    assert pins_before_clip(overlap) == 4

    entry = _entry(overlap.lower().compile().as_text())
    scale_at = next(i for i, l in enumerate(entry)
                    if "/clip/" in l and "/clip/reduce_sum" not in l)
    scatters = [i for i, l in enumerate(entry)
                if re.search(r"(reduce-scatter|all-reduce)(-start)?\(", l)
                and any(s in l for s in _GRAD_SHAPES)]
    assert len(scatters) >= 2 and max(scatters) < scale_at, entry
    # 3 of the 4 tensors split over dp=4 (the (2,) bias cannot)
    assert _clip_reads_slices(entry[:scale_at]) >= 3, entry


def test_grad_overlap_hlo_independent_scatter_collectives():
    """Compiled overlap module: >=2 INDEPENDENT grad-shaped scatter
    collectives on distinct channels (per-tensor schedule, not one fused
    barrier).  This jaxlib's CPU backend spells reduce-scatter as a
    full-shape all-reduce feeding a dynamic-slice; TPU lowers the same
    pins to reduce-scatter proper — accept either."""
    text = _traced(True).lower().compile().as_text()
    chans = set()
    for line in text.splitlines():
        if not re.search(r"(reduce-scatter|all-reduce)(-start)?\(", line):
            continue
        if not any(s in line for s in _GRAD_SHAPES):
            continue
        m = re.search(r"channel_id=(\d+)", line)
        if m:
            chans.add(m.group(1))
    assert len(chans) >= 2, text[:3000]


# ---------------------------------------------------------------------------
# fast: ZeRO x pp composition (placement + resume; construction-only —
# the pp superstep itself needs partial-manual shard_map, gated below)
# ---------------------------------------------------------------------------


def _tiny_gpt(num_layers=4):
    from paddle_hackathon_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(123)
    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=num_layers,
        num_heads=2, intermediate_size=32, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        use_flash_attention=False))


def _build_pp_zero(mesh_dims, **kw):
    from paddle_hackathon_tpu.models import param_sharding_spec
    n = int(np.prod(list(mesh_dims.values())))
    mesh = parallel.create_mesh(mesh_dims, devices=jax.devices()[:n])
    model = _tiny_gpt()
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
        zero_stage=1, grad_clip_norm=None, **kw)
    return step, state, mesh


def test_zero_pp_moments_shard_stage_and_data_axis():
    """zero_stage=1 composed with pp: each stacked moment keeps 'pp' on
    the stage dim AND gains the data axis on a weight dim — the moments
    shard over dp WITHIN each pipeline stage."""
    _, state, _ = _build_pp_zero({"pp": 2, "dp": 2})
    k = "gpt.blocks.$stacked.attn.qkv_proj.weight"
    mom = state["opt_state"][k]["m"]
    spec = tuple(mom.sharding.spec)
    flat_axes = [a for s in spec if s is not None
                 for a in (s if isinstance(s, tuple) else (s,))]
    assert spec[0] == "pp" and "dp" in flat_axes
    # 1/(pp*dp) per device
    shard = mom.sharding.shard_shape(mom.shape)
    assert int(np.prod(shard)) == mom.size // 4


def test_zero_pp_offload_state_is_host_numpy():
    """zero_offload composes with pp at construction: the composed
    (stacked) moments live in host numpy with the full stacked shape."""
    _, state, _ = _build_pp_zero({"pp": 2, "dp": 2}, zero_offload=True)
    k = "gpt.blocks.$stacked.attn.qkv_proj.weight"
    st = state["opt_state"][k]
    assert isinstance(st["m"], np.ndarray)
    assert st["m"].shape == tuple(state["params"][k].shape)


def test_zero_pp_dp_reshard_resume_composed(tmp_path):
    """dp-reshard resume on COMPOSED state: a pp2 x dp2-written ZeRO
    checkpoint restores onto a pp2 x dp4 rebuild via `restore_like` —
    bitwise bytes, new mesh's composed sharding."""
    from paddle_hackathon_tpu.parallel.checkpointing import (
        CheckpointManager, flatten_train_state, restore_like)
    _, state, _ = _build_pp_zero({"pp": 2, "dp": 2})
    key_order = list(state["params"])
    flat = flatten_train_state(
        state["params"], [state["opt_state"][k] for k in key_order],
        state["step"])
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(flat, step=0, block=True)
    mgr.close()

    _, state2, mesh2 = _build_pp_zero({"pp": 2, "dp": 4})
    flat2 = flatten_train_state(
        state2["params"], [state2["opt_state"][k] for k in key_order],
        state2["step"])
    placed, manifest = restore_like(str(tmp_path), flat2)
    i = key_order.index("gpt.blocks.$stacked.attn.qkv_proj.weight")
    mom = placed[f"opt::{i}::m"]
    spec = tuple(mom.sharding.spec)
    flat_axes = [a for s in spec if s is not None
                 for a in (s if isinstance(s, tuple) else (s,))]
    assert spec[0] == "pp" and "dp" in flat_axes
    assert mom.sharding.mesh.devices.size == 8
    np.testing.assert_array_equal(np.asarray(mom),
                                  np.asarray(flat[f"opt::{i}::m"]))


# ---------------------------------------------------------------------------
# fast: perf-gate evidence units
# ---------------------------------------------------------------------------


def test_perf_gate_zero_offload_evidence():
    """compare_zero_offload fails vacuous offload rows (single-device,
    non-zero device bytes, empty host bytes) and passes real evidence."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from perf_gate import compare_zero_offload
    good = {"metric": "hapi_fit_offload_tokens_per_sec",
            "zero_offload": True, "dp": 8,
            "opt_state_bytes_vs_replicated": 0.0,
            "opt_state_host_bytes": 7320}
    single = {"metric": "o1", "zero_offload": True, "dp": 1,
              "opt_state_bytes_vs_replicated": 0.0,
              "opt_state_host_bytes": 7320}
    resident = {"metric": "o2", "zero_offload": True, "dp": 8,
                "opt_state_bytes_vs_replicated": 0.5,
                "opt_state_host_bytes": 7320}
    hostless = {"metric": "o3", "zero_offload": True, "dp": 8,
                "opt_state_bytes_vs_replicated": 0.0,
                "opt_state_host_bytes": 0}
    dense = {"metric": "hapi_fit_tokens_per_sec", "zero_stage": 0}
    assert compare_zero_offload([good, dense]) == []
    bad = compare_zero_offload([good, single, resident, hostless, dense])
    assert [m for m, _ in bad] == ["o1", "o2", "o3"]


# ---------------------------------------------------------------------------
# slow: end-to-end drills
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_grad_overlap_series_tolerance_vs_fused():
    """6-step loss series: overlap vs fused reassociates only the clip
    reduction — stated f32 tolerance; offload composes with overlap."""
    l_fused, _, _ = _run_sharded(6, grad_clip_norm=1.0)
    l_ov, _, _ = _run_sharded(6, grad_clip_norm=1.0, grad_overlap=True)
    np.testing.assert_allclose(l_ov, l_fused, rtol=1e-4, atol=1e-5)
    l_oo, _, _ = _run_sharded(6, grad_clip_norm=1.0, grad_overlap=True,
                              zero_offload=True)
    np.testing.assert_allclose(l_oo, l_fused, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_sharded_step_offload_series_tolerance():
    """6 steps end-to-end: the split program materializes the
    all-reduced grad at the program boundary (the fused one
    reduce-scatters) — stated ~1 ulp/step reassociation tolerance, with
    a bit-exact head."""
    l_res, s_res, _ = _run_sharded(6)
    l_off, s_off, _ = _run_sharded(6, zero_offload=True)
    assert l_res[:2] == l_off[:2]
    np.testing.assert_allclose(l_off, l_res, rtol=1e-5, atol=1e-6)
    for k in s_res["params"]:
        np.testing.assert_allclose(np.asarray(s_res["params"][k]),
                                   np.asarray(s_off["params"][k]),
                                   rtol=1e-5, atol=1e-6)


class _DS(io.Dataset):
    def __init__(self, n=64, d=16, seed=0):
        r = np.random.RandomState(seed)
        self.x = r.randn(n, d).astype(np.float32)
        self.y = (self.x.sum(1) > 0).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


@pytest.mark.slow
def test_model_fit_offload_matches_resident_zero():
    """`Model.fit(zero_stage=1, zero_offload=True)`: the K-step
    superstep becomes a grads program + streamed host update — loss
    series and final params bitwise vs the resident ZeRO fit."""
    def fit(offload):
        parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
        np.random.seed(0)
        net = _mlp(7)
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
              callbacks=[Rec()], zero_stage=1, zero_offload=offload)
        assert m._fit_used_compiled
        return losses, {k: np.asarray(p._value)
                        for k, p in net.named_parameters()}

    l_res, p_res = fit(False)
    l_off, p_off = fit(True)
    assert l_res == l_off
    for k in p_res:
        np.testing.assert_array_equal(p_res[k], p_off[k])


@pytest.mark.slow
def test_engine_offload_matches_resident_zero():
    """`Engine.fit` with Strategy(zero_offload=True): loss series and
    params bitwise vs the resident sharded strategy; state host numpy;
    merge_k composes."""
    from paddle_hackathon_tpu.parallel.auto_parallel import (Engine,
                                                             ProcessMesh,
                                                             Strategy)
    parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])

    def run(**kw):
        np.random.seed(11)
        net = _mlp(3)
        pm = ProcessMesh([0, 1, 2, 3], dim_names=["dp"])
        eng = Engine(net, loss=nn.CrossEntropyLoss(),
                     optimizer=optim.Adam(learning_rate=1e-2,
                                          parameters=net.parameters()),
                     process_mesh=pm,
                     strategy=Strategy(sharding=True, sharding_stage=1,
                                       **kw))
        hist = eng.fit(_DS(), epochs=1, batch_size=8, verbose=0)
        return (hist["loss"],
                {k: np.asarray(v) for k, v in
                 eng._state["params"].items()}, eng)

    l_res, p_res, _ = run()
    l_off, p_off, eng = run(zero_offload=True)
    assert l_res == l_off
    for k in p_res:
        np.testing.assert_array_equal(p_res[k], p_off[k])
    assert all(isinstance(a, np.ndarray)
               for st in eng._state["opt_states"] for a in st.values())
    l_merge, _, _ = run(zero_offload=True, gradient_merge_k=2)
    assert all(np.isfinite(l_merge))


@pytest.mark.slow
def test_offload_clean_under_donation_sanitizer():
    """The streamed update donates only the h2d'd state arg; one
    offloaded superstep of each trainer runs clean under the donation
    sanitizer (the ring holds strong refs until each d2h completes)."""
    from paddle_hackathon_tpu.observability import sanitizers
    with sanitizers.donation_sanitizer():
        _run_sharded(2, zero_offload=True, grad_overlap=True)


@pytest.mark.slow
def test_zero_pp_superstep_loss_matches_unsharded_pp():
    """The composed ZeRO x pp program trains: pp microbatch grad
    accumulation feeds the dp-sharded update, and the loss series
    matches the unsharded pp trainer to reassociation tolerance."""
    def run(zero):
        from paddle_hackathon_tpu.models import param_sharding_spec
        mesh = parallel.create_mesh({"pp": 2, "dp": 2},
                                    devices=jax.devices()[:4])
        model = _tiny_gpt()
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            zero_stage=1 if zero else 0, grad_clip_norm=None)
        r = np.random.RandomState(0)
        ids = jnp.asarray(r.randint(0, 64, (8, 16)))
        labels = jnp.asarray(r.randint(0, 64, (8, 16)))
        out = []
        for _ in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            out.append(float(loss))
        return out

    np.testing.assert_allclose(run(True), run(False), rtol=2e-4)
