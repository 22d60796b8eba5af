"""Collective byte accounting for the DP scaling-efficiency artifact
(tools/scaling_model.py — driver BASELINE target #2, the 8->256-chip
allreduce scaling row; the HLO-measured half of the model).
"""

import os
import sys

import pytest

from jax import set_mesh as _set_mesh

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from scaling_model import (collective_bytes_from_hlo, efficiency_table,
                           measure_dp_step, ring_allreduce_s)


def test_hlo_parse_shapes_and_kinds():
    hlo = """
  %ar = bf16[1024,768]{1,0} all-reduce(bf16[1024,768] %p), replica_groups={}
  %ars = f32[16]{0} all-reduce-start(f32[16] %x), to_apply=%sum
  %ard = f32[16]{0} all-reduce-done(f32[16] %ars)
  ROOT %t = (f32[8]{0}, u32[2]{0}) all-to-all(f32[8] %a, u32[2] %b)
  %cp = bf16[4,4]{1,0} collective-permute(bf16[4,4] %y)
  %noise = f32[64]{0} add(f32[64] %a, f32[64] %b)
"""
    r = collective_bytes_from_hlo(hlo)
    assert r["all-reduce"] == 1024 * 768 * 2 + 16 * 4  # -done not re-counted
    assert r["all-to-all"] == 8 * 4 + 2 * 4
    assert r["collective-permute"] == 16 * 2
    assert "add" not in r


def test_dp_allreduce_bytes_track_grad_payload():
    """The compiled DP step's all-reduce traffic must be the gradient
    payload (plus small scalars: loss, global-norm), and invariant in the
    mesh size — the weak-scaling property the 8->256 model relies on."""
    r4, g4 = measure_dp_step(4)
    r8, g8 = measure_dp_step(8)
    assert g4 == g8
    ar4, ar8 = r4["all-reduce"], r8["all-reduce"]
    assert ar4 == ar8, "DP allreduce bytes must not depend on mesh size"
    assert g8 <= ar8 <= 1.5 * g8, (ar8, g8)


def test_zero3_adds_param_gather_traffic():
    """ZeRO-3 over a 'sharding' axis must show up as all-gather traffic
    (params re-materialized per step) on top of the grad reduction."""
    import jax
    import jax.numpy as jnp

    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)

    paddle.seed(0)
    mesh = parallel.create_mesh({"dp": 2, "sharding": 4})
    try:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=32,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            zero_stage=3)
        ids = jnp.zeros((8, 32), jnp.int32)
        with _set_mesh(mesh):
            compiled = step._jitted.lower(
                state["params"], state["opt_state"], state["step"],
                (ids, ids), jax.random.key(0), jnp.float32(1e-3)).compile()
        r = collective_bytes_from_hlo(compiled.as_text())
    finally:
        parallel.set_mesh(None)
    grad_bytes = sum(v.size * v.dtype.itemsize
                     for v in state["params"].values())
    assert r.get("all-gather", 0) >= grad_bytes, r


def test_ring_model_properties():
    b = 250e6
    # ring cost grows with n, saturating at 2B/bw
    t8 = ring_allreduce_s(8, b, 9e10)
    t256 = ring_allreduce_s(256, b, 9e10)
    assert 0 < t8 < t256 < 2 * b / 9e10
    rows = efficiency_table(b, 0.2)
    assert [r["chips"] for r in rows] == [8, 16, 32, 64, 256]
    for r in rows:
        assert 0 < r["eff_no_overlap"] <= r["eff_overlap"] <= 1.0
    # efficiency is non-increasing in chip count
    no = [r["eff_no_overlap"] for r in rows]
    assert all(a >= b_ for a, b_ in zip(no, no[1:]))
    # the DCN tier must make the 256-chip row strictly costlier per byte
    assert rows[-1]["t_comm_ms"] > rows[-2]["t_comm_ms"]
