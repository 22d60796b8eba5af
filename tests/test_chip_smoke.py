"""``chip_smoke.py``'s phases at toy size on the CPU, through their
functions (PR 21).  The script itself runs only on a TPU; these keep the
phase code from rotting between chip runs.  The loud-failure contract
around it is in ``tests/test_no_hidden_fallbacks.py``.
"""

import importlib.util
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(num_layers=2, hidden_size=128, num_heads=2, vocab_size=512)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_chip(cs):
    facts, state, _ = cs.phase_trainer(TINY, batch=4, seqlen=64, steps=4)
    return facts, set(state)


# ---------------------------------------------------------------------------
# chip_smoke phases, toy size, CPU
# ---------------------------------------------------------------------------

def test_trainer_phase_trains_and_compiles_once(one_chip):
    facts, state_keys = one_chip
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["program_builds"] == 1      # no second compile of the step
    assert state_keys == {"params", "opt_state", "step"}


def test_trace_phase_finds_the_programs_spans_in_the_profilers_file(cs):
    facts = cs.phase_trace(TINY, batch=4, seqlen=64, steps=3)
    assert facts.pop("recorded_step_seconds") > 0
    assert facts == {"steps": 3, "host_spans": {
        "train_step": 3, "train.dispatch": 3, "train.rebind": 3}}


@pytest.mark.parametrize("quant", [None, "int8"])
def test_server_phase_agrees_with_reference(cs, quant):
    facts = cs.phase_server(TINY, quant=quant, prompt_lens=(5, 17, 9, 12),
                            new_tokens=10, slots=4, chunk=8, page=8)
    for mode in ("paged", "dense"):
        assert facts[mode]["max_logit_gap_vs_reference"] <= cs.LOGIT_GAP_TOL
    assert facts["paged_vs_dense_token_agreement"] == 1.0   # f32 CPU: exact


def test_multichip_phase_places_shards_on_four_devices(cs, one_chip):
    facts = cs.phase_multichip(TINY, batch=4, seqlen=64, steps=3,
                               one_chip_first_loss=one_chip[0]["losses"][0])
    assert facts["param_shards"] == {"spec": ["None", "mp"],
                                     "shard_shape": [128, 192], "devices": 4}
    assert facts["moment_shards"] == {"spec": ["dp", "mp"],
                                      "shard_shape": [64, 192], "devices": 4}
    assert facts["first_loss_delta_vs_one_chip"] <= 0.05


@pytest.mark.slow   # ~10 s of interpreter-mode compiles; the kernels'
# CPU numerics are tier-1 in test_incubate / test_paged /
# test_quant_serving and their TPU lowering in test_tpu_lowering
def test_kernel_phase_compares_every_family_with_its_reference(cs):
    facts = cs.phase_kernels(hidden=128, heads=2, seqlen=64, batch=1,
                             slots=2, chunk=8, page=8, pages_per_slot=2,
                             extra_shapes=False)
    names = set(facts["cases"])
    assert {"flash_packed.fwd", "flash_packed.bwd", "flash_bhd.dq",
            "paged_decode"} <= names
    assert sum(n.startswith("quant_matmul_int8") for n in names) == 8


def test_kernel_check_fails_outside_tolerance(cs):
    import jax.numpy as jnp
    ref = jnp.ones((4, 4), jnp.float32)
    cs._check("same", ref.astype(jnp.bfloat16), ref, {})
    with pytest.raises(RuntimeError, match="exceeds"):
        cs._check("off", ref * 1.05, ref, {})


def test_mosaic_evidence_is_required_not_assumed(cs):
    with pytest.raises(RuntimeError, match="bypassed or interpreted"):
        cs._require_kernels({"flash_packed_fwd": 2}, {"paged_decode": 2},
                            "decode tick")
    # a jit site nothing was built at is a failure, not an empty census
    with pytest.raises(RuntimeError, match="no program was built"):
        cs._mosaic_since({}, "serving.no_such_site")


def test_memory_stats_are_asserted_not_defaulted(cs):
    dev = types.SimpleNamespace(memory_stats=lambda: None)
    with pytest.raises(RuntimeError, match="no memory_stats"):
        cs.bytes_in_use(dev)
    dev = types.SimpleNamespace(memory_stats=lambda: {"bytes_in_use": 0})
    with pytest.raises(RuntimeError, match="bytes_in_use=0"):
        cs.bytes_in_use(dev)
