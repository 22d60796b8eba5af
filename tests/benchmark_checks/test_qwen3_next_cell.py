"""The benchmark's side of the hybrid DeltaNet / attention / experts
configuration, on the CPU at a toy size: its rehearsal cell through
``run.main``; the plain reference's layer-by-layer gradients against
``jax.grad`` of itself; the operation count by hand; the configuration's
file against the catalog's numbers; the work the new readers measure
against, by hand; the readers on a synthetic trace, and silent where the
program gives them nothing to read (as the parent commit does)."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmark")
CELL = "qwen3-next-80b-a3b.train-s4096"
TINY = "qwen3-next-tiny-rehearsal.train-s64"
SITE = "parallel.sharded_train_step"
NEW_READERS = ("gdn_rule_fwd_roofline", "gdn_rule_bwd_roofline",
               "moe_experts_roofline", "gdn_ms", "gated_attn_ms", "moe_ms",
               "moe_rows_computed_share")

# the catalog row's ``config`` (model-configs guide, Qwen3-Next-80B-A3B-
# Instruct), the numbers a configuration's file has to hold under the same
# key unless the key is listed in ``reduced``
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 512, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "vocab_size": 151936}


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _reader(name):
    from benchmark import run as harness
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers_and_states_its_cut():
    cfg = _load("configs", "qwen3-next-80b-a3b")
    changed = {k for k, v in CATALOG.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    # the floors of a model_config cut: a whole period and four layers, at
    # least 8 routed experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 4 == cfg["full_attention_interval"]
    assert cfg["num_experts"] == 32 == cfg["experts_held"][1] >= 8
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 151936
    assert cfg["num_experts_published"] == 512
    assert cfg["deployment"]["chips_sharing_a_layer"] * cfg["num_experts"] \
        == cfg["num_experts_published"]
    from benchmark.reference import qwen3_next_f32 as ref
    n = sum(math.prod(s) for s in ref.param_spec(cfg).values())
    assert round(n / 1e6, 1) == cfg["parameters_millions"] == 625.7
    cell = _load("workloads", CELL)
    assert cell["traffic"]["batch"] == 2 and cell["traffic"]["seqlen"] == 4096
    assert cell["traffic"]["pool"] == 16 and cell["chips"] == 1


def test_the_manifest_gives_the_cell_its_readers():
    from benchmark import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e, layer = harness.cell_metrics(manifest, CELL)
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}
    names = [m["name"] for m in layer]
    assert set(names) == set(NEW_READERS) | {
        "train_step_mfu", "step_ms_p50", "device_idle_share",
        "hbm_peak_share"}
    # the cells that were there are given none of the new readers
    _, old = harness.cell_metrics(manifest, "gpt2-medium.train-s1024")
    assert not set(NEW_READERS) & {m["name"] for m in old}


def test_operations_a_token_by_hand():
    """The toy: hidden 64; DeltaNet 2 key heads x 32, 4 value heads x 32
    (projections 64 x 384 and 64 x 8, taps 256 x 4, output 128 x 64);
    attention 2 heads x 64, 1 KV head (q and gate 64 x 256, k and v 64 x
    64 each, output 128 x 64); router 64 x 8, shared expert 3 x 64 x 32
    and its gate 64, 2 x 4 / 8 = 1 held expert a token of 3 x 64 x 32;
    head 64 x 512; at s64 one attention layer's 12 x 64 x 128 and three
    recurrences of 3 x 6 x 32 x 32 x 4."""
    from benchmark.op_counts import qwen3_next as count
    cfg = _load("configs", "qwen3-next-tiny-rehearsal")
    delta = 64 * 384 + 64 * 8 + 256 * 4 + 128 * 64
    attention = 64 * 256 + 2 * 64 * 64 + 128 * 64
    moe = 64 * 8 + 3 * 64 * 32 + 64 + 1.0 * 3 * 64 * 32
    params = 3 * delta + attention + 4 * moe + 64 * 512
    assert count.matmul_params_per_token(cfg) == params
    by_hand = 6 * params + 12 * 64 * 128 + 3 * 3 * 6 * 32 * 32 * 4
    assert count.train_flops_per_token(cfg, 64) == by_hand == 1638912.0
    # the cell: 192.0 M parameters a token, 1.38 GFLOP a token at s4096
    real = _load("configs", "qwen3-next-80b-a3b")
    assert round(count.matmul_params_per_token(real) / 1e6, 1) == 192.0
    assert round(count.train_flops_per_token(real, 4096) / 1e9, 2) == 1.38


def test_the_work_of_the_rule_and_of_the_experts_by_hand():
    from benchmark import flops, mixer_work
    fwd = mixer_work.gated_delta_rule_work(2, 4096, 32, 128, 128, 2, False)
    tokens = 2 * 4096 * 32
    assert fwd["flops"] == 6 * 128 * 128 * tokens
    assert fwd["bytes"] == tokens * ((128 + 128 + 128) * 2 + 8 + 128 * 2)
    bwd = mixer_work.gated_delta_rule_work(2, 4096, 32, 128, 128, 2, True)
    assert bwd["flops"] == 2 * fwd["flops"]
    experts = mixer_work.grouped_expert_work(5120, 32, 2048, 512, 2)
    assert experts["flops"] == 3 * 3 * 2 * 5120 * 2048 * 512
    assert experts["bytes"] == 3 * 32 * 3 * 2048 * 512 * 2 \
        + 3 * 2 * 5120 * 2048 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(experts, peaks)["bound"] == "memory"
    assert flops.roofline_seconds(fwd, peaks)["bound"] == "memory"


def test_reference_layer_by_layer_gradients_equal_jax_grad():
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.reference import qwen3_next_f32 as ref
    cfg = _load("configs", "qwen3-next-tiny-rehearsal")
    params = weights.make_params(11, ref.param_spec(cfg), jnp.float32)
    (ids, labels), = weights.make_batches(11, 1, 2, 70, cfg["vocab_size"])

    def loss_fn(p):
        logits = ref.logits_fn(p, ids, cfg)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    want_loss, want = jax.value_and_grad(loss_fn)(params)
    got = {}
    loss = ref.grads_pass(params, ids, labels, cfg, got.__setitem__)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert set(got) == set(want) == set(ref.param_spec(cfg))
    for k in want:
        scale = float(jnp.abs(want[k]).max())
        # 4 of the 8 experts are held: the routers get no gradient
        assert (scale > 0) == (not k.endswith("router.weight")), k
        assert float(jnp.abs(got[k] - want[k]).max()) <= 1e-4 * scale, k
    # the fused leaves split into equal parts at their blocks' edges
    segs = ref.leaf_segments(cfg)
    assert segs["layers.0.linear_attn.in_proj_qkvz.weight"] == 6
    assert segs["layers.3.self_attn.q_proj.weight"] == 2
    choices = ref.routing_choices(params, ids, cfg)
    assert len(choices) == 4 and choices[0].shape == (2 * 70, 2)


def test_run_end_to_end_on_the_rehearsal_cell(capsys):
    from benchmark import run as harness
    rc = harness.main(["--workload", TINY, "--seed", "3000000019",
                       "--seconds", "0.3", "--trace", "0"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    assert "operations a token at s64: 1638912.0 " in out.err
    # the four routers: a part of the experts is held (4 of 8), so they
    # get no gradient; every other leaf has one
    assert "'leaves_left_out': 4" in out.err
    # the step handed its experts' counters to the program observatory
    from benchmark import scope_times
    counters = scope_times.program_counters(
        {"config": {"program": {"jit_site": SITE}}})
    assert sorted(counters) == [f"layers.{i}.mlp" for i in range(4)]


# one step of a synthetic trace: (own name, start ms, duration ms), and the
# census that places each instruction
CENSUS = {
    "fusion.1": ("fwd", "gdn", False),
    "fusion.2": ("fwd", "gdn/gdn_conv", False),
    "while.3": ("fwd", "gdn/gdn_rule", False),
    "fusion.4": ("fwd", "gdn/gdn_rule", False),      # the while's body
    "while.5": ("bwd", "gdn/gdn_rule", False),
    "flash_packed_fwd.6": ("fwd", "attn", False),
    "fusion.7": ("bwd", "attn", False),
    "fusion.8": ("fwd", "moe/router", False),
    "sort.9": ("fwd", "moe/experts", False),
    "ragged-dot-none.10": ("other", "", False),
    "fusion.11": ("bwd", "moe/shared_expert", False),
    "fusion.12": ("fwd", "moe", False),
    "fusion.13": ("update", "update", False),
}
STEP = (("fusion.1", 0, 3), ("fusion.2", 3, 1), ("while.3", 4, 6),
        ("fusion.4", 5, 4), ("while.5", 10, 12), ("flash_packed_fwd.6", 22, 2),
        ("fusion.7", 24, 5), ("fusion.8", 29, 1), ("sort.9", 30, 2),
        ("ragged-dot-none.10", 32, 0.5), ("fusion.11", 33, 1.5),
        ("fusion.12", 35, 1), ("fusion.13", 36, 4))
MS = 1e6


def _run(monkeypatch, census=CENSUS, counters="default"):
    from benchmark import phase_times, scope_times, trace_reduce
    if counters == "default":
        counters = {"layers.0.mlp": [5000.0, 81920.0, 200.0, 156.25],
                    "layers.1.mlp": [5240.0, 81920.0, 210.0, 163.75]}

    class Registry:
        def phase_census(self, site):
            return census

    monkeypatch.setattr(phase_times, "_registry", Registry)
    monkeypatch.setattr(scope_times, "program_counters", lambda run: counters)
    ops = [(f"%{name} = bf16[8,128]{{1,0}} fusion(%while.5)",
            (s * 50 + start) * MS, dur * MS)
           for s in range(2) for name, start, dur in STEP]
    cfg = dict(_load("configs", "qwen3-next-80b-a3b"))
    return {"config": cfg, "notes": [],
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "facts": {"batch": 2, "seqlen": 4096,
                      "traced": {"ops": ops,
                                 "busy_s": trace_reduce.busy_ns(ops) / 1e9}}}


def test_the_new_readers_give_the_hand_counted_values(monkeypatch):
    from benchmark import flops, mixer_work
    run = _run(monkeypatch)
    peaks = run["peaks"]
    assert _reader("gdn_ms").read(run) == pytest.approx(3 + 1 + 6 + 12)
    assert _reader("gated_attn_ms").read(run) == pytest.approx(2 + 5)
    # moe: its scoped ops 1 + 2 + 1.5 + 1 and the unscoped kernel 0.5
    assert _reader("moe_ms").read(run) == pytest.approx(6.0)
    fwd = flops.roofline_seconds(mixer_work.gated_delta_rule_work(
        2, 4096, 32, 128, 128, 2, False), peaks)["seconds"]
    assert _reader("gdn_rule_fwd_roofline").read(run) == pytest.approx(
        100 * fwd * 3 / 6e-3)          # three DeltaNet layers, 6 ms a step
    bwd = flops.roofline_seconds(mixer_work.gated_delta_rule_work(
        2, 4096, 32, 128, 128, 2, True), peaks)["seconds"]
    assert _reader("gdn_rule_bwd_roofline").read(run) == pytest.approx(
        100 * bwd * 3 / 12e-3)
    least = sum(flops.roofline_seconds(mixer_work.grouped_expert_work(
        rows, 32, 2048, 512, 2), peaks)["seconds"] for rows in (5000, 5240))
    assert _reader("moe_experts_roofline").read(run) == pytest.approx(
        100 * least / 2.5e-3)          # sort 2 ms + the kernel's 0.5
    assert _reader("moe_rows_computed_share").read(run) == pytest.approx(
        100 * 10240 / 163840)
    assert any("ragged" not in n and "router 1.000" in n
               for n in run["notes"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_with_nothing_to_read_is_silent(monkeypatch, name):
    """A program without the scopes or the counters: a census that places
    nothing under them, no counters; and a run without a trace.  ``None``
    both times, never 0 and never an error."""
    old = {k: (p, c if c == "update" else "", m)
           for k, (p, c, m) in CENSUS.items()}
    run = _run(monkeypatch, census=old, counters=None)
    assert _reader(name).read(run) is None
    run = _run(monkeypatch)
    run["facts"]["traced"] = None
    monkeypatch.setattr("benchmark.scope_times.program_counters",
                        lambda run: None)
    assert _reader(name).read(run) is None
