"""The benchmark's side of the KDA / MLA / dense / experts configuration, on
the CPU at a toy size: its rehearsal cell through ``run.main``; the plain
reference's layer-by-layer gradients against ``jax.grad`` of itself; the
operation count and the kernels' work by hand, at the toy's sizes and at
the cell's; the configuration's file against the catalog's numbers; the
readers on a synthetic trace, and silent where the program gives them
nothing to read (as the parent commit does); the packed flash kernel's
plan for the cell's attention, with the accepted cells' plans as they
were."""

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
CELL = "ling-3.0-flash.train-s4096"
TINY = "ling3-tiny-rehearsal.train-s64"
SITE = "parallel.sharded_train_step"
NEW_READERS = ("kda_rule_fwd_roofline", "kda_rule_bwd_roofline",
               "mla_flash_fwd_roofline", "mla_flash_bwd_roofline", "kda_ms",
               "mla_ms", "group_router_ms")

# the numbers of the catalog row's ``config`` (model-configs guide,
# Ling-3.0-flash): a configuration's file has to hold each under the same
# key unless the key is listed in ``reduced``
CATALOG = {
    "first_k_dense_replace": 2, "group_norm_size": 1, "head_dim": 128,
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kv_lora_rank": 512, "layer_group_size": 6,
    "max_position_embeddings": 262144, "max_window_layers": 20,
    "moe_intermediate_size": 768, "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "n_group": 8, "num_attention_heads": 32,
    "num_experts": 512, "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 6000000, "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "short_conv_kernel_size": 4, "topk_group": 4, "v_head_dim": 128,
    "vocab_size": 157184}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _reader(name):
    from benchmark import run as harness
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers_and_states_its_cut():
    cfg = _load("configs", "ling-3.0-flash")
    changed = {k for k, v in CATALOG.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "first_k_dense_replace", "num_nextn_predict_layers"}
    # the reduced keys beside their published values
    for key, here in (("num_hidden_layers", 7), ("num_experts", 8),
                      ("vocab_size", 19648), ("first_k_dense_replace", 1),
                      ("num_nextn_predict_layers", 0)):
        assert cfg[key] == here and cfg[key + "_published"] == CATALOG[key]
    # the floors of a model_config cut: the leading dense layer once and a
    # whole period of the layers that follow, at least 8 routed experts,
    # at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        == cfg["layer_group_size"] == 6
    assert cfg["experts_held"] == [0, 8]
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["deployment"]["chips_sharing_a_layer"] * cfg["num_experts"] \
        == cfg["num_experts_published"] == 512
    # what is no number stands as published too, the clamp lists whole
    for key, value in (("score_function", "sigmoid"), ("q_lora_rank", None),
                       ("topk_method", "noaux_tc"), ("rope_interleave", True),
                       ("kda_safe_gate", True), ("no_kda_lora", True),
                       ("use_mla_nope", False), ("norm_topk_prob", True),
                       ("moe_router_enable_expert_bias", True),
                       ("gated_attention_proj_granularity_type", "head_wise"),
                       ("model_type", "bailing_hybrid")):
        assert cfg[key] == value, key
    assert len(cfg["expert_swiglu_limit_list"]) == 42 \
        == len(cfg["share_expert_swiglu_limit_list"])
    assert cfg["expert_swiglu_limit_list_here"] == [0] * 7 \
        == cfg["share_expert_swiglu_limit_list_here"] \
        == cfg["expert_swiglu_limit_list"][:7] \
        == cfg["share_expert_swiglu_limit_list"][:7]
    from benchmark.reference import ling3_f32 as ref
    n = sum(math.prod(s) for s in ref.param_spec(cfg).values())
    assert n == 884459456
    assert round(n / 1e6, 1) == cfg["parameters_millions"] == 884.5
    cell = _load("workloads", CELL)
    assert cell["traffic"]["batch"] == 1 and cell["traffic"]["seqlen"] == 4096
    assert cell["traffic"]["pool"] == 16 and cell["chips"] == 1
    assert cell["check"]["steps"] == 3


def test_the_manifest_gives_the_cell_its_readers():
    from benchmark import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e, layer = harness.cell_metrics(manifest, CELL)
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in layer} == set(NEW_READERS) | {
        "train_step_mfu", "step_ms_p50", "device_idle_share",
        "hbm_peak_share"}
    # the cells that were there are given none of the new readers
    for old in ("gpt2-medium.train-s1024", "qwen3-next-80b-a3b.train-s4096"):
        _, theirs = harness.cell_metrics(manifest, old)
        assert not set(NEW_READERS) & {m["name"] for m in theirs}
    entry, = [c for c in manifest["configs"] if c["name"] == "ling-3.0-flash"]
    cfg = _load("configs", "ling-3.0-flash")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_operations_a_token_by_hand():
    """The toy: hidden 64, 2 heads x 32.  KDA: projections 64 x 192, 64 x
    128 and 64 x 2, taps 192 x 4, output 64 x 64.  MLA (nope 32, rope 16,
    v 32, latent 24): q 64 x 96, latent 64 x 40, up 24 x 128, gate 64 x 2,
    output 64 x 64.  Dense MLP 3 x 64 x 96.  Experts: router 64 x 16, the
    shared expert 3 x 64 x 32, 2 x 8 / 16 = 1 held expert a token of 3 x
    64 x 32.  Head 64 x 512.  Four layers: three KDA and one MLA, one
    dense and three of experts; at s64 one attention's 6 x 64 x 2 x (48 +
    32) and three recurrences of 3 x 6 x 32 x 32 x 2."""
    from benchmark.op_counts import ling3 as count
    cfg = _load("configs", "ling3-tiny-rehearsal")
    kda = 64 * 192 + 64 * 128 + 64 * 2 + 192 * 4 + 64 * 64
    mla = 64 * 96 + 64 * 40 + 24 * 128 + 64 * 2 + 64 * 64
    dense = 3 * 64 * 96
    moe = 64 * 16 + 3 * 64 * 32 + 1.0 * 3 * 64 * 32
    params = 3 * kda + mla + dense + 3 * moe + 64 * 512
    assert count.matmul_params_per_token(cfg) == params == 183552.0
    by_hand = 6 * params + 6 * 64 * 2 * 80 + 3 * 3 * 6 * 32 * 32 * 2
    assert count.train_flops_per_token(cfg, 64) == by_hand == 1273344.0
    # the cell: 555.4 M parameters a token multiplies by -- six KDA mixers
    # and one MLA 410.2, the dense part 47.2, six routers + shared experts
    # + 0.125 experts 47.7, the head 50.3 -- and 3.64 GFLOP a token
    real = _load("configs", "ling-3.0-flash")
    kda = 2560 * (12288 + 8192 + 32) + 12288 * 4 + 4096 * 2560
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    assert round((6 * kda + mla) / 1e6, 1) == 410.2
    dense = 3 * 2560 * 6144
    moe = 2560 * 512 + 3 * 2560 * 768 + 0.125 * 3 * 2560 * 768
    assert round(dense / 1e6, 1) == 47.2 and round(6 * moe / 1e6, 1) == 47.7
    params = 6 * kda + mla + dense + 6 * moe + 2560 * 19648
    assert count.matmul_params_per_token(real) == params == 555401216.0
    by_hand = 6 * params + 6 * 4096 * 32 * 320 + 6 * 3 * 6 * 128 * 128 * 32
    assert count.train_flops_per_token(real, 4096) == by_hand
    assert round(by_hand / 1e9, 2) == 3.64


def test_the_work_of_the_rule_and_of_the_attention_by_hand():
    from benchmark import flops, kda_work, mixer_work
    tokens = 4096 * 32
    fwd = kda_work.channel_decay_rule_work(1, 4096, 32, 128, 128, 2, False)
    assert fwd["flops"] == 6 * 128 * 128 * tokens
    # q, k, v, o in bfloat16, the decay 128 float32 a token and head, beta
    assert fwd["bytes"] == tokens * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 2)
    bwd = kda_work.channel_decay_rule_work(1, 4096, 32, 128, 128, 2, True)
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == tokens * (2 * (3 * 128 * 2 + 128 * 4 + 4)
                                     + 128 * 2)
    # the scalar rule's count with 127 more float32 of decay a token
    scalar = mixer_work.gated_delta_rule_work(1, 4096, 32, 128, 128, 2, False)
    assert fwd["flops"] == scalar["flops"]
    assert fwd["bytes"] - scalar["bytes"] == tokens * 127 * 4
    assert flops.roofline_seconds(fwd, PEAKS)["bound"] == "memory"
    half = 32 * 4096 * 4096 / 2
    attn = kda_work.causal_attention_work(1, 32, 4096, 192, 128, 2, False)
    assert attn["flops"] == 2 * half * (192 + 128)
    assert attn["bytes"] == tokens * 2 * (2 * 192 + 2 * 128)
    back = kda_work.causal_attention_work(1, 32, 4096, 192, 128, 2, True)
    assert back["flops"] == 2 * half * (3 * 192 + 2 * 128)
    assert back["bytes"] == tokens * 2 * (4 * 192 + 4 * 128)
    assert flops.roofline_seconds(attn, PEAKS)["bound"] == "compute"
    # with one width for keys and values it is the shared count
    same = kda_work.causal_attention_work(2, 16, 1024, 64, 64, 2, True)
    assert same == flops.causal_attention_work(2, 16, 1024, 64, 2, True)


def test_reference_layer_by_layer_gradients_equal_jax_grad():
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.reference import ling3_f32 as ref
    cfg = _load("configs", "ling3-tiny-rehearsal")
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
        # 8 of the 16 experts are held: the routers get no gradient, and
        # the selection bias never has one
        assert (scale > 0) == (not k.endswith(("router.weight",
                                               "router_bias"))), k
        assert float(jnp.abs(got[k] - want[k]).max()) <= 1e-4 * scale, k
    segs = ref.leaf_segments(cfg)
    assert segs["layers.0.linear_attn.in_proj_qkv.weight"] == 3
    assert segs["layers.0.mlp.gate_up_proj.weight"] == 2
    assert segs["layers.2.self_attn.kv_a_proj.weight"] == 5   # 24 + 16 by 8
    # half of a batch of one: the second half of its tokens left out
    one = [(ids[:1], labels[:1])]
    train = dict(cfg["training"])
    half = ref.train_readings(
        cfg, train, lambda: weights.make_params(11, ref.param_spec(cfg),
                                                jnp.float32),
        one, rows=slice(0, 0))
    whole = ref.train_readings(
        cfg, train, lambda: weights.make_params(11, ref.param_spec(cfg),
                                                jnp.float32),
        [(ids[:1, :35], labels[:1, :35])])
    assert half["losses"] == whole["losses"] \
        and all(math.isfinite(x) for x in half["losses"])


def test_run_end_to_end_on_the_rehearsal_cell(capsys):
    from benchmark import run as harness
    rc = harness.main(["--workload", TINY, "--seed", "3000000035",
                       "--seconds", "0.3", "--trace", "0"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    assert "operations a token at s64: 1273344.0 " in out.err
    # three routers and three selection biases have no gradient; a 2-long
    # A_log's may fall under a thousandth of the median leaf's besides
    left_out = int(out.err.split("'leaves_left_out': ")[1].split("}")[0])
    assert 6 <= left_out <= 9
    # the step handed its expert layers' counters to the program
    # observatory: every layer but the leading dense one
    from benchmark import scope_times
    counters = scope_times.program_counters(
        {"config": {"program": {"jit_site": SITE}}})
    assert sorted(counters) == [f"layers.{i}.mlp" for i in (1, 2, 3)]


# one step of a synthetic trace: (own name, start ms, duration ms), and the
# census that places each instruction
CENSUS = {
    "fusion.1": ("fwd", "kda", False),
    "fusion.2": ("fwd", "kda/kda_conv", False),
    "while.3": ("fwd", "kda/kda_rule", False),
    "fusion.4": ("fwd", "kda/kda_rule", False),      # the while's body
    "while.5": ("bwd", "kda/kda_rule", False),
    "flash_packed_fwd.6": ("fwd", "mla", False),
    "flash_packed_bwd_dkdv.7": ("bwd", "mla", False),
    "flash_packed_bwd_dq.8": ("bwd", "mla", False),
    "fusion.9": ("bwd", "mla", False),
    "fusion.10": ("fwd", "moe/router", False),
    "sort.11": ("fwd", "moe/experts", False),
    "fusion.12": ("fwd", "mlp", False),
    "fusion.13": ("update", "update", False),
}
STEP = (("fusion.1", 0, 3), ("fusion.2", 3, 1), ("while.3", 4, 6),
        ("fusion.4", 5, 4), ("while.5", 10, 12), ("flash_packed_fwd.6", 22, 2),
        ("flash_packed_bwd_dkdv.7", 24, 3), ("flash_packed_bwd_dq.8", 27, 2),
        ("fusion.9", 29, 5), ("fusion.10", 34, 1.5), ("sort.11", 36, 2),
        ("fusion.12", 38, 1), ("fusion.13", 39, 4))
MS = 1e6


def _run(monkeypatch, census=CENSUS):
    from benchmark import phase_times, trace_reduce

    class Registry:
        def phase_census(self, site):
            return census

    monkeypatch.setattr(phase_times, "_registry", Registry)
    ops = [(f"%{name} = bf16[8,128]{{1,0}} fusion(%while.5)",
            (s * 50 + start) * MS, dur * MS)
           for s in range(2) for name, start, dur in STEP]
    cfg = dict(_load("configs", "ling-3.0-flash"))
    return {"config": cfg, "notes": [], "peaks": PEAKS,
            "facts": {"batch": 1, "seqlen": 4096,
                      "traced": {"ops": ops,
                                 "busy_s": trace_reduce.busy_ns(ops) / 1e9}}}


def test_the_new_readers_give_the_hand_counted_values(monkeypatch):
    from benchmark import flops, kda_work
    run = _run(monkeypatch)
    assert _reader("kda_ms").read(run) == pytest.approx(3 + 1 + 6 + 12)
    assert _reader("mla_ms").read(run) == pytest.approx(2 + 3 + 2 + 5)
    assert _reader("group_router_ms").read(run) == pytest.approx(1.5)

    def least(work):
        return flops.roofline_seconds(work, PEAKS)["seconds"]

    # six KDA layers of the seven; 6 ms forward and 12 backward a step
    assert _reader("kda_rule_fwd_roofline").read(run) == pytest.approx(
        100 * 6 * least(kda_work.channel_decay_rule_work(
            1, 4096, 32, 128, 128, 2, False)) / 6e-3)
    assert _reader("kda_rule_bwd_roofline").read(run) == pytest.approx(
        100 * 6 * least(kda_work.channel_decay_rule_work(
            1, 4096, 32, 128, 128, 2, True)) / 12e-3)
    # one call of each kernel a step: 2 ms forward, 3 + 2 backward
    assert _reader("mla_flash_fwd_roofline").read(run) == pytest.approx(
        100 * least(kda_work.causal_attention_work(
            1, 32, 4096, 192, 128, 2, False)) / 2e-3)
    assert _reader("mla_flash_bwd_roofline").read(run) == pytest.approx(
        100 * least(kda_work.causal_attention_work(
            1, 32, 4096, 192, 128, 2, True)) / 5e-3)
    assert any("kda_conv 1.000 fwd" in n for n in run["notes"])
    assert any("flash_packed_bwd_dkdv 3.000" in n and "(512, 512, 2, 128)"
               in n for n in run["notes"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_with_nothing_to_read_is_silent(monkeypatch, name):
    """A program without the scopes and the kernels (a census that places
    nothing under them, a trace without their events), and a run without a
    trace: ``None`` both times, never 0 and never an error."""
    old = {k.replace("flash_packed", "fusion"):
           (p, c if c == "update" else "", m)
           for k, (p, c, m) in CENSUS.items()}
    run = _run(monkeypatch, census=old)
    run["facts"]["traced"]["ops"] = [
        (n.replace("flash_packed", "fusion"), t, d)
        for n, t, d in run["facts"]["traced"]["ops"]]
    assert _reader(name).read(run) is None
    run = _run(monkeypatch)
    run["facts"]["traced"] = None
    assert _reader(name).read(run) is None


@pytest.mark.parametrize("shape, plan", [
    ((4096, 32, 192), (512, 512, 2, 128)),     # this cell's latent attention
    ((1024, 16, 128), (512, 512, 4, 128)),     # gpt3-1.3b.train-s1024
    ((1024, 16, 64), (512, 512, 8, 128)),      # gpt2-medium.train-s1024
    ((4096, 16, 256), (512, 512, 2, 128)),     # qwen3-next-80b-a3b
])
def test_packed_flash_plans_of_the_cells(shape, plan):
    import jax.numpy as jnp
    from paddle_hackathon_tpu.incubate.nn.kernels import \
        flash_attention_packed as fap
    s, heads, head_dim = shape
    assert fap._plan(s, s, heads, head_dim, jnp.bfloat16) == plan
    assert fap.supported(s, s, heads, head_dim, jnp.bfloat16)
