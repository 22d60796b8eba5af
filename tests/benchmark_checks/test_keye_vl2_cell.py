"""The benchmark's side of the sparse-attention / experts configuration, on
the CPU at a toy size: the configuration's file against the catalog's
numbers; the manifest's readers for the cell; the work the readers measure
against and the operation count, by hand at the cell's shapes; the plain
reference's layer-by-layer gradients against ``jax.grad`` of itself; its
rehearsal cell through ``run.main``, the reference against the model; the
readers on a synthetic trace and on counters, and silent where the program
gives them nothing to read (as the parent commit does)."""

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
CELL = "keye-vl-2.0-30b-a3b.train-s16384"
CONFIG = "keye-vl-2.0-30b-a3b"
TINY = "keye-vl2-tiny-rehearsal.train-s128"
SITE = "parallel.sharded_train_step"
NEW_READERS = ("dsa_attn_fwd_roofline", "dsa_attn_bwd_roofline",
               "dsa_indexer_roofline", "dsa_select_ms",
               "dsa_live_tile_share")

# the numbers of the catalog row's ``config`` (model-configs guide,
# Keye-VL-2.0-30B-A3B): a configuration's file has to hold each under the
# same key unless the key is listed in ``reduced``; nested groups whole
CATALOG = {
    "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "moe_intermediate_size": 768,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_theta": 10000000, "vocab_size": 151936}
GROUPS = {
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _reader(name):
    from benchmark import run as harness
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers_and_states_its_cut():
    cfg = _load("configs", CONFIG)
    changed = {k for k, v in CATALOG.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in GROUPS.items():
        assert cfg[key] == value, key
    for key, here in (("num_hidden_layers", 6), ("num_experts", 16),
                      ("vocab_size", 18992)):
        assert cfg[key] == here and cfg[key + "_published"] == CATALOG[key]
    # the floors: at least four layers (the period is one layer, no
    # leading dense ones), 8 routed experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["experts_held"] == [0, 16]
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["deployment"]["chips_sharing_a_layer"] * cfg["num_experts"] \
        == cfg["num_experts_published"] == 128
    for key, value in (("attention_bias", False), ("hidden_act", "silu"),
                       ("mlp_only_layers", []), ("model_type", "KeyeVL2"),
                       ("norm_topk_prob", True), ("sliding_window", None),
                       ("tie_word_embeddings", False),
                       ("use_sliding_window", False)):
        assert cfg[key] == value, key
    from benchmark.reference import keye_vl2_f32 as ref
    n = sum(math.prod(s) for s in ref.param_spec(cfg).values())
    # a layer: attention 18.87 M, indexer 2.26 M, router 0.26 M, 16
    # experts 75.5 M; the embedding and the head 77.8 M
    layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128
             + 2048 * 1024 + 2048 * 64 + 2 * 64 + 2048 * 16
             + 2048 * 128 + 16 * 3 * 2048 * 768 + 2 * 2048)
    assert n == 6 * layer + 2 * 18992 * 2048 + 2048 == 659190016
    assert round(n / 1e6, 1) == cfg["parameters_millions"]
    cell = _load("workloads", CELL)
    assert cell["traffic"]["batch"] == 1 \
        and cell["traffic"]["seqlen"] == 16384
    assert cell["traffic"]["pool"] == 16 and cell["chips"] == 1
    assert cell["check"]["steps"] == 3
    assert cfg["program"]["mosaic_kernels"] == {
        k: 6 for k in ("dsa_index", "dsa_select", "dsa_attn_fwd",
                       "dsa_attn_bwd_dkdv", "dsa_attn_bwd_dq", "dsa_kl_fwd",
                       "dsa_kl_bwd")}


def test_the_manifest_gives_the_cell_its_readers():
    from benchmark import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e, layer = harness.cell_metrics(manifest, CELL)
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}
    # at least these: a later benchmark PR may append the cell to an
    # accepted reader's list (PERF.md section 7 names those that apply)
    assert set(NEW_READERS) | {
        "train_step_mfu", "step_ms_p50", "device_idle_share",
        "hbm_peak_share"} <= {m["name"] for m in layer}
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            _, theirs = harness.cell_metrics(manifest, other["name"])
            assert not set(NEW_READERS) & {m["name"] for m in theirs}
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    cfg = _load("configs", CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_work_of_the_sparse_attention_and_the_indexer_by_hand():
    from benchmark import dsa_work, flops
    # 2,048 queries keep all of their t + 1 keys, 14,336 keep 2,048
    pairs = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert dsa_work.selected_pairs(16384, 2048) == pairs == 31458304
    assert dsa_work.selected_pairs(100, 2048) == 5050
    fwd = dsa_work.sparse_attention_work(1, 32, 4, 16384, 128, 2048, 2,
                                         False)
    assert fwd["flops"] == 4 * pairs * 32 * 128 == 515412852736
    assert fwd["bytes"] == 16384 * 2 * (2 * 32 * 128 + 2 * 4 * 128)
    least = flops.roofline_seconds(fwd, PEAKS)
    assert least["bound"] == "compute"
    assert round(least["seconds"] * 1e3, 2) == 2.62
    bwd = dsa_work.sparse_attention_work(1, 32, 4, 16384, 128, 2048, 2, True)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    assert round(flops.roofline_seconds(bwd, PEAKS)["seconds"] * 1e3, 2) \
        == 6.54
    index = dsa_work.indexer_work(1, 16384, 16, 64, 2048, 2)
    causal = 16384 * 16385 // 2
    assert index["flops"] == 2 * 1024 * causal + 4 * 1024 * pairs
    assert flops.roofline_seconds(index, PEAKS)["bound"] == "compute"


def test_operations_a_token_by_hand():
    """The cell: a token multiplies by the attention's projections (2048 x
    4096, two of 2048 x 512, 4096 x 2048) and one held expert (8 x 16 /
    128) of 3 x 2048 x 768 at 6; the indexer's projections (2048 x 1024,
    2048 x 64, 2048 x 16) at 4; the router 2048 x 128 at 2; the head
    2048 x 18,992 at 6.  Per layer besides: 12 x 32 x 128 operations a
    selected pair (1,920.06 a query on average) and the indexer, 2 x 1024 a
    causal pair (8,192.5 a query) and 4 x 1024 a selected pair."""
    from benchmark.op_counts import keye_vl2 as count
    cfg = _load("configs", CONFIG)
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    expert = 3 * 2048 * 768
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16
    pairs = 31458304 / 16384
    assert pairs == 1920.0625
    sparse = 12 * 32 * 128 * pairs
    index = 2 * 1024 * 16385 / 2 + 4 * 1024 * pairs
    layer = 6 * (attention + expert) + 4 * indexer + 2 * 2048 * 128 \
        + sparse + index
    by_hand = 6 * layer + 6 * 2048 * 18992
    assert count.train_flops_per_token(cfg, 16384) == pytest.approx(
        by_hand, rel=1e-12)
    assert round(by_hand / 1e9, 3) == 1.854
    # the sparse attention's forward is 31.5 MFLOP a token and the
    # indexer's scores 16.8
    assert round(sparse / 3 / 1e6, 1) == 31.5
    assert round(2 * 1024 * 16385 / 2 / 1e6, 1) == 16.8


def test_reference_layer_by_layer_gradients_equal_jax_grad():
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.reference import keye_vl2_f32 as ref
    cfg = _load("configs", "keye-vl2-tiny-rehearsal")
    params = weights.make_params(11, ref.param_spec(cfg), jnp.float32)
    (ids, labels), = weights.make_batches(11, 1, 2, 128, cfg["vocab_size"])

    def loss_fn(p):
        logits, kl = ref.logits_fn(p, ids, cfg)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked) + kl

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(loss_fn)(params)
        got = {}
        loss = ref.grads_pass(params, ids, labels, cfg, got.__setitem__)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert set(got) == set(want) == set(ref.param_spec(cfg))
    for k in want:
        scale = float(jnp.abs(want[k]).max())
        # 8 of the 16 experts are held: the routers get no gradient
        assert (scale > 0) == (not k.endswith("router.weight")), k
        assert float(jnp.abs(got[k] - want[k]).max()) <= 1e-4 * scale, k
    assert ref.leaf_segments(cfg) == {
        "layers.0.mlp.experts_gate_up": 2, "layers.1.mlp.experts_gate_up": 2}


def test_run_end_to_end_on_the_rehearsal_cell(capsys):
    """The model through `drivers/train_steps.py` against the reference,
    at a toy size: every check within its limit."""
    from benchmark import run as harness
    rc = harness.main(["--workload", TINY, "--seed", "3000000041",
                       "--seconds", "0.3", "--trace", "0"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    # the two routers have no gradient
    left_out = int(out.err.split("'leaves_left_out': ")[1].split("}")[0])
    assert left_out == 2
    from benchmark import scope_times
    counters = scope_times.program_counters(
        {"config": {"program": {"jit_site": SITE}}})
    assert sorted(counters) == ["layers.0.mlp", "layers.0.self_attn",
                                "layers.1.mlp", "layers.1.self_attn"]
    pairs = sum(min(t + 1, 32) for t in range(128))
    # a batch of two sequences, one tile each
    assert counters["layers.0.self_attn"] == [2 * pairs, 2.0, 2.0]
    run = {"config": _load("configs", "keye-vl2-tiny-rehearsal"),
           "notes": []}
    assert _reader("dsa_live_tile_share").read(run) == 100.0


# one step of a synthetic trace: (own name, start ms, duration ms), and the
# census that places each instruction
CENSUS = {
    "fusion.1": ("fwd", "dsa", False),
    "dsa_index.2": ("fwd", "dsa/dsa_index", False),
    "sort.3": ("fwd", "dsa/dsa_select", False),
    "dsa_attn_fwd.4": ("fwd", "dsa/dsa_attn", False),
    "dsa_kl_fwd.5": ("fwd", "dsa/dsa_kl", False),
    "dsa_attn_bwd_dkdv.6": ("bwd", "dsa/dsa_attn", False),
    "dsa_attn_bwd_dq.7": ("bwd", "dsa/dsa_attn", False),
    "dsa_kl_bwd.8": ("bwd", "dsa/dsa_kl", False),
    "fusion.9": ("fwd", "moe/router", False),
    "fusion.10": ("update", "update", False),
}
STEP = (("fusion.1", 0, 3), ("dsa_index.2", 3, 4), ("sort.3", 7, 10),
        ("dsa_attn_fwd.4", 17, 30), ("dsa_kl_fwd.5", 47, 20),
        ("dsa_attn_bwd_dkdv.6", 67, 40), ("dsa_attn_bwd_dq.7", 107, 35),
        ("dsa_kl_bwd.8", 142, 20), ("fusion.9", 162, 2),
        ("fusion.10", 164, 5))
MS = 1e6


def _run(monkeypatch, census=CENSUS):
    from benchmark import phase_times, trace_reduce

    class Registry:
        def phase_census(self, site):
            return census

    monkeypatch.setattr(phase_times, "_registry", Registry)
    ops = [(f"%{name} = bf16[8,128]{{1,0}} fusion(%x)",
            (s * 200 + start) * MS, dur * MS)
           for s in range(2) for name, start, dur in STEP]
    return {"config": dict(_load("configs", CONFIG)), "notes": [],
            "peaks": PEAKS,
            "facts": {"batch": 1, "seqlen": 16384,
                      "traced": {"ops": ops,
                                 "busy_s": trace_reduce.busy_ns(ops) / 1e9}}}


def test_the_new_readers_give_the_hand_counted_values(monkeypatch):
    from benchmark import dsa_work, flops
    run = _run(monkeypatch)

    def least(work):
        return flops.roofline_seconds(work, PEAKS)["seconds"]

    # six layers: 30 ms forward a step, 40 + 35 backward
    assert _reader("dsa_attn_fwd_roofline").read(run) == pytest.approx(
        100 * 6 * least(dsa_work.sparse_attention_work(
            1, 32, 4, 16384, 128, 2048, 2, False)) / 30e-3)
    assert _reader("dsa_attn_bwd_roofline").read(run) == pytest.approx(
        100 * 6 * least(dsa_work.sparse_attention_work(
            1, 32, 4, 16384, 128, 2048, 2, True)) / 75e-3)
    assert _reader("dsa_indexer_roofline").read(run) == pytest.approx(
        100 * 6 * least(dsa_work.indexer_work(1, 16384, 16, 64, 2048, 2))
        / 44e-3)
    assert _reader("dsa_select_ms").read(run) == pytest.approx(10.0)
    assert any("dsa_kl 20.000 fwd + 20.000 bwd" in n for n in run["notes"])


def test_the_live_tile_share_reads_the_attention_layers_counters(
        monkeypatch):
    from benchmark import scope_times
    counters = {"layers.0.self_attn": [31458304.0, 528.0, 520.0],
                "layers.1.self_attn": [31458304.0, 528.0, 528.0],
                "layers.0.mlp": [1000.0, 2048.0, 90.0, 62.5]}
    monkeypatch.setattr(scope_times, "program_counters", lambda run: counters)
    run = {"config": _load("configs", CONFIG), "notes": []}
    assert _reader("dsa_live_tile_share").read(run) == pytest.approx(
        100 * 1048 / 1056)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_with_nothing_to_read_is_silent(monkeypatch, name):
    """A program without the scopes, kernels and counters (a census that
    places nothing under ``dsa``, the parent commit's counters of expert
    layers alone), and a run without a trace: ``None`` both times, never 0
    and never an error."""
    from benchmark import scope_times
    old = {k: (p, c if not c.startswith("dsa") else "", m)
           for k, (p, c, m) in CENSUS.items()}
    monkeypatch.setattr(scope_times, "program_counters",
                        lambda run: {"layers.0.mlp": [1.0, 2.0, 3.0, 4.0]})
    run = _run(monkeypatch, census=old)
    assert _reader(name).read(run) is None
    monkeypatch.setattr(scope_times, "program_counters", lambda run: None)
    run = _run(monkeypatch)
    run["facts"]["traced"] = None
    assert _reader(name).read(run) is None
