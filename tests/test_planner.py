"""Auto-parallel planner (parallel/planner.py): dataflow plan derivation
(the reference's completion/planner/mapper, ``auto_parallel/planner.py``
``cost_model.py``) + compiler-measured scoring.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import nn, parallel
from paddle_hackathon_tpu.models import GPTConfig, GPTForCausalLM
from paddle_hackathon_tpu.parallel.planner import plan_sharding, score_plan

# plan_mesh has no default peak: these tests plan for a v5e (bf16 peak,
# public spec) from the virtual CPU mesh
_V5E = 197e12


def _tiny_gpt():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=32,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    return GPTForCausalLM(cfg)


class TestPlanGPT:
    def test_reproduces_megatron_alternation(self):
        """From pure dataflow — no name patterns — the planner must land on
        the hand-written models/gpt.py::param_sharding_spec plan."""
        m = _tiny_gpt()
        mesh = parallel.create_mesh({"dp": 2, "mp": 4})
        try:
            rule = plan_sharding(m, mesh, (jnp.zeros((2, 32), jnp.int32),),
                                 min_shard_elems=1)
        finally:
            parallel.set_mesh(None)
        p = rule.plan
        for i in range(2):
            assert p[f"gpt.blocks.{i}.attn.qkv_proj.weight"] == (None, "mp")
            assert p[f"gpt.blocks.{i}.attn.out_proj.weight"] == ("mp", None)
            assert p[f"gpt.blocks.{i}.mlp.fc_in.weight"] == (None, "mp")
            assert p[f"gpt.blocks.{i}.mlp.fc_out.weight"] == ("mp", None)
            # column biases ride the shard; row biases replicate
            assert p[f"gpt.blocks.{i}.attn.qkv_proj.bias"] == ("mp",)
            assert f"gpt.blocks.{i}.attn.out_proj.bias" not in p
            # LayerNorm params replicate
            assert f"gpt.blocks.{i}.ln_1.weight" not in p
        assert p["gpt.wte.weight"] == ("mp", None)
        # the rule is total: unknown names fall back to replication
        assert rule("no.such.param", (3, 5)) == (None, None)

    def test_planned_step_matches_replicated(self):
        mesh = parallel.create_mesh({"dp": 2, "mp": 4})
        try:
            paddle.seed(0)
            m1 = _tiny_gpt()
            rule = plan_sharding(m1, mesh,
                                 (jnp.zeros((8, 32), jnp.int32),),
                                 min_shard_elems=1)
            step1, st1 = parallel.make_sharded_train_step(
                m1, mesh, rule=rule, learning_rate=1e-3)
            m2 = _tiny_gpt()
            step2, st2 = parallel.make_sharded_train_step(
                m2, mesh, rule=None, learning_rate=1e-3)
            rng = np.random.RandomState(0)
            ids = jnp.asarray(rng.randint(0, 256, (8, 32)), jnp.int32)
            lab = jnp.asarray(rng.randint(0, 256, (8, 32)), jnp.int32)
            for _ in range(3):
                st1, l1 = step1(st1, ids, lab, jax.random.key(7))
                st2, l2 = step2(st2, ids, lab, jax.random.key(7))
            np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)
        finally:
            parallel.set_mesh(None)

    def test_score_plan_measures_memory_win(self):
        """The cost-model analog must report the TP plan's param-memory
        saving from the actual compiled executable."""
        mesh = parallel.create_mesh({"dp": 2, "mp": 4})
        try:
            m = _tiny_gpt()
            rule = plan_sharding(m, mesh, (jnp.zeros((8, 32), jnp.int32),),
                                 min_shard_elems=1)
            planned = score_plan(m, mesh, rule,
                                 (jnp.zeros((8, 32), jnp.int32),))
            repl = score_plan(m, mesh, None,
                              (jnp.zeros((8, 32), jnp.int32),))
        finally:
            parallel.set_mesh(None)
        assert planned["arg_bytes_per_device"] < repl["arg_bytes_per_device"]
        assert planned["collective_bytes"] > 0
        assert "all-reduce" in repl["collectives"]


class _PlainMLP(nn.Layer):
    """Generic names (l0/l1/l2) the GPT hand-rule regexes would never
    match — the planner must still alternate column/row from dataflow."""

    def __init__(self):
        super().__init__()
        self.l0 = nn.Linear(64, 256)
        self.l1 = nn.Linear(256, 256)
        self.l2 = nn.Linear(256, 64)
        self.act = nn.GELU()

    def forward(self, x):
        return self.l2(self.act(self.l1(self.act(self.l0(x)))))


class TestPlanNameFree:
    def test_mlp_alternates_from_dataflow(self):
        paddle.seed(0)
        m = _PlainMLP()
        mesh = parallel.create_mesh({"dp": 2, "mp": 4})
        try:
            rule = plan_sharding(m, mesh,
                                 (jnp.zeros((4, 64), jnp.float32),),
                                 min_shard_elems=1)
        finally:
            parallel.set_mesh(None)
        p = rule.plan
        assert p["l0.weight"] == (None, "mp")   # column
        assert p["l1.weight"] == ("mp", None)   # row: input sharded
        assert p["l2.weight"] == (None, "mp")   # column again after psum
        assert p["l0.bias"] == ("mp",)
        assert "l1.bias" not in p

    def test_engine_plan_applies_shardings(self):
        from paddle_hackathon_tpu.parallel.auto_parallel import (Engine,
                                                                 ProcessMesh)
        paddle.seed(0)
        m = _PlainMLP()
        pm = ProcessMesh(np.arange(8).reshape(2, 4),
                         dim_names=["dp", "mp"])
        try:
            eng = Engine(m, process_mesh=pm)
            rule = eng.plan(jnp.zeros((4, 64), jnp.float32))
            assert rule.plan["l0.weight"] == (None, "mp")
            # params were placed: the column weight is device-sharded on mp
            w = dict(m.named_parameters())["l0.weight"]._value
            spec = w.sharding.spec
            assert tuple(spec) == (None, "mp")
        finally:
            parallel.set_mesh(None)


class TestPlanMesh:
    """Planner v2 (VERDICT r4 missing #7): recommend the MESH — every
    candidate factorization AOT-compiled and measured (memory gate +
    compute/bubble/comm score)."""

    def _model(self, layers=2):
        paddle.seed(0)
        from paddle_hackathon_tpu.models import GPTConfig, GPTForCausalLM
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=layers,
                        num_heads=4, max_position_embeddings=32,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        use_flash_attention=False)
        return GPTForCausalLM(cfg)

    def test_enumerate_meshes_filters(self):
        from paddle_hackathon_tpu.parallel import enumerate_meshes
        cands = enumerate_meshes(8, n_layers=2, batch=8)
        keys = [tuple(sorted(d.items())) for d in cands]
        assert len(set(keys)) == len(keys)  # deduped
        for d in cands:
            n = 1
            for v in d.values():
                n *= v
            assert n == 8 or (n < 8 and list(d) == ["dp"])
            assert d.get("pp", 1) in (1, 2)  # pp must divide 2 layers
        assert {"dp": 8} in cands and {"mp": 8} in cands

    def test_plan_mesh_picks_measured_best_and_pins_table(self):
        """On the 8-device virtual mesh the recommendation must be the
        feasible candidate with the minimal estimated step — and for
        this comm-dominated tiny GPT that is a pp-bearing config (pp
        halves the dp grad-allreduce payload), with pure-dp next."""
        m = self._model()
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (8, 32)),
                          jnp.int32)
        cands = [{"dp": 8}, {"dp": 4, "pp": 2}, {"dp": 4, "mp": 2},
                 {"sharding": 4, "mp": 2}, {"dp": 2, "mp": 4}]
        try:
            choice = parallel.plan_mesh(m, 8, (ids,), candidates=cands,
                                        zero_stages=(0,), peak_flops=_V5E)
        finally:
            parallel.set_mesh(None)
        feas = [r for r in choice.table if r.get("feasible")]
        assert len(feas) >= 4
        best = min(feas, key=lambda r: r["est_step_s"])
        assert choice.mesh_dims == best["mesh"]
        assert choice.mesh_dims == {"dp": 4, "pp": 2}
        # every row carries the compiler's measurements
        for r in feas:
            assert r["bytes_per_device"] > 0
            assert "collective_bytes" in r

    def test_plan_mesh_memory_budget_forces_sharding(self):
        """A budget below the replicated footprint must push the choice
        to a config that shards parameters (zero-3 or mp)."""
        m = self._model()
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (8, 32)),
                          jnp.int32)
        cands = [{"dp": 8}, {"sharding": 8}, {"dp": 2, "sharding": 4}]
        try:
            full = parallel.plan_mesh(m, 8, (ids,), candidates=[{"dp": 8}],
                                      zero_stages=(0,), peak_flops=_V5E)
            dp8 = full.table[0]["bytes_per_device"]
            choice = parallel.plan_mesh(m, 8, (ids,), candidates=cands,
                                        hbm_bytes=dp8 * 0.8,
                                        peak_flops=_V5E)
        finally:
            parallel.set_mesh(None)
        assert "sharding" in choice.mesh_dims
        assert choice.zero_stage == 3
        dp8_rows = [r for r in choice.table if r["mesh"] == {"dp": 8}]
        assert all(not r["feasible"] for r in dp8_rows)

    def test_plan_mesh_no_fit_raises(self):
        m = self._model()
        ids = jnp.asarray(np.zeros((8, 32)), jnp.int32)
        with pytest.raises(RuntimeError, match="memory budget"):
            try:
                parallel.plan_mesh(m, 8, (ids,), candidates=[{"dp": 8}],
                                   zero_stages=(0,), hbm_bytes=1.0,
                                   peak_flops=_V5E)
            finally:
                parallel.set_mesh(None)

    def test_engine_plan_n_devices(self):
        from paddle_hackathon_tpu.parallel.auto_parallel import Engine
        m = self._model()
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (8, 32)),
                          jnp.int32)
        try:
            eng = Engine(m)
            choice = eng.plan((ids,), n_devices=8,
                              candidates=[{"dp": 8}, {"dp": 4, "pp": 2}],
                              zero_stages=(0,), peak_flops=_V5E)
            assert dict(eng.mesh.shape) == choice.mesh_dims
        finally:
            parallel.set_mesh(None)
