"""Cross-process sharded train step — the TestDistBase analog.

The reference's distributed test backbone spawns real trainer processes on
one host and asserts 1-proc vs N-proc loss parity
(``test_dist_base.py:786``, ``_run_cluster:1041``). Single-process virtual
meshes cannot catch per-process data-feed skew, coordinator rendezvous
bugs, or host-local array leaks — so here the launcher spawns 2 OS
processes (4 virtual CPU devices each) that ``jax.distributed.initialize``
into ONE 8-device dp×mp mesh, run ``make_sharded_train_step`` for 3 steps,
and rank 0's losses must match the same mesh run in a single process.
"""

import json
import os
import sys
import textwrap

import jax
import numpy as np
import pytest

from paddle_hackathon_tpu.distributed.launch import launch

# Old jax's CPU backend has no cross-process collectives ("Multiprocess
# computations aren't implemented on the CPU backend") — the 2-process
# rendezvous itself works, but the first sharded device_put aborts the
# workers.  Keyed on the same capability marker as the other jax>=0.6
# gates (jax-0437 container note).
pytestmark = pytest.mark.skipif(
    not hasattr(jax, "set_mesh"),
    reason="requires_multiprocess_cpu: jax<0.6 CPU backend has no "
           "multiprocess collectives")

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _launch_two(script, log_dir, job):
    """Two ranks through the launcher; returns (exit code, both logs).
    A rank that fails is not restarted (the failure is the result), and
    whatever ends the wait, the per-test limit included, carries what
    the ranks had written: two processes that sit at their rendezvous
    say nothing in the launcher's own stack."""
    def logs():
        return "".join(f"--- {f.name}\n{f.read_text()}"
                       for f in sorted(log_dir.iterdir()))
    try:
        rc = launch(["--nproc_per_node", "2", "--max_restart", "0",
                     "--log_dir", str(log_dir), "--job_id", job,
                     str(script)])
    except BaseException as e:
        e.add_note("the ranks' logs:\n" + (logs() if log_dir.exists()
                                            else "(none written)"))
        raise
    return rc, logs()


@pytest.fixture(autouse=True)
def _no_leaked_mesh():
    """The single-process references create pp meshes through
    ``create_mesh``, which also sets the package-global mesh; this file
    sorts first in tier-1, so a leaked 'pp' mesh turns every later
    ``ServingEngine`` construction into a pipeline engine."""
    yield
    from paddle_hackathon_tpu import parallel
    parallel.set_mesh(None)

_WORKER = """
    import os
    flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sys
    sys.path.insert(0, %r)
    import json
    import numpy as np
    import jax.numpy as jnp
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)

    parallel.init_parallel_env()
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.local_devices()) == 4
    assert len(jax.devices()) == 8

    def run(mesh_dims):
        paddle.seed(123)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=32,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        mesh = parallel.create_mesh(mesh_dims)
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            grad_clip_norm=None)
        r = np.random.RandomState(0)
        ids = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
        labels = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
        losses = []
        for i in range(3):
            state, loss = step(state, ids, labels, jax.random.key(0))
            losses.append(float(loss))
        return losses

    out = {"dpmp": run({"dp": 4, "mp": 2}),
           # the pp axis SPANS the two processes: the 1F1B ppermute ticks
           # cross the controller boundary
           "ppdpmp": run({"pp": 2, "dp": 2, "mp": 2})}
    print("LOSSES", jax.process_index(), json.dumps(out))
""" % _REPO


def _single_process_reference(mesh_dims):
    """The same mesh/model/data in THIS (8-virtual-device) process."""
    import jax
    import jax.numpy as jnp

    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)

    paddle.seed(123)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=32,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    mesh = parallel.create_mesh(mesh_dims)
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
        grad_clip_norm=None)
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
    labels = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
    losses = []
    for i in range(3):
        state, loss = step(state, ids, labels, jax.random.key(0))
        losses.append(float(loss))
    return losses


_CKPT_WORKER = """
    import os
    flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sys
    sys.path.insert(0, %r)
    import json
    import numpy as np
    import jax.numpy as jnp
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)
    from paddle_hackathon_tpu.parallel.dist_checkpoint import (
        load_train_state, save_train_state)

    parallel.init_parallel_env()
    assert jax.process_count() == 2

    phase = os.environ["CKPT_PHASE"]
    ckpt = os.environ["CKPT_PATH"]
    paddle.seed(123)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=32,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    mesh = parallel.create_mesh({"dp": 4, "mp": 2})
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
        grad_clip_norm=None)
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
    labels = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
    losses = []
    if phase == "save":
        for i in range(2):
            state, loss = step(state, ids, labels, jax.random.key(0))
            losses.append(float(loss))
        save_train_state(state, ckpt)
    else:
        state = load_train_state(ckpt, state)
        assert int(np.asarray(state["step"])) == 2
        for i in range(2):
            state, loss = step(state, ids, labels, jax.random.key(0))
            losses.append(float(loss))
    print("CKLOSS", jax.process_index(), json.dumps(losses))
""" % _REPO


def test_two_process_checkpoint_save_then_resume(tmp_path):
    """ADVICE r4 #5: the multihost barrier / rank-0 swap / device_put
    branch of save_train_state/load_train_state, exercised across real OS
    processes — save on one 2-process run, resume on a second, and the
    resumed trajectory must continue the single-process 4-step one."""
    script = tmp_path / "dist_ckpt.py"
    script.write_text(textwrap.dedent(_CKPT_WORKER))
    ckpt = str(tmp_path / "ck")

    def run(phase, job):
        os.environ["CKPT_PHASE"] = phase
        os.environ["CKPT_PATH"] = ckpt
        try:
            rc, logs = _launch_two(script, tmp_path / ("logs_" + phase), job)
        finally:
            del os.environ["CKPT_PHASE"], os.environ["CKPT_PATH"]
        assert rc == 0, logs
        per_rank = {}
        for line in logs.splitlines():
            if line.startswith("CKLOSS "):
                _, rank, payload = line.split(" ", 2)
                per_rank[int(rank)] = json.loads(payload)
        assert sorted(per_rank) == [0, 1], logs
        np.testing.assert_allclose(per_rank[0], per_rank[1], rtol=1e-6)
        return per_rank[0]

    first = run("save", "ckxp1")
    resumed = run("resume", "ckxp2")

    # single-process 4-step reference over the same mesh/data
    import jax
    import jax.numpy as jnp

    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)
    paddle.seed(123)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=32,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    mesh = parallel.create_mesh({"dp": 4, "mp": 2})
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
        grad_clip_norm=None)
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
    labels = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
    ref = []
    for i in range(4):
        state, loss = step(state, ids, labels, jax.random.key(0))
        ref.append(float(loss))
    np.testing.assert_allclose(first + resumed, ref, rtol=2e-4)


def test_two_process_trainstep_matches_single_process(tmp_path):
    script = tmp_path / "dist_trainstep.py"
    script.write_text(textwrap.dedent(_WORKER))
    rc, logs = _launch_two(script, tmp_path / "logs", "xproc")
    assert rc == 0, logs

    per_rank = {}
    for line in logs.splitlines():
        if line.startswith("LOSSES "):
            _, rank, payload = line.split(" ", 2)
            per_rank[int(rank)] = json.loads(payload)
    assert sorted(per_rank) == [0, 1], logs
    for config in ("dpmp", "ppdpmp"):
        # both controllers run the same SPMD program — identical losses
        np.testing.assert_allclose(per_rank[0][config], per_rank[1][config],
                                   rtol=1e-6, err_msg=config)
    np.testing.assert_allclose(per_rank[0]["dpmp"],
                               _single_process_reference({"dp": 4, "mp": 2}),
                               rtol=2e-4)
    np.testing.assert_allclose(
        per_rank[0]["ppdpmp"],
        _single_process_reference({"pp": 2, "dp": 2, "mp": 2}), rtol=2e-4)
