"""Train -> checkpoint -> resume workflows across meshes and pp layouts.

The reference's story: ``fleet.save_persistables`` + auto_checkpoint
resume (SURVEY §5.4), with ``converter.py`` re-sharding checkpoints
across different meshes. Here ``parallel.save_train_state`` /
``load_train_state`` checkpoint the full one-program trainer state
(params + Adam moments + step) and resume on ANY mesh — including moving
between pp-stacked and per-layer parameter layouts — with the loss
trajectory of an uninterrupted run.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import parallel
from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                         param_sharding_spec)


def _tiny():
    return GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_heads=4, max_position_embeddings=32,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     use_flash_attention=False)


def _data():
    r = np.random.RandomState(0)
    return (jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32),
            jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32))


def _build(mesh_dims, zero=0):
    paddle.seed(123)
    model = GPTForCausalLM(_tiny())
    n = int(np.prod(list(mesh_dims.values())))
    mesh = parallel.create_mesh(mesh_dims, devices=jax.devices()[:n])
    step, state = parallel.make_sharded_train_step(
        model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
        zero_stage=zero, grad_clip_norm=None)
    return step, state


def _run(step, state, ids, labels, n, start=0):
    out = []
    for i in range(start, start + n):
        state, loss = step(state, ids, labels, jax.random.key(i))
        out.append(float(loss))
    return state, out


@pytest.mark.parametrize("mesh_a,zero_a,mesh_b,zero_b", [
    ({"dp": 4, "mp": 2}, 0, {"dp": 4, "mp": 2}, 0),         # same mesh
    ({"dp": 4, "mp": 2}, 1, {"dp": 2, "sharding": 2, "mp": 2}, 3),  # reshard
    ({"pp": 2, "dp": 2, "mp": 2}, 0, {"dp": 4, "mp": 2}, 0),  # pp -> flat
    ({"dp": 4, "mp": 2}, 0, {"pp": 2, "dp": 2, "mp": 2}, 0),  # flat -> pp
])
def test_resume_matches_uninterrupted(tmp_path, mesh_a, zero_a, mesh_b,
                                      zero_b, request):
    ids, labels = _data()

    # the reference trajectory: 4 steps uninterrupted on mesh B
    step_b, state_b = _build(mesh_b, zero_b)
    _, straight = _run(step_b, state_b, ids, labels, 4)

    # 2 steps on mesh A, checkpoint, resume 2 more on mesh B
    step_a, state_a = _build(mesh_a, zero_a)
    state_a, first = _run(step_a, state_a, ids, labels, 2)
    path = str(tmp_path / "ck")
    parallel.save_train_state(state_a, path)

    step_b2, fresh_b = _build(mesh_b, zero_b)
    resumed = parallel.load_train_state(path, fresh_b)
    assert int(np.asarray(resumed["step"])) == 2
    _, rest = _run(step_b2, resumed, ids, labels, 2, start=2)

    np.testing.assert_allclose(first + rest, straight, rtol=2e-3)
    parallel.set_mesh(None)


def test_missing_key_raises(tmp_path):
    step, state = _build({"dp": 8})
    parallel.save_train_state(state, str(tmp_path / "ck"))
    bad = {"params": dict(state["params"]), "opt_state": state["opt_state"],
           "step": state["step"]}
    bad["params"]["nonexistent.weight"] = next(iter(
        state["params"].values()))
    with pytest.raises(KeyError, match="nonexistent"):
        parallel.load_train_state(str(tmp_path / "ck"), bad)
    parallel.set_mesh(None)


def test_crash_relaunch_resumes_from_checkpoint(tmp_path):
    """The auto-checkpoint story end-to-end (ref ``auto_checkpoint.py``
    TrainEpochRange resume-after-relaunch + the launcher's restart
    policy): a trainer that checkpoints every step crashes mid-run; the
    launcher restarts it; the relaunched process resumes from the
    checkpoint and the full loss trajectory matches an uninterrupted
    run."""
    import textwrap

    from paddle_hackathon_tpu.distributed.launch import launch

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    ck = tmp_path / "ck"
    sentinel = tmp_path / "crashed_once"
    out = tmp_path / "losses.txt"
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os
        flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                         if "host_platform_device_count" not in f)
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
        import sys
        sys.path.insert(0, %r)
        import numpy as np
        import jax.numpy as jnp
        import paddle_hackathon_tpu as paddle
        from paddle_hackathon_tpu import parallel
        from paddle_hackathon_tpu.models import (GPTConfig, GPTForCausalLM,
                                                 param_sharding_spec)

        CK, SENTINEL, OUT = %r, %r, %r
        paddle.seed(123)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=32,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        mesh = parallel.create_mesh({"dp": 4, "mp": 2})
        step, state = parallel.make_sharded_train_step(
            model, mesh, rule=param_sharding_spec, learning_rate=1e-3,
            grad_clip_norm=None)
        try:                                      # resume after relaunch
            state = parallel.load_train_state(CK, state)
        except FileNotFoundError:                 # cold start
            pass
        r = np.random.RandomState(0)
        ids = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
        labels = jnp.asarray(r.randint(0, 128, (8, 16)), jnp.int32)
        start = int(np.asarray(state["step"]))
        for i in range(start, 4):
            state, loss = step(state, ids, labels, jax.random.key(i))
            with open(OUT, "a") as f:
                f.write(f"{i} {float(loss):.6f}\\n")
            parallel.save_train_state(state, CK)
            if i == 1 and not os.path.exists(SENTINEL):
                open(SENTINEL, "w").write("x")    # simulate a crash
                os._exit(17)
        print("DONE at", int(np.asarray(state["step"])))
    """ % (repo, str(ck), str(sentinel), str(out))))

    rc = launch(["--nproc_per_node", "1", "--max_restart", "2",
                 "--log_dir", str(tmp_path / "logs"), "--job_id",
                 "resume_e2e", str(script)])
    logs = "".join(f.read_text()
                   for f in (tmp_path / "logs").iterdir())
    assert rc == 0, logs
    assert "DONE at 4" in logs
    assert sentinel.exists()

    # per-step losses across the crash == one uninterrupted run
    rows = {}
    for line in out.read_text().splitlines():
        i, v = line.split()
        rows[int(i)] = float(v)    # re-run of step 1 overwrites by key
    assert sorted(rows) == [0, 1, 2, 3]

    ids, labels = _data()
    step, state = _build({"dp": 4, "mp": 2})
    _, straight = _run(step, state, ids, labels, 4)
    np.testing.assert_allclose([rows[i] for i in range(4)], straight,
                               rtol=2e-3)
    parallel.set_mesh(None)


def test_atomic_save_recovers_from_torn_write(tmp_path):
    """A crash mid-save must never destroy the last good checkpoint: the
    save lands in {path}.saving and swaps in atomically; a torn .saving
    (no COMMITTED marker) is ignored and the previous checkpoint loads."""
    ids, labels = _data()
    step, state = _build({"dp": 8})
    state, _ = _run(step, state, ids, labels, 1)
    path = str(tmp_path / "ck")
    parallel.save_train_state(state, path)

    # simulate a torn follow-up save: partial files, no COMMITTED marker
    os.makedirs(path + ".saving", exist_ok=True)
    with open(os.path.join(path + ".saving", "shards-p0.npz"), "wb") as f:
        f.write(b"truncated")
    resumed = parallel.load_train_state(path, state)
    assert int(np.asarray(resumed["step"])) == 1

    # a COMMITTED .saving (crash after commit, before the swap) wins
    state2, _ = _run(step, state, ids, labels, 1, start=1)
    os.rename(path, path + ".old2")
    import shutil
    shutil.rmtree(path + ".saving", ignore_errors=True)
    parallel.save_train_state(state2, path)           # full save
    os.rename(path, path + ".saving")                 # pretend mid-swap
    os.rename(path + ".old2", path)                   # old ck back in place
    resumed2 = parallel.load_train_state(path, state)
    assert int(np.asarray(resumed2["step"])) == 2
    parallel.set_mesh(None)
