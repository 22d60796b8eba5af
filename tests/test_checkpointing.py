"""Crash-safe checkpointing + fault-injection harness (tier-1 units).

Lean by design (tier-1 is compile-bound on the CPU): everything here is
host-side — tiny numpy arrays, tmp_path, no engine/trainer compiles.
The full crash drill (subprocess kill mid-fit, corruption, dp-reshard
resume) lives in ``test_crash_drill.py`` behind the ``slow`` marker.

Covers: fault-point arming/disarm + seeded schedule determinism
(``observability/faults.py``), the atomic commit protocol and its
torn-manifest/torn-shard detection with previous-checkpoint fallback
(``parallel/checkpointing.py``), keep-last-K retention, elastic
lease-store retry/backoff + ``LeaseLostError``
(``distributed/elastic.py``), the queued-deadline abort
(``ServingEngine.submit(deadline_s=)``) and torn serving artifacts
(``save_for_serving``/``load_for_serving``).
"""

import json
import os
import time

import numpy as np
import pytest

from paddle_hackathon_tpu.observability import faults
from paddle_hackathon_tpu.parallel import checkpointing as ck


@pytest.fixture(autouse=True)
def _disarmed():
    faults.disarm()
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# fault harness
# ---------------------------------------------------------------------------

class TestFaults:
    def test_disarmed_point_is_silent_noop(self):
        # the production steady state: unknown / disarmed names never
        # raise, never allocate — one empty-dict probe
        assert faults.armed() == {}
        faults.point("never.armed")
        assert faults.hits("never.armed") == 0

    def test_fail_on_nth_hit_fires_exactly_once(self):
        faults.arm("p.a=fail@2")
        faults.point("p.a")                      # hit 1: passes
        with pytest.raises(faults.InjectedFault):
            faults.point("p.a")                  # hit 2: fires
        faults.point("p.a")                      # hit 3: passes (retry ok)
        assert faults.hits("p.a") == 3
        assert faults.armed("p.a").fired == 1

    def test_prob_schedule_is_seed_deterministic(self):
        def run():
            faults.arm("p.b=prob@0.5,seed=11")
            seq = []
            for _ in range(12):
                try:
                    faults.point("p.b")
                    seq.append(0)
                except faults.InjectedFault:
                    seq.append(1)
            return seq

        s1, s2 = run(), run()
        assert s1 == s2
        assert 0 < sum(s1) < 12   # actually probabilistic, not constant

    def test_delay_flavor_sleeps_then_passes(self):
        faults.arm("p.c=delay@1,secs=0.02")
        t0 = time.perf_counter()
        faults.point("p.c")
        assert time.perf_counter() - t0 >= 0.015

    def test_grammar_errors_are_named(self):
        with pytest.raises(faults.FaultSpecError):
            faults.arm("no-equals-sign")
        with pytest.raises(faults.FaultSpecError):
            faults.arm("x=unknownkind@1")
        with pytest.raises(faults.FaultSpecError):
            faults.arm("x=fail@1,bogus=2")
        with pytest.raises(faults.FaultSpecError):
            faults.arm("x=prob@0.5,flavor=nope")

    def test_arm_is_all_or_nothing(self):
        # a malformed second entry must not leave the first one armed
        # with no context manager ever disarming it
        with pytest.raises(faults.FaultSpecError):
            faults.arm("p.good=fail@1;p.bad=bogus@1")
        assert faults.armed("p.good") is None

    def test_injected_context_manager_disarms_its_names(self):
        faults.arm("keep.me=fail@99")
        with faults.injected("p.d=fail@1"):
            assert faults.armed("p.d") is not None
            with pytest.raises(faults.InjectedFault):
                faults.point("p.d")
        assert faults.armed("p.d") is None
        assert faults.armed("keep.me") is not None

    def test_fired_faults_leave_flight_events(self):
        from paddle_hackathon_tpu.observability import flight
        faults.arm("p.e=fail@1")
        with pytest.raises(faults.InjectedFault):
            faults.point("p.e")
        evts = [e for e in flight.get_flight_recorder().events()
                if e["kind"] == "fault" and e.get("point") == "p.e"]
        assert evts and evts[-1]["flavor"] == "fail"


# ---------------------------------------------------------------------------
# atomic commit protocol
# ---------------------------------------------------------------------------

def _flat(step=3):
    return {"params::w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "params::b": np.ones(4, np.float32),
            "opt::0::m": np.zeros((3, 4), np.float32),
            "step": np.asarray(step, np.int32)}


def _mgr(tmp_path, **kw):
    kw.setdefault("keep_last_k", 3)
    return ck.CheckpointManager(str(tmp_path), **kw)


class TestAtomicCommit:
    def test_roundtrip_and_manifest_shape(self, tmp_path):
        m = _mgr(tmp_path)
        m.save(_flat(), step=3, epoch=1, cursor=2, block=True)
        assert m.last_error is None
        (step, path), = ck.list_checkpoints(str(tmp_path))
        assert step == 3
        man = json.load(open(os.path.join(path, "manifest.json")))
        assert man["version"] == 1 and man["epoch"] == 1 and man["cursor"] == 2
        # every shard entry carries its integrity evidence
        for meta in man["arrays"].values():
            assert {"file", "crc32", "bytes", "shape", "dtype"} <= set(meta)
        flat, man2 = ck.load_latest(str(tmp_path))
        for k, v in _flat().items():
            np.testing.assert_array_equal(np.asarray(flat[k]), v)

    def test_extension_dtypes_roundtrip(self, tmp_path):
        import ml_dtypes
        m = _mgr(tmp_path)
        want = np.asarray([1.5, -2.0, 0.25], ml_dtypes.bfloat16)
        m.save({"params::h": want}, step=1, block=True)
        flat, _ = ck.load_latest(str(tmp_path))
        got = np.asarray(flat["params::h"])
        assert got.dtype.name == "bfloat16"
        np.testing.assert_array_equal(got.astype(np.float32),
                                      want.astype(np.float32))

    def test_torn_shard_detected_and_falls_back(self, tmp_path):
        m = _mgr(tmp_path)
        m.save(_flat(1), step=1, block=True)
        m.save(_flat(2), step=2, block=True)
        p2 = dict(ck.list_checkpoints(str(tmp_path)))[2]
        shard = sorted(f for f in os.listdir(p2) if f.startswith("shard"))[0]
        with open(os.path.join(p2, shard), "r+b") as f:
            f.write(b"\xde\xad\xbe\xef")   # flip bytes: crc must catch it
        with pytest.warns(UserWarning, match="corrupt"):
            flat, man = ck.load_latest(str(tmp_path))
        assert man["step"] == 1   # previous valid checkpoint, not garbage
        with pytest.raises(ck.CorruptCheckpointError, match="torn shard"):
            ck.load_checkpoint(p2)

    def test_torn_manifest_detected_and_falls_back(self, tmp_path):
        m = _mgr(tmp_path)
        m.save(_flat(1), step=1, block=True)
        m.save(_flat(2), step=2, block=True)
        p2 = dict(ck.list_checkpoints(str(tmp_path)))[2]
        mf = os.path.join(p2, "manifest.json")
        torn = open(mf).read()[:17]        # truncated json: torn write
        open(mf, "w").write(torn)
        with pytest.warns(UserWarning, match="corrupt"):
            flat, man = ck.load_latest(str(tmp_path))
        assert man["step"] == 1
        # corruption is counted, never silently loaded
        from paddle_hackathon_tpu.observability import get_registry
        assert get_registry().total("checkpoint_failures_total",
                                    stage="load") >= 1

    def test_all_corrupt_returns_none(self, tmp_path):
        m = _mgr(tmp_path)
        m.save(_flat(1), step=1, block=True)
        p1 = dict(ck.list_checkpoints(str(tmp_path)))[1]
        open(os.path.join(p1, "manifest.json"), "w").write("{")
        with pytest.warns(UserWarning):
            flat, man = ck.load_latest(str(tmp_path))
        assert flat is None and man is None

    def test_retention_keeps_last_k(self, tmp_path):
        m = _mgr(tmp_path, keep_last_k=2)
        for s in (1, 2, 3, 4):
            m.save(_flat(s), step=s, block=True)
        assert [s for s, _ in ck.list_checkpoints(str(tmp_path))] == [3, 4]

    def test_injected_write_failure_keeps_previous(self, tmp_path):
        m = _mgr(tmp_path)
        m.save(_flat(1), step=1, block=True)
        faults.arm("ckpt.manifest_write=fail@1")
        m.save(_flat(2), step=2, block=True)
        assert isinstance(m.last_error, faults.InjectedFault)
        # no tmp litter, previous checkpoint intact and loadable
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.startswith(".tmp-")]
        flat, man = ck.load_latest(str(tmp_path))
        assert man["step"] == 1
        # and a LATER save succeeds (the writer thread survived)
        m.save(_flat(3), step=3, block=True)
        assert ck.load_latest(str(tmp_path))[1]["step"] == 3

    def test_step_collision_replaces_stale_checkpoint(self, tmp_path):
        # a resume=False restart re-reaches a step an older run already
        # committed into the same root: the new run's state must WIN —
        # a silent keep would let a later resume load the other run's
        # weights as this one's
        m = _mgr(tmp_path)
        old = dict(_flat(7))
        old["params::w"] = np.full((3, 4), 111.0, np.float32)
        m.save(old, step=7, block=True)
        new = dict(_flat(7))
        new["params::w"] = np.full((3, 4), 222.0, np.float32)
        m.save(new, step=7, block=True)
        assert m.last_error is None
        flat, man = ck.load_latest(str(tmp_path))
        np.testing.assert_array_equal(np.asarray(flat["params::w"]),
                                      np.full((3, 4), 222.0, np.float32))
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.endswith(".replaced")]

    def test_stale_tmp_dirs_swept_at_init(self, tmp_path):
        stale = tmp_path / ".tmp-ckpt-000000000009-123"
        stale.mkdir()
        (stale / "shard-00000.bin").write_bytes(b"junk")
        _mgr(tmp_path)
        assert not stale.exists()

    def test_coalescing_under_writer_pressure(self, tmp_path):
        from paddle_hackathon_tpu.observability import get_registry
        before = get_registry().total("checkpoint_coalesced_total")
        m = _mgr(tmp_path)
        faults.arm("ckpt.shard_write=prob@1.0,flavor=delay,secs=0.01")
        m.save(_flat(1), step=1)
        m.save(_flat(2), step=2)   # parked while the writer is busy...
        m.save(_flat(3), step=3)   # ...replaced by the newer snapshot
        assert m.wait(timeout=60), "the writer never drained its snapshots"
        faults.disarm()
        steps = [s for s, _ in ck.list_checkpoints(str(tmp_path))]
        # WHICH early snapshot got replaced depends on writer timing;
        # the invariants don't: the NEWEST state always commits, and at
        # least one older parked snapshot was coalesced away
        assert steps[-1] == 3 and len(steps) <= 2
        assert get_registry().total("checkpoint_coalesced_total") >= \
            before + 1

    def test_flatten_unflatten_roundtrip(self):
        flat = ck.flatten_train_state(
            {"w": 1, "b": 2}, [{"m": 3, "v": 4}, {"m": 5, "v": 6}], 7)
        params, opt, step = ck.unflatten_train_state(flat)
        assert params == {"w": 1, "b": 2}
        assert opt == [{"m": 3, "v": 4}, {"m": 5, "v": 6}]
        assert step == 7

    def test_flatten_roundtrips_slotless_optimizers(self):
        # plain SGD: every accumulator dict is empty — the inverse must
        # preserve the LIST, not collapse it to None
        flat = ck.flatten_train_state({"w": 1}, [{}, {}], 3)
        _, opt, _ = ck.unflatten_train_state(flat)
        assert opt == [{}, {}]
        # mixed: an empty entry between full ones must not shift later
        # slots onto the wrong param index
        flat = ck.flatten_train_state(
            {"a": 0, "b": 0, "c": 0}, [{"m": 10}, {}, {"m": 30}], 3)
        _, opt, _ = ck.unflatten_train_state(flat)
        assert opt == [{"m": 10}, {}, {"m": 30}]


@pytest.mark.slow
def test_restore_like_reshards_across_dp_sizes(tmp_path):
    """A checkpoint written dp=4-sharded loads onto a dp=2 mesh (and the
    values survive bit-exact) — the array-level core of elastic resume;
    the full Engine-level drill is in test_crash_drill.py."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("dp",))
    sharded = jax.device_put(
        np.arange(16, dtype=np.float32),
        NamedSharding(mesh4, P("dp")))
    m = ck.CheckpointManager(str(tmp_path))
    m.save({"params::w": sharded}, step=1, block=True)
    assert m.last_error is None
    man = ck.load_latest(str(tmp_path))[1]
    assert man["arrays"]["params::w"]["spec"] == ["dp"]   # provenance
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("dp",))
    like = {"params::w": jax.device_put(
        np.zeros(16, np.float32), NamedSharding(mesh2, P("dp")))}
    placed, _ = ck.restore_like(str(tmp_path), like)
    assert placed["params::w"].sharding == like["params::w"].sharding
    np.testing.assert_array_equal(np.asarray(placed["params::w"]),
                                  np.arange(16, dtype=np.float32))


def test_restore_like_missing_keys_is_loud(tmp_path):
    m = ck.CheckpointManager(str(tmp_path))
    m.save({"params::w": np.ones(2, np.float32)}, step=1, block=True)
    with pytest.raises(KeyError, match="different"):
        ck.restore_like(str(tmp_path),
                        {"params::other": np.zeros(2, np.float32)})


# ---------------------------------------------------------------------------
# FitCheckpointer (host-side logic only)
# ---------------------------------------------------------------------------

class TestFitCheckpointer:
    def test_every_steps_gating_and_dedup(self, tmp_path):
        fc = ck.FitCheckpointer(ck.CheckpointConfig(
            dir=str(tmp_path), every_steps=4, async_save=False))
        flat = _flat()
        fc.advance(2)
        assert fc.maybe_save(flat, epoch=0, cursor=2)      # first: saves
        assert not fc.maybe_save(flat, epoch=0, cursor=2)  # same step: no
        fc.advance(2)
        assert not fc.maybe_save(flat, epoch=0, cursor=4)  # 2 < every=4
        fc.advance(2)
        assert fc.maybe_save(flat, epoch=0, cursor=6)      # 4 past last
        fc.advance(1)
        assert fc.maybe_save(flat, epoch=1, cursor=0, force=True)
        assert [s for s, _ in ck.list_checkpoints(str(tmp_path))] == \
            [2, 6, 7]

    def test_resume_restores_shuffle_rng(self, tmp_path):
        fc = ck.FitCheckpointer(ck.CheckpointConfig(
            dir=str(tmp_path), async_save=False))
        np.random.seed(77)
        fc.mark_epoch()
        epoch_perm = np.random.permutation(8)   # the epoch's shuffle draw
        fc.advance(3)
        fc.maybe_save(_flat(), epoch=0, cursor=3)
        np.random.seed(0)                       # a fresh process's state
        fc2 = ck.FitCheckpointer(str(tmp_path))
        got = fc2.resume(_flat())
        assert got is not None
        placed, epoch, cursor = got
        assert (epoch, cursor) == (0, 3)
        assert fc2.global_step == 3
        # the resumed epoch re-draws the SAME permutation the crashed
        # epoch trained on — cursor fast-forward lands on unseen batches
        np.testing.assert_array_equal(np.random.permutation(8), epoch_perm)

    def test_resume_disabled_starts_fresh(self, tmp_path):
        fc = ck.FitCheckpointer(ck.CheckpointConfig(
            dir=str(tmp_path), async_save=False))
        fc.advance(1)
        fc.maybe_save(_flat(), epoch=0, cursor=1)
        fc2 = ck.FitCheckpointer(ck.CheckpointConfig(
            dir=str(tmp_path), resume=False))
        assert fc2.resume(_flat()) is None


def test_elastic_rendezvous_sizes_world_from_leases():
    from paddle_hackathon_tpu.distributed.elastic import MemLeaseStore
    store = MemLeaseStore()
    store.put_with_lease("/job9/nodes/hostB", "hostB", 5.0)
    rank, world, mgr = ck.elastic_rendezvous(
        "job9", "hostA", store=store, np_range="1:4",
        timeout=2.0, settle=0.05)
    try:
        assert world == 2
        assert rank == sorted(["hostA", "hostB"]).index("hostA")
    finally:
        mgr.exit()


def test_elastic_rendezvous_timeout_outside_range_raises():
    # only 1 member ever shows up but the job declares np=3:4 — the
    # rendezvous must ERROR, not hand back an undersized world to
    # resume on
    from paddle_hackathon_tpu.distributed.elastic import MemLeaseStore
    with pytest.raises(TimeoutError, match="outside the declared"):
        ck.elastic_rendezvous("jobT", "hostA", store=MemLeaseStore(),
                              np_range="3:4", timeout=0.3, settle=0.05)


def test_manager_close_stops_writer_thread(tmp_path):
    m = ck.CheckpointManager(str(tmp_path))
    m.save(_flat(1), step=1, block=True)
    t = m._thread
    assert t is not None and t.is_alive()
    m.close()
    assert m._thread is None and not t.is_alive()   # no immortal thread
    with pytest.raises(RuntimeError, match="closed"):
        m.save(_flat(2), step=2)
    # the committed checkpoint survives the close
    assert ck.load_latest(str(tmp_path))[1]["step"] == 1


# ---------------------------------------------------------------------------
# elastic lease-store retries
# ---------------------------------------------------------------------------

class _FakeKV:
    """Minimal TCPStore look-alike (set/get/check/add/delete_key)."""

    def __init__(self):
        self.d = {}

    def set(self, k, v):
        self.d[k] = v.encode() if isinstance(v, str) else v

    def get(self, k):
        return self.d[k]

    def check(self, k):
        return k in self.d

    def add(self, k, v):
        cur = int(self.d.get(k, b"0")) + v
        self.d[k] = str(cur).encode()
        return cur

    def delete_key(self, k):
        self.d.pop(k, None)


class TestLeaseStoreRetries:
    def test_put_retries_transient_error_and_counts(self):
        from paddle_hackathon_tpu.distributed.elastic import TCPLeaseStore
        from paddle_hackathon_tpu.observability import get_registry
        st = TCPLeaseStore(_FakeKV(), retries=3, backoff_base=0.001)
        before = get_registry().total("elastic_store_retries_total",
                                      op="put_with_lease")
        faults.arm("elastic.put=fail@1")
        st.put_with_lease("/j/nodes/a", "a", 5.0)   # retry succeeds
        assert st.list_prefix("/j/nodes/") == {"/j/nodes/a": "a"}
        assert get_registry().total("elastic_store_retries_total",
                                    op="put_with_lease") == before + 1

    def test_retried_put_reuses_its_index_slot(self):
        # a transient failure AFTER the slot claim must not claim a
        # second slot on retry — the index every hosts() poll scans
        # would grow by one per hiccup, forever
        from paddle_hackathon_tpu.distributed.elastic import TCPLeaseStore

        class _FlakyIndexKV(_FakeKV):
            def __init__(self):
                super().__init__()
                self.fail_next_index_set = True

            def set(self, k, v):
                if k.startswith("__elastic_index/") and k != \
                        "__elastic_index/n" and self.fail_next_index_set:
                    self.fail_next_index_set = False
                    raise ConnectionError("store hiccup")
                super().set(k, v)

        kv = _FlakyIndexKV()
        st = TCPLeaseStore(kv, retries=3, backoff_base=0.001)
        st.put_with_lease("/j/nodes/a", "a", 5.0)
        assert int(kv.d["__elastic_index/n"]) == 1   # ONE slot claimed
        assert st.list_prefix("/j/nodes/") == {"/j/nodes/a": "a"}

    def test_refresh_retries_then_succeeds(self):
        from paddle_hackathon_tpu.distributed.elastic import TCPLeaseStore
        st = TCPLeaseStore(_FakeKV(), retries=3, backoff_base=0.001)
        st.put_with_lease("/j/nodes/a", "a", 5.0)
        faults.arm("elastic.refresh=fail@1")
        assert st.refresh("/j/nodes/a", 5.0) is True

    def test_refresh_exhausted_raises_named_lease_lost(self):
        from paddle_hackathon_tpu.distributed.elastic import (
            LeaseLostError, TCPLeaseStore)
        st = TCPLeaseStore(_FakeKV(), retries=2, backoff_base=0.001)
        st.put_with_lease("/j/nodes/a", "a", 5.0)
        faults.arm("elastic.refresh=prob@1.0")   # every attempt fails
        with pytest.raises(LeaseLostError, match="re-register"):
            st.refresh("/j/nodes/a", 5.0)
        assert faults.hits("elastic.refresh") == 3   # 1 try + 2 retries

    def test_missing_key_is_false_not_error(self):
        from paddle_hackathon_tpu.distributed.elastic import TCPLeaseStore
        st = TCPLeaseStore(_FakeKV(), retries=1, backoff_base=0.001)
        # a legitimately expired/absent lease is a False verdict, not a
        # LeaseLostError — callers re-register on False
        assert st.refresh("/j/nodes/never", 5.0) is False

    def test_heartbeat_survives_lease_lost(self):
        from paddle_hackathon_tpu.distributed.elastic import (
            ElasticManager, TCPLeaseStore)
        st = TCPLeaseStore(_FakeKV(), retries=1, backoff_base=0.001)
        em = ElasticManager("jobH", "1:4", "hostA", store=st,
                            heartbeat_interval=0.02, ttl=5.0)
        em.register()
        try:
            faults.arm("elastic.refresh=fail@2")   # one mid-beat loss
            time.sleep(0.15)
            faults.disarm()
            assert em._hb_thread.is_alive()
            assert em.hosts() == ["hostA"]   # re-registered, not dead
        finally:
            em.exit()


# ---------------------------------------------------------------------------
# serving: queued-deadline abort + torn artifacts
# ---------------------------------------------------------------------------

def _tiny_model():
    import paddle_hackathon_tpu as paddle
    from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestQueuedDeadline:
    def test_expired_queued_request_aborts_named(self):
        # stays lean: the expiry runs in _admit BEFORE any tick program
        # would compile — step() returns False with nothing admitted
        from paddle_hackathon_tpu.inference.serving import (
            DeadlineExceededError, ServingEngine)
        from paddle_hackathon_tpu.observability import get_registry
        eng = ServingEngine(_tiny_model(), max_slots=2, max_len=32,
                            auto_run=False)
        before = get_registry().total("serving_aborted_tokens_total",
                                      engine=eng._engine_id)
        req = eng.submit([1, 2, 3], 4, deadline_s=0.0)
        time.sleep(0.005)
        assert eng.step() is False
        assert isinstance(req.error, DeadlineExceededError)
        # the fleet PR unified queue- and decode-budget aborts under
        # one lifecycle terminal: where="deadline"
        assert req.lifecycle["where"] == "deadline"
        assert req.lifecycle["aborted"] and "t_abort" in req.lifecycle
        assert req._event.is_set()          # wait() returns immediately
        with pytest.raises(RuntimeError):
            req.result()
        # zero generated tokens fed into the goodput books (the named
        # counter path ran; a queued abort carries no committed work)
        assert get_registry().total("serving_aborted_tokens_total",
                                    engine=eng._engine_id) == before
        assert eng._deadline_queued == 0   # O(1) gate back to steady state

    def test_deadline_gate_counter_tracks_mixed_queue(self):
        from paddle_hackathon_tpu.inference.serving import ServingEngine
        eng = ServingEngine(_tiny_model(), max_slots=1, max_len=32,
                            auto_run=False)
        r_plain = eng.submit([1, 2], 2)
        r_dead = eng.submit([3, 4], 2, deadline_s=0.0)
        assert eng._deadline_queued == 1
        time.sleep(0.005)
        with eng._lock:
            eng._expire_queued_locked()
        assert eng._deadline_queued == 0
        assert r_dead.error is not None and r_plain.error is None
        assert list(eng._pending) == [r_plain]

    def test_no_deadline_requests_unaffected(self):
        from paddle_hackathon_tpu.inference.serving import ServingEngine
        eng = ServingEngine(_tiny_model(), max_slots=1, max_len=32,
                            auto_run=False)
        r1 = eng.submit([1, 2], 2)
        time.sleep(0.005)
        with eng._lock:
            eng._expire_queued_locked()
        assert r1.error is None and len(eng._pending) == 1


class TestTornServingArtifact:
    def test_atomic_save_and_roundtrip(self, tmp_path):
        from paddle_hackathon_tpu.inference.serving import (
            load_for_serving, save_for_serving)
        m = _tiny_model()
        art = str(tmp_path / "art")
        save_for_serving(m, art)
        assert sorted(os.listdir(art)) == ["config.json", "params.npz"]
        save_for_serving(m, art)   # atomic RE-save over a live artifact
        assert not os.path.isdir(art + ".old")
        assert not [n for n in os.listdir(str(tmp_path))
                    if ".saving-" in n]
        m2 = load_for_serving(art)
        for (k, p), (_, q) in zip(m.named_parameters(),
                                  m2.named_parameters()):
            np.testing.assert_array_equal(
                np.asarray(p._value).astype(np.float32),
                np.asarray(q._value).astype(np.float32))

    def test_missing_config_is_torn_not_half_loaded(self, tmp_path):
        from paddle_hackathon_tpu.inference.serving import (
            TornArtifactError, load_for_serving)
        torn = tmp_path / "torn"
        torn.mkdir()
        (torn / "params.npz").write_bytes(b"partial")
        with pytest.raises(TornArtifactError, match="config.json"):
            load_for_serving(str(torn))

    def test_truncated_config_is_torn(self, tmp_path):
        from paddle_hackathon_tpu.inference.serving import (
            TornArtifactError, load_for_serving)
        torn = tmp_path / "torn"
        torn.mkdir()
        (torn / "params.npz").write_bytes(b"partial")
        (torn / "config.json").write_text('{"model": "GPTFor')
        with pytest.raises(TornArtifactError, match="parse"):
            load_for_serving(str(torn))

    def test_stale_tmp_from_killed_save_is_swept(self, tmp_path):
        from paddle_hackathon_tpu.inference.serving import save_for_serving
        m = _tiny_model()
        art = str(tmp_path / "art")
        # a previous process (different pid) was kill -9'd mid-save,
        # leaving its full-size tmp dir behind
        orphan = art + ".saving-99999"
        os.makedirs(orphan)
        open(os.path.join(orphan, "params.npz"), "wb").write(b"big")
        save_for_serving(m, art)
        assert not os.path.isdir(orphan)
        assert not [n for n in os.listdir(str(tmp_path))
                    if ".saving-" in n]

    def test_swap_window_crash_falls_back_to_old(self, tmp_path):
        from paddle_hackathon_tpu.inference.serving import (
            load_for_serving, save_for_serving)
        m = _tiny_model()
        art = str(tmp_path / "art")
        save_for_serving(m, art)
        # simulate a crash between the two renames: path moved to .old,
        # replacement never landed
        os.rename(art, art + ".old")
        m2 = load_for_serving(art)   # serves the surviving artifact
        assert m2 is not None
        # and a RE-SAVE from this state commits cleanly (never deleting
        # .old before the new artifact lands) and cleans up after
        save_for_serving(m, art)
        assert os.path.isdir(art) and not os.path.isdir(art + ".old")
        load_for_serving(art)

    def test_resave_preserves_sidecar_files(self, tmp_path):
        from paddle_hackathon_tpu.inference.serving import (
            save_for_serving)
        m = _tiny_model()
        art = str(tmp_path / "art")
        save_for_serving(m, art)
        open(os.path.join(art, "tokenizer.json"), "w").write('{"v": 1}')
        save_for_serving(m, art)   # re-export must not destroy sidecars
        assert open(os.path.join(art, "tokenizer.json")).read() == \
            '{"v": 1}'
