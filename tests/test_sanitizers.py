"""Runtime sanitizers (observability/sanitizers.py): lock-order checker
units + cross-subsystem runs under instrumented locks, and the
transfer-guard steady-state proofs — a mid-flight decode tick (dense,
paged, speculative) and a compiled-trainer step each perform ZERO
implicit device→host transfers.

Lean by design: the fast subset is pure-threading/jnp units plus the
dataloader + observability-stack runs under instrumented locks (~6s);
every engine/trainer-compiling test is slow-marked per the tier-1
budget (ROADMAP).
"""

import threading
import time

import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import hapi, io, nn, optimizer as optim
from paddle_hackathon_tpu.observability import (flight, metrics,
                                                sanitizers as S, tracing)


@pytest.fixture(autouse=True)
def _isolated_lock_graph():
    """One test's legitimate order must not poison another's graph."""
    S.reset_lock_graph()
    yield
    S.reset_lock_graph()


# ----------------------------------------------------------- lock units
@pytest.mark.skipif(S.lock_sanitizer_enabled(),
                    reason="suite launched with PHT_LOCK_SANITIZER=1")
def test_make_lock_disabled_returns_plain_stdlib_lock():
    """The zero-cost-off contract: no wrapper, not even a frame."""
    lk = S.make_lock("x")
    assert type(lk) is type(threading.Lock())
    rl = S.make_rlock("x")
    assert type(rl) is type(threading.RLock())
    assert not S.lock_sanitizer_enabled()


def test_consistent_order_is_silent():
    with S.lock_sanitizer():
        a, b, c = (S.make_lock(n) for n in ("ord.a", "ord.b", "ord.c"))
        for _ in range(3):
            with a:
                with b:
                    with c:
                        pass


def test_opposite_order_raises_with_both_stacks():
    with S.lock_sanitizer():
        a, b = S.make_lock("cyc.a"), S.make_lock("cyc.b")
        with a:
            with b:
                pass
        with pytest.raises(S.LockOrderError) as ei:
            with b:
                with a:
                    pass
        msg = str(ei.value)
        assert "cyc.a" in msg and "cyc.b" in msg
        assert "test_sanitizers" in msg   # acquisition stacks attached
    # the failed acquire must not leave `a` held
    assert a.acquire(blocking=False)
    a.release()


def test_cross_thread_order_evidence():
    """Thread 1 establishes a->b; the MAIN thread acquiring b->a fails
    fast — the whole point: the deadlock needs both threads to race,
    the sanitizer needs only the two orders to ever happen."""
    with S.lock_sanitizer():
        a, b = S.make_lock("xt.a"), S.make_lock("xt.b")

        def t1():
            with a:
                with b:
                    pass
        th = threading.Thread(target=t1)
        th.start()
        th.join(5)
        with pytest.raises(S.LockOrderError):
            with b:
                with a:
                    pass


def test_same_name_cross_instance_nesting_raises():
    """Two instances of the same lock class nested = the unordered-
    instances hazard (PHT003's static twin)."""
    with S.lock_sanitizer():
        e1, e2 = S.make_lock("serving.engine"), S.make_lock("serving.engine")
        with pytest.raises(S.LockOrderError, match="another instance"):
            with e1:
                with e2:
                    pass


def test_self_deadlock_raises_instead_of_hanging():
    with S.lock_sanitizer():
        lk = S.make_lock("self.lk")
        with lk:
            with pytest.raises(S.LockOrderError, match="re-acquired"):
                lk.acquire()
            # a TIMED blocking acquire is still a guaranteed failure —
            # raise instead of burning the timeout
            with pytest.raises(S.LockOrderError, match="re-acquired"):
                lk.acquire(timeout=5)
            # a genuine try-acquire probe stays a probe
            assert lk.acquire(blocking=False) is False


def test_error_cites_the_matched_acquisition_stack():
    """Holding A then B, re-acquiring A: the evidence must be A's
    acquisition stack, not whatever happens to be held[-1] (B's)."""
    with S.lock_sanitizer():
        a, b = S.make_lock("ev.a"), S.make_lock("ev.b")

        def grab_a():
            a.acquire()

        def grab_b():
            b.acquire()
        grab_a()
        grab_b()
        try:
            with pytest.raises(S.LockOrderError) as ei:
                a.acquire()
            msg = str(ei.value)
            assert "grab_a" in msg
            assert "grab_b" not in msg
        finally:
            b.release()
            a.release()


def test_rlock_reentry_is_fine():
    with S.lock_sanitizer():
        rl = S.make_rlock("re.lk")
        with rl:
            with rl:
                pass


def test_cross_thread_release_handoff_leaves_no_stale_entry():
    """stdlib Lock legally supports acquire-in-A / release-in-B (the
    handoff pattern): release must clear the OWNER's held entry, or A's
    next acquire raises a phantom self-deadlock."""
    with S.lock_sanitizer():
        lk = S.make_lock("handoff.lk")
        acquired = threading.Event()
        released = threading.Event()
        errs = []

        def worker():
            try:
                lk.acquire()
                acquired.set()
                assert released.wait(5)
                with lk:            # reacquire: must NOT self-deadlock
                    pass
            except BaseException as e:   # noqa: BLE001
                errs.append(e)
        th = threading.Thread(target=worker)
        th.start()
        assert acquired.wait(5)
        lk.release()                # cross-thread release (main thread)
        released.set()
        th.join(5)
        assert not errs, errs


def test_reverse_order_try_acquire_is_not_a_finding():
    """try-lock is the standard deadlock-AVOIDANCE pattern: a reverse-
    order acquire(blocking=False) cannot deadlock (it backs off), so it
    must neither raise nor poison the order graph for later legitimate
    blocking acquires."""
    with S.lock_sanitizer():
        a, b = S.make_lock("try.a"), S.make_lock("try.b")
        with a:
            with b:
                pass
        with b:
            assert a.acquire(blocking=False)   # reverse order: no raise
            a.release()
        # the probe recorded no (b, a) edge: the forward order still works
        with a:
            with b:
                pass


def test_reset_lock_graph_isolates():
    with S.lock_sanitizer():
        a, b = S.make_lock("iso.a"), S.make_lock("iso.b")
        with a:
            with b:
                pass
        S.reset_lock_graph()
        with b:       # opposite order, but the old edge is gone
            with a:
                pass


def test_condition_wait_notify_through_sanitized_lock():
    """The dataloader pattern: threading.Condition over a sanitized
    lock — wait() releases/reacquires through the wrapper and the
    held-stack bookkeeping stays consistent."""
    with S.lock_sanitizer():
        lk = S.make_lock("cv.lk")
        cv = threading.Condition(lk)
        got = []

        def waiter():
            with cv:
                while not got:
                    cv.wait(timeout=5)
                got.append("woke")
        th = threading.Thread(target=waiter)
        th.start()
        time.sleep(0.05)
        with cv:
            got.append("sent")
            cv.notify()
        th.join(5)
        assert got == ["sent", "woke"]


def test_condition_over_sanitized_rlock_with_nested_hold():
    """Condition(make_rlock(...)): wait() must fully release a
    RECURSIVE hold (the RLock _release_save protocol) and restore the
    same held-stack depth on wake — the delegation the wrapper exposes
    so Condition does not fall back to its broken-for-RLock probe."""
    with S.lock_sanitizer():
        rl = S.make_rlock("cvr.lk")
        cv = threading.Condition(rl)
        got = []

        def waiter():
            with cv:
                with rl:             # depth 2 when wait() releases
                    while not got:
                        cv.wait(timeout=5)
                    got.append("woke")
        th = threading.Thread(target=waiter)
        th.start()
        time.sleep(0.05)
        with cv:                     # acquirable: the waiter released BOTH
            got.append("sent")
            cv.notify()
        th.join(5)
        assert got == ["sent", "woke"]
        # and the wrapper reports clean ownership afterwards
        assert not rl._is_owned()


# ------------------------------------------- locks wired into subsystems
class _TinyDS(io.Dataset):
    def __init__(self, n=24):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((3,), i, np.float32), np.int64(i % 2)


def test_dataloader_runs_under_instrumented_locks():
    """Thread-worker prefetch (lock + Condition) under the sanitizer:
    a full pass with no order finding is the acceptance signal."""
    with S.lock_sanitizer():
        loader = io.DataLoader(_TinyDS(), batch_size=4, num_workers=2)
        seen = sum(1 for _ in loader)
        assert seen == 6
        # workers are long gone; a second epoch re-creates the iterator
        assert sum(1 for _ in loader) == 6


def test_observability_stack_under_instrumented_locks():
    """Registry (registry/family/child lock tiers), flight ring and the
    tracing source registry exercised cross-thread under the sanitizer —
    the PR 5 engine-unregister inversion class would fail fast here."""
    old = tracing._sources_lock
    with S.lock_sanitizer():
        tracing._sources_lock = S.make_lock("tracing.sources")
        try:
            reg = metrics.MetricRegistry()
            fr = flight.FlightRecorder(capacity=256)
            c = reg.counter("sanit_test_total", "t").labels(mode="x")
            h = reg.histogram("sanit_test_seconds", "t", unit="s").labels()

            class _Src:
                def introspect_requests(self):
                    # a source that touches metrics while the registry
                    # iterates sources (snapshot-then-call on the other
                    # side keeps this inversion-free)
                    c.inc()
                    return {"ok": True}

            src = _Src()
            tracing.register_introspection_source("sanit.src", src)
            stop = threading.Event()
            errs = []

            def hammer(fn):
                try:
                    while not stop.is_set():
                        fn()
                except BaseException as e:   # noqa: BLE001
                    errs.append(e)

            jobs = [lambda: c.inc(),
                    lambda: h.observe(0.01),
                    lambda: fr.record("tick", n=1),
                    lambda: reg.expose_text(),
                    lambda: reg.snapshot(),
                    lambda: fr.dump(),
                    lambda: tracing.introspection_tables()]
            threads = [threading.Thread(target=hammer, args=(j,))
                       for j in jobs]
            for t in threads:
                t.start()
            time.sleep(0.5)
            stop.set()
            for t in threads:
                t.join(5)
            assert not errs, errs
        finally:
            tracing.unregister_introspection_source("sanit.src")
            tracing._sources_lock = old


# ------------------------------------------------------- transfer guard
def test_forbid_host_transfers_blocks_implicit_syncs():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(6)
    with S.forbid_host_transfers():
        y = jax.device_get(x)             # the designed explicit fetch
        assert y.sum() == 15
        z = jnp.asarray(np.arange(3))     # h2d stays allowed
        assert z.shape == (3,)
        for bad in (lambda: float(x[0]), lambda: int(x[1]),
                    lambda: bool(x[2] > 0), lambda: x[0].item(),
                    lambda: x.tolist()):
            with pytest.raises(S.HostTransferError, match="device_get"):
                bad()
    # fully restored on exit
    assert float(x[0]) == 0.0 and x[1].item() == 1


def test_forbid_host_transfers_nests_and_restores_on_error():
    import jax.numpy as jnp
    x = jnp.ones(())
    try:
        with S.forbid_host_transfers():
            with S.forbid_host_transfers():
                pass
            with pytest.raises(S.HostTransferError):
                float(x)                  # outer level still armed
            raise RuntimeError("escape")
    except RuntimeError:
        pass
    assert float(x) == 1.0                # restored despite the escape


# ------------------------------------------------- donation sanitizer
def _donstep(s, b):
    return s + b


def test_donation_sanitizer_disabled_is_zero_cost_plain_call():
    """The make_lock contract: off (default) returns the callable
    UNCHANGED — not even a wrapper frame."""
    import jax
    assert not S.donation_sanitizer_enabled()
    f = jax.jit(_donstep, donate_argnums=(0,))
    assert S.sanitize_donation(f, donate_argnums=(0,)) is f


def test_use_after_donate_read_raises_with_both_stacks():
    import jax.numpy as jnp
    import jax
    with S.donation_sanitizer():
        g = S.sanitize_donation(jax.jit(_donstep, donate_argnums=(0,)),
                                donate_argnums=(0,), site="unit.step")
        s = jnp.zeros((4,))
        out = g(s, jnp.ones((4,)))
        with pytest.raises(S.UseAfterDonateError) as ei:
            float(s[0])
        msg = str(ei.value)
        assert "unit.step" in msg           # the donating site, named
        assert "donating call" in msg
        assert "test_sanitizers" in msg     # ...with its recorded stack
        assert "PHT006" in msg              # points at the static rule
        # the OUTPUT is alive and readable
        assert float(out.sum()) == 4.0
    # context exit disarms the interposition: fresh arrays unaffected,
    # and the dead handle now raises jax's OWN context-free error (on
    # this jaxlib CPU donation really deletes) — which is exactly the
    # un-annotated failure mode the sanitizer exists to improve on
    import jax.numpy as jnp2
    assert float(jnp2.ones(())[()]) == 1.0
    with pytest.raises(RuntimeError) as ei2:
        float(s[0])
    assert not isinstance(ei2.value, S.UseAfterDonateError)


def test_donated_buffer_as_program_input_raises():
    """The serving stale-cache class: on CPU (donation a no-op) feeding
    a dead buffer back in would silently compute on stale bytes."""
    import jax
    import jax.numpy as jnp
    with S.donation_sanitizer():
        g = S.sanitize_donation(jax.jit(_donstep, donate_argnums=(0,)),
                                donate_argnums=(0,), site="unit.reinput")
        s = jnp.zeros((4,))
        g(s, jnp.ones((4,)))
        with pytest.raises(S.UseAfterDonateError,
                           match="passing it back into"):
            g(s, jnp.ones((4,)))


def test_donate_then_rebind_is_clean():
    import jax
    import jax.numpy as jnp
    with S.donation_sanitizer():
        g = S.sanitize_donation(jax.jit(_donstep, donate_argnums=(0,)),
                                donate_argnums=(0,), site="unit.rebind")
        s = jnp.zeros((4,))
        for _ in range(3):
            s = g(s, jnp.ones((4,)))      # the clean shape
        assert float(s.sum()) == 12.0


def test_broken_consumer_raises_naming_the_donation_site():
    """The deliberately-broken shape: a trainer-alike that forgets to
    rebind its state after the donating call — the SECOND run must be a
    named error, not a silent stale-state step."""
    import jax
    import jax.numpy as jnp

    class BrokenTrainer:
        def __init__(self):
            self._jit = S.sanitize_donation(
                jax.jit(_donstep, donate_argnums=(0,)),
                donate_argnums=(0,), site="broken.trainer")
            self.state = jnp.zeros((4,))

        def run(self, b):
            return self._jit(self.state, b)   # BUG: state never rebound

    with S.donation_sanitizer():
        t = BrokenTrainer()
        t.run(jnp.ones((4,)))
        with pytest.raises(S.UseAfterDonateError,
                           match="broken.trainer"):
            t.run(jnp.ones((4,)))


def test_donation_env_flag_arms_at_creation(monkeypatch):
    """PHT_DONATION_SANITIZER=1 in the environment enables wrapping at
    CREATION time, same contract as PHT_LOCK_SANITIZER."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv("PHT_DONATION_SANITIZER", "1")
    try:
        assert S.donation_sanitizer_enabled()
        g = S.sanitize_donation(jax.jit(_donstep, donate_argnums=(0,)),
                                donate_argnums=(0,), site="env.step")
        assert getattr(g, "_pht_donation_guard", False)
        s = jnp.zeros((2,))
        g(s, jnp.ones((2,)))
        with pytest.raises(S.UseAfterDonateError, match="env.step"):
            s.tolist()
    finally:
        S._reset_donation_sanitizer_for_tests()
    # a wrapper built AFTER the flag is gone is a plain call again
    monkeypatch.delenv("PHT_DONATION_SANITIZER")
    f = jax.jit(_donstep, donate_argnums=(0,))
    assert S.sanitize_donation(f, donate_argnums=(0,)) is f


def test_donation_registry_is_bounded():
    import jax
    import jax.numpy as jnp
    with S.donation_sanitizer():
        g = S.sanitize_donation(jax.jit(_donstep, donate_argnums=(0,)),
                                donate_argnums=(0,), site="unit.bound")
        s = jnp.zeros((2,))
        for _ in range(16):
            s = g(s, jnp.ones((2,)))
        from paddle_hackathon_tpu.observability.sanitizers import (
            _DONATED_MAX, _donated)
        assert 0 < len(_donated) <= _DONATED_MAX


def test_interleaved_guards_restore_cleanly():
    """Regression: the transfer guard and the donation sanitizer patch
    the SAME ArrayImpl surface — with independent save/restore pairs, a
    forbid_host_transfers() block exiting while the donation sanitizer
    was armed wiped the donation read-guard, and the later donation
    disarm reinstalled the transfer TRIP as the 'original', poisoning
    float()/item() on every array process-wide."""
    import jax
    import jax.numpy as jnp
    with S.donation_sanitizer():
        g = S.sanitize_donation(jax.jit(_donstep, donate_argnums=(0,)),
                                donate_argnums=(0,), site="mix.step")
        with S.forbid_host_transfers():
            # non-LIFO interleaving: the transfer block closes while
            # the donation guard must stay armed
            pass
        s = jnp.zeros((4,))
        g(s, jnp.ones((4,)))
        with pytest.raises(S.UseAfterDonateError, match="mix.step"):
            float(s[0])       # donation guard survived the inner exit
    # ...and after the donation context exits too, NO trip is left
    # behind: scalar reads on fresh arrays are plain reads again
    assert float(jnp.ones(())) == 1.0
    assert jnp.arange(3).tolist() == [0, 1, 2]


def test_wrapper_outliving_its_context_is_a_plain_call():
    """Regression: a wrapper created inside donation_sanitizer() used to
    stay half-armed after the context exited — still pinning every
    donated leaf in the strong-ref registry and still raising on
    re-input while the read-side guard was disarmed."""
    import jax
    import jax.numpy as jnp
    with S.donation_sanitizer():
        g = S.sanitize_donation(jax.jit(_donstep, donate_argnums=(0,)),
                                donate_argnums=(0,), site="outlive.step")
    from paddle_hackathon_tpu.observability.sanitizers import _donated
    s = jnp.zeros((4,))
    out = g(s, jnp.ones((4,)))
    assert len(_donated) == 0          # no registry growth when disabled
    assert float(out.sum()) == 4.0
    # re-arming a NEW context resumes guarding through the same wrapper
    with S.donation_sanitizer():
        s2 = jnp.zeros((4,))
        g(s2, jnp.ones((4,)))
        with pytest.raises(S.UseAfterDonateError, match="outlive.step"):
            g(s2, jnp.ones((4,)))


# ------------------------------------------------- race sanitizer
class _SharedBox:
    def __init__(self):
        self.val = 0
        self.flag = False


def test_share_object_disabled_is_zero_cost_plain_object():
    """The make_lock contract: off (default) returns the object
    UNCHANGED — same identity, same class, no shim."""
    assert not S.race_sanitizer_enabled()
    b = _SharedBox()
    out = S.share_object(b, "unit.box", atomic=("val",))
    assert out is b
    assert type(out) is _SharedBox


def test_seeded_write_write_race_cites_both_stacks_and_locksets():
    """THE report-quality pin (acceptance criterion): a seeded
    write/write race raises DataRaceError naming the shared attribute,
    BOTH access stacks, and the lockset held at each access."""
    with S.race_sanitizer():
        box = S.share_object(_SharedBox(), "unit.box")
        guard = S.make_lock("race.guard")

        def locked_writer():
            with guard:
                box.val = 1

        def unlocked_writer():
            box.val = 2

        for name in ("locked-1", "locked-2"):
            th = threading.Thread(target=locked_writer, name=name)
            th.start()
            th.join(5)
        errs = []

        def racing():
            try:
                unlocked_writer()
            except S.DataRaceError as e:
                errs.append(e)
        th = threading.Thread(target=racing, name="unlocked")
        th.start()
        th.join(5)
        assert errs, "write/write with empty lockset intersection " \
                     "must raise DataRaceError"
        msg = str(errs[0])
        assert "unit.box.val" in msg
        assert "earlier access" in msg and "this access" in msg
        assert "locked_writer" in msg      # the earlier side's stack...
        assert "unlocked_writer" in msg    # ...and the racing side's
        assert "race.guard" in msg         # the lockset held earlier
        assert "(none)" in msg             # the empty lockset here
        assert "PHT009" in msg             # points at the static rule


def test_read_write_race_detected():
    with S.race_sanitizer():
        box = S.share_object(_SharedBox(), "unit.rw")
        guard = S.make_lock("rw.guard")

        def locked_reader():
            with guard:
                _ = box.val
        for _ in range(2):
            th = threading.Thread(target=locked_reader)
            th.start()
            th.join(5)
        # the attribute is shared with lockset {rw.guard}; an unlocked
        # write from a third thread empties the intersection
        with pytest.raises(S.DataRaceError, match="unit.rw"):
            box.val = 9


def test_common_lock_discipline_is_clean():
    with S.race_sanitizer():
        box = S.share_object(_SharedBox(), "unit.clean")
        guard = S.make_lock("clean.guard")
        errs = []

        def worker():
            try:
                for _ in range(20):
                    with guard:
                        box.val += 1
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
        threads = [threading.Thread(target=worker) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(5)
        assert not errs, errs
        with guard:
            assert box.val == 60


def test_publish_then_single_driver_is_clean():
    """The engine pattern: the constructing thread publishes, ONE
    driver thread then owns the attribute exclusively — the single
    ownership handoff must not false-alarm."""
    with S.race_sanitizer():
        box = S.share_object(_SharedBox(), "unit.owner")
        box.val = 1              # init-thread write
        errs = []

        def driver():
            try:
                for i in range(10):
                    box.val = i      # handoff, then exclusive
                    _ = box.val
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
        th = threading.Thread(target=driver)
        th.start()
        th.join(5)
        assert not errs, errs


def test_atomic_exemption_mirrors_gil_atomic():
    """share_object(atomic=...) is the runtime half of the static
    `# pht-lint: gil-atomic` annotation: exempted attrs never race,
    everything else stays checked."""
    with S.race_sanitizer():
        box = S.share_object(_SharedBox(), "unit.at", atomic=("val",))

        def bump():
            box.val += 1
        for _ in range(3):
            th = threading.Thread(target=bump)
            th.start()
            th.join(5)
        assert box.val == 3      # no raise: exempt
        # the un-exempted attr still races

        def flip():
            box.flag = True
        for _ in range(2):
            th = threading.Thread(target=flip)
            th.start()
            th.join(5)
        with pytest.raises(S.DataRaceError, match="unit.at.flag"):
            box.flag = False


def test_race_context_exit_restores_plain_objects():
    with S.race_sanitizer():
        box = S.share_object(_SharedBox(), "unit.restore")
        assert type(box) is not _SharedBox     # shimmed while armed
    assert type(box) is _SharedBox             # restored on exit
    box.val = 5                                # plain write, no recording
    assert not S.race_sanitizer_enabled()


def test_race_env_flag_arms_at_declaration(monkeypatch):
    """PHT_RACE_SANITIZER=1 enables share_object at declaration AND
    implies lock instrumentation (the locksets ride make_lock's
    held-lock bookkeeping)."""
    monkeypatch.setenv("PHT_RACE_SANITIZER", "1")
    try:
        assert S.race_sanitizer_enabled()
        assert S.lock_sanitizer_enabled()
        lk = S.make_lock("env.race.lk")
        assert type(lk) is not type(threading.Lock())
        box = S.share_object(_SharedBox(), "env.box")
        assert type(box) is not _SharedBox
    finally:
        S._reset_race_sanitizer_for_tests()
    assert type(box) is _SharedBox


def test_race_registry_does_not_pin_dead_objects():
    """Env-flag mode runs for the process lifetime, and per-epoch
    objects (a fresh prefetch iterator every epoch) must not accumulate:
    the registry holds WEAK refs whose GC callback prunes the object's
    row and per-attribute entries."""
    import gc

    from paddle_hackathon_tpu.observability.sanitizers import (
        _race_objects, _race_table)
    with S.race_sanitizer():
        box = S.share_object(_SharedBox(), "unit.gc")
        box.val = 1
        oid = id(box)
        assert oid in _race_objects
        assert any(k[0] == oid for k in _race_table)
        del box
        gc.collect()
        assert oid not in _race_objects
        assert not any(k[0] == oid for k in _race_table)


def test_dataloader_prefetch_epoch_under_race_sanitizer():
    """Acceptance drive: a full thread-worker prefetch epoch (workers +
    consumer + the cv handshake) with the prefetch iterator declared
    shared — every cross-thread access lockset-checked, zero races."""
    with S.race_sanitizer():
        loader = io.DataLoader(_TinyDS(), batch_size=4, num_workers=2)
        assert sum(1 for _ in loader) == 6
        assert sum(1 for _ in loader) == 6   # second epoch, fresh iter


@pytest.mark.slow
def test_trainer_and_dense_tick_run_clean_under_donation_sanitizer(
        monkeypatch):
    """The acceptance drive: one CompiledTrainer superstep and a dense
    serving decode run complete with ZERO use-after-donate under
    PHT_DONATION_SANITIZER=1 — every donating program rebinds before
    any re-read, engine and trainer both."""
    import jax

    monkeypatch.setenv("PHT_DONATION_SANITIZER", "1")
    try:
        from paddle_hackathon_tpu.inference import ServingEngine
        m = _tiny_gpt()
        eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                            auto_run=False)
        prompts = _prompts()
        reqs = [eng.submit(p, 10) for p in prompts]
        eng.run_until_idle()
        outs = [r.result() for r in reqs]
        for p, o in zip(prompts, outs):
            assert len(o) == len(p) + 10
        eng.shutdown()

        from paddle_hackathon_tpu.hapi.compiled import CompiledTrainer
        paddle.seed(7)
        net = nn.Sequential(nn.Linear(10, 32), nn.ReLU(),
                            nn.Linear(32, 2))
        mdl = hapi.Model(net)
        mdl.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                         parameters=net.parameters()),
                    loss=nn.CrossEntropyLoss())
        trainer = CompiledTrainer(mdl)
        rs = np.random.RandomState(0)
        x = rs.randn(8, 10).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int64)
        for _ in range(2):
            losses = trainer.run((x[None],), (y[None],))
        assert np.isfinite(jax.device_get(losses)).all()
    finally:
        S._reset_donation_sanitizer_for_tests()


# ---------------------------------------------------- engines (slow)
def _tiny_gpt(num_layers=2):
    from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=num_layers,
                    num_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(k=2, lens=(6, 9)):
    rs = np.random.RandomState(5)
    return [rs.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(k)]


def _steady_state_tick_is_transfer_clean(**engine_kw):
    """Warm an engine past prefill + first decode (programs compiled),
    then prove one mid-flight steady-state tick performs zero implicit
    device→host transfers, then drain normally."""
    from paddle_hackathon_tpu.inference import ServingEngine
    m = _tiny_gpt()
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                        auto_run=False, **engine_kw)
    prompts = _prompts()
    reqs = [eng.submit(p, 10) for p in prompts]
    for _ in range(5):        # 2-3 prefill ticks + >=2 decode ticks
        eng.step()
    with S.forbid_host_transfers():
        eng.step()            # the guarded steady-state tick
    eng.run_until_idle()
    outs = [r.result() for r in reqs]
    for p, o in zip(prompts, outs):
        assert len(o) == len(p) + 10    # prompt + generated
    eng.shutdown()
    return outs


@pytest.mark.slow
def test_dense_decode_tick_transfer_clean():
    _steady_state_tick_is_transfer_clean()


@pytest.mark.slow
def test_paged_decode_tick_transfer_clean():
    _steady_state_tick_is_transfer_clean(cache_mode="paged", page_size=8)


@pytest.mark.slow
def test_spec_decode_tick_transfer_clean():
    _steady_state_tick_is_transfer_clean(spec_k=2)


@pytest.mark.slow
def test_compiled_trainer_step_transfer_clean():
    """One compiled superstep under the guard: losses stay on device,
    params rebind without a fetch — the designed loss sync happens only
    at log_freq, outside the step."""
    from paddle_hackathon_tpu.hapi.compiled import CompiledTrainer
    import jax
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(10, 32), nn.ReLU(), nn.Linear(32, 2))
    m = hapi.Model(net)
    m.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                   parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    trainer = CompiledTrainer(m)
    rs = np.random.RandomState(0)
    x = rs.randn(8, 10).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int64)
    xs, ys = (x[None],), (y[None],)   # K=1 stacked leaves
    trainer.run(xs, ys)               # warm: trace + compile
    with S.forbid_host_transfers():
        losses = trainer.run(xs, ys)
    got = jax.device_get(losses)      # designed fetch, outside the step
    assert np.isfinite(got).all()


@pytest.mark.slow
def test_engine_loop_under_instrumented_locks():
    """The acceptance run: a live auto_run engine (instrumented engine
    lock) with concurrent submitters and introspection readers hammering
    the registry/tracing/flight surfaces — any lock-order cycle between
    the engine lock and the observability locks fails the loop (and the
    futures) instead of deadlocking once a year in production."""
    from paddle_hackathon_tpu.inference import ServingEngine
    old = tracing._sources_lock
    with S.lock_sanitizer():
        tracing._sources_lock = S.make_lock("tracing.sources")
        try:
            m = _tiny_gpt()
            eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                                auto_run=True, spec_k=2)
            reg = metrics.get_registry()
            stop = threading.Event()
            errs = []

            def reader():
                try:
                    while not stop.is_set():
                        eng.introspect_requests()
                        reg.expose_text()
                        eng.stats.get("tokens")
                except BaseException as e:   # noqa: BLE001
                    errs.append(e)

            th = threading.Thread(target=reader)
            th.start()
            prompts = _prompts(4, (6, 9, 5, 11))
            reqs = [eng.submit(p, 8) for p in prompts]
            for r in reqs:
                assert r.wait(300), "request did not finish"
            outs = [r.result() for r in reqs]
            stop.set()
            th.join(10)
            eng.shutdown()
            assert not errs, errs
            for p, o in zip(prompts, outs):
                assert len(o) == len(p) + 8
        finally:
            tracing._sources_lock = old


@pytest.mark.slow
def test_serving_runs_clean_under_race_sanitizer(monkeypatch):
    """Acceptance drive: one dense steady-state run and one live
    auto_run SPEC engine with concurrent submit / introspection /
    load_report / expose_text, all under the race sanitizer — the
    engine, a fresh process-wide registry and a fresh flight ring are
    declared shared, so every cross-thread attribute access is
    Eraser-lockset-checked.  A single unguarded access anywhere in the
    engine/observability stack fails this test with both stacks."""
    import paddle_hackathon_tpu.observability.flight as flight_mod
    import paddle_hackathon_tpu.observability.metrics as metrics_mod
    from paddle_hackathon_tpu.inference import ServingEngine
    with S.race_sanitizer():
        # fresh registry/flight constructed INSIDE the sanitizer so
        # they are instrumented (the import-time singletons stay plain
        # by the declaration-time zero-cost contract)
        monkeypatch.setattr(metrics_mod, "_default_registry",
                            metrics.MetricRegistry())
        monkeypatch.setattr(flight_mod, "_default_recorder",
                            flight.FlightRecorder(capacity=512))
        m = _tiny_gpt()
        # dense, synchronously driven
        eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                            auto_run=False)
        prompts = _prompts()
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            assert len(r.result()) == len(p) + 8
        eng.shutdown()
        # spec, auto_run loop + concurrent readers
        eng2 = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                             auto_run=True, spec_k=2)
        reg = metrics.get_registry()
        stop = threading.Event()
        errs = []

        def reader():
            try:
                while not stop.is_set():
                    eng2.introspect_requests()
                    eng2.load_report()
                    reg.expose_text()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
        th = threading.Thread(target=reader, name="introspector")
        th.start()
        prompts = _prompts(4, (6, 9, 5, 11))
        reqs = [eng2.submit(p, 8) for p in prompts]
        for r in reqs:
            assert r.wait(300), "request did not finish"
        outs = [r.result() for r in reqs]
        stop.set()
        th.join(10)
        eng2.shutdown()
        assert not errs, errs
        for p, o in zip(prompts, outs):
            assert len(o) == len(p) + 8


@pytest.mark.slow
def test_compiled_trainer_superstep_under_race_sanitizer(monkeypatch):
    """Acceptance drive: CompiledTrainer supersteps with the shared
    registry/flight instrumented and a concurrent scraper hammering
    expose_text — the trainer's telemetry writes are lockset-checked
    against the scrape reads."""
    import jax

    import paddle_hackathon_tpu.observability.flight as flight_mod
    import paddle_hackathon_tpu.observability.metrics as metrics_mod
    from paddle_hackathon_tpu.hapi.compiled import CompiledTrainer
    with S.race_sanitizer():
        monkeypatch.setattr(metrics_mod, "_default_registry",
                            metrics.MetricRegistry())
        monkeypatch.setattr(flight_mod, "_default_recorder",
                            flight.FlightRecorder(capacity=512))
        paddle.seed(7)
        net = nn.Sequential(nn.Linear(10, 32), nn.ReLU(),
                            nn.Linear(32, 2))
        mdl = hapi.Model(net)
        mdl.prepare(optimizer=optim.Adam(learning_rate=1e-2,
                                         parameters=net.parameters()),
                    loss=nn.CrossEntropyLoss())
        trainer = CompiledTrainer(mdl)
        reg = metrics.get_registry()
        fr = flight_mod.get_flight_recorder()
        stop = threading.Event()
        errs = []

        def scraper():
            try:
                while not stop.is_set():
                    reg.expose_text()
                    fr.events()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
        th = threading.Thread(target=scraper, name="scraper")
        th.start()
        rs = np.random.RandomState(0)
        x = rs.randn(8, 10).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int64)
        for _ in range(2):
            losses = trainer.run((x[None],), (y[None],))
        stop.set()
        th.join(10)
        assert not errs, errs
        assert np.isfinite(jax.device_get(losses)).all()
