"""Process-based DataLoader workers (VERDICT r4 missing #6/directive #5).

Ref ``fluid/dataloader/dataloader_iter.py:342`` (_DataLoaderIterMultiProcess)
+ ``dataloader/worker.py``: worker PROCESSES with shared-memory batch
transfer — the path for GIL-bound Python per-sample transforms, which the
thread pool serializes."""

import time

import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import io
from paddle_hackathon_tpu.core.tensor import Tensor


class _SquareDataset(io.Dataset):
    def __init__(self, n=20):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((3,), i * i, np.float32), np.int64(i)


class _GilBoundDataset(io.Dataset):
    """Pure-Python busy loop per sample — holds the GIL the whole time,
    so thread workers serialize; processes parallelize."""

    def __init__(self, n=24, iters=500000):
        self.n = n
        self.iters = iters

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        acc = 0
        for k in range(self.iters):
            acc = (acc + k * i) % 1000003
        return np.asarray([acc, i], np.float32)


class _BackendProbeDataset(io.Dataset):
    """Each sample reports how many JAX backends its worker process has
    initialised by the time the sample is built."""

    def __len__(self):
        return 8

    def __getitem__(self, i):
        from jax._src import xla_bridge
        return np.asarray([len(xla_bridge._backends)], np.float32)


def _run_epoch(loader):
    return [b for b in loader]


def test_proc_workers_never_initialise_a_backend():
    """A chip belongs to one process — the parent.  Workers import the
    package (to unpickle the dataset) but must never touch a device."""
    loader = io.DataLoader(_BackendProbeDataset(), batch_size=4,
                           num_workers=2, use_process_workers=True)
    rows = np.concatenate([np.asarray(b.numpy()) for b in loader])
    assert rows.shape == (8, 1) and (rows == 0).all()


def test_proc_workers_order_and_values():
    loader = io.DataLoader(_SquareDataset(37), batch_size=5, num_workers=3,
                           use_process_workers=True)
    seen = []
    for xb, yb in loader:
        assert isinstance(xb, Tensor)
        np.testing.assert_array_equal(
            np.asarray(xb.numpy())[:, 0],
            (np.asarray(yb.numpy()) ** 2).astype(np.float32))
        seen.extend(np.asarray(yb.numpy()).tolist())
    assert seen == list(range(37))  # submission order preserved


def test_proc_workers_two_epochs():
    loader = io.DataLoader(_SquareDataset(12), batch_size=4, num_workers=2,
                           use_process_workers=True)
    for _ in range(2):  # a fresh iterator per epoch spawns fresh workers
        assert len(_run_epoch(loader)) == 3


def test_proc_workers_no_shared_memory_path():
    loader = io.DataLoader(_SquareDataset(13), batch_size=4, num_workers=2,
                           use_process_workers=True, use_shared_memory=False)
    seen = [int(v) for _, yb in loader
            for v in np.asarray(yb.numpy()).tolist()]
    assert seen == list(range(13))


def test_proc_workers_error_propagates():
    class Bad(io.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            if i == 5:
                raise ValueError("boom at 5")
            return np.zeros(2, np.float32)

    loader = io.DataLoader(Bad(), batch_size=2, num_workers=2,
                           use_process_workers=True)
    with pytest.raises(RuntimeError, match="boom at 5"):
        _run_epoch(loader)


def test_proc_workers_worker_init_fn_and_info():
    """worker_init_fn runs in the worker process; get_worker_info is
    populated there (ref worker.py _worker_loop semantics)."""
    class Probe(io.Dataset):
        def __len__(self):
            return 6

        def __getitem__(self, i):
            info = io.get_worker_info()
            assert info is not None and 0 <= info.id < 2
            import os
            time.sleep(0.2)  # keep both workers busy so each takes tasks
            return np.asarray([os.getpid(), getattr(
                _probe_state, "tag", -1)], np.int64)

    import threading
    global _probe_state
    _probe_state = threading.local()

    def init_fn(wid):
        _probe_state.tag = 1000 + wid

    loader = io.DataLoader(Probe(), batch_size=1, num_workers=2,
                           use_process_workers=True, worker_init_fn=init_fn)
    rows = np.concatenate([np.asarray(b.numpy()) for b in loader])
    pids = set(rows[:, 0].tolist())
    import os
    assert os.getpid() not in pids  # samples built OUTSIDE this process
    assert set(rows[:, 1].tolist()) <= {1000, 1001}  # init_fn ran per worker


def test_proc_workers_forkserver_no_fork_warnings():
    """A picklable payload takes the FORKSERVER path (the server is
    spawned, not forked) — no fork-of-a-threaded-process warnings: the
    Python 3.12 DeprecationWarning and jax's os.fork RuntimeWarning both
    fire only on fork().  Fork stays available for unpicklable payloads
    (numpy-only-child constraint documented on _ProcPrefetchIter)."""
    import warnings

    from paddle_hackathon_tpu.io.dataloader import (_np_collate,
                                                    _ProcPrefetchIter)

    loader = io.DataLoader(_SquareDataset(12), batch_size=4, num_workers=2,
                           use_process_workers=True)
    ctx = _ProcPrefetchIter._pick_context(loader, _np_collate)
    assert ctx.get_start_method() == "forkserver"
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert len(_run_epoch(loader)) == 3
    bad = [w for w in rec
           if issubclass(w.category, (DeprecationWarning, RuntimeWarning))
           and "fork" in str(w.message)]
    assert not bad, [str(w.message) for w in bad]


def test_proc_workers_unpicklable_payload_falls_back_to_fork():
    class Local(io.Dataset):  # locally-defined: not picklable
        def __len__(self):
            return 6

        def __getitem__(self, i):
            return np.full((2,), i, np.float32)

    loader = io.DataLoader(Local(), batch_size=2, num_workers=2,
                           use_process_workers=True)
    from paddle_hackathon_tpu.io.dataloader import (_np_collate,
                                                    _ProcPrefetchIter)
    ctx = _ProcPrefetchIter._pick_context(loader, _np_collate)
    assert ctx.get_start_method() == "fork"
    vals = sorted(int(v) for b in loader
                  for v in np.asarray(b.numpy())[:, 0].tolist())
    assert vals == [0, 1, 2, 3, 4, 5]


def test_proc_workers_timeout():
    class Slow(io.Dataset):
        def __len__(self):
            return 4

        def __getitem__(self, i):
            time.sleep(30)
            return np.zeros(2, np.float32)

    loader = io.DataLoader(Slow(), batch_size=2, num_workers=1,
                           use_process_workers=True, timeout=2)
    with pytest.raises(RuntimeError, match="timed out"):
        _run_epoch(loader)


class _ExitsMidEpoch(io.Dataset):
    """The worker that is handed sample 3 leaves, with exit code 0 or
    killed: its batch is lost and the other worker idles."""

    def __init__(self, how):
        self.how = how

    def __len__(self):
        return 8

    def __getitem__(self, i):
        if i == 3:
            import os
            import signal
            if self.how == "exit0":
                os._exit(0)
            os.kill(os.getpid(), signal.SIGKILL)
        return np.zeros(2, np.float32)


@pytest.mark.parametrize("how", ["exit0", "killed"])
def test_proc_worker_gone_mid_epoch_raises_and_does_not_hang(how):
    """A worker that is gone before the epoch ends never delivers its
    batch.  The parent must raise, whatever the exit code: at the parent
    commit a worker that left with code 0 counted as finished and the
    loader polled for its batch for ever."""
    # no shared memory: a worker that dies with a finished batch still in
    # its queue's feeder would strand that segment in /dev/shm
    loader = io.DataLoader(_ExitsMidEpoch(how), batch_size=2, num_workers=2,
                           use_process_workers=True, use_shared_memory=False)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="died"):
        _run_epoch(loader)
    assert time.monotonic() - t0 < 60


def _never_comes_up(wid):
    time.sleep(3600)


def test_proc_worker_that_never_comes_up_raises_within_the_start_limit(
        monkeypatch):
    """Workers that are alive but stuck before their loop (here in the
    init function; in the field a forked child blocked on a lock it
    inherited held) take no task and deliver nothing.  The parent raises
    once the start limit has passed, naming the processes, and takes
    them down; at the parent commit it polled for ever.  (One stuck
    worker among healthy ones loses nothing: the others do its work.)"""
    from paddle_hackathon_tpu.io import dataloader as dl
    monkeypatch.setattr(dl, "_WORKER_START_TIMEOUT_S", 3.0)
    loader = io.DataLoader(_SquareDataset(12), batch_size=2, num_workers=2,
                           use_process_workers=True,
                           worker_init_fn=_never_comes_up)
    it = iter(loader)
    workers = list(it.workers)
    with pytest.raises(RuntimeError, match="did not come up within 3 s"):
        for _ in it:
            pass
    assert not any(w.is_alive() for w in workers)


class _CheckInDataset(io.Dataset):
    """Each sample reports who built it and when.  A worker's FIRST
    sample checks in (a file named for its pid) and then waits, for at
    most ``patience`` seconds, until ``n_workers`` processes have checked
    in: that all of them are inside ``__getitem__`` at one moment is then
    a fact of the processes, not of the machine's load."""

    def __init__(self, rendezvous, n_workers, n=16, patience=120.0):
        self.dir, self.n_workers, self.n = str(rendezvous), n_workers, n
        self.patience = patience
        self.t0 = time.time()

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        import os
        start = time.time()
        mine = os.path.join(self.dir, str(os.getpid()))
        if not os.path.exists(mine):
            open(mine, "w").close()
            while (len(os.listdir(self.dir)) < self.n_workers
                   and time.time() - start < self.patience):
                time.sleep(0.01)
        return np.asarray([os.getpid(), start - self.t0,
                           time.time() - self.t0], np.float64)


def test_work_runs_in_n_worker_processes_at_once(tmp_path):
    """What process workers are for (dataloader_iter.py:342): N samples
    are being built at the same moment in N distinct processes, none of
    them this one.  Asserted from the pids and timestamps the workers
    return.  How much faster that makes a GIL-bound transform than the
    same workers as threads depends on the machine and its load, so the
    ratio is printed, not asserted."""
    import os
    n = 4
    loader = io.DataLoader(_CheckInDataset(tmp_path, n), batch_size=1,
                           num_workers=n, use_process_workers=True,
                           use_buffer_reader=False)
    rows = np.concatenate([np.asarray(b.numpy(), np.float64).reshape(1, 3)
                           for b in loader])
    assert len(rows) == 16
    first = {}  # pid -> (start, end) of that worker's first sample
    for pid, start, end in rows.tolist():
        if int(pid) not in first or start < first[int(pid)][0]:
            first[int(pid)] = (start, end)
    assert len(first) == n and os.getpid() not in first, first
    # every worker had started its first sample before any finished it
    assert max(s for s, _ in first.values()) < min(
        e for _, e in first.values()), first

    ds = _GilBoundDataset(n=16)

    def timed(procs):
        loader = io.DataLoader(ds, batch_size=2, num_workers=n,
                               use_process_workers=procs,
                               use_buffer_reader=False)
        t0 = time.perf_counter()
        assert len(_run_epoch(loader)) == 8
        return time.perf_counter() - t0

    # the forkserver is warm from the epoch above; the processes' epoch
    # still pays their start, as a real first epoch does
    t_proc, t_thread = timed(True), timed(False)
    print(f"GIL-bound epoch of 16 samples, workers' start included: {n} "
          f"threads {t_thread:.2f} s, {n} processes {t_proc:.2f} s, ratio "
          f"{t_thread / t_proc:.2f}")
