"""DataLoader (ref ``fluid/reader.py:275`` DataLoader;
``fluid/dataloader/dataloader_iter.py`` single/multi-process iterators).

TPU-native design: batches are assembled on the host by a pool of worker
threads feeding a bounded prefetch queue (PJRT transfers are zero-copy
from numpy, and numpy/image decode releases the GIL, so threads cover
the numpy-bound case). For PYTHON-heavy per-sample transforms — which
serialize on the GIL — ``use_process_workers=True`` switches to worker
processes with shared-memory batch transfer, the reference's
``dataloader_iter.py:342`` + ``worker.py`` design. ``prefetch_factor``
batches are kept in flight, overlapping input assembly with device
compute like the reference's ``buffered_reader.cc``.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Optional

import numpy as np

from ..core.tensor import Tensor
from ..observability.sanitizers import make_lock, share_object
from .dataset import IterableDataset
from .sampler import BatchSampler

_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


class WorkerInfo:
    def __init__(self, wid, num_workers, dataset):
        self.id = wid
        self.num_workers = num_workers
        self.dataset = dataset


def default_collate_fn(batch):
    """Stack samples into batched Tensors (ref
    ``fluid/dataloader/collate.py`` default_collate_fn)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        import jax.numpy as jnp
        return Tensor(jnp.stack([s._value for s in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return Tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        return tuple(default_collate_fn([s[i] for s in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    raise TypeError(f"cannot collate type {type(sample)}")


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, use_process_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.use_buffer_reader = use_buffer_reader
        self.prefetch_factor = max(prefetch_factor, 1)
        self.use_shared_memory = use_shared_memory
        self.use_process_workers = use_process_workers
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle,
                batch_size=batch_size if batch_size is not None else 1,
                drop_last=drop_last)
        self._no_batch = batch_size is None

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("length of IterableDataset DataLoader is unknown")
        return len(self.batch_sampler)

    def __call__(self):
        return self.__iter__()

    def __iter__(self):
        if self._iterable_mode:
            return self._iter_iterable()
        if self.num_workers == 0:
            return self._iter_single()
        if self.use_process_workers:
            return iter(_ProcPrefetchIter(self))
        if self.use_buffer_reader:
            from ..core import native
            if native.available():
                return iter(_BufferedPrefetchIter(self))
        return iter(_PrefetchIter(self))

    def _iter_single(self):
        for batch_idx in self.batch_sampler:
            samples = [self.dataset[i] for i in batch_idx]
            if self._no_batch:
                yield samples[0]
            else:
                yield self.collate_fn(samples)

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == (self.batch_size or 1):
                yield self.collate_fn(batch)
                batch = []
        if batch and not getattr(self, "drop_last", False):
            yield self.collate_fn(batch)


def device_prefetch(iterator, size=2, device=None):
    """Device-prefetch iterator (ref ``buffered_reader.cc``'s H2D staging
    stage): pull up to ``size`` batches ahead of the consumer and start
    their host→device transfers immediately.  ``jax.device_put`` is
    asynchronous, so the copies overlap device compute — the consumer
    (e.g. ``Model.fit``'s compiled trainer) finds its next batch already
    resident instead of paying H2D on the critical path.

    numpy leaves are ``device_put``; jax arrays and Tensors pass through
    (already resident or in flight).  Works on any iterator of (nested)
    batches — tuples/lists/dicts of arrays.

    Each host-side pull is timed into the
    ``input_wait_seconds{site=device_prefetch}`` histogram: when the
    consumer outruns the producer, this distribution fattening is the
    input-starvation signal (docs/OBSERVABILITY.md).
    """
    import time as _time

    import jax

    from ..observability import metrics as _obs
    wait_hist = _obs.get_registry().histogram(
        "input_wait_seconds",
        "host wait per batch pulled from the input pipeline",
        unit="s").labels(site="device_prefetch")

    def _put_leaf(a):
        if isinstance(a, Tensor):
            return a
        if isinstance(a, np.ndarray) and a.dtype.kind not in "OUSV":
            return jax.device_put(a, device)
        return a

    def _put(batch):
        return jax.tree.map(_put_leaf, batch,
                            is_leaf=lambda t: isinstance(t, Tensor))

    from ..observability import faults as _faults
    from .transfer import TransferRing

    it = iter(iterator)
    size = max(int(size), 1)
    # a buffer of ``size`` batches = ``size - 1`` still in flight after
    # each yield (the ring pops the oldest once it is over depth)
    ring = TransferRing(depth=size - 1)
    while True:
        try:
            # drill point for the crash harness: a dataloader dying
            # (or stalling) mid-fit is a canonical training failure
            _faults.point("io.prefetch")
            t0 = _time.perf_counter()
            nxt = next(it)
            wait_hist.observe(_time.perf_counter() - t0)
        except StopIteration:
            for b in ring.drain():
                yield b
            return
        ready = ring.push(_put(nxt))
        if ready is not None:
            yield ready


class _PrefetchIter:
    """Thread-pool prefetching iterator (ref
    ``_DataLoaderIterMultiProcess`` ``dataloader_iter.py:342``: outstanding
    batch queue + in-order reordering)."""

    _SENTINEL = object()

    def __init__(self, loader: DataLoader):
        self.loader = loader
        self.batches = list(loader.batch_sampler)
        self.max_outstanding = loader.num_workers * loader.prefetch_factor
        self.task_q: "queue.Queue" = queue.Queue()
        self.results = {}
        self.next_emit = 0
        self.lock = make_lock("dataloader.prefetch")
        self.cv = threading.Condition(self.lock)
        self.error = None
        for i, b in enumerate(self.batches):
            self.task_q.put((i, b))
        self.n_tasks = len(self.batches)
        self.workers = []
        # declare shared BEFORE the workers start: every worker access
        # from here on is lockset-checked when the race sanitizer is
        # armed (zero cost otherwise — share_object returns self as-is)
        share_object(self, "dataloader.prefetch")
        for wid in range(loader.num_workers):
            t = threading.Thread(target=self._worker, args=(wid,), daemon=True)
            t.start()
            self.workers.append(t)

    def _worker(self, wid):
        _worker_info.info = WorkerInfo(wid, self.loader.num_workers,
                                       self.loader.dataset)
        if self.loader.worker_init_fn is not None:
            self.loader.worker_init_fn(wid)
        while True:
            try:
                i, idxs = self.task_q.get_nowait()
            except queue.Empty:
                return
            try:
                samples = [self.loader.dataset[j] for j in idxs]
                batch = self.loader.collate_fn(samples)
            except Exception as e:  # propagate to consumer
                with self.cv:
                    self.error = e
                    self.cv.notify_all()
                return
            with self.cv:
                while i > self.next_emit + self.max_outstanding and self.error is None:
                    self.cv.wait(timeout=1.0)
                self.results[i] = batch
                self.cv.notify_all()

    def __iter__(self):
        return self

    def __next__(self):
        with self.cv:
            # the drained check must read next_emit UNDER the cv: it is
            # written under the cv below, and two consumer threads (or
            # the buffered stager racing a direct consumer) checking it
            # lock-free could both pass and one would wait forever on a
            # batch the other already emitted (PHT009 check-then-act)
            if self.next_emit >= self.n_tasks:
                raise StopIteration
            while self.next_emit not in self.results and self.error is None:
                self.cv.wait(timeout=1.0)
            if self.error is not None:
                raise self.error
            batch = self.results.pop(self.next_emit)
            self.next_emit += 1
            self.cv.notify_all()
        return batch


def _np_collate(batch):
    """Numpy-only collate for worker PROCESSES: the default collate
    builds jax arrays, but a forked child must not call into XLA (its
    runtime threads do not survive fork) — the parent re-wraps the
    numpy leaves into Tensors after transport."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return tuple(_np_collate([s[i] for s in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: _np_collate([s[k] for s in batch]) for k in sample}
    raise TypeError(
        f"cannot collate type {type(sample)} in a worker process; "
        "datasets used with use_process_workers=True must yield "
        "numpy/scalar/list/dict samples (jax arrays cannot cross fork)")


def _proc_worker(dataset, collate_fn, worker_init_fn, wid, num_workers,
                 task_q, data_q, use_shm):
    """Worker-process body (ref ``fluid/dataloader/worker.py``
    ``_worker_loop``): fetch index batches from ``task_q``, collate, ship
    results back — numeric arrays through shared memory when ``use_shm``
    (the reference's shared-memory tensor transfer), everything else
    pickled on the queue."""
    import traceback
    _worker_info.info = WorkerInfo(wid, num_workers, dataset)
    if worker_init_fn is not None:
        worker_init_fn(wid)
    data_q.put(("ready", wid, None))
    while True:
        task = task_q.get()
        if task is None:
            return
        i, idxs = task
        try:
            batch = collate_fn([dataset[j] for j in idxs])
            arrays, structure = _flatten_batch(batch)
            metas = []
            for a in arrays:
                if use_shm and a.dtype.kind not in "OUSV" and a.nbytes > 0:
                    from multiprocessing import (resource_tracker,
                                                 shared_memory)
                    shm = shared_memory.SharedMemory(create=True,
                                                     size=a.nbytes)
                    np.ndarray(a.shape, a.dtype, buffer=shm.buf)[...] = a
                    metas.append(("shm", shm.name, a.dtype.str, a.shape))
                    shm.close()
                    # ownership transfers to the parent (which unlinks
                    # after copying): drop this process's tracker
                    # registration, or the tracker double-cleans (noise)
                    # — and a worker-private tracker (possible if the
                    # fork predated the parent's tracker) would unlink
                    # segments the parent has not read yet on worker exit
                    try:
                        resource_tracker.unregister(
                            shm._name, "shared_memory")
                    except Exception:
                        pass
                else:
                    metas.append(("raw", a))
            data_q.put((i, metas, structure))
        except Exception as e:  # noqa: BLE001 — relayed to the parent
            data_q.put(("error", f"{type(e).__name__}: {e}\n"
                                 f"{traceback.format_exc(limit=8)}", None))
            return


# A worker process says "ready" once, when it has been started, has
# imported what its payload needs and has run ``worker_init_fn``.  One
# that is alive and has not said so after this long never will (a child
# forked from a threaded parent that blocks on an inherited lock, a
# forkserver child stuck in an import): the parent raises instead of
# polling for ever.
_WORKER_START_TIMEOUT_S = 120.0


class _ProcPrefetchIter:
    """Worker-PROCESS prefetching iterator (ref
    ``_DataLoaderIterMultiProcess`` ``dataloader_iter.py:342``): index
    batches fan out to worker processes; results return in submission
    order through a bounded outstanding-task window.  This is the path
    for Python-heavy (GIL-bound) per-sample transforms — the thread pool
    (`_PrefetchIter`) serializes those on the GIL; processes run them in
    parallel (VERDICT r4 directive #5).

    Start method: a FORKSERVER context is preferred when the worker
    payload (dataset, collate, worker_init_fn) pickles — the server is
    posix_spawn'ed single-threaded, so workers never fork() a
    multi-threaded JAX parent (Python 3.12 deprecates that; forked
    children can also deadlock on locks held by threads that don't
    survive the fork).  When the payload doesn't pickle (closures,
    open handles) the iterator falls back to plain fork(): the dataset
    needn't pickle then, but child-side work MUST stay numpy-only —
    no XLA/jax calls (the runtime threads don't survive the fork; see
    ``_np_collate``).  Either way a worker never initialises a JAX
    backend: the chip belongs to the parent process, and a forkserver
    worker that touched ``jax.devices()`` would fail or hang on it
    (tests/test_dataloader_procs.py pins that the worker loop leaves
    ``xla_bridge._backends`` empty).  Numeric batch leaves travel through POSIX shared
    memory either way (one memcpy in the worker, one attach+copy in the
    parent); non-numeric leaves pickle."""

    @staticmethod
    def _pick_context(loader, collate):
        import multiprocessing
        cached = getattr(loader, "_proc_mp_start_method", None)
        if cached is not None:
            return multiprocessing.get_context(cached)
        method = "fork"
        if "forkserver" in multiprocessing.get_all_start_methods():
            # probe picklability through a null sink: no bytes are
            # materialized, so a multi-GB in-memory dataset costs one
            # serialization pass, not a 2x RAM spike
            import io as _io
            import pickle

            class _Null(_io.RawIOBase):
                def writable(self):
                    return True

                def write(self, b):
                    return len(b)

            try:
                pickle.Pickler(_Null(),
                               protocol=pickle.HIGHEST_PROTOCOL).dump(
                    (loader.dataset, collate, loader.worker_init_fn))
                method = "forkserver"
            except Exception:  # unpicklable payload: fork keeps working
                pass
        loader._proc_mp_start_method = method  # probe once per loader
        return multiprocessing.get_context(method)

    def __init__(self, loader: DataLoader):
        self.loader = loader
        collate = (loader.collate_fn
                   if loader.collate_fn is not default_collate_fn
                   else _np_collate)
        ctx = self._pick_context(loader, collate)
        if loader.use_shared_memory:
            # spawn the resource tracker BEFORE forking: children must
            # inherit the parent's tracker, not spawn private ones whose
            # exit-cleanup unlinks segments the parent still needs
            from multiprocessing import resource_tracker
            resource_tracker.ensure_running()
        self.batches = list(loader.batch_sampler)
        self.n_tasks = len(self.batches)
        self.max_outstanding = max(
            loader.num_workers * loader.prefetch_factor, 1)
        self.task_q = ctx.Queue()
        self.data_q = ctx.Queue()
        self.results = {}
        self.next_emit = 0
        self.next_task = 0
        # close() runs from the consumer AND from __del__ (which the GC
        # may fire on any thread): the closed check-then-set must be
        # atomic or both callers race past it (PHT010's shape) and
        # double-drain the queues
        self._close_lock = make_lock("dataloader.close")
        self._closed = False
        self.workers = [
            ctx.Process(target=_proc_worker,
                        args=(loader.dataset, collate,
                              loader.worker_init_fn, wid,
                              loader.num_workers, self.task_q, self.data_q,
                              loader.use_shared_memory),
                        daemon=True)
            for wid in range(loader.num_workers)]
        for w in self.workers:
            w.start()
        self._ready = set()
        self._start_deadline = time.monotonic() + _WORKER_START_TIMEOUT_S
        while (self.next_task < self.n_tasks
               and self.next_task < self.max_outstanding):
            self._submit()

    def _submit(self):
        self.task_q.put((self.next_task, self.batches[self.next_task]))
        self.next_task += 1

    def _reconstruct(self, metas, structure):
        from multiprocessing import shared_memory

        import jax.numpy as jnp
        arrays = []
        for meta in metas:
            if meta[0] == "raw":
                a = meta[1]
                arrays.append(Tensor(jnp.asarray(a))
                              if isinstance(a, np.ndarray)
                              and a.dtype.kind not in "OUSV" else a)
                continue
            _, name, dtype, shape = meta
            shm = shared_memory.SharedMemory(name=name)
            try:
                view = np.ndarray(shape, np.dtype(dtype), buffer=shm.buf)
                arrays.append(Tensor(jnp.asarray(view.copy())))
            finally:
                shm.close()
                shm.unlink()
        return _unflatten_batch(arrays, structure)

    def __iter__(self):
        return self

    def __next__(self):
        if self.next_emit >= self.n_tasks:
            self.close()
            raise StopIteration
        timeout = self.loader.timeout or None
        while self.next_emit not in self.results:
            try:
                item = self.data_q.get(
                    timeout=timeout if timeout else 5.0)
            except queue.Empty:
                if timeout:
                    self.close()
                    raise RuntimeError(
                        f"DataLoader worker timed out after {timeout}s")
                # a worker that is gone mid-epoch (killed by OOM or a
                # segfault, or exited by itself: the sentinels go out
                # only after the last batch) never delivers its batch —
                # waiting for the rest would hang forever
                dead = [w for w in self.workers if w.exitcode is not None]
                if dead:
                    codes = [w.exitcode for w in dead]
                    self.close()
                    raise RuntimeError(
                        f"DataLoader worker process(es) died "
                        f"(exitcode {codes}); their in-flight batches "
                        "are lost") from None
                if (len(self._ready) < len(self.workers)
                        and time.monotonic() > self._start_deadline):
                    silent = [w.pid for wid, w in enumerate(self.workers)
                              if wid not in self._ready]
                    self.close()
                    raise RuntimeError(
                        f"DataLoader worker process(es) {silent} are alive "
                        "but did not come up within "
                        f"{_WORKER_START_TIMEOUT_S:g} s (stuck at start, "
                        "in an import or in worker_init_fn)") from None
                continue
            if item[0] == "ready":
                self._ready.add(item[1])
                continue
            if item[0] == "error":
                self.close()
                raise RuntimeError(
                    f"DataLoader worker raised:\n{item[1]}")
            i, metas, structure = item
            self.results[i] = (metas, structure)
        metas, structure = self.results.pop(self.next_emit)
        self.next_emit += 1
        if self.next_task < self.n_tasks:
            self._submit()
        elif self.next_emit >= self.n_tasks:
            for _ in self.workers:
                self.task_q.put(None)  # drain workers at epoch end
        return self._reconstruct(metas, structure)

    def close(self):
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        # graceful first: sentinels let each worker finish its CURRENT
        # task and flush its queue feeder — terminating straight away
        # would strand in-flight shm segments that no process can name
        # anymore (the worker already unregistered them)
        for _ in self.workers:
            self.task_q.put(None)
        pending = list(self.results.values())
        self.results.clear()
        deadline = time.monotonic() + 5.0
        while (any(w.is_alive() for w in self.workers)
               and time.monotonic() < deadline):
            try:
                item = self.data_q.get(timeout=0.1)
            except queue.Empty:
                continue
            if item and not isinstance(item[0], str):
                pending.append((item[1], item[2]))
        for w in self.workers:
            if w.is_alive():
                w.terminate()
            w.join(5.0)
            if w.is_alive():  # SIGTERM blocked or ignored
                w.kill()
                w.join(5.0)
        # final drain after join: everything the feeders flushed
        while True:
            try:
                item = self.data_q.get_nowait()
            except Exception:
                break
            if item and not isinstance(item[0], str):
                pending.append((item[1], item[2]))
        # unlink segments parked in results or undrained in the queue —
        # ownership transferred to the parent; an early-terminated epoch
        # must not leak /dev/shm
        from multiprocessing import shared_memory
        for metas, _ in pending:
            for meta in metas:
                if meta[0] == "shm":
                    try:
                        shm = shared_memory.SharedMemory(name=meta[1])
                        shm.close()
                        shm.unlink()
                    except FileNotFoundError:
                        pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class _BufferedPrefetchIter:
    """Prefetch iterator with the native staging ring (ref
    ``operators/reader/buffered_reader.cc``).

    Pipeline: worker threads (dataset fetch + collate, Python) -> stager
    thread (C++ memcpy into recycled slots, GIL released during the copy) ->
    consumer (copies to a device buffer, then recycles the slot).

    Metadata for each batch is queued BEFORE its arrays are staged so the
    consumer can drain slots while the stager fills them — a batch with more
    arrays than ring slots therefore streams through instead of
    deadlocking. Object/str arrays (non-numeric dtypes) bypass the ring and
    travel on the metadata queue directly.
    """

    def __init__(self, loader: DataLoader):
        from ..core import native
        self.inner = _PrefetchIter(loader)
        slot_bytes = 1 << 20
        n_slots = max(4, loader.num_workers * loader.prefetch_factor * 2)
        self.ring = native.StagingRing(n_slots=n_slots, slot_bytes=slot_bytes)
        self.meta_q: "queue.Queue" = queue.Queue()
        # same contract as _ProcPrefetchIter: close() is reachable from
        # the consumer and from GC-driven __del__ concurrently
        self._close_lock = make_lock("dataloader.close")
        self._closed = False
        # the thread target closes over (inner, ring, meta_q) directly — NOT
        # self — so an abandoned iterator can be garbage-collected, firing
        # __del__ -> close() -> ring.close(), which unblocks this thread
        self._stager = threading.Thread(
            target=_stage_loop, args=(self.inner, self.ring, self.meta_q),
            daemon=True)
        self._stager.start()

    def close(self):
        """Unblock and tear down (also called on abandonment via __del__)."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self.ring.close()  # unblocks a stager stuck waiting for a free slot
        with self.inner.cv:
            if self.inner.error is None:
                self.inner.error = GeneratorExit("DataLoader iterator closed")
            self.inner.cv.notify_all()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __iter__(self):
        return self

    def __next__(self):
        item = self.meta_q.get()
        if item is None:
            self.close()
            raise StopIteration
        if isinstance(item, Exception):
            self.close()
            raise item
        metas, structure = item
        import jax.numpy as jnp
        import numpy as np
        from ..core.tensor import Tensor
        arrays = []
        for meta in metas:
            if meta[0] == "raw":
                arrays.append(Tensor(jnp.asarray(meta[1]))
                              if np.asarray(meta[1]).dtype.kind not in "OUSV"
                              else meta[1])
                continue
            dtype, shape = meta
            slot, view = self.ring.next(dtype, shape)
            if slot is None:
                self.close()
                raise RuntimeError(
                    "staging ring drained mid-batch (stager failed)")
            # host memcpy BEFORE recycling the slot: a device-side
            # block_until_ready here would serialize one host<->device
            # sync per array, while np.array is a plain memcpy; the
            # fresh host array is never mutated again, so an aliasing
            # CPU backend is safe and the H2D stays async
            host = np.array(view)
            arrays.append(Tensor(jnp.asarray(host)))
            self.ring.release(slot)
        return _unflatten_batch(arrays, structure)


def _stage_loop(inner, ring, meta_q):
    """Stager thread body (module-level: must not keep the iterator alive)."""
    seq = 0
    try:
        for batch in inner:
            arrays, structure = _flatten_batch(batch)
            metas = []
            ringable = []
            for a in arrays:
                if a.dtype.kind in "OUSV":  # object/str: bypass ring
                    metas.append(("raw", a))
                else:
                    metas.append((a.dtype, a.shape))
                    ringable.append(a)
            # meta first: the consumer starts draining slots while the
            # arrays stream through the ring (no capacity deadlock)
            meta_q.put((metas, structure))
            for a in ringable:
                if ring.stage(a, seq) < 0:
                    raise RuntimeError("staging ring closed mid-epoch")
                seq += 1
        meta_q.put(None)
    except Exception as e:
        meta_q.put(e)
    except BaseException:  # GeneratorExit from close(): silent exit
        meta_q.put(None)
    finally:
        ring.close()


def _flatten_batch(batch):
    """Split a collated batch into (list of numpy arrays, structure)."""
    import numpy as np
    from ..core.tensor import Tensor
    if isinstance(batch, dict):
        arrays, struct = [], []
        for k in batch:
            a, s = _flatten_batch(batch[k])
            struct.append((k, len(a), s))
            arrays.extend(a)
        return arrays, ("dict", struct)
    if isinstance(batch, (list, tuple)):
        arrays, struct = [], []
        for item in batch:
            a, s = _flatten_batch(item)
            struct.append((len(a), s))
            arrays.extend(a)
        return arrays, (type(batch).__name__, struct)
    if isinstance(batch, Tensor):
        return [np.asarray(batch.numpy())], "tensor"
    return [np.asarray(batch)], "array"


def _unflatten_batch(arrays, structure):
    if structure in ("tensor", "array"):
        return arrays[0]
    kind, struct = structure
    if kind == "dict":
        out = {}
        i = 0
        for k, n, s in struct:
            out[k] = _unflatten_batch(arrays[i:i + n], s)
            i += n
        return out
    out = []
    i = 0
    for n, s in struct:
        out.append(_unflatten_batch(arrays[i:i + n], s))
        i += n
    return tuple(out) if kind == "tuple" else out
