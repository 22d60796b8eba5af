"""Native C++ runtime core: allocator, workqueue, tracer, flags, TCP store.

Mirrors the reference's C++ runtime test coverage (gtest suites for the
allocator ``memory/allocation/*_test.cc``, the standalone executor
``new_executor/standalone_executor_test.cc``, and the store
``distributed/store``), driven from Python via the ctypes bindings.
"""

import json
import multiprocessing as mp
import os
import threading
import time

import numpy as np
import pytest
from conftest import join_within

from paddle_hackathon_tpu.core import native
from paddle_hackathon_tpu.parallel.store import TCPStore

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native runtime unavailable")


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

class TestAllocator:
    def test_alloc_free_stats(self):
        before = native.memory_stats()
        a = native.HostAllocation(1 << 16)
        mid = native.memory_stats()
        assert mid["current_in_use"] >= before["current_in_use"] + (1 << 16)
        assert mid["peak_in_use"] >= mid["current_in_use"]
        a.free()
        after = native.memory_stats()
        assert after["current_in_use"] == before["current_in_use"]
        assert after["free_count"] > before["free_count"]

    def test_view_keeps_allocation_alive(self):
        import gc
        arr = native.HostAllocation(1 << 12).as_numpy(np.int32, (1024,))
        gc.collect()  # temporary HostAllocation must be pinned by the view
        arr[:] = 9
        assert int(arr.sum()) == 9 * 1024

    def test_numpy_view_roundtrip(self):
        a = native.HostAllocation(4 * 1024)
        arr = a.as_numpy(np.float32, (32, 8))
        arr[:] = np.arange(256, dtype=np.float32).reshape(32, 8)
        arr2 = a.as_numpy(np.float32, (32, 8))
        np.testing.assert_array_equal(arr2,
                                      np.arange(256,
                                                dtype=np.float32).reshape(32, 8))
        a.free()

    def test_reuse_and_coalesce(self):
        """Freeing then allocating again should not grow reserved bytes."""
        ptrs = [native.HostAllocation(1 << 12) for _ in range(64)]
        reserved1 = native.memory_stats()["reserved"]
        for p in ptrs:
            p.free()
        big = native.HostAllocation(1 << 17)  # should fit in coalesced space
        reserved2 = native.memory_stats()["reserved"]
        assert reserved2 == reserved1
        big.free()

    def test_large_allocation(self):
        a = native.HostAllocation(8 << 20)  # bigger than the 1MiB chunk
        arr = a.as_numpy(np.uint8, (8 << 20,))
        arr[:16] = 7
        assert int(arr[0]) == 7
        a.free()


# ---------------------------------------------------------------------------
# WorkQueue DAG scheduling
# ---------------------------------------------------------------------------

class TestWorkQueue:
    def test_map(self):
        wq = native.WorkQueue(4)
        out = wq.map(lambda x: x * x, list(range(50)))
        assert out == [i * i for i in range(50)]
        wq.close()

    def test_dag_ordering(self):
        """Diamond DAG: 0 -> (1,2) -> 3; 3 must observe 1 and 2."""
        wq = native.WorkQueue(4)
        order = []
        lock = threading.Lock()

        def mk(i):
            def t():
                with lock:
                    order.append(i)
            return t

        wq.run_dag([mk(0), mk(1), mk(2), mk(3)],
                   successors=[[1, 2], [3], [3], []])
        assert order[0] == 0 and order[-1] == 3
        assert set(order[1:3]) == {1, 2}
        wq.close()

    def test_chain_many(self):
        wq = native.WorkQueue(8)
        n = 200
        acc = []
        tasks = [lambda i=i: acc.append(i) for i in range(n)]
        succ = [[i + 1] if i + 1 < n else [] for i in range(n)]
        wq.run_dag(tasks, succ)
        assert acc == list(range(n))  # pure chain must serialize
        wq.close()

    def test_error_propagates(self):
        wq = native.WorkQueue(2)

        def boom():
            raise ValueError("boom")

        with pytest.raises(RuntimeError, match="task 0 failed"):
            wq.run_dag([boom], [[]])
        wq.close()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_push_pop_dump(self, tmp_path):
        native.trace_clear()
        native.trace_enable(True)
        native.trace_push("outer")
        native.trace_push("inner")
        time.sleep(0.001)
        native.trace_pop()
        native.trace_pop()
        native.trace_enable(False)
        assert native.trace_count() == 2
        path = str(tmp_path / "trace.json")
        n = native.trace_dump_chrome(path)
        assert n == 2
        data = json.load(open(path))
        names = {e["name"] for e in data["traceEvents"]}
        assert names == {"outer", "inner"}
        for e in data["traceEvents"]:
            assert e["dur"] >= 0
        native.trace_clear()

    def test_name_escaping(self, tmp_path):
        native.trace_clear()
        native.trace_enable(True)
        native.trace_push('load "x"\\y')
        native.trace_pop()
        native.trace_enable(False)
        path = str(tmp_path / "esc.json")
        native.trace_dump_chrome(path)
        data = json.load(open(path))  # must be valid JSON
        assert data["traceEvents"][0]["name"] == 'load "x"\\y'
        native.trace_clear()

    def test_disabled_records_nothing(self):
        native.trace_clear()
        native.trace_enable(False)
        native.trace_push("x")
        native.trace_pop()
        assert native.trace_count() == 0

    def test_workqueue_task_spans(self, tmp_path):
        native.trace_clear()
        native.trace_enable(True)
        wq = native.WorkQueue(2)
        wq.map(lambda x: x + 1, [1, 2, 3], trace=True)
        wq.close()
        native.trace_enable(False)
        assert native.trace_count() == 3
        native.trace_clear()


# ---------------------------------------------------------------------------
# Flags
# ---------------------------------------------------------------------------

class TestNativeFlags:
    def test_set_get(self):
        native.sync_flags({"check_nan_inf": "True", "custom": "42"})
        assert native.flag_get("check_nan_inf") == "True"
        assert native.flag_get("custom") == "42"
        assert native.flag_get("missing_flag") is None


# ---------------------------------------------------------------------------
# TCP store
# ---------------------------------------------------------------------------

def _store_worker(port, rank, world, q):
    try:
        store = TCPStore("127.0.0.1", port, is_master=False, timeout=20)
        store.set(f"rank{rank}", f"hello{rank}")
        store.barrier("init", rank, world, timeout=20)
        peers = sorted(store.get(f"rank{r}").decode() for r in range(world))
        total = store.add("counter", rank + 1)
        q.put((rank, peers, total))
        store.close()
    except Exception as e:  # pragma: no cover
        q.put((rank, "ERR", repr(e)))


class TestTCPStore:
    def test_set_get_add_check(self):
        store = TCPStore(is_master=True)
        store.set("k", b"v1")
        assert store.get("k") == b"v1"
        assert store.check("k")
        assert not store.check("nope")
        assert store.add("cnt", 5) == 5
        assert store.add("cnt", 2) == 7
        assert store.delete_key("k")
        assert not store.check("k")
        store.close()

    def test_get_blocks_until_set(self):
        store = TCPStore(is_master=True)
        other = TCPStore("127.0.0.1", store.port)

        def setter():
            time.sleep(0.2)
            other.set("late", b"arrived")

        t = threading.Thread(target=setter)
        t.start()
        t0 = time.time()
        assert store.get("late", timeout=10) == b"arrived"
        assert time.time() - t0 >= 0.15
        join_within([t], 30, "the setter thread")
        other.close()
        store.close()

    def test_get_timeout(self):
        store = TCPStore(is_master=True)
        with pytest.raises(TimeoutError):
            store.get("never", timeout=0.2)
        store.close()

    def test_large_value(self):
        store = TCPStore(is_master=True)
        blob = os.urandom(300_000)
        store.set("big", blob)
        assert store.get("big") == blob
        store.close()

    def test_multiprocess_rendezvous(self):
        """The TestDistBase pattern (ref test_dist_base.py:786): spawn ranks
        as subprocesses, rendezvous through the store, verify all-rank
        visibility and barrier semantics."""
        master = TCPStore(is_master=True)
        world = 4
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        procs = [ctx.Process(target=_store_worker,
                             args=(master.port, r, world, q))
                 for r in range(world)]
        for p in procs:
            p.start()
        results = [q.get(timeout=60) for _ in range(world)]
        for p in procs:
            p.join(timeout=30)
        expect = sorted(f"hello{r}" for r in range(world))
        for rank, peers, _total in results:
            assert peers != "ERR", _total
            assert peers == expect
        # counter accumulated sum(1..world)
        assert master.get("counter")[:8] != b""
        final = master.add("counter", 0)
        assert final == sum(range(1, world + 1))
        master.close()


def test_staging_ring_strict_order():
    import threading

    import numpy as np

    from paddle_hackathon_tpu.core import native
    if not native.available():
        pytest.skip("native runtime unavailable")
    ring = native.StagingRing(n_slots=4, slot_bytes=256)
    data = [np.full((4,), i, np.float32) for i in range(8)]

    def producer():
        for i in [1, 0, 2, 4, 3, 5, 7, 6]:  # out-of-order within window
            ring.stage(data[i], i)
        ring.close()

    t = threading.Thread(target=producer)
    t.start()
    got = []
    while True:
        slot, arr = ring.next(np.float32, (4,))
        if slot is None:
            break
        got.append(int(arr[0]))
        ring.release(slot)
    join_within([t], 30, "the ring's producer")
    assert got == list(range(8))


def test_buffered_dataloader_in_order_and_structured():
    import numpy as np

    from paddle_hackathon_tpu.core import native
    from paddle_hackathon_tpu.io import DataLoader, Dataset
    if not native.available():
        pytest.skip("native runtime unavailable")

    class DS(Dataset):
        def __len__(self):
            return 23

        def __getitem__(self, i):
            return np.full((3,), i, np.float32), np.int64(i)

    seen = []
    for xb, yb in DataLoader(DS(), batch_size=4, num_workers=2,
                             use_buffer_reader=True):
        assert xb.shape[1] == 3
        seen.extend(yb.numpy().tolist())
    assert seen == list(range(23))
