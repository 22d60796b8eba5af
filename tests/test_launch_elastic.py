"""Launcher / elastic / fleet_executor tests.

Modeled on the reference's patterns: launcher shell tests
(``test_fleet_launch_*.sh``) become in-process ``launch()`` calls over
subprocess scripts; elastic tests mock the lease store
(``test_fleet_elastic_manager.py``); pipeline runtime checked for 1F1B-like
flow control.
"""

import os
import sys
import textwrap
import time

import pytest

from paddle_hackathon_tpu.distributed.elastic import (ElasticManager,
                                                      ElasticStatus,
                                                      MemLeaseStore)
from paddle_hackathon_tpu.distributed.fleet_executor import (
    AmplifierInterceptor, FleetExecutor, TaskNode)
from paddle_hackathon_tpu.distributed.launch import launch
from paddle_hackathon_tpu.distributed.launch.context import (Context,
                                                             parse_args)
from paddle_hackathon_tpu.distributed.launch.controllers import (
    CollectiveController, PSController, make_controller)


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


class TestLauncher:
    def test_parse_args(self):
        a = parse_args(["--nproc_per_node", "4", "--job_id", "j1",
                        "train.py", "--lr", "0.1"])
        assert a.nproc_per_node == 4 and a.job_id == "j1"
        assert a.training_script == "train.py"
        assert a.training_script_args == ["--lr", "0.1"]
        # elastic range N:M keeps min for nnodes
        a2 = parse_args(["--nnodes", "2:4", "x.py"])
        assert a2.nnodes == 2

    def test_collective_env_protocol(self, tmp_path):
        script = _write(tmp_path, "train.py", """
            import json, os
            out = {k: os.environ[k] for k in
                   ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                    "PADDLE_LOCAL_RANK", "PADDLE_TRAINER_ENDPOINTS")}
            print(json.dumps(out))
        """)
        rc = launch(["--nproc_per_node", "2", "--log_dir",
                     str(tmp_path / "logs"), "--job_id", "envtest", script])
        assert rc == 0
        import json
        logs = sorted((tmp_path / "logs").iterdir())
        assert len(logs) == 2
        seen = set()
        for f in logs:
            rec = json.loads(f.read_text().strip().splitlines()[-1])
            assert rec["PADDLE_TRAINERS_NUM"] == "2"
            assert len(rec["PADDLE_TRAINER_ENDPOINTS"].split(",")) == 2
            seen.add(rec["PADDLE_TRAINER_ID"])
        assert seen == {"0", "1"}

    def test_failure_restart_then_give_up(self, tmp_path):
        script = _write(tmp_path, "fail.py", """
            import sys
            sys.exit(3)
        """)
        t0 = time.monotonic()
        rc = launch(["--nproc_per_node", "1", "--max_restart", "1",
                     "--log_dir", str(tmp_path / "logs"),
                     "--job_id", "failtest", script])
        assert rc == 3
        assert time.monotonic() - t0 < 60

    def test_ps_controller_topology(self, tmp_path):
        # the launcher stops its servers as soon as every trainer has
        # ended, and a real trainer cannot end before its servers are
        # up: it talks to them.  These trainers wait for the servers'
        # marks (at most 60 s); without that, a server that starts
        # slowly on a loaded machine is stopped before its first print
        up = tmp_path / "up"
        up.mkdir()
        script = _write(tmp_path, "role.py", """
            import os, sys, time
            print(os.environ["PADDLE_ROLE"],
                  os.environ["PADDLE_PSERVER_ENDPOINTS"], flush=True)
            if os.environ["PADDLE_ROLE"] == "PSERVER":
                open(os.path.join(
                    sys.argv[1], os.environ["PADDLE_SERVER_ID"]), "w").close()
            else:
                deadline = time.time() + 60
                while (len(os.listdir(sys.argv[1])) < 2
                       and time.time() < deadline):
                    time.sleep(0.05)
        """)
        rc = launch(["--run_mode", "ps", "--server_num", "2",
                     "--trainer_num", "2",
                     "--log_dir", str(tmp_path / "logs"),
                     "--job_id", "pstest", script, str(up)])
        assert rc == 0
        logs = {f.name: f.read_text() for f in
                sorted((tmp_path / "logs").iterdir())}
        roles = [v.split()[0] for v in logs.values() if v.strip()]
        assert roles.count("PSERVER") == 2 and roles.count("TRAINER") == 2

    def test_a_server_that_dies_at_start_fails_the_job_with_its_log(
            self, tmp_path, capfd):
        """The launcher does not wait for trainers whose server is gone:
        it stops what it started, returns the server's exit code, and
        says what the server last wrote."""
        script = _write(tmp_path, "role.py", """
            import os, sys, time
            if os.environ["PADDLE_ROLE"] == "PSERVER":
                print("cannot bind: address already in use", flush=True)
                sys.exit(5)
            time.sleep(600)   # a trainer waiting for that server
        """)
        t0 = time.monotonic()
        rc = launch(["--run_mode", "ps", "--server_num", "1",
                     "--trainer_num", "1", "--max_restart", "0",
                     "--log_dir", str(tmp_path / "logs"),
                     "--job_id", "psdead", script])
        assert rc == 5
        assert time.monotonic() - t0 < 60
        err = capfd.readouterr().err
        assert "psdead.server0.log" in err
        assert "cannot bind: address already in use" in err

    def test_make_controller_dispatch(self):
        ctx = Context(parse_args(["--run_mode", "ps", "--server_num", "1",
                                  "x.py"]))
        assert isinstance(make_controller(ctx), PSController)
        ctx2 = Context(parse_args(["x.py"]))
        assert isinstance(make_controller(ctx2), CollectiveController)


class TestElastic:
    def test_register_and_membership(self):
        store = MemLeaseStore()
        m1 = ElasticManager("job", "1:3", "hostA", store=store,
                            heartbeat_interval=0.05, ttl=0.5)
        m2 = ElasticManager("job", "1:3", "hostB", store=store,
                            heartbeat_interval=0.05, ttl=0.5)
        m1.register(); m2.register()
        try:
            assert m1.hosts() == ["hostA", "hostB"]
            assert m1.health() == "ok"
            assert m1.rank_map() == {"hostA": 0, "hostB": 1}
        finally:
            m1.exit(); m2.exit()

    def test_scale_down_triggers_restart_event(self):
        store = MemLeaseStore()
        m1 = ElasticManager("job", "1:3", "hostA", store=store,
                            heartbeat_interval=0.05, ttl=0.5)
        m2 = ElasticManager("job", "1:3", "hostB", store=store,
                            heartbeat_interval=0.05, ttl=0.5)
        m1.register(); m2.register()
        try:
            m1._last_members = m1.hosts()
            m2.exit()  # node leaves
            status = m1.watch(timeout=3.0)
            assert status == ElasticStatus.RESTART
            assert m1.rank_map() == {"hostA": 0}
        finally:
            m1.exit()

    def test_below_min_holds(self):
        store = MemLeaseStore()
        m1 = ElasticManager("job", "2:3", "hostA", store=store,
                            heartbeat_interval=0.05, ttl=0.5)
        m1.register()
        try:
            assert m1.health() == ElasticStatus.HOLD
        finally:
            m1.exit()

    def test_lease_expiry_removes_dead_node(self):
        store = MemLeaseStore()
        store.put_with_lease("/job/nodes/dead", "dead", ttl=0.1)
        m = ElasticManager("job", "1:2", "live", store=store,
                           heartbeat_interval=0.05, ttl=0.5)
        m.register()
        try:
            time.sleep(0.3)  # dead node's lease expires (no heartbeat)
            assert m.hosts() == ["live"]
        finally:
            m.exit()


class TestFleetExecutor:
    def test_linear_pipeline_order_and_results(self):
        trace = []
        n0 = TaskNode(0, fn=lambda _, mb: mb * 10, max_run_times=4)
        n1 = TaskNode(1, fn=lambda x, mb: trace.append((1, mb)) or x + 1,
                      max_run_times=4)
        n2 = TaskNode(2, fn=lambda x, mb: x * 2, max_run_times=4)
        n0.add_downstream_task(1, buff_size=1)
        n1.add_downstream_task(2, buff_size=1)
        res = FleetExecutor([n0, n1, n2]).run(timeout=10)
        assert res[2] == {0: 2, 1: 22, 2: 42, 3: 62}
        assert [mb for _, mb in trace] == [0, 1, 2, 3]

    def test_flow_control_bounds_in_flight(self):
        """With buff_size=1, the source can be at most 1 microbatch ahead."""
        import threading
        state = {"src": 0, "max_lead": 0}
        lock = threading.Lock()

        def src_fn(_, mb):
            with lock:
                state["src"] = mb
            return mb

        def sink_fn(x, mb):
            with lock:
                state["max_lead"] = max(state["max_lead"],
                                        state["src"] - mb)
            time.sleep(0.01)
            return x

        n0 = TaskNode(0, fn=src_fn, max_run_times=6)
        n1 = TaskNode(1, fn=sink_fn, max_run_times=6)
        n0.add_downstream_task(1, buff_size=1)
        FleetExecutor([n0, n1]).run(timeout=10)
        assert state["max_lead"] <= 2  # credit-bounded, not free-running

    def test_amplifier_accumulates(self):
        n0 = TaskNode(0, fn=lambda _, mb: mb + 1, max_run_times=6)
        n1 = TaskNode(1, fn=lambda xs, mb: sum(xs), role="amplifier",
                      max_run_times=2, run_per_steps=3)
        n0.add_downstream_task(1, buff_size=3)
        res = FleetExecutor([n0, n1]).run(timeout=10)
        assert res[1] == {0: 1 + 2 + 3, 1: 4 + 5 + 6}


class TestMultiProcessBootstrap:
    @pytest.mark.skipif(
        not hasattr(__import__("jax"), "set_mesh"),
        reason="requires_multiprocess_cpu: jax<0.6 CPU backend has no "
               "multiprocess collectives")
    def test_two_process_collective_via_launcher(self, tmp_path):
        """End-to-end: launcher env protocol -> init_parallel_env ->
        jax.distributed two-process psum on CPU (ref test_dist_base.py
        multi-process-on-one-host pattern)."""
        script = _write(tmp_path, "dist_train.py", """
            import jax
            jax.config.update("jax_platforms", "cpu")
            import sys
            sys.path.insert(0, %r)
            import numpy as np
            from paddle_hackathon_tpu import parallel
            parallel.init_parallel_env()
            assert jax.process_count() == 2
            rank = jax.process_index()
            # global psum across the two single-device processes
            from jax.experimental import multihost_utils
            total = multihost_utils.process_allgather(
                np.array([rank + 1.0], np.float32))
            assert float(total.sum()) == 3.0, total
            print("OK rank", rank)
        """ % os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))
        rc = launch(["--nproc_per_node", "2", "--log_dir",
                     str(tmp_path / "logs"), "--job_id", "dist2", script])
        logs = "".join(f.read_text() for f in (tmp_path / "logs").iterdir())
        assert rc == 0, logs
        assert logs.count("OK rank") == 2


class TestNativeStoreThreading:
    def test_concurrent_clients_one_connection(self):
        """TCPStore client must serialize concurrent ops (heartbeat thread +
        watcher share one connection; unsynchronized use corrupts the wire
        protocol)."""
        import threading
        from paddle_hackathon_tpu.parallel.store import MasterStore, TCPStore
        try:
            srv = MasterStore()
        except RuntimeError:
            pytest.skip("native runtime unavailable")
        cli = TCPStore(port=srv.port)
        errs = []

        def worker(tid):
            try:
                for i in range(50):
                    cli.set(f"k{tid}/{i}", f"v{i}")
                    assert cli.get(f"k{tid}/{i}") == f"v{i}".encode()
                    cli.add("ctr", 1)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not errs, errs
        assert cli.add("ctr", 0) == 200
        cli.close(); srv.close()

    def test_elastic_over_native_store(self):
        from paddle_hackathon_tpu.parallel.store import MasterStore, TCPStore
        from paddle_hackathon_tpu.distributed.elastic import TCPLeaseStore
        try:
            srv = MasterStore()
        except RuntimeError:
            pytest.skip("native runtime unavailable")
        m1 = ElasticManager("j", "1:3", "hostA",
                            store=TCPLeaseStore(TCPStore(port=srv.port)),
                            heartbeat_interval=0.05, ttl=1.0)
        m2 = ElasticManager("j", "1:3", "hostB",
                            store=TCPLeaseStore(TCPStore(port=srv.port)),
                            heartbeat_interval=0.05, ttl=1.0)
        m1.register(); m2.register()
        try:
            assert m1.watch(timeout=5.0) == ElasticStatus.RESTART  # join
            assert m1.hosts() == ["hostA", "hostB"]
            m2.exit()
            assert m1.watch(timeout=5.0) == ElasticStatus.RESTART  # leave
            assert m1.rank_map() == {"hostA": 0}
        finally:
            m1.exit(); srv.close()
