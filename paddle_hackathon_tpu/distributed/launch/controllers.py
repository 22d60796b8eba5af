"""Launch controllers: collective and parameter-server.

Ref ``launch/controllers/controller.py:35`` (watch loop + restart policy),
``launch/controllers/collective.py:23`` (CollectiveController),
``launch/controllers/ps.py`` (PSController) and
``launch/controllers/master.py`` (rendezvous master). The reference's
HTTP/etcd master is replaced by the framework's native TCPStore
(``parallel/store.py`` over ``native/runtime.cc``).
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional

from .context import Context, free_port
from .job import Container, Pod


class UnsupportedLaunchError(ValueError):
    """The requested process layout cannot work on this platform."""


def children_platform(env=None) -> str:
    """The platform the launched processes' JAX will select, worked out
    WITHOUT initialising a backend here: a launcher that touched
    ``jax.devices()`` would hold the chip and its children could not get
    it.  ``JAX_PLATFORMS`` decides (first entry); unset, jax probes for
    a TPU itself whenever libtpu is installed."""
    import importlib.util
    env = os.environ if env is None else env
    plats = env.get("JAX_PLATFORMS", "").strip().lower()
    if plats:
        return plats.split(",")[0]
    return "tpu" if importlib.util.find_spec("libtpu") else "cpu"


class Master:
    """Multi-node rendezvous over TCPStore (ref ``controllers/master.py``:
    ``HTTPMaster:66``/``ETCDMaster:175`` sync_peers)."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self._store = None

    def sync_peers(self, my_endpoint: str) -> tuple:
        """Register this node, wait for all; returns (rank, endpoints)."""
        from ...parallel.store import TCPStore
        args = self.ctx.args
        host, port = args.master.split(":")
        is_master = args.rank == 0 or (args.rank == -1 and
                                       host in ("127.0.0.1", "localhost",
                                                self.ctx.node.ip))
        # rank 0's launcher hosts the store; everyone connects
        if is_master:
            try:
                self._store = TCPStore(host="127.0.0.1", port=int(port),
                                       is_master=True, timeout=120.0)
            except RuntimeError:
                is_master = False  # another launcher on this host won the bind
        if self._store is None:
            self._store = TCPStore(host=host, port=int(port), timeout=120.0)
        s = self._store
        job = self.ctx.args.job_id
        rank = (self.ctx.args.rank if self.ctx.args.rank >= 0
                else s.add(f"{job}/nodes") - 1)
        s.set(f"{job}/ep/{rank}", my_endpoint)
        eps = [s.get(f"{job}/ep/{r}").decode()
               for r in range(self.ctx.args.nnodes)]
        return rank, eps

    def close(self):
        if self._store is not None:
            self._store.close()


class Controller:
    """Base controller: build pod → deploy → watch (ref
    ``controllers/controller.py:35``)."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.pod = Pod()
        self.master: Optional[Master] = None

    # -- subclass API -------------------------------------------------------
    def build_pod(self) -> None:
        raise NotImplementedError

    # -- lifecycle ----------------------------------------------------------
    def run(self) -> int:
        self.build_pod()
        self.pod.deploy()
        return self.watch()

    def watch(self) -> int:
        """Exit-code watch loop with bounded restart (ref controller.py
        pod-status loop + ``launch/job/job.py`` restart policy)."""
        restarts = 0
        while True:
            rc = self.pod.join()
            if rc == 0:
                return 0
            if restarts >= self.ctx.args.max_restart:
                failed = self.pod.first_failed
                sys.stderr.write(
                    f"[launch] job failed (exit={rc}) after {restarts} "
                    f"restarts; giving up.  Last lines of "
                    f"{failed.out_path}:\n{failed.logs()}\n")
                return rc
            restarts += 1
            sys.stderr.write(
                f"[launch] rank failure (exit={rc}); restart "
                f"{restarts}/{self.ctx.args.max_restart}\n")
            self.rebuild()

    def rebuild(self) -> None:
        self.pod.stop(force=True)
        self.pod = Pod()
        self.build_pod()
        self.pod.deploy()

    def stop(self) -> None:
        self.pod.stop(force=True)
        if self.master:
            self.master.close()

    # -- helpers ------------------------------------------------------------
    def _script_cmd(self) -> List[str]:
        a = self.ctx.args
        script = a.training_script
        if script.endswith(".py"):
            return [sys.executable, "-u", script] + a.training_script_args
        return [script] + a.training_script_args

    def _log_path(self, name: str) -> str:
        return os.path.join(self.ctx.args.log_dir,
                            f"{self.ctx.args.job_id}.{name}.log")


class CollectiveController(Controller):
    """One process per rank; env protocol consumed by
    ``parallel.env.init_parallel_env`` (ref ``collective.py:23``)."""

    def build_pod(self) -> None:
        ctx = self.ctx
        a = ctx.args
        nprocs = ctx.nprocs()
        if nprocs > 1 and children_platform() == "tpu":
            # a chip belongs to one process; the children get no
            # per-process chip assignment, so the second one could never
            # reach the device (parallel/env.py: one process drives all
            # local chips, single-controller SPMD)
            raise UnsupportedLaunchError(
                f"--nproc_per_node={nprocs} on a TPU host: a chip belongs "
                "to one process at a time and the launcher assigns none "
                "per child.  Supported form: one process per host "
                "(omit --nproc_per_node) driving all local chips through "
                "a mesh; set JAX_PLATFORMS=cpu to run several CPU "
                "processes.")

        if a.nnodes > 1:
            if not a.master:
                raise ValueError("--master host:port is required for "
                                 "multi-node jobs")
            self.master = Master(ctx)
            node_rank, _ = self.master.sync_peers(ctx.node.ip)
            # the jax coordinator lives in global rank 0's process on the
            # master node; its address is agreed through the store
            s = self.master._store
            if node_rank == 0:
                coord = f"{ctx.node.ip}:{free_port()}"
                s.set(f"{a.job_id}/coord", coord)
            else:
                coord = s.get(f"{a.job_id}/coord").decode()
        else:
            node_rank = 0
            coord = (f"127.0.0.1:{free_port()}"
                     if nprocs > 1 else None)

        world = a.nnodes * nprocs
        endpoints = [f"{self.ctx.node.ip}:{free_port()}"
                     for _ in range(nprocs)]
        for local_rank in range(nprocs):
            rank = node_rank * nprocs + local_rank
            env = {
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_LOCAL_RANK": str(local_rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
                "PADDLE_CURRENT_ENDPOINT": endpoints[local_rank],
                "PADDLE_JOB_ID": a.job_id,
            }
            if coord:
                env["PADDLE_MASTER"] = coord
            self.pod.add(Container(self._script_cmd(), env,
                                   self._log_path(f"rank{rank}")))


class PSController(Controller):
    """Parameter-server topology: N servers + M trainers (ref
    ``controllers/ps.py``). Env protocol consumed by ``distributed.ps``."""

    def build_pod(self) -> None:
        a = self.ctx.args
        n_srv = a.server_num or 1
        n_trn = a.trainer_num or 1
        server_eps = [f"127.0.0.1:{free_port()}" for _ in range(n_srv)]
        common = {
            "PADDLE_PSERVER_ENDPOINTS": ",".join(server_eps),
            "PADDLE_TRAINERS_NUM": str(n_trn),
            "PADDLE_JOB_ID": a.job_id,
        }
        for i, ep in enumerate(server_eps):
            env = dict(common, PADDLE_ROLE="PSERVER", PADDLE_PORT=ep.split(":")[1],
                       PADDLE_SERVER_ID=str(i))
            self.pod.add(Container(self._script_cmd(), env,
                                   self._log_path(f"server{i}"),
                                   essential=False))
        for i in range(n_trn):
            env = dict(common, PADDLE_ROLE="TRAINER", PADDLE_TRAINER_ID=str(i))
            self.pod.add(Container(self._script_cmd(), env,
                                   self._log_path(f"trainer{i}")))


def make_controller(ctx: Context) -> Controller:
    if ctx.args.run_mode == "ps" or ctx.args.server_num > 0:
        return PSController(ctx)
    return CollectiveController(ctx)
