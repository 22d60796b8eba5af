"""Containers and pods: per-rank process management.

Ref ``launch/job/container.py`` (process wrapper w/ log redirection and
status) and ``launch/job/pod.py`` (the set of containers on one node).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Dict, List, Optional


class Container:
    """One rank's OS process (ref ``launch/job/container.py``)."""

    def __init__(self, entrypoint: List[str], env: Dict[str, str],
                 out_path: str, err_path: Optional[str] = None,
                 essential: bool = True):
        self.entrypoint = list(entrypoint)
        self.env = dict(env)
        self.out_path = out_path
        self.err_path = err_path or out_path
        # essential containers define job completion (trainers); a PS server
        # is non-essential: it serves until the trainers are done, then is
        # stopped by the pod (ref launch watcher stopping pserver pods)
        self.essential = essential
        self._proc: Optional[subprocess.Popen] = None
        self._out_f = None
        self._err_f = None

    def start(self) -> None:
        d = os.path.dirname(self.out_path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._out_f = open(self.out_path, "ab")
        self._err_f = (self._out_f if self.err_path == self.out_path
                       else open(self.err_path, "ab"))
        full_env = dict(os.environ)
        full_env.update(self.env)
        self._proc = subprocess.Popen(
            self.entrypoint, env=full_env,
            stdout=self._out_f, stderr=self._err_f)

    @property
    def pid(self) -> Optional[int]:
        return self._proc.pid if self._proc else None

    def exit_code(self) -> Optional[int]:
        if self._proc is None:
            return None
        return self._proc.poll()

    def is_running(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        if self._proc is None:
            return None
        try:
            return self._proc.wait(timeout)
        except subprocess.TimeoutExpired:
            return None

    def terminate(self, force: bool = False) -> None:
        if self._proc is not None and self._proc.poll() is None:
            (self._proc.kill if force else self._proc.terminate)()
        for f in (self._out_f, self._err_f):
            try:
                if f and not f.closed:
                    f.close()
            except Exception:
                pass

    def logs(self, tail: int = 50) -> str:
        try:
            with open(self.out_path, "rb") as f:
                return b"\n".join(f.read().splitlines()[-tail:]).decode(
                    errors="replace")
        except OSError:
            return ""


class Pod:
    """All containers of this node (ref ``launch/job/pod.py``)."""

    def __init__(self):
        self.containers: List[Container] = []
        # the container whose exit ended ``join`` (the others were killed
        # after it, so their exit codes say nothing)
        self.first_failed: Optional[Container] = None

    def add(self, c: Container) -> None:
        self.containers.append(c)

    def deploy(self) -> None:
        for c in self.containers:
            c.start()

    def is_running(self) -> bool:
        return any(c.is_running() for c in self.containers)

    def exit_codes(self) -> List[Optional[int]]:
        return [c.exit_code() for c in self.containers]

    def join(self, poll_interval: float = 0.2) -> int:
        """Wait until every essential container exits; on any failure stop
        the rest. Non-essential containers (PS servers) are stopped once the
        essential set completes. Returns the first non-zero exit code
        (0 on success)."""
        while True:
            # essential success is checked FIRST: once every trainer has
            # exited 0 the job succeeded — a PS server exiting non-zero
            # when its trainer connections drop must not fail the run
            essential = [c.exit_code() for c in self.containers if c.essential]
            if essential and all(rc == 0 for rc in essential):
                self.stop_graceful()  # reap the non-essential servers
                return 0
            bad = [c for c in self.containers
                   if c.exit_code() not in (None, 0)]
            if bad:
                self.first_failed = bad[0]
                self.stop(force=True)
                return bad[0].exit_code()
            if not essential and all(rc == 0 for rc in self.exit_codes()):
                return 0
            time.sleep(poll_interval)

    def stop(self, force: bool = False) -> None:
        for c in self.containers:
            c.terminate(force=force)

    def stop_graceful(self, grace: float = 5.0) -> None:
        """SIGTERM, bounded wait, then SIGKILL stragglers — lets PS servers
        flush/save on shutdown (the reference's watcher stops pserver pods
        gracefully)."""
        for c in self.containers:
            c.terminate(force=False)
        deadline = time.monotonic() + grace
        for c in self.containers:
            c.wait(timeout=max(0.0, deadline - time.monotonic()))
        for c in self.containers:
            if c.is_running():
                c.terminate(force=True)
