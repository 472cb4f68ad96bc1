"""Multi-host job bootstrap & worker lifecycle — RayOnSpark capability parity.

The reference bootstraps a Ray cluster inside Spark executors
(/root/reference/pyzoo/zoo/ray/raycontext.py:51-187: partition 0 starts the head,
others join after a barrier) and guards against leaked worker processes
(``JVMGuard.register_pids`` :30-48, ``ProcessMonitor`` ray/process.py).

TPU-native redesign: a pod job is N identical host processes running
``jax.distributed.initialize`` against a coordinator (no data-plane role for the
launcher). This module provides:

* :class:`ClusterLauncher` — spawn the N per-host worker processes locally
  (single-machine simulation of a pod, or per-host agent on real machines),
  with env injection (coordinator address, process id).
* :class:`ProcessMonitor` — track children, detect failures, kill-on-exit
  (the JVMGuard role, minus the JVM).
* :func:`barrier` — a host-level sync over the jax.distributed client, used by
  fault-recovery tests.
"""

from __future__ import annotations

import atexit
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from .locks import traced_lock


@dataclass
class WorkerProc:
    rank: int
    proc: subprocess.Popen
    cmd: List[str]

    @property
    def pid(self) -> int:
        return self.proc.pid

    def alive(self) -> bool:
        return self.proc.poll() is None

    def returncode(self) -> Optional[int]:
        return self.proc.poll()


class ProcessMonitor:
    """Tracks spawned workers; kills the whole group on exit or on first failure
    (JVMGuard parity — no orphaned raylets/workers)."""

    def __init__(self):
        self.workers: List[WorkerProc] = []
        self._registered = False
        # zoo-lock: guards(workers) — kill_all snapshots under it and signals
        # outside (holding it through the grace wait was a hold-hazard)
        self._lock = traced_lock("ProcessMonitor._lock")

    def register(self, worker: WorkerProc):
        with self._lock:
            self.workers.append(worker)
            if not self._registered:
                atexit.register(self.kill_all)
                self._registered = True

    def poll(self) -> Dict[int, Optional[int]]:
        return {w.rank: w.returncode() for w in self.workers}

    def failed(self) -> List[WorkerProc]:
        return [w for w in self.workers if w.returncode() not in (None, 0)]

    def all_done(self) -> bool:
        return all(not w.alive() for w in self.workers)

    def kill_all(self, sig=signal.SIGTERM, grace_s: float = 3.0):
        # snapshot under the lock; signalling and the grace wait run OUTSIDE
        # it — holding it through the full grace window would stall any
        # concurrent register() (and a re-entrant kill) for grace_s
        with self._lock:
            workers = list(self.workers)
        for w in workers:
            if w.alive():
                try:
                    w.proc.send_signal(sig)
                except ProcessLookupError:
                    pass
        deadline = time.time() + grace_s
        for w in workers:
            while w.alive() and time.time() < deadline:
                time.sleep(0.05)
            if w.alive():
                try:
                    w.proc.kill()
                except ProcessLookupError:
                    pass

    def wait(self, timeout_s: Optional[float] = None,
             on_failure: str = "kill") -> Dict[int, Optional[int]]:
        """Block until all workers exit, a worker fails, or timeout.

        ``on_failure='kill'``: first non-zero exit tears down the rest (fail-fast
        — one lost host kills a pod job's collectives anyway, SURVEY.md §5.3).
        """
        deadline = None if timeout_s is None else time.time() + timeout_s
        while True:
            bad = self.failed()
            if bad:
                if on_failure == "kill":
                    self.kill_all()
                return self.poll()
            if self.all_done():
                return self.poll()
            if deadline is not None and time.time() > deadline:
                still = [w.rank for w in self.workers if w.alive()]
                if on_failure == "kill":
                    self.kill_all()  # no-orphans guarantee holds on timeout too
                raise TimeoutError(f"workers still running: {still}")
            time.sleep(0.1)


def one_process_per_chip(n_children: int, platform: Optional[str],
                         what: str) -> None:
    """Refuse to start a second process of this machine on the default JAX
    backend. A TPU chip admits one process: a second one fails in PJRT
    start-up ("Unable to initialize backend 'tpu' ... libtpu multi-process
    lockfile", a v5e under jax 0.9.0), and so does a child whose parent has
    already touched JAX. Children pinned to the CPU backend, by ``platform``
    or by an inherited ``JAX_PLATFORMS=cpu``, are as many as you like."""
    effective = (platform or os.environ.get("JAX_PLATFORMS", "")).lower()
    if n_children > 1 and effective != "cpu":
        raise RuntimeError(
            f"{what}: {n_children} processes on this machine would each "
            f"initialise the default JAX backend, and a TPU chip admits one "
            f"process. Pin them with platform='cpu' (or JAX_PLATFORMS=cpu), "
            f"keep them in one process, or run one per machine")


class ClusterLauncher:
    """Spawn ``num_processes`` copies of a worker script, each with the env a
    multi-host JAX job needs (coordinator address, process id/count).

    Single-machine pods use distinct CPU processes (``platform="cpu"``; more
    than one worker on the default backend is refused at launch, see
    :func:`one_process_per_chip`); on real clusters run one launcher per
    host with ``process_id`` preassigned.
    """

    def __init__(self, num_processes: int, coordinator_port: int = 7877,
                 env_extra: Optional[Dict[str, str]] = None,
                 python: Optional[str] = None,
                 platform: Optional[str] = None,
                 collectives: Optional[str] = None):
        self.num_processes = int(num_processes)
        self.coordinator = f"127.0.0.1:{coordinator_port}"
        self.env_extra = dict(env_extra or {})
        self.python = python or sys.executable
        # backend threading: workers that call configure_worker_jax() pick
        # these up BEFORE importing anything that initializes jax —
        # collectives="gloo" is what makes multi-process CPU jobs (the
        # single-machine pod simulation) actually exchange gradients
        self.platform = platform
        self.collectives = collectives
        self.monitor = ProcessMonitor()

    def worker_env(self, rank: int) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(self.env_extra)
        env.update({
            "ZOO_TPU_COORDINATOR": self.coordinator,
            # RuntimeConfig field name — picked up by apply_env_overrides so
            # init_zoo_context() in the worker needs no explicit wiring
            "ZOO_TPU_COORDINATOR_ADDRESS": self.coordinator,
            "ZOO_TPU_NUM_PROCESSES": str(self.num_processes),
            "ZOO_TPU_PROCESS_ID": str(rank),
        })
        if self.platform:
            env["ZOO_TPU_WORKER_PLATFORM"] = self.platform
        if self.collectives:
            env["ZOO_TPU_CPU_COLLECTIVES"] = self.collectives
        return env

    def launch(self, script: str, args: Sequence[str] = (),
               log_dir: Optional[str] = None) -> ProcessMonitor:
        """Workers log to ``log_dir/worker-<rank>.log`` (default: a tempdir) —
        never a PIPE, which nobody drains and which would deadlock any worker
        producing more than the OS pipe buffer."""
        import tempfile

        one_process_per_chip(self.num_processes, self.platform,
                             "ClusterLauncher")
        log_dir = log_dir or tempfile.mkdtemp(prefix="zoo_cluster_")
        os.makedirs(log_dir, exist_ok=True)
        self.log_dir = log_dir
        for rank in range(self.num_processes):
            cmd = [self.python, script, *map(str, args)]
            log_path = os.path.join(log_dir, f"worker-{rank}.log")
            with open(log_path, "wb") as logf:
                proc = subprocess.Popen(cmd, env=self.worker_env(rank),
                                        stdout=logf, stderr=subprocess.STDOUT)
            self.monitor.register(WorkerProc(rank=rank, proc=proc, cmd=cmd))
        return self.monitor

def configure_worker_jax():
    """Apply the launcher-threaded JAX backend settings in a worker process.

    Call this FIRST — before importing anything that initializes jax — so
    the platform/collectives config lands before the backend does. Reads
    the env :meth:`ClusterLauncher.worker_env` injected:

    * ``ZOO_TPU_WORKER_PLATFORM`` → ``jax_platforms`` (e.g. ``cpu`` for the
      single-machine pod simulation)
    * ``ZOO_TPU_CPU_COLLECTIVES`` → ``jax_cpu_collectives_implementation``
      (``gloo`` makes multi-process CPU collectives real, not N isolated
      single-process meshes)

    ``jax.distributed`` itself is joined later by ``init_zoo_context`` from
    the ``ZOO_TPU_COORDINATOR_ADDRESS``/``NUM_PROCESSES``/``PROCESS_ID``
    env the launcher also injected.
    """
    import jax

    platform = os.environ.get("ZOO_TPU_WORKER_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
    collectives = os.environ.get("ZOO_TPU_CPU_COLLECTIVES")
    if collectives:
        jax.config.update("jax_cpu_collectives_implementation", collectives)


def barrier(name: str = "zoo_barrier", timeout_s: float = 120.0):
    """Host-level barrier across the jax.distributed job (BarrierTaskContext
    parity, raycontext.py:155-187). No-op single-process."""
    import jax

    if jax.process_count() == 1:
        return
    # a tiny global psum forces a cross-host collective = barrier
    import jax.numpy as jnp

    jax.block_until_ready(
        jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
            jnp.ones((jax.local_device_count(),))))
