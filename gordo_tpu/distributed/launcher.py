"""Fork-N-local-processes launcher: the simulated multi-host slice.

A CPU SIMULATION by design: every worker is pinned to ``JAX_PLATFORMS=cpu``
with virtual devices, so nothing here competes for an accelerator.  The
launching parent must not touch a jax backend before it forks (it only
builds environments and ``Popen``s; keep it that way).

Real deployments get one process per host from the orchestrator (the
Indexed-Job manifest ``workflow generate --multihost N`` emits).  For
development and the CPU dryrun, this module IS the orchestrator: it forks
N local worker processes, each pinned to its own
``--xla_force_host_platform_device_count`` virtual-CPU backend, wired
together with the same ``GORDO_*`` env contract — so
``jax.distributed.initialize`` runs for real across process boundaries
(coordination service, heartbeats, barriers), which is strictly more
faithful than the single-process ``dryrun_multichip`` device simulation.
"""

from __future__ import annotations

import logging
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from gordo_tpu.distributed.runtime import (
    ENV_BARRIER_TIMEOUT,
    ENV_COORDINATOR,
    ENV_LOCAL_DEVICES,
    ENV_NUM_PROCESSES,
    ENV_PROCESS_ID,
)

logger = logging.getLogger(__name__)


def pick_free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (bind-to-0 then close; the tiny race
    window is fine for a dev-box dryrun)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def worker_env(
    process_id: int,
    num_processes: int,
    coordinator: str,
    local_devices: int = 2,
    barrier_timeout: Optional[float] = None,
    base_env: Optional[Dict[str, str]] = None,
    compile_cache_dir: Optional[str] = None,
) -> Dict[str, str]:
    """Environment for one simulated worker: the ``GORDO_*`` multi-host
    contract plus a CPU backend with ``local_devices`` virtual devices
    (set BEFORE the child's jax initializes — the whole reason launching
    is process-granular).

    ``compile_cache_dir``: point every worker's persistent XLA
    compilation cache (``JAX_COMPILATION_CACHE_DIR``) at one shared path,
    so the N forked processes compile each fleet program ONCE between
    them instead of N times — the same wiring the generated multi-host
    Indexed Job gets from its shared cache volume.
    """
    env = dict(os.environ if base_env is None else base_env)
    env[ENV_COORDINATOR] = coordinator
    env[ENV_NUM_PROCESSES] = str(num_processes)
    env[ENV_PROCESS_ID] = str(process_id)
    env[ENV_LOCAL_DEVICES] = str(local_devices)
    if barrier_timeout is not None:
        env[ENV_BARRIER_TIMEOUT] = str(barrier_timeout)
    if compile_cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir
    env["JAX_PLATFORMS"] = "cpu"
    # replace (not append) any inherited device-count flag: each worker
    # must see exactly its own count
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={local_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def launch_workers(
    argv: Sequence[str],
    num_processes: int,
    coordinator: Optional[str] = None,
    local_devices: int = 2,
    barrier_timeout: Optional[float] = None,
    stdout_dir: Optional[str] = None,
    compile_cache_dir: Optional[str] = None,
) -> List[subprocess.Popen]:
    """Fork ``num_processes`` copies of ``argv`` wired as one multi-host
    job.  Returns the live Popen list (index == process_id).

    ``stdout_dir``: when given, worker i's combined output streams to
    ``worker-i.log`` there (the dryrun tails these on failure); otherwise
    workers inherit this process's stdio.
    """
    coordinator = coordinator or f"127.0.0.1:{pick_free_port()}"
    procs: List[subprocess.Popen] = []
    for pid in range(num_processes):
        env = worker_env(
            pid, num_processes, coordinator,
            local_devices=local_devices, barrier_timeout=barrier_timeout,
            compile_cache_dir=compile_cache_dir,
        )
        if stdout_dir:
            os.makedirs(stdout_dir, exist_ok=True)
            out = open(os.path.join(stdout_dir, f"worker-{pid}.log"), "wb")
        else:
            out = None
        procs.append(
            subprocess.Popen(
                list(argv),
                env=env,
                stdout=out,
                stderr=subprocess.STDOUT if out else None,
            )
        )
    return procs


def wait_all(
    procs: Sequence[subprocess.Popen], timeout: float = 600.0
) -> List[int]:
    """Wait for every worker; on deadline, kill stragglers (rc -9).

    Returns per-worker exit codes.  Callers decide what codes mean —
    the dryrun treats :data:`~gordo_tpu.distributed.partition.
    EXIT_SHARD_RESUMABLE` as the expected survivor outcome of a killed
    peer."""
    deadline = time.time() + timeout
    codes: List[int] = []
    for p in procs:
        remaining = max(0.0, deadline - time.time())
        try:
            codes.append(p.wait(timeout=remaining))
        except subprocess.TimeoutExpired:
            logger.error("worker pid=%s overran the deadline; killing", p.pid)
            p.kill()
            codes.append(p.wait())
    return codes


def python_argv(*args: str) -> List[str]:
    """``[sys.executable, *args]`` — the interpreter the launcher itself
    runs under, so venvs survive the fork."""
    return [sys.executable, *args]
