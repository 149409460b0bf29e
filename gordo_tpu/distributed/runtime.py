"""Coordinator/worker bring-up around ``jax.distributed.initialize``.

One :class:`DistributedRuntime` per worker process.  Process 0 hosts the
coordination service; every process connects to it, after which
``jax.devices()`` is the GLOBAL device list and the canonical
``("models", "data")`` mesh spans hosts (``global_mesh``).  Barriers ride
the coordination service's own ``wait_at_barrier`` — a real distributed
barrier with a timeout, which is also the worker-death detector: a killed
peer stops heartbeating, every surviving process's barrier raises
:class:`BarrierTimeout` (the coordination service names the dead task in
the error), and the caller exits with the resumable per-shard code
instead of hanging the slice.

Configuration comes from either the CLI spec ``coordinator:port,N,pid``
(:func:`parse_multihost_spec`) or the env equivalents
``GORDO_COORDINATOR`` / ``GORDO_NUM_PROCESSES`` / ``GORDO_PROCESS_ID``
(:meth:`DistributedConfig.from_env`) — the latter is what the generated
Indexed-Job manifest wires up (``workflow/generator.py``).

Hazard notes (both reproduced in-container, see scripts/multihost_dryrun.py):

- ``jax.distributed.shutdown()`` SIGABRTs when a peer already died; the
  resumable exit path must therefore use ``os._exit`` and NEVER attempt
  the clean shutdown (:meth:`DistributedRuntime.shutdown` guards this).
- On simulated CPU hosts the per-process virtual device count must be in
  ``XLA_FLAGS`` BEFORE jax initializes a backend, so ``ensure_env`` runs
  first and raises if the backend already exists with the wrong count.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

from gordo_tpu import faults, telemetry

logger = logging.getLogger(__name__)

# -- telemetry instruments (docs/observability.md) --------------------------
_BARRIER_WAIT_SECONDS = telemetry.histogram(
    "gordo_barrier_wait_seconds",
    "Time this process spent waiting at cross-process barriers, by name",
    labels=("barrier",),
)
_BARRIER_TIMEOUTS_TOTAL = telemetry.counter(
    "gordo_barrier_timeouts_total",
    "Barriers that expired (a peer is dead or wedged), by name",
    labels=("barrier",),
)

#: default barrier timeout: generous enough for a straggler host's XLA
#: compile skew, far below a wedged-slice babysitting interval
DEFAULT_BARRIER_TIMEOUT_SECONDS = 600.0

ENV_COORDINATOR = "GORDO_COORDINATOR"
ENV_NUM_PROCESSES = "GORDO_NUM_PROCESSES"
ENV_PROCESS_ID = "GORDO_PROCESS_ID"
ENV_LOCAL_DEVICES = "GORDO_LOCAL_DEVICES"
ENV_BARRIER_TIMEOUT = "GORDO_BARRIER_TIMEOUT"


class BarrierTimeout(RuntimeError):
    """A cross-process barrier expired — some peer is dead or wedged."""


@dataclass
class DistributedConfig:
    """One process's view of the multi-host job."""

    coordinator: str  #: ``host:port`` of process 0's coordination service
    num_processes: int
    process_id: int
    #: simulated hosts only: virtual CPU devices per process (sets
    #: ``--xla_force_host_platform_device_count``); None on real TPU hosts
    local_device_count: Optional[int] = None
    barrier_timeout: float = DEFAULT_BARRIER_TIMEOUT_SECONDS

    def __post_init__(self):
        if ":" not in self.coordinator:
            raise ValueError(
                f"coordinator must be host:port, got {self.coordinator!r}"
            )
        if self.num_processes < 1:
            raise ValueError(f"num_processes must be >= 1, got {self.num_processes}")
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError(
                f"process_id {self.process_id} outside [0, {self.num_processes})"
            )

    @classmethod
    def from_env(cls, environ=None) -> Optional["DistributedConfig"]:
        """Build from ``GORDO_*`` env vars; None when not a multi-host job
        (no ``GORDO_COORDINATOR``)."""
        env = os.environ if environ is None else environ
        coordinator = env.get(ENV_COORDINATOR)
        if not coordinator:
            return None
        missing = [
            name for name in (ENV_NUM_PROCESSES, ENV_PROCESS_ID)
            if not env.get(name)
        ]
        if missing:
            raise ValueError(
                f"{ENV_COORDINATOR} is set but {missing} are not — a "
                "multi-host worker needs all three"
            )
        local = env.get(ENV_LOCAL_DEVICES)
        timeout = env.get(ENV_BARRIER_TIMEOUT)
        return cls(
            coordinator=coordinator,
            num_processes=int(env[ENV_NUM_PROCESSES]),
            process_id=int(env[ENV_PROCESS_ID]),
            local_device_count=int(local) if local else None,
            barrier_timeout=(
                float(timeout) if timeout else DEFAULT_BARRIER_TIMEOUT_SECONDS
            ),
        )


def parse_multihost_spec(spec: str) -> DistributedConfig:
    """Parse the CLI form ``coordinator:port,N,pid``.

    Example: ``--multihost 10.0.0.2:8476,16,3`` — 16 processes, this one
    is process 3, process 0 serves the coordination service on port 8476.
    """
    parts = [p.strip() for p in spec.split(",")]
    if len(parts) != 3:
        raise ValueError(
            f"multihost spec must be 'coordinator:port,N,pid', got {spec!r}"
        )
    try:
        n, pid = int(parts[1]), int(parts[2])
    except ValueError as exc:
        raise ValueError(
            f"multihost spec N and pid must be integers, got {spec!r}"
        ) from exc
    return DistributedConfig(coordinator=parts[0], num_processes=n, process_id=pid)


class DistributedRuntime:
    """Lifecycle owner for one worker process of a multi-host job.

    Usage::

        runtime = DistributedRuntime(config)
        runtime.ensure_env()     # BEFORE any jax import touches a backend
        runtime.initialize()     # jax.distributed + device checks
        mesh = runtime.global_mesh()           # "models" axis spans hosts
        runtime.barrier("pre-build")
        ...                       # build this process's shard
        runtime.barrier("post-build")          # raises BarrierTimeout on
        runtime.shutdown()                     # peer death -> resumable exit
    """

    def __init__(self, config: DistributedConfig):
        self.config = config
        self.initialized = False
        self._barrier_failed = False

    # -- environment ---------------------------------------------------------
    def ensure_env(self) -> None:
        """Pin the simulated-host env BEFORE jax backend init.

        No-op on real hosts (``local_device_count`` unset).  On simulated
        hosts, sets ``--xla_force_host_platform_device_count`` so each
        forked process contributes that many virtual CPU devices to the
        global mesh.  The flag is dead once a backend is up;
        :meth:`initialize` checks the count it actually got."""
        n = self.config.local_device_count
        if n is None:
            return
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()

    # -- bring-up ------------------------------------------------------------
    def initialize(self) -> None:
        """``jax.distributed.initialize`` + post-init sanity checks."""
        self.ensure_env()
        import jax

        cfg = self.config
        if cfg.local_device_count is not None or (
            os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
        ):
            # simulated hosts: XLA:CPU refuses multi-process computations
            # unless the gloo CPU-collectives backend is selected (must
            # happen before backend init; reproduced in-container)
            try:
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo"
                )
            except Exception:  # jax without the option: surfaced at jit time
                logger.warning(
                    "could not enable gloo CPU collectives; cross-process "
                    "CPU programs may be refused by XLA"
                )
        logger.info(
            "multihost init: process %d/%d, coordinator %s",
            cfg.process_id, cfg.num_processes, cfg.coordinator,
        )
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
        )
        if jax.process_count() != cfg.num_processes:
            raise RuntimeError(
                f"jax sees {jax.process_count()} processes, config says "
                f"{cfg.num_processes}"
            )
        if jax.process_index() != cfg.process_id:
            raise RuntimeError(
                f"jax assigned process_index {jax.process_index()}, config "
                f"says {cfg.process_id}"
            )
        n = cfg.local_device_count
        if n is not None and jax.local_device_count() != n:
            # a backend that was up (or an XLA_FLAGS that already named a
            # count) before ensure_env ran: the request never took effect
            raise RuntimeError(
                f"jax backend initialized with {jax.local_device_count()} "
                f"local devices, not the {n} the config asks for; set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n} in "
                "the worker's environment before jax touches a backend"
            )
        self.initialized = True
        logger.info(
            "multihost init ok: %d global devices (%d local) across %d "
            "processes",
            len(jax.devices()), len(jax.local_devices()), jax.process_count(),
        )

    # -- meshes --------------------------------------------------------------
    def global_mesh(self, data_parallel: int = 1):
        """The canonical mesh over ALL processes' devices (``"models"``
        axis spans hosts)."""
        from gordo_tpu.mesh import global_fleet_mesh

        return global_fleet_mesh(data_parallel=data_parallel)

    def local_mesh(self, data_parallel: int = 1):
        """Mesh over THIS process's devices only — what the per-shard
        fleet build runs on (each process trains its own machine shard;
        the global mesh carries bring-up validation and any future
        cross-host program).  None on a single local device, matching the
        single-host CLI's behaviour."""
        import jax

        from gordo_tpu.mesh import fleet_mesh

        local = jax.local_devices()
        if len(local) <= 1:
            return None
        return fleet_mesh(local, data_parallel=data_parallel)

    def validate_global_mesh(self) -> int:
        """Run one tiny sharded program over the process-spanning mesh and
        check every process's devices actually participated.  Returns the
        global device count.  This is the 'real cross-process init'
        evidence the dryrun asserts on — initialize() succeeding only
        proves the coordination handshake, not that XLA can place a
        program across the process boundary."""
        import jax
        import numpy as np

        from gordo_tpu.mesh import model_sharding

        mesh = self.global_mesh()  # data axis = 1: models axis is every device
        flat = list(mesh.devices.reshape(-1))
        n = len(flat)
        full = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        # this process's rows, derived from mesh positions (never device ids)
        mine = [
            i for i, d in enumerate(flat)
            if d.process_index == jax.process_index()
        ]
        sharding = model_sharding(mesh)
        x = jax.make_array_from_process_local_data(
            sharding, full[mine], full.shape
        )
        from gordo_tpu import compile as compile_plane

        y = compile_plane.jit(
            lambda a: a * 2.0, name="runtime.mesh_check",
            out_shardings=sharding,
        )(x)
        # every process checks ITS addressable shards came back right
        for shard in y.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(shard.data), full[shard.index] * 2.0
            )
        return n

    # -- coordination --------------------------------------------------------
    def barrier(self, name: str, timeout: Optional[float] = None) -> None:
        """Block until every process reaches ``barrier(name)``.

        Rides the coordination service (no device collectives — works
        mid-build regardless of what the devices are doing).  Raises
        :class:`BarrierTimeout` after ``timeout`` seconds; a dead peer is
        the usual cause and the service names it in the message."""
        if not self.initialized:
            raise RuntimeError("barrier() before initialize()")
        timeout = self.config.barrier_timeout if timeout is None else timeout
        from jax._src import distributed as jax_distributed

        client = jax_distributed.global_state.client
        t0 = time.monotonic()
        if faults.enabled():
            # chaos seam: an injected peer loss behaves exactly like the
            # real thing — the barrier "expires", the timeout is counted,
            # and the caller takes the resumable-exit path
            try:
                faults.check(
                    "barrier.wait", barrier=name,
                    process_id=self.config.process_id,
                )
            except faults.InjectedFault as exc:
                self._note_barrier_timeout(name, timeout, t0)
                raise BarrierTimeout(
                    f"barrier {name!r}: injected peer loss "
                    f"(process {self.config.process_id}/"
                    f"{self.config.num_processes}): {exc}"
                ) from exc
        try:
            if client is not None and hasattr(client, "wait_at_barrier"):
                client.wait_at_barrier(
                    f"gordo:{name}", timeout_in_ms=int(timeout * 1000)
                )
            else:  # pragma: no cover - jax without the coordination client
                self._sync_with_thread_timeout(name, timeout)
        except BarrierTimeout:
            self._note_barrier_timeout(name, timeout, t0)
            raise
        except Exception as exc:
            self._note_barrier_timeout(name, timeout, t0)
            raise BarrierTimeout(
                f"barrier {name!r} failed after <= {timeout:.0f}s "
                f"(process {self.config.process_id}/"
                f"{self.config.num_processes}): {exc}"
            ) from exc
        _BARRIER_WAIT_SECONDS.observe(time.monotonic() - t0, name)

    def _note_barrier_timeout(
        self, name: str, timeout: float, t0: float
    ) -> None:
        """Count + one structured line per expired barrier (previously the
        only trace was the raised exception's message)."""
        self._barrier_failed = True
        _BARRIER_WAIT_SECONDS.observe(time.monotonic() - t0, name)
        _BARRIER_TIMEOUTS_TOTAL.inc(1.0, name)
        telemetry.log_event(
            logger, "barrier_timeout",
            barrier=name,
            timeout_s=round(timeout, 1),
            process_id=self.config.process_id,
            num_processes=self.config.num_processes,
        )

    @staticmethod
    def _sync_with_thread_timeout(name: str, timeout: float) -> None:
        """Fallback barrier: ``sync_global_devices`` on a watchdog thread.
        The sync has no native timeout, so a join-timeout abandons the
        (daemon) thread and raises — the abandoned thread blocks forever,
        which is fine because the caller is about to ``os._exit``."""
        from jax.experimental import multihost_utils

        done = threading.Event()
        error: list = []

        def _run():
            try:
                multihost_utils.sync_global_devices(f"gordo:{name}")
            except Exception as exc:  # surfaced below
                error.append(exc)
            finally:
                done.set()

        t = threading.Thread(target=_run, name=f"gordo-barrier-{name}", daemon=True)
        t.start()
        if not done.wait(timeout):
            raise BarrierTimeout(
                f"barrier {name!r} timed out after {timeout:.0f}s"
            )
        if error:
            raise BarrierTimeout(
                f"barrier {name!r} failed: {error[0]}"
            ) from error[0]

    # -- teardown ------------------------------------------------------------
    def shutdown(self) -> None:
        """Clean coordination-service disconnect.

        MUST NOT run after a failed barrier: ``jax.distributed.shutdown``
        SIGABRTs when a peer is already dead (reproduced in-container) —
        the resumable exit path uses ``os._exit`` instead, and this method
        turns into a logged no-op."""
        if not self.initialized:
            return
        if self._barrier_failed:
            logger.warning(
                "skipping jax.distributed.shutdown() after barrier failure "
                "(it aborts when a peer is dead); exiting without clean "
                "disconnect"
            )
            return
        import jax

        jax.distributed.shutdown()
        self.initialized = False
