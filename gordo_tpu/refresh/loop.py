"""The refresh driver: poll fleet health, select drifting machines under
hysteresis, warm-start rebuild exactly those, wait for the generation to
go live.

Reference pattern: Podracer's continuously-running actor/learner split
(PAPERS.md) — serving telemetry feeds training, training feeds serving,
forever.  The cost model is the point: one cycle's work scales with the
number of DRIFTED machines, never with fleet size.

Interfaces only (the lint-enforced plane boundary):

- health IN: the shard-keyed rollup JSONL files under the artifact dir
  (``telemetry.read_rollups``) or a watchman/server ``/fleet-health``
  HTTP endpoint — never scorer internals;
- models OUT: ``builder.build_project(warm_start=True)``, which
  publishes through ``artifacts.delta_write`` + ``stamp_generation``;
- liveness: ``client.wait_for_generation`` — the same generation
  handshake any external consumer uses.

Selection is hysteretic so one noisy scoring window can't thrash
rebuilds: a machine must be observed ``status=drifting`` on K
CONSECUTIVE health polls (``GORDO_REFRESH_HYSTERESIS``) and sit outside
its per-machine cooldown (``GORDO_REFRESH_COOLDOWN_SECONDS``) before it
is rebuilt.  Selector state persists under
``<output_dir>/.gordo-refresh/state.json`` so ``gordo refresh --once``
(the CronJob face) accumulates streaks across invocations.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Any, Dict, List, Optional, Sequence

from gordo_tpu import artifacts, telemetry

logger = logging.getLogger(__name__)

# -- knobs (docs/configuration.md "Incremental refresh") --------------------
ENV_HYSTERESIS = "GORDO_REFRESH_HYSTERESIS"
DEFAULT_HYSTERESIS = 2
ENV_COOLDOWN_SECONDS = "GORDO_REFRESH_COOLDOWN_SECONDS"
DEFAULT_COOLDOWN_SECONDS = 900.0
ENV_INTERVAL = "GORDO_REFRESH_INTERVAL"
DEFAULT_INTERVAL = 300.0

#: selector state under the artifact dir — file-per-project, like the
#: telemetry snapshots and health rollups it sits next to
STATE_DIR = ".gordo-refresh"
STATE_FILE = "state.json"

# -- telemetry instruments (docs/observability.md) --------------------------
_CYCLES_TOTAL = telemetry.counter(
    "gordo_refresh_cycles_total",
    "Refresh cycles run, by outcome",
    labels=("outcome",),  # rebuilt | idle | no-health | failed
)
_MACHINES_TOTAL = telemetry.counter(
    "gordo_refresh_machines_total",
    "Machines handled by refresh rebuilds, by path",
    labels=("path",),  # warm | fallback | failed
)
_DRIFT_TO_LIVE_SECONDS = telemetry.histogram(
    "gordo_refresh_drift_to_live_seconds",
    "End-to-end seconds from drift selection to the rebuilt generation "
    "being live (build + publish + reload confirmation)",
    buckets=(0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
             600.0, 1800.0),
)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def state_path(output_dir: str) -> str:
    return os.path.join(output_dir, STATE_DIR, STATE_FILE)


class DriftSelector:
    """Hysteretic drift selection with per-machine cooldown.

    Pure bookkeeping over health docs — time arrives as an argument, so
    the unit tests never sleep.  ``observe`` returns the machines whose
    drifting streak reached the hysteresis threshold AND whose last
    rebuild is outside the cooldown window; ``mark_rebuilt`` resets the
    streak and starts the cooldown."""

    def __init__(
        self,
        hysteresis: Optional[int] = None,
        cooldown_seconds: Optional[float] = None,
    ):
        self.hysteresis = max(1, (
            _env_int(ENV_HYSTERESIS, DEFAULT_HYSTERESIS)
            if hysteresis is None else int(hysteresis)
        ))
        self.cooldown_seconds = max(0.0, (
            _env_float(ENV_COOLDOWN_SECONDS, DEFAULT_COOLDOWN_SECONDS)
            if cooldown_seconds is None else float(cooldown_seconds)
        ))
        #: {machine: {"streak": int, "last_rebuild": float|None}}
        self._state: Dict[str, Dict[str, Any]] = {}

    def _entry(self, name: str) -> Dict[str, Any]:
        return self._state.setdefault(
            name, {"streak": 0, "last_rebuild": None}
        )

    def observe(self, doc: Dict[str, Any], now: float) -> List[str]:
        """Fold one health doc into the streaks; return the machines
        selected for rebuild.  Machines absent from the doc keep their
        streak (a silent shard is not evidence the drift cleared)."""
        selected: List[str] = []
        for name, entry in (doc.get("machines") or {}).items():
            state = self._entry(name)
            if entry.get("status") == "drifting":
                state["streak"] = int(state["streak"]) + 1
            else:
                state["streak"] = 0
        for name, state in self._state.items():
            if state["streak"] < self.hysteresis:
                continue
            last = state.get("last_rebuild")
            if last is not None and now - float(last) < self.cooldown_seconds:
                continue
            selected.append(name)
        return sorted(selected)

    def mark_rebuilt(self, names: Sequence[str], now: float) -> None:
        for name in names:
            state = self._entry(name)
            state["streak"] = 0
            state["last_rebuild"] = float(now)

    # -- persistence (the --once / CronJob face needs streaks to survive
    # -- process exits; atomic tmp+rename like every other sidecar) ---------
    def to_doc(self) -> Dict[str, Any]:
        return {
            "gordo-refresh-state": 1,
            "hysteresis": self.hysteresis,
            "cooldown-seconds": self.cooldown_seconds,
            "machines": {n: dict(s) for n, s in self._state.items()},
        }

    def save(self, path: str) -> None:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(self.to_doc(), fh, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            logger.exception("refresh state save failed: %s", path)

    @classmethod
    def load(
        cls,
        path: str,
        hysteresis: Optional[int] = None,
        cooldown_seconds: Optional[float] = None,
    ) -> "DriftSelector":
        """A selector seeded from ``path`` when it exists (torn/corrupt
        files start fresh — hysteresis only delays a rebuild, never
        loses one)."""
        selector = cls(
            hysteresis=hysteresis, cooldown_seconds=cooldown_seconds
        )
        try:
            with open(path) as fh:
                doc = json.load(fh)
            for name, state in (doc.get("machines") or {}).items():
                selector._state[name] = {
                    "streak": int(state.get("streak", 0)),
                    "last_rebuild": state.get("last_rebuild"),
                }
        except (OSError, ValueError):
            pass
        return selector


@dataclasses.dataclass
class RefreshConfig:
    """One refresh deployment's wiring: the machine configs it may
    rebuild, where artifacts live, and which health surface it polls."""

    machines: Sequence[Any]
    output_dir: str
    model_register_dir: Optional[str] = None
    project: str = "project"
    #: HTTP health surface (watchman or server base URL); None polls the
    #: rollup files under ``output_dir`` instead — no HTTP needed
    health_url: Optional[str] = None
    #: server base URL to confirm the generation went live on (via the
    #: client's wait_for_generation handshake); None skips confirmation
    server_url: Optional[str] = None
    hysteresis: Optional[int] = None
    cooldown_seconds: Optional[float] = None
    wait_timeout: float = 120.0
    #: extra build_project kwargs (mesh, max_bucket_size, ...)
    build_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)


def read_health(cfg: RefreshConfig) -> Optional[Dict[str, Any]]:
    """The current fleet-health doc over a public interface: HTTP when
    ``cfg.health_url`` is set, else the rollup files under the artifact
    dir.  None when no health is observable (nothing to select from)."""
    if not cfg.health_url:
        return telemetry.read_rollups(cfg.output_dir)
    import urllib.request

    base = cfg.health_url.rstrip("/")
    candidates = [
        f"{base}/gordo/v0/{cfg.project}/fleet-health",
        f"{base}/fleet-health",  # watchman surface
    ]
    last_err: Optional[Exception] = None
    for candidate in candidates:
        try:
            with urllib.request.urlopen(candidate, timeout=30) as resp:
                doc = json.loads(resp.read().decode())
            if doc.get("gordo-fleet-health") or doc.get("machines"):
                return doc
        except Exception as exc:  # 404 on one surface, conn errors
            last_err = exc
    logger.warning(
        "fleet-health fetch failed from %s: %s", candidates, last_err
    )
    return None


def _wait_live(cfg: RefreshConfig, generation: int) -> Optional[Dict]:
    """Block until every serving replica reports ``generation`` (the
    client's public handshake).  Returns the per-replica map, or None on
    timeout — the rebuild is still published; confirmation is what
    failed, and the summary says so."""
    from gordo_tpu.client import Client

    client = Client(
        project=cfg.project, base_url=cfg.server_url,
        timeout=cfg.wait_timeout,
    )
    try:
        return client.wait_for_generation(
            generation, timeout=cfg.wait_timeout
        )
    except TimeoutError as exc:
        logger.warning("generation %d not confirmed live: %s",
                       generation, exc)
        return None


def refresh_once(
    cfg: RefreshConfig,
    selector: Optional[DriftSelector] = None,
    now: Optional[float] = None,
) -> Dict[str, Any]:
    """One refresh cycle: poll → select → warm rebuild → publish → wait
    for the flip.  Returns a summary dict (the CLI prints it as JSON).

    Pass a :class:`DriftSelector` to keep streak state in-process (the
    ``--interval`` loop); without one, state loads from and saves to
    ``<output_dir>/.gordo-refresh/state.json`` so repeated ``--once``
    invocations (the CronJob) accumulate hysteresis correctly."""
    from gordo_tpu.builder import build_project

    t_cycle = time.time()
    now = t_cycle if now is None else now
    path = state_path(cfg.output_dir)
    if selector is None:
        selector = DriftSelector.load(
            path, hysteresis=cfg.hysteresis,
            cooldown_seconds=cfg.cooldown_seconds,
        )

    doc = read_health(cfg)
    if doc is None:
        _CYCLES_TOTAL.inc(1.0, "no-health")
        return {"outcome": "no-health", "selected": []}

    selected = selector.observe(doc, now)
    by_name = {m.name: m for m in cfg.machines}
    subset = [by_name[n] for n in selected if n in by_name]
    unknown = [n for n in selected if n not in by_name]
    if unknown:
        logger.warning(
            "drifting machine(s) not in this refresh deployment's "
            "config: %s", unknown,
        )
    drifting = sorted(
        n for n, e in (doc.get("machines") or {}).items()
        if e.get("status") == "drifting"
    )
    if not subset:
        selector.save(path)
        _CYCLES_TOTAL.inc(1.0, "idle")
        return {
            "outcome": "idle", "selected": [], "drifting": drifting,
            "unknown": unknown,
        }

    logger.info(
        "refresh cycle: rebuilding %d drifted machine(s): %s",
        len(subset), [m.name for m in subset],
    )
    result = build_project(
        subset,
        cfg.output_dir,
        model_register_dir=cfg.model_register_dir,
        warm_start=True,
        **cfg.build_kwargs,
    )
    rebuilt = sorted(result.fleet_built + result.single_built)
    _MACHINES_TOTAL.inc(float(len(result.warm_started)), "warm")
    fallback_built = [n for n in result.warm_fallbacks if n in set(rebuilt)]
    _MACHINES_TOTAL.inc(float(len(fallback_built)), "fallback")
    _MACHINES_TOTAL.inc(float(len(result.failed)), "failed")

    generation = result.generation
    if generation is None:
        generation = artifacts.read_generation(cfg.output_dir)
    confirmed = None
    if cfg.server_url and generation:
        confirmed = _wait_live(cfg, int(generation))

    latency = time.time() - t_cycle
    if rebuilt:
        # drift → build → publish → (confirmed) live, end to end
        _DRIFT_TO_LIVE_SECONDS.observe(latency)
    selector.mark_rebuilt(rebuilt, time.time() if now is t_cycle else now)
    selector.save(path)
    _CYCLES_TOTAL.inc(1.0, "failed" if result.failed else "rebuilt")

    summary = {
        "outcome": "failed" if result.failed else "rebuilt",
        "selected": [m.name for m in subset],
        "drifting": drifting,
        "rebuilt": rebuilt,
        "warm_started": sorted(result.warm_started),
        "warm_fallbacks": dict(result.warm_fallbacks),
        "failed": dict(result.failed),
        "generation": int(generation) if generation else None,
        "live_confirmed": confirmed is not None,
        "seconds": latency,
        # the refresh rides the builder's ingest plane (warm_start chunks
        # load through it too) — surface the fetch-dedup accounting
        "ingest": dict(result.ingest),
    }
    return summary


def run_refresh(
    cfg: RefreshConfig,
    interval: Optional[float] = None,
    max_cycles: Optional[int] = None,
    sleep=time.sleep,
) -> List[Dict[str, Any]]:
    """The continuous loop: ``refresh_once`` every ``interval`` seconds
    (default ``GORDO_REFRESH_INTERVAL``), sharing one selector so
    hysteresis streaks span cycles without touching disk between them.
    ``max_cycles`` bounds the loop (tests; ``--once`` uses 1)."""
    interval = (
        _env_float(ENV_INTERVAL, DEFAULT_INTERVAL)
        if interval is None else float(interval)
    )
    selector = DriftSelector.load(
        state_path(cfg.output_dir), hysteresis=cfg.hysteresis,
        cooldown_seconds=cfg.cooldown_seconds,
    )
    summaries: List[Dict[str, Any]] = []
    cycle = 0
    while True:
        summaries.append(refresh_once(cfg, selector=selector))
        cycle += 1
        if max_cycles is not None and cycle >= max_cycles:
            return summaries
        sleep(interval)
