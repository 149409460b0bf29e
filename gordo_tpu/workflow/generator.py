"""Workflow generation: project config → orchestration documents.

Reference equivalent: ``gordo_components/workflow/workflow_generator/
workflow_generator.py`` + ``resources/argo-workflow.yml.template`` — a
Jinja2-rendered Argo ``Workflow`` fanning out **one model-builder pod per
machine**, then per-machine ml-server Deployments/Services with Ambassador
route annotations and a watchman Deployment.

TPU-native redesign: the unit of training orchestration is no longer one
pod per machine — it is ONE builder job per project that runs the fleet
engine (``gordo_tpu.builder.fleet_build``) on a TPU slice, training whole
buckets of machines as single sharded XLA programs.  So this generator
emits:

- a **build plan**: machines bucketed by fleet signature (model-config
  shape x feature width), with cache keys — the document the fleet
  builder executes and the thing tests assert on (the reference's
  per-machine DAG assertions map to per-bucket assertions here);
- **kubernetes manifests** for deploy parity: builder Job (TPU nodepool),
  one ml-server Deployment/Service hosting every machine, watchman
  Deployment/Service, and per-machine Ambassador-style route Mappings so
  the reference's per-machine URLs keep working.

Documents are built as Python dicts and serialized with ``yaml.dump`` —
no string templating to escape-bug.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

import yaml

from gordo_tpu.builder.build_model import calculate_model_key
from gordo_tpu.ingest.fingerprint import dataset_fingerprint
from gordo_tpu.workflow.config import Machine, NormalizedConfig

API_PREFIX = "/gordo/v0"
DEFAULT_IMAGE = "gordo-tpu"
DEFAULT_SERVER_PORT = 5555
DEFAULT_WATCHMAN_PORT = 5556
#: jax.distributed coordination-service port on process 0 of a multi-host
#: builder Job (the conventional jax coordinator port)
DEFAULT_COORDINATOR_PORT = 8476
#: where the shared persistent XLA compilation cache mounts in builder and
#: server pods — one PVC per project, so a restarted server (or any worker
#: of a --multihost Indexed Job) loads executables its peers already
#: compiled instead of re-paying every cold compile
COMPILE_CACHE_MOUNT = "/compile-cache"


def unique_tags(machines: List[Machine]) -> List[str]:
    """Sorted distinct tag names across the project (reference:
    ``workflow unique-tags``)."""
    tags = set()
    for machine in machines:
        for t in machine.dataset.get("tag_list") or machine.dataset.get("tags") or []:
            tags.add(t["name"] if isinstance(t, dict) else str(t))
    return sorted(tags)


def _fleet_signature(machine: Machine) -> str:
    """Static bucketing signature: machines whose model-config (minus
    per-machine irrelevancies) and tag width match can train as one
    stacked XLA program.  A cheap host-side proxy for
    ``parallel.anomaly.analyze_definition`` — the builder re-verifies with
    a real prototype at run time and falls back per machine if needed."""
    n_tags = len(
        machine.dataset.get("tag_list") or machine.dataset.get("tags") or []
    )
    return json.dumps({"model": machine.model, "n_tags": n_tags}, sort_keys=True)


#: measured per-distinct-row-count XLA compile cost of the fleet CV+fit
#: program (docs/perf.md "Ragged-length fleets": 218.9s cold for 16
#: lengths ≈ 13.7s each, CPU jax; TPU compiles are comparable)
COMPILE_SECONDS_PER_LENGTH = 13.7


def _ragged_length_estimate(members: List[Machine]) -> int:
    """Config-level upper estimate of DISTINCT train-row-counts in one
    bucket — each distinct length compiles its own fleet program.

    Machines without a row filter share a length whenever their
    (train window, resolution) agree; a machine WITH a ``row_filter``
    drops an unpredictable number of rows, so each one must be assumed a
    distinct length (that unpredictability is exactly why raggedness is
    the production norm)."""
    windows = set()
    filtered = 0
    for m in members:
        ds = m.dataset
        if ds.get("row_filter"):
            filtered += 1
        else:
            windows.add((
                str(ds.get("train_start_date")),
                str(ds.get("train_end_date")),
                str(ds.get("resolution")),
            ))
    return len(windows) + filtered


def build_plan(
    config: NormalizedConfig,
    max_bucket_size: int = 512,
    mesh: Optional[Dict[str, int]] = None,
    align_lengths: Optional[int] = None,
    pad_lengths: Optional[int] = None,
) -> Dict[str, Any]:
    """Bucketed fleet build plan for the project.

    ``align_lengths`` / ``pad_lengths`` must match the value the build
    will run with: they are part of fleet-built machines' cache identity,
    so plan keys computed without them would never match the registry
    entries an aligned/padded ``build_project`` writes.  (Like the
    bucketing itself, the keys are the fleet-path prediction: a machine
    the builder demotes to the single path at run time keys without the
    component there.)

    When NEITHER is set and the configs predict multiple distinct train
    lengths per bucket, the plan carries a ``ragged_compile_warning``
    with the estimated per-distinct-length compile bill — explicit, not
    silent: a 1000-machine filtered project that forgets the flag would
    otherwise discover the cost an hour into its build."""
    if align_lengths and pad_lengths:
        raise ValueError(
            "align_lengths and pad_lengths are mutually exclusive"
        )
    key_extra = None
    if align_lengths:
        key_extra = {"align_lengths": align_lengths}
    elif pad_lengths:
        key_extra = {"pad_lengths": pad_lengths}
    buckets: Dict[str, List[Machine]] = {}
    for machine in config.machines:
        buckets.setdefault(_fleet_signature(machine), []).append(machine)

    plan_buckets = []
    for i, (_, members) in enumerate(sorted(buckets.items())):
        for start in range(0, len(members), max_bucket_size):
            chunk = members[start : start + max_bucket_size]
            plan_buckets.append(
                {
                    "bucket": f"bucket-{i:03d}-{start // max_bucket_size:03d}",
                    "n_machines": len(chunk),
                    "machines": [m.name for m in chunk],
                    "model_config": chunk[0].model,
                    "cache_keys": {
                        m.name: calculate_model_key(
                            m.name, m.model, m.dataset, m.metadata,
                            extra=key_extra,
                        )
                        for m in chunk
                    },
                }
            )
    plan = {
        "project-name": config.project_name,
        "mesh": mesh or {"models": -1, "data": 1},  # -1: all available chips
        "n_machines": len(config.machines),
        "n_buckets": len(plan_buckets),
        "buckets": plan_buckets,
        # artifact volume layout: the generated builder writes format v2,
        # so the models PVC holds ~one pack per planned chunk (plus the
        # index) instead of one directory per machine
        "artifact_format": "v2",
        "artifact_packs_estimate": len(plan_buckets),
    }
    # ingest-plane projection: one provider fetch per distinct dataset
    # fingerprint (gordo_tpu/ingest/fingerprint.py) — the plan surfaces
    # the dedup the build will get, so a replicated fleet's operator
    # sees the fetch bill up front in `workflow plan`
    fingerprints = {
        dataset_fingerprint(dict(m.dataset)) for m in config.machines
    }
    n_machines = len(config.machines)
    dedup_hits = n_machines - len(fingerprints)
    plan["ingest"] = {
        "distinct_dataset_fingerprints": len(fingerprints),
        "dedup_hits": dedup_hits,
        "fetch_dedup_ratio": round(
            dedup_hits / n_machines, 4
        ) if n_machines else 0.0,
    }
    if align_lengths:
        plan["align_lengths"] = int(align_lengths)
    if pad_lengths:
        plan["pad_lengths"] = int(pad_lengths)
    if key_extra is None:
        est_lengths = sum(
            _ragged_length_estimate(members) for members in buckets.values()
        )
        extra = est_lengths - len(buckets)  # 1 compile/bucket is the floor
        if extra > 0:
            plan["ragged_compile_warning"] = {
                "estimated_distinct_lengths": est_lengths,
                "estimated_extra_compiles": extra,
                "estimated_extra_compile_seconds": round(
                    extra * COMPILE_SECONDS_PER_LENGTH, 1
                ),
                "hint": (
                    "Exact mode compiles one fleet program per distinct "
                    "train-row-count (~"
                    f"{COMPILE_SECONDS_PER_LENGTH:g}s each, measured). "
                    "Set align_lengths (truncate down, exact parity on "
                    "the truncated data) or pad_lengths (zero data loss, "
                    "padded fold geometry) to collapse them."
                ),
            }
    return plan


# ---------------------------------------------------------------------------
# kubernetes manifests
# ---------------------------------------------------------------------------

def _labels(project: str, component: str) -> Dict[str, str]:
    return {
        "app.kubernetes.io/part-of": "gordo-tpu",
        "app.kubernetes.io/instance": project,
        "app.kubernetes.io/component": component,
    }


def _scrape_annotations(port: int) -> Dict[str, str]:
    """Prometheus discovery annotations for a pod exposing ``/metrics``
    (the de-facto prometheus.io convention most cluster scrape configs
    key on).  Emitted by default on the server and watchman pod
    templates; ``--no-scrape-annotations`` opts out for clusters using
    ServiceMonitors or a different discovery scheme."""
    return {
        "prometheus.io/scrape": "true",
        "prometheus.io/port": str(port),
        "prometheus.io/path": "/metrics",
    }


def _multihost_builder_docs(
    project: str,
    image: str,
    tpu_resources: Dict[str, Any],
    num_processes: int,
    serve_dtype: Optional[str] = None,
) -> List[Dict]:
    """Indexed builder Job (one pod per process) + the headless Service
    that gives process 0 a stable coordinator DNS name.

    Env wiring is the ``GORDO_*`` contract of
    ``gordo_tpu.distributed.runtime``: every pod gets the same
    ``GORDO_COORDINATOR`` (pod 0's stable hostname) and its own
    ``GORDO_PROCESS_ID`` from the index kubernetes injects as
    ``JOB_COMPLETION_INDEX``.  ``gordo build-project`` picks the env
    contract up with no extra flags, shards the machine list
    deterministically, and barriers at the build edges — a pod that dies
    exits its peers with the resumable code, and the Job's retry
    (``backoffLimit``) re-runs into cache hits plus the dead shard's
    remainder."""
    job_name = f"gordo-builder-{project}"
    svc_name = f"gordo-builder-{project}"
    job = _builder_job(project, image, tpu_resources, serve_dtype=serve_dtype)
    spec = job["spec"]
    spec["completions"] = num_processes
    spec["parallelism"] = num_processes
    spec["completionMode"] = "Indexed"
    pod_spec = spec["template"]["spec"]
    # Indexed pods get hostname {job}-{index}; the headless subdomain
    # makes {job}-0.{svc} resolvable as the coordinator address
    pod_spec["subdomain"] = svc_name
    container = pod_spec["containers"][0]
    container["env"].extend(
        [
            {
                "name": "GORDO_COORDINATOR",
                "value": (
                    f"{job_name}-0.{svc_name}:{DEFAULT_COORDINATOR_PORT}"
                ),
            },
            {"name": "GORDO_NUM_PROCESSES", "value": str(num_processes)},
            # JOB_COMPLETION_INDEX is injected by kubernetes for Indexed
            # Jobs; dependent-env expansion turns it into the process id
            {"name": "GORDO_PROCESS_ID", "value": "$(JOB_COMPLETION_INDEX)"},
        ]
    )
    headless = {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "name": svc_name,
            "labels": _labels(project, "model-builder"),
        },
        "spec": {
            "clusterIP": "None",  # headless: per-pod DNS, no VIP
            "selector": _labels(project, "model-builder"),
            "ports": [
                {
                    "port": DEFAULT_COORDINATOR_PORT,
                    "targetPort": DEFAULT_COORDINATOR_PORT,
                }
            ],
        },
    }
    return [job, headless]


def _compile_cache_volume(project: str) -> Dict:
    return {
        "name": "compile-cache",
        "persistentVolumeClaim": {
            "claimName": f"gordo-compile-cache-{project}"
        },
    }


def _compile_cache_env() -> Dict[str, str]:
    # jax reads the variable itself; the program sets no directory in code
    return {"name": "JAX_COMPILATION_CACHE_DIR", "value": COMPILE_CACHE_MOUNT}


def _serve_dtype_env(serve_dtype: Optional[str]) -> List[Dict[str, str]]:
    """``GORDO_SERVE_DTYPE`` env entries for a pod template.  Stamped on
    BOTH the builder (so the warmup manifest records the precision and
    warmup compiles for it) and the server (so dispatch matches) — the
    serving-precision plane's one-config contract.  Validated here so a
    typo fails manifest GENERATION, not a pod at 3am."""
    if serve_dtype is None:
        return []
    from gordo_tpu.serve.precision import canonical

    return [{"name": "GORDO_SERVE_DTYPE", "value": canonical(serve_dtype)}]


def _evict_after_env() -> Dict[str, str]:
    """``GORDO_WATCHMAN_EVICT_AFTER`` for the watchman pod: a target
    replica failing this many consecutive index scrapes is marked
    ``down`` in the status doc (clients then skip it when bootstrapping
    their shard table and when choosing failover candidates).  Stamped
    explicitly (3 is also the library default) so the manifest documents
    the knob where operators tune it."""
    return {"name": "GORDO_WATCHMAN_EVICT_AFTER", "value": "3"}


def _reload_watch_env() -> Dict[str, str]:
    """``GORDO_RELOAD_WATCH_SECONDS`` for server pods: poll the artifact
    index's generation sidecar (one tiny file read off the models PVC)
    so a builder Job's generation stamp hot-reloads only the changed
    machines into the running replicas — no pod restart, no recompile.
    Stamped explicitly (even though 5 is also the library default) so
    the manifest documents the knob where operators tune it."""
    return {"name": "GORDO_RELOAD_WATCH_SECONDS", "value": "5"}


def _builder_job(
    project: str,
    image: str,
    tpu_resources: Dict[str, Any],
    serve_dtype: Optional[str] = None,
) -> Dict:
    return {
        "apiVersion": "batch/v1",
        "kind": "Job",
        "metadata": {
            "name": f"gordo-builder-{project}",
            "labels": _labels(project, "model-builder"),
        },
        "spec": {
            "backoffLimit": 3,  # idempotent: cache-hit machines skip
            "template": {
                "metadata": {"labels": _labels(project, "model-builder")},
                "spec": {
                    "restartPolicy": "OnFailure",
                    "containers": [
                        {
                            "name": "model-builder",
                            "image": image,
                            "command": ["gordo", "build-project"],
                            "args": [
                                "--machine-config", "/config/project.yaml",
                                "--output-dir", "/models",
                                "--model-register-dir", "/models/.register",
                            ],
                            "env": [
                                {"name": "PROJECT_NAME", "value": project},
                                # artifact format v2 (one mmap-able pack
                                # per fleet chunk, the server's zero-copy
                                # load path) is the library default; set
                                # GORDO_ARTIFACT_FORMAT=v1 here only for
                                # tooling that needs per-machine dirs
                                # shared persistent XLA compile cache: a
                                # retried Job (and every worker of a
                                # --multihost Indexed Job, which extends
                                # this template) reuses peers' compiles
                                _compile_cache_env(),
                                *_serve_dtype_env(serve_dtype),
                            ],
                            "resources": tpu_resources,
                            "volumeMounts": [
                                {"name": "models", "mountPath": "/models"},
                                {"name": "project-config", "mountPath": "/config"},
                                {"name": "compile-cache",
                                 "mountPath": COMPILE_CACHE_MOUNT},
                            ],
                        }
                    ],
                    "volumes": [
                        {
                            "name": "models",
                            "persistentVolumeClaim": {
                                "claimName": f"gordo-models-{project}"
                            },
                        },
                        {
                            "name": "project-config",
                            "configMap": {"name": f"gordo-config-{project}"},
                        },
                        _compile_cache_volume(project),
                    ],
                },
            },
        },
    }


def _validate_cron_schedule(schedule: str) -> str:
    """Reject obviously-malformed CronJob schedules at manifest
    GENERATION (the same fail-early posture as ``_serve_dtype_env``):
    kubernetes cron is five whitespace-separated fields."""
    fields = str(schedule).split()
    if len(fields) != 5:
        raise ValueError(
            f"--refresh-cron schedule {schedule!r} is not a 5-field cron "
            f"expression (minute hour day-of-month month day-of-week), "
            f"got {len(fields)} field(s)"
        )
    allowed = set("0123456789*/,-")
    for field in fields:
        if not field or not set(field) <= allowed:
            raise ValueError(
                f"--refresh-cron schedule {schedule!r}: field {field!r} "
                f"contains characters outside [0-9*/,-]"
            )
    return " ".join(fields)


def _refresh_cronjob(
    project: str,
    image: str,
    schedule: str,
    builder_job: Dict[str, Any],
) -> Dict:
    """A ``batch/v1`` CronJob running ``gordo refresh --once`` on
    ``schedule`` — the drift-driven incremental rebuild face of the
    builder (docs/operations.md "Incremental refresh").

    The pod template mirrors the builder Job's volumes and env (models
    PVC, project-config ConfigMap, shared compile cache, GORDO_* wiring)
    so the refresh cycle sees exactly the artifacts and config the full
    build produced — refused when the builder template carries no models
    volume, because a refresh with nowhere to read the previous
    generation from (or publish the next one to) can only rebuild cold
    into the void."""
    import copy

    schedule = _validate_cron_schedule(schedule)
    builder_spec = builder_job["spec"]["template"]["spec"]
    volume_names = {v.get("name") for v in builder_spec.get("volumes", [])}
    if "models" not in volume_names:
        raise ValueError(
            "--refresh-cron requires the builder template to mount a "
            "'models' volume (the artifact dir the refresh warm-starts "
            "from and publishes to); this builder configuration has "
            f"volumes {sorted(volume_names)}"
        )
    pod_spec = copy.deepcopy(builder_spec)
    container = pod_spec["containers"][0]
    container["name"] = "model-refresh"
    container["command"] = ["gordo", "refresh"]
    container["args"] = [
        "--machine-config", "/config/project.yaml",
        "--output-dir", "/models",
        "--model-register-dir", "/models/.register",
        "--once",
    ]
    # health comes off the rollup files under /models (no HTTP from the
    # cron pod); selection knobs documented where operators tune them
    container.setdefault("env", []).extend([
        {"name": "GORDO_REFRESH_HYSTERESIS", "value": "2"},
        {"name": "GORDO_REFRESH_COOLDOWN_SECONDS", "value": "900"},
    ])
    return {
        "apiVersion": "batch/v1",
        "kind": "CronJob",
        "metadata": {
            "name": f"gordo-refresh-{project}",
            "labels": _labels(project, "model-refresh"),
        },
        "spec": {
            "schedule": schedule,
            # a slow warm rebuild must not pile up concurrent cycles
            # racing the artifact index; the selector state file makes
            # skipped runs harmless (streaks persist)
            "concurrencyPolicy": "Forbid",
            "jobTemplate": {
                "spec": {
                    "backoffLimit": 2,  # idempotent: delta publish retries
                    "template": {
                        "metadata": {
                            "labels": _labels(project, "model-refresh")
                        },
                        "spec": pod_spec,
                    },
                },
            },
        },
    }


def _backfill_job(
    project: str,
    image: str,
    start: str,
    end: str,
    shards: int,
    builder_job: Dict[str, Any],
) -> Dict:
    """An Indexed ``batch/v1`` Job running ``gordo backfill`` over
    ``[start, end)`` — the offline backfill plane fanned out across
    ``shards`` pods (docs/batch.md "Sharded backfill").

    The pod template mirrors the builder Job's volumes and env (models
    PVC, project-config ConfigMap, shared compile cache, GORDO_* wiring)
    so each shard scores with exactly the artifacts the build produced
    and archives next to them.  Shard identity rides the same
    ``JOB_COMPLETION_INDEX`` dependent-env wiring as the multihost
    builder: ``GORDO_BACKFILL_SHARD_INDEX`` is the pod's completion
    index and ``GORDO_BACKFILL_NUM_SHARDS`` the fan-out, which
    ``batch.runner.resolve_shard`` consumes with no extra flags.
    Refused when the builder template carries no models volume — a
    backfill with no artifacts to load can only score the void."""
    import copy

    import pandas as pd

    try:
        ts_start = pd.Timestamp(start)
        ts_end = pd.Timestamp(end)
    except (TypeError, ValueError) as exc:
        raise ValueError(
            f"--backfill range ({start!r}, {end!r}) does not parse as "
            f"timestamps: {exc}"
        )
    if ts_start.tz_localize(None) >= ts_end.tz_localize(None):
        raise ValueError(
            f"--backfill start {start!r} must precede end {end!r} "
            f"(the range is half-open [start, end))"
        )
    builder_spec = builder_job["spec"]["template"]["spec"]
    volume_names = {v.get("name") for v in builder_spec.get("volumes", [])}
    if "models" not in volume_names:
        raise ValueError(
            "--backfill requires the builder template to mount a "
            "'models' volume (the artifact dir the backfill loads models "
            "from and archives scores under); this builder configuration "
            f"has volumes {sorted(volume_names)}"
        )
    pod_spec = copy.deepcopy(builder_spec)
    container = pod_spec["containers"][0]
    container["name"] = "backfill"
    container["command"] = ["gordo", "backfill"]
    container["args"] = [
        "--model-dir", "/models",
        "--start", str(start),
        "--end", str(end),
    ]
    container.setdefault("env", []).extend([
        # JOB_COMPLETION_INDEX is injected by kubernetes for Indexed
        # Jobs; the pair below is the env spelling of --shard i/N
        {"name": "GORDO_BACKFILL_SHARD_INDEX",
         "value": "$(JOB_COMPLETION_INDEX)"},
        {"name": "GORDO_BACKFILL_NUM_SHARDS", "value": str(shards)},
    ])
    return {
        "apiVersion": "batch/v1",
        "kind": "Job",
        "metadata": {
            "name": f"gordo-backfill-{project}",
            "labels": _labels(project, "backfill"),
        },
        "spec": {
            "completions": shards,
            "parallelism": shards,
            "completionMode": "Indexed",
            # exit 75 (EX_TEMPFAIL) = archived progress, not finished;
            # the retry resumes from completion records into byte-
            # identical segments, so a generous backoffLimit is cheap
            "backoffLimit": 6,
            "template": {
                "metadata": {"labels": _labels(project, "backfill")},
                "spec": pod_spec,
            },
        },
    }


def _server_deployment(
    project: str,
    image: str,
    replicas: int,
    server_args: Optional[List[str]] = None,
    scrape_annotations: bool = True,
    serve_dtype: Optional[str] = None,
    shard: Optional[Any] = None,
) -> Dict:
    """``shard`` (a ``serve.shard.ShardSpec``): emit one shard replica's
    Deployment of a fleet-sharded serving tier — its own name/labels (so
    per-shard Services select only it) and ``GORDO_SERVE_SHARD=i/N``
    stamped in the pod env, which makes the server load, warm, and make
    device-resident ONLY its shard's artifacts."""
    component = "ml-server" if shard is None else f"ml-server-shard-{shard.index}"
    name = f"gordo-server-{project}" + (
        "" if shard is None else f"-shard-{shard.index}"
    )
    shard_env = (
        []
        if shard is None
        else [{"name": "GORDO_SERVE_SHARD", "value": str(shard)}]
    )
    template_meta: Dict[str, Any] = {
        "labels": _labels(project, component),
    }
    if scrape_annotations:
        template_meta["annotations"] = _scrape_annotations(
            DEFAULT_SERVER_PORT
        )
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {
            "name": name,
            "labels": _labels(project, component),
        },
        "spec": {
            "replicas": replicas,
            "selector": {"matchLabels": _labels(project, component)},
            "template": {
                "metadata": template_meta,
                "spec": {
                    "containers": [
                        {
                            "name": "ml-server",
                            "image": image,
                            "command": ["gordo", "run-server"],
                            "args": [
                                "--model-dir", "/models",
                                "--project", project,
                                "--port", str(DEFAULT_SERVER_PORT),
                                # warmup by default + the /ready-gated
                                # readinessProbe below: pods receive no
                                # traffic until their programs are compiled
                                "--warmup",
                                *(server_args or []),
                            ],
                            # the warmup loads executables the builder (or
                            # a previous server incarnation) already put in
                            # the shared compile cache — a rescheduled pod
                            # goes ready in cache-load time, not compile
                            # time
                            "env": [
                                _compile_cache_env(),
                                _reload_watch_env(),
                                *shard_env,
                                *_serve_dtype_env(serve_dtype),
                            ],
                            "ports": [{"containerPort": DEFAULT_SERVER_PORT}],
                            "readinessProbe": {
                                # /ready returns 503 until the startup
                                # warmup finishes compiling, so a
                                # rescheduled pod only receives traffic
                                # with warm programs
                                "httpGet": {
                                    "path": f"{API_PREFIX}/{project}/ready",
                                    "port": DEFAULT_SERVER_PORT,
                                },
                            },
                            "volumeMounts": [
                                {"name": "models", "mountPath": "/models",
                                 "readOnly": True},
                                {"name": "compile-cache",
                                 "mountPath": COMPILE_CACHE_MOUNT},
                            ],
                        }
                    ],
                    "volumes": [
                        {
                            "name": "models",
                            "persistentVolumeClaim": {
                                "claimName": f"gordo-models-{project}"
                            },
                        },
                        _compile_cache_volume(project),
                    ],
                },
            },
        },
    }


#: Service-level idle-timeout annotation for components that carry
#: long-lived SSE connections (the streaming plane): cloud LB defaults
#: (AWS ELB: 60s) would sever a healthy stream between events; an hour
#: keeps the connection while the server's keepalive comments (default
#: every 15s) prove liveness far inside it.
_SSE_SERVICE_ANNOTATIONS = {
    "service.beta.kubernetes.io/aws-load-balancer-connection-idle-timeout":
        "3600",
}


def _service(
    project: str,
    component: str,
    port: int,
    annotations: Optional[Dict[str, str]] = None,
) -> Dict:
    metadata: Dict[str, Any] = {
        "name": f"gordo-{component}-{project}",
        "labels": _labels(project, component),
    }
    if annotations:
        metadata["annotations"] = dict(annotations)
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": metadata,
        "spec": {
            "selector": _labels(project, component),
            "ports": [{"port": port, "targetPort": port}],
        },
    }


def _machine_mapping(
    project: str, machine: str, component: str = "ml-server"
) -> Dict:
    """Ambassador-style route: per-machine URL → the owning server service
    (the reference annotated one Mapping per machine Service; machines now
    share one server — or, sharded, one replica — the outward URL contract
    is identical).  With a sharded tier, ``component`` is the OWNING
    shard's service, computed with the same shard function the servers
    load with: ingress-level machine-affinity routing, no lookup hop."""
    return {
        "apiVersion": "getambassador.io/v2",
        "kind": "Mapping",
        "metadata": {
            "name": f"gordo-mapping-{project}-{machine}",
            "labels": _labels(project, "route"),
        },
        "spec": {
            "prefix": f"{API_PREFIX}/{project}/{machine}/",
            "rewrite": f"{API_PREFIX}/{project}/{machine}/",
            "service": f"gordo-{component}-{project}:{DEFAULT_SERVER_PORT}",
        },
    }


def _stream_mapping(
    project: str,
    name: str,
    prefix: str,
    rewrite: str,
    component: str,
    port: int = DEFAULT_SERVER_PORT,
) -> Dict:
    """Route Mapping for the streaming plane (``serve/stream.py``).

    SSE subscriptions are long-lived by design; Ambassador's default
    per-request timeout (3s) and Envoy's idle timeout would sever a
    healthy stream between events.  The stream routes pin
    ``timeout_ms: 0`` (no request ceiling) and a day-long
    ``idle_timeout_ms`` — the server's keepalive comments
    (``GORDO_STREAM_KEEPALIVE``, default 15s) tick far inside it, so a
    dead peer is still reaped by TCP, not by a proxy guessing."""
    return {
        "apiVersion": "getambassador.io/v2",
        "kind": "Mapping",
        "metadata": {
            "name": name,
            "labels": _labels(project, "route"),
        },
        "spec": {
            "prefix": prefix,
            "rewrite": rewrite,
            "service": f"gordo-{component}-{project}:{port}",
            "timeout_ms": 0,
            "idle_timeout_ms": 86400000,
        },
    }


def _server_hpa(
    project: str, shard: Any, max_replicas: int = 4
) -> Dict:
    """HorizontalPodAutoscaler for one shard's Deployment, driven by the
    queue-wait-vs-service-time telemetry the coalescer already exports:
    ``gordo_coalesce_wait_service_ratio`` (p99 queue wait / median
    service time, refreshed at scrape time on ``/metrics``).  The target
    averageValue of 2 sits at HALF the coalescer's stand-down ratio (4):
    the tier scales out while batching still wins, well before replicas
    start shedding with 429.  Requires a prometheus adapter exposing the
    gauge as a Pods metric — the scrape annotations are already stamped.
    Scaling a shard Deployment adds replicas OF THAT SHARD (same machine
    subset, load-balanced by its Service); the shard count itself is
    static config, rendered at generation time."""
    name = f"gordo-server-{project}-shard-{shard.index}"
    return {
        "apiVersion": "autoscaling/v2",
        "kind": "HorizontalPodAutoscaler",
        "metadata": {
            "name": name,
            "labels": _labels(project, f"ml-server-shard-{shard.index}"),
        },
        "spec": {
            "scaleTargetRef": {
                "apiVersion": "apps/v1",
                "kind": "Deployment",
                "name": name,
            },
            "minReplicas": 1,
            "maxReplicas": max_replicas,
            "metrics": [
                {
                    "type": "Pods",
                    "pods": {
                        "metric": {
                            "name": "gordo_coalesce_wait_service_ratio"
                        },
                        "target": {
                            "type": "AverageValue",
                            "averageValue": "2",
                        },
                    },
                }
            ],
        },
    }


def _watchman_deployment(
    project: str,
    image: str,
    machines: List[str],
    scrape_annotations: bool = True,
    targets: Optional[List[str]] = None,
) -> Dict:
    template_meta: Dict[str, Any] = {
        "labels": _labels(project, "watchman"),
    }
    if scrape_annotations:
        # watchman's /metrics is the FLEET scrape surface (it merges every
        # target server's exposition under instance labels), so clusters
        # that only scrape one target per project point here
        template_meta["annotations"] = _scrape_annotations(
            DEFAULT_WATCHMAN_PORT
        )
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {
            "name": f"gordo-watchman-{project}",
            "labels": _labels(project, "watchman"),
        },
        "spec": {
            "replicas": 1,
            "selector": {"matchLabels": _labels(project, "watchman")},
            "template": {
                "metadata": template_meta,
                "spec": {
                    "containers": [
                        {
                            "name": "watchman",
                            "image": image,
                            "command": ["gordo", "run-watchman"],
                            "args": [
                                "--project", project,
                                "--machines", ",".join(machines),
                                # one --target per serving service: the
                                # whole tier when sharded (watchman polls
                                # every replica and republishes each one's
                                # shard index + fleet generation)
                                *(
                                    arg
                                    for target in (
                                        targets
                                        or [
                                            f"http://gordo-ml-server-{project}"
                                            f":{DEFAULT_SERVER_PORT}"
                                        ]
                                    )
                                    for arg in ("--target", target)
                                ),
                                "--port", str(DEFAULT_WATCHMAN_PORT),
                            ],
                            "env": [_evict_after_env()],
                            "ports": [{"containerPort": DEFAULT_WATCHMAN_PORT}],
                        }
                    ],
                },
            },
        },
    }


def generate_workflow(
    config: NormalizedConfig,
    image: str = DEFAULT_IMAGE,
    server_replicas: int = 1,
    tpu_resources: Optional[Dict[str, Any]] = None,
    include_plan: bool = True,
    server_args: Optional[List[str]] = None,
    multihost: Optional[int] = None,
    scrape_annotations: bool = True,
    serve_dtype: Optional[str] = None,
    serve_shards: Optional[int] = None,
    hpa_max_replicas: int = 4,
    refresh_cron: Optional[str] = None,
    backfill: Optional[Tuple[str, str]] = None,
    backfill_shards: int = 1,
) -> List[Dict[str, Any]]:
    """Project config → list of k8s manifest dicts (+ the build plan as a
    ConfigMap so the cluster state carries the bucketing decision).

    ``server_args``: extra ``gordo run-server`` flags for the ml-server
    Deployment (e.g. ``["--coalesce-ms", "2"]`` or ``["--model-parallel"]``
    on a slice-backed node pool).

    ``multihost``: emit the builder as an N-process Indexed Job (one pod
    per process, ``jax.distributed`` wired via ``GORDO_*`` env) instead of
    a single-pod Job.  Refused when N exceeds the plan's machine-shard
    count — the extra pods would have empty shards yet still hold every
    barrier, so the spec is a config error, not a scheduling preference.

    ``scrape_annotations`` (default on): stamp ``prometheus.io/*``
    discovery annotations on the server and watchman pod templates so a
    conventionally-configured Prometheus scrapes their ``/metrics``
    without extra config; disable for clusters using ServiceMonitors.

    ``serve_dtype`` (e.g. ``"bfloat16"``): stamp ``GORDO_SERVE_DTYPE`` on
    the builder AND server pod templates — the build's warmup manifest
    then records the precision, warmup compiles for it, and dispatch
    matches (the serving-precision plane's one-config contract).  Only
    set this after the fp32 parity suite passes for the project's model
    family (docs/perf.md "Serving precision").

    ``serve_shards`` N>1: emit a fleet-sharded serving tier — one
    Deployment + Service per shard index (``GORDO_SERVE_SHARD=i/N`` in
    each pod env, so every replica loads only its shard's artifacts), an
    HPA per shard driven by the coalescer's queue-wait/service-time
    ratio gauge, per-machine Mappings routed to the OWNING shard's
    service (the same shard function everywhere — docs/serving.md
    "Sharded serving tier"), and the watchman polling every shard
    service.  Refused when N exceeds the machine count, mirroring the
    ``--multihost`` rule: machines are the atoms of the partition.

    ``refresh_cron`` (a 5-field cron schedule): additionally emit a
    CronJob running ``gordo refresh --once`` against the same models
    PVC and project config as the builder — the drift-driven
    incremental rebuild loop (docs/operations.md "Incremental
    refresh").  Refused when the builder template has no models volume
    to warm-start from, or when the schedule is malformed.

    ``backfill`` (a ``(start, end)`` timestamp pair): additionally emit
    an Indexed Job running ``gordo backfill`` over the half-open range
    against the same models PVC as the builder, fanned out across
    ``backfill_shards`` pods via the ``GORDO_BACKFILL_SHARD_INDEX`` /
    ``GORDO_BACKFILL_NUM_SHARDS`` env pair (docs/batch.md).  Refused
    when the range is malformed, when the builder has no models volume,
    or when ``backfill_shards`` exceeds the machine count — machines
    are the atoms of the backfill partition.
    """
    project = config.project_name
    machines = [m.name for m in config.machines]
    if serve_shards is not None:
        if serve_shards < 1:
            raise ValueError(
                f"serve_shards must be >= 1, got {serve_shards}"
            )
        if serve_shards > len(machines):
            raise ValueError(
                f"--serve-shards {serve_shards} exceeds the project's "
                f"machine count ({len(machines)}): machines are the atoms "
                f"of the serving partition, so extra replicas would own "
                f"empty shards. Use --serve-shards <= {len(machines)}."
            )
    if multihost is not None:
        if multihost < 1:
            raise ValueError(f"multihost must be >= 1, got {multihost}")
        from gordo_tpu.distributed.partition import max_processes

        shard_count = max_processes(config.machines)
        if multihost > shard_count:
            raise ValueError(
                f"--multihost {multihost} exceeds the plan's machine-shard "
                f"count ({shard_count}): machines are the atoms of the "
                f"process partition, so processes beyond that would idle "
                f"while holding every barrier. Use --multihost <= "
                f"{shard_count}, or grow the project."
            )
    tpu_resources = tpu_resources or {
        "limits": {"google.com/tpu": 8},
        "requests": {"google.com/tpu": 8},
    }
    if multihost is not None and multihost > 1:
        builder_docs = _multihost_builder_docs(
            project, image, tpu_resources, multihost,
            serve_dtype=serve_dtype,
        )
    else:
        builder_docs = [
            _builder_job(
                project, image, tpu_resources, serve_dtype=serve_dtype
            )
        ]
    if refresh_cron is not None:
        # mirror the single-pod builder template even under --multihost:
        # the refresh subset is small by construction, so one process is
        # the right shape regardless of how the FULL build fans out
        template = _builder_job(
            project, image, tpu_resources, serve_dtype=serve_dtype
        )
        builder_docs.append(
            _refresh_cronjob(project, image, refresh_cron, template)
        )
    if backfill is not None:
        start, end = backfill
        if backfill_shards < 1:
            raise ValueError(
                f"backfill_shards must be >= 1, got {backfill_shards}"
            )
        if backfill_shards > len(machines):
            raise ValueError(
                f"--backfill-shards {backfill_shards} exceeds the "
                f"project's machine count ({len(machines)}): machines are "
                f"the atoms of the backfill partition, so extra pods "
                f"would own empty shards. Use --backfill-shards <= "
                f"{len(machines)}."
            )
        # same single-pod template shape as the refresh CronJob: each
        # backfill shard is one process staging its own fleet subset
        template = _builder_job(
            project, image, tpu_resources, serve_dtype=serve_dtype
        )
        builder_docs.append(
            _backfill_job(
                project, image, start, end, backfill_shards, template
            )
        )
    sharded = serve_shards is not None and serve_shards > 1
    if sharded:
        from gordo_tpu.serve.shard import ShardSpec, shard_map

        specs = [ShardSpec(i, serve_shards) for i in range(serve_shards)]
        server_docs: List[Dict[str, Any]] = []
        for spec in specs:
            server_docs.append(
                _server_deployment(
                    project, image, server_replicas, server_args,
                    scrape_annotations=scrape_annotations,
                    serve_dtype=serve_dtype, shard=spec,
                )
            )
            server_docs.append(
                _service(
                    project, f"ml-server-shard-{spec.index}",
                    DEFAULT_SERVER_PORT,
                    annotations=_SSE_SERVICE_ANNOTATIONS,
                )
            )
            server_docs.append(
                _server_hpa(project, spec, max_replicas=hpa_max_replicas)
            )
        watchman_targets = [
            f"http://gordo-ml-server-shard-{i}-{project}:"
            f"{DEFAULT_SERVER_PORT}"
            for i in range(serve_shards)
        ]
        owner = shard_map(machines, serve_shards)
        mapping_component = {
            m: f"ml-server-shard-{owner[m]}" for m in machines
        }
    else:
        server_docs = [
            _server_deployment(
                project, image, server_replicas, server_args,
                scrape_annotations=scrape_annotations,
                serve_dtype=serve_dtype,
            ),
            _service(
                project, "ml-server", DEFAULT_SERVER_PORT,
                annotations=_SSE_SERVICE_ANNOTATIONS,
            ),
        ]
        watchman_targets = [
            f"http://gordo-ml-server-{project}:{DEFAULT_SERVER_PORT}"
        ]
        mapping_component = {m: "ml-server" for m in machines}
    docs: List[Dict[str, Any]] = [
        *builder_docs,
        *server_docs,
        _watchman_deployment(
            project, image, machines,
            scrape_annotations=scrape_annotations,
            targets=watchman_targets,
        ),
        _service(
            project, "watchman", DEFAULT_WATCHMAN_PORT,
            annotations=_SSE_SERVICE_ANNOTATIONS,
        ),
    ]
    docs.extend(
        _machine_mapping(project, m, mapping_component[m]) for m in machines
    )
    # streaming-plane routes (docs/serving.md "Streaming"): SSE-safe
    # Mappings with the per-request timeout disabled.  Sharded tiers get
    # one route per shard (ingest + subscribe against the replica that
    # OWNS the machines — streams are per-replica state) plus a merged
    # read-only route through the watchman relay's fan-in.
    if sharded:
        for spec in specs:
            docs.append(_stream_mapping(
                project,
                name=f"gordo-mapping-{project}-stream-shard-{spec.index}",
                prefix=f"{API_PREFIX}/{project}/shard-{spec.index}/stream",
                rewrite=f"{API_PREFIX}/{project}/stream",
                component=f"ml-server-shard-{spec.index}",
            ))
        docs.append(_stream_mapping(
            project,
            name=f"gordo-mapping-{project}-stream-merged",
            prefix=f"{API_PREFIX}/{project}/stream/merged",
            rewrite="/stream",
            component="watchman",
            port=DEFAULT_WATCHMAN_PORT,
        ))
    else:
        docs.append(_stream_mapping(
            project,
            name=f"gordo-mapping-{project}-stream",
            prefix=f"{API_PREFIX}/{project}/stream",
            rewrite=f"{API_PREFIX}/{project}/stream",
            component="ml-server",
        ))
    if include_plan:
        docs.append(
            {
                "apiVersion": "v1",
                "kind": "ConfigMap",
                "metadata": {
                    "name": f"gordo-build-plan-{project}",
                    "labels": _labels(project, "build-plan"),
                },
                "data": {"plan.yaml": yaml.safe_dump(build_plan(config))},
            }
        )
    return docs


def workflow_to_yaml(docs: List[Dict[str, Any]]) -> str:
    return yaml.safe_dump_all(docs, sort_keys=False)


# ---------------------------------------------------------------------------
# Argo shim
# ---------------------------------------------------------------------------

def generate_argo_workflow(
    config: NormalizedConfig,
    image: str = DEFAULT_IMAGE,
    max_bucket_size: int = 512,
    tpu_resources: Optional[Dict[str, Any]] = None,
    serve_dtype: Optional[str] = None,
) -> Dict[str, Any]:
    """Project config → one ``argoproj.io/v1alpha1 Workflow`` document.

    Reference equivalent: ``gordo_components/workflow`` rendered an Argo
    Workflow with one pod per machine.  The TPU-native build is the
    bucketed fleet program (one Job), so this shim exists for clusters
    whose tooling consumes Argo documents: a DAG with ONE task per fleet
    chunk (not per machine — a chunk is the unit that shares a stacked
    XLA program), each running ``gordo build-project --machines <chunk>``
    against the shared project ConfigMap and models PVC.  Chunk tasks are
    independent (no DAG edges): Argo schedules them with whatever
    parallelism the cluster allows, and the config-hash registry makes
    retries idempotent.
    """
    project = config.project_name
    plan = build_plan(config, max_bucket_size=max_bucket_size)
    tpu_resources = tpu_resources or {
        "limits": {"google.com/tpu": 8},
        "requests": {"google.com/tpu": 8},
    }
    tasks = [
        {
            "name": bucket["bucket"],
            "template": "build-chunk",
            "arguments": {
                "parameters": [
                    {
                        "name": "machines",
                        "value": ",".join(bucket["machines"]),
                    }
                ]
            },
        }
        for bucket in plan["buckets"]
    ]
    return {
        "apiVersion": "argoproj.io/v1alpha1",
        "kind": "Workflow",
        "metadata": {
            "generateName": f"gordo-build-{project}-",
            "labels": _labels(project, "model-builder"),
        },
        "spec": {
            "entrypoint": "build",
            "templates": [
                {"name": "build", "dag": {"tasks": tasks}},
                {
                    "name": "build-chunk",
                    "inputs": {"parameters": [{"name": "machines"}]},
                    "container": {
                        "name": "model-builder",
                        "image": image,
                        "command": ["gordo", "build-project"],
                        "args": [
                            "--machine-config", "/config/project.yaml",
                            "--output-dir", "/models",
                            "--model-register-dir", "/models/.register",
                            "--max-bucket-size", str(max_bucket_size),
                            "--machines",
                            "{{inputs.parameters.machines}}",
                        ],
                        "env": [
                            {"name": "PROJECT_NAME", "value": project},
                            # chunk tasks share one models PVC: each task
                            # writes its chunk's pack + an index merge
                            # (flock-serialized), not per-machine dirs —
                            # the v2 library default
                            *_serve_dtype_env(serve_dtype),
                        ],
                        "resources": tpu_resources,
                        "volumeMounts": [
                            {"name": "models", "mountPath": "/models"},
                            {
                                "name": "project-config",
                                "mountPath": "/config",
                            },
                        ],
                    },
                },
            ],
            "volumes": [
                {
                    "name": "models",
                    "persistentVolumeClaim": {
                        "claimName": f"gordo-models-{project}"
                    },
                },
                {
                    "name": "project-config",
                    "configMap": {"name": f"gordo-config-{project}"},
                },
            ],
        },
    }
