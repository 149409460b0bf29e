"""The ``gordo`` CLI.

Reference equivalent: ``gordo_components/cli/cli.py`` — the click group
binding container entrypoints to the layers: ``build`` (env-var driven,
one machine per invocation — one Argo pod each), ``run-server``,
``run-watchman``, ``client ...``, ``workflow ...``.

TPU-era addition: ``build-project`` — the whole project in one process via
the fleet engine (buckets of machines as single sharded XLA programs); the
per-machine ``build`` verb is kept verb-for-verb for parity and for
heterogeneous stragglers.
"""

from __future__ import annotations

import json
import logging
import os
import sys
from typing import Any, Dict, Optional

import click
import yaml

import gordo_tpu
from gordo_tpu import telemetry

logger = logging.getLogger(__name__)

_RESUMABLE_EXITS_TOTAL = telemetry.counter(
    "gordo_resumable_exits_total",
    "exit-75 (EX_TEMPFAIL) resumable exits of multi-host build workers, "
    "by stage",
    labels=("stage",),
)


def _parse_config(value: Optional[str], name: str) -> Dict[str, Any]:
    """YAML/JSON text or a path to a YAML file → dict."""
    if not value:
        raise click.ClickException(f"{name} is required (option or env var)")
    if os.path.exists(value):
        with open(value) as f:
            value = f.read()
    loaded = yaml.safe_load(value)
    if not isinstance(loaded, dict):
        raise click.ClickException(f"{name} did not parse to a mapping")
    return loaded


@click.group("gordo")
@click.version_option(version=gordo_tpu.__version__)
@click.option(
    "--log-level",
    type=click.Choice(["CRITICAL", "ERROR", "WARNING", "INFO", "DEBUG"]),
    default="INFO",
    envvar="GORDO_LOG_LEVEL",
    help="Logging level for all gordo components.",
)
def gordo(log_level: str):
    """gordo-tpu: build, serve and fleet-manage per-sensor-tag anomaly
    models on TPU."""
    logging.basicConfig(
        level=getattr(logging, log_level),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )


# ---------------------------------------------------------------------------
# build (single machine — reference parity verb)
# ---------------------------------------------------------------------------

@gordo.command("build")
@click.argument("output_dir", envvar="OUTPUT_DIR", default="./models")
@click.option("--name", envvar="MACHINE_NAME", default="machine", help="Machine name.")
@click.option("--model-config", envvar="MODEL_CONFIG", help="Model definition (YAML/JSON text or file).")
@click.option("--data-config", envvar="DATA_CONFIG", help="Dataset config (YAML/JSON text or file).")
@click.option("--metadata", envvar="METADATA", default="{}", help="User metadata (YAML/JSON).")
@click.option("--evaluation-config", envvar="EVALUATION_CONFIG", default=None,
              help="Evaluation config, e.g. '{\"cv_mode\": \"full_build\"}'.")
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR", default=None,
              help="Config-hash cache registry dir; hits skip training.")
@click.option("--print-cv-scores", is_flag=True, help="Print CV scores to stdout.")
def build(output_dir, name, model_config, data_config, metadata,
          evaluation_config, model_register_dir, print_cv_scores):
    """Build one machine's model into OUTPUT_DIR (reference: the per-pod
    entrypoint of the Argo fan-out)."""
    from gordo_tpu import serializer
    from gordo_tpu.builder.build_model import provide_saved_model
    from gordo_tpu.workflow.config import DEFAULT_MODEL

    model_cfg = (
        _parse_config(model_config, "MODEL_CONFIG")
        if model_config
        else DEFAULT_MODEL
    )
    data_cfg = _parse_config(data_config, "DATA_CONFIG")
    meta = _parse_config(metadata, "METADATA") if metadata else {}
    eval_cfg = (
        _parse_config(evaluation_config, "EVALUATION_CONFIG")
        if evaluation_config
        else None
    )
    path = provide_saved_model(
        name,
        model_cfg,
        data_cfg,
        metadata=meta,
        output_dir=output_dir,
        model_register_dir=model_register_dir,
        evaluation_config=eval_cfg,
    )
    if print_cv_scores:
        build_meta = serializer.load_metadata(path)
        for metric, value in (
            build_meta.get("model", {})
            .get("cross_validation", {})
            .get("scores", {})
            .items()
        ):
            click.echo(f"{metric}: {value}")
    click.echo(path)


# ---------------------------------------------------------------------------
# build-project (fleet engine)
# ---------------------------------------------------------------------------

@gordo.command("build-project")
@click.option("--machine-config", required=True, envvar="MACHINE_CONFIG",
              help="Project YAML (text or file) with machines/globals.")
@click.option("--project-name", envvar="PROJECT_NAME", default="project")
@click.option("--output-dir", envvar="OUTPUT_DIR", default="./models")
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR", default=None)
@click.option("--max-bucket-size", default=None, type=int,
              help="Max machines per stacked XLA program. Default: "
                   "per-model-family (512 dense, 256 recurrent — see "
                   "builder.fleet_build.default_bucket_size).")
@click.option("--data-parallel", default=1, show_default=True,
              help="Mesh 'data' axis size (chips per model shard).")
@click.option("--mesh-devices", default=None, envvar="GORDO_MESH_DEVICES",
              help="Fleet-mesh width: 'all'/'auto' (default) spreads the "
                   "models axis over every visible device, '1' forces the "
                   "single-device path, an integer N takes the first N "
                   "devices. Resolved by gordo_tpu.mesh.FleetMesh; env "
                   "equivalent GORDO_MESH_DEVICES.")
@click.option("--data-workers", default=None, show_default="2",
              type=click.IntRange(min=1),
              help="Concurrent data-loader threads feeding the stream. "
                   "Default: 2, the ingest plane's prefetch depth (a task "
                   "loads a whole chunk); the resolved count lands in the "
                   "result summary as loader_workers.")
@click.option("--align-lengths", default=None,
              type=click.IntRange(min=2),
              help="Truncate each machine's train rows down to a multiple "
                   "of this (oldest rows drop): ragged projects compile one "
                   "XLA program per DISTINCT row count, so alignment trades "
                   "up to N-1 old rows for ~N-fold fewer compiles.")
@click.option("--pad-lengths", default=None,
              type=click.IntRange(min=2),
              help="Pad each machine's train rows UP to a multiple of this "
                   "with weight-masked rows (zero data loss): one program "
                   "per aligned length, at the cost of fold/batch geometry "
                   "deriving from the padded length. Mutually exclusive "
                   "with --align-lengths.")
@click.option("--machines", "machines_filter", default=None,
              help="Comma-separated machine names: build only this subset "
                   "of the project (partial rebuilds; the unit of work in "
                   "the generated Argo DAG).")
@click.option("--multihost", default=None, envvar="GORDO_MULTIHOST",
              help="'coordinator:port,N,pid': run as process pid of an "
                   "N-process multi-host build (jax.distributed; process 0 "
                   "hosts the coordination service). Each process builds "
                   "its deterministic shard of the machine list into the "
                   "shared --output-dir/--model-register-dir. Env "
                   "equivalents: GORDO_COORDINATOR + GORDO_NUM_PROCESSES + "
                   "GORDO_PROCESS_ID (what the generated Indexed-Job "
                   "manifest sets).")
@click.option("--barrier-timeout", default=None, type=click.FloatRange(min=1),
              help="Seconds before a multi-host barrier declares a peer "
                   "dead; the survivor exits 75 (EX_TEMPFAIL) with its "
                   "shard state resumable. Default 600.")
@click.option("--auto-pad/--no-auto-pad", default=True, show_default=True,
              help="When neither --align-lengths nor --pad-lengths is set "
                   "and the config-level estimate predicts a large ragged "
                   "compile bill, auto-enable --pad-lengths at a computed "
                   "alignment (loudly logged) instead of paying one XLA "
                   "compile per distinct row count.")
@click.option("--artifact-format", default=None,
              type=click.Choice(["v1", "v2"]),
              help="v2 (default): one memory-mapped parameter pack per "
                   "fleet chunk + index (gordo_tpu/artifacts/) — "
                   "O(chunks) files instead of O(machines), zero-copy "
                   "server loads. v1: one directory per machine (the "
                   "compatibility escape hatch, also via "
                   "GORDO_ARTIFACT_FORMAT=v1).")
@click.option("--replace-cache", is_flag=True)
def build_project_cmd(machine_config, project_name, output_dir,
                      model_register_dir, max_bucket_size, data_parallel,
                      mesh_devices, data_workers, align_lengths,
                      pad_lengths, machines_filter, multihost,
                      barrier_timeout, auto_pad, artifact_format,
                      replace_cache):
    """Build EVERY machine in the project config — homogeneous machines
    train as single mesh-sharded fleet programs (the TPU-native
    replacement for the reference's one-pod-per-machine Argo DAG)."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    config = NormalizedConfig.from_source(machine_config, project_name)
    machines = config.machines
    if machines_filter:
        wanted = {n.strip() for n in machines_filter.split(",") if n.strip()}
        machines = [m for m in machines if m.name in wanted]
        missing = wanted - {m.name for m in machines}
        if missing:
            raise click.BadParameter(
                f"--machines names not in the project: {sorted(missing)}"
            )

    # ---- multi-host: one process of an N-process sharded build ----
    from gordo_tpu.distributed.runtime import DistributedConfig, parse_multihost_spec

    if multihost:
        try:
            dist_cfg = parse_multihost_spec(multihost)
        except ValueError as exc:
            raise click.BadParameter(str(exc), param_hint="--multihost")
    else:
        dist_cfg = DistributedConfig.from_env()
    if dist_cfg is not None:
        if barrier_timeout:
            dist_cfg.barrier_timeout = barrier_timeout
        _run_multihost_build(
            dist_cfg, machines, output_dir, model_register_dir,
            replace_cache, max_bucket_size, data_parallel, data_workers,
            align_lengths, pad_lengths, auto_pad, artifact_format,
        )
        return

    # ---- single host ----
    from gordo_tpu.mesh import FleetMesh

    try:
        fleet_mesh = FleetMesh.resolve(
            mesh_devices, data_parallel=data_parallel
        )
    except ValueError as exc:
        raise click.BadParameter(str(exc), param_hint="--mesh-devices")
    mesh = fleet_mesh.mesh
    result = build_project(
        machines,
        output_dir,
        model_register_dir=model_register_dir,
        mesh=mesh,
        replace_cache=replace_cache,
        max_bucket_size=max_bucket_size,
        data_workers=data_workers,
        align_lengths=align_lengths,
        pad_lengths=pad_lengths,
        auto_pad=auto_pad,
        artifact_format=artifact_format,
    )
    click.echo(json.dumps(result.summary()))
    if result.failed:
        sys.exit(1)


def _run_multihost_build(dist_cfg, machines, output_dir, model_register_dir,
                         replace_cache, max_bucket_size, data_parallel,
                         data_workers, align_lengths, pad_lengths, auto_pad,
                         artifact_format=None):
    """One worker of an N-process build: init jax.distributed, build this
    process's shard, barrier at the edges.  A barrier timeout (dead peer)
    exits EXIT_SHARD_RESUMABLE with this shard's state file resumable —
    `os._exit`, because jax.distributed.shutdown() aborts once a peer is
    gone (see distributed/runtime.py)."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.distributed.partition import (
        EXIT_SHARD_RESUMABLE,
        process_shard,
    )
    from gordo_tpu.distributed.runtime import BarrierTimeout, DistributedRuntime

    runtime = DistributedRuntime(dist_cfg)
    runtime.ensure_env()  # before ANY jax backend init
    runtime.initialize()
    n_global = runtime.validate_global_mesh()
    logger.info(
        "multihost build: process %d/%d, %d global devices, mesh validated",
        dist_cfg.process_id, dist_cfg.num_processes, n_global,
    )
    shard = process_shard(
        machines, dist_cfg.num_processes, dist_cfg.process_id,
        output_dir=output_dir,
    )

    def _resumable_exit(stage: str, exc: Exception, result=None) -> None:
        _RESUMABLE_EXITS_TOTAL.inc(1.0, stage)
        telemetry.log_event(
            logger, "resumable_exit",
            stage=stage,
            process_id=dist_cfg.process_id,
            num_processes=dist_cfg.num_processes,
            exit_code=EXIT_SHARD_RESUMABLE,
        )
        if shard.state is not None:
            if not shard.state.machines:
                shard.state.start(shard.names)
            shard.state.mark_resumable(f"{stage}: {exc}")
        # last-gasp shard-local snapshot: the barrier-wait/timeout series
        # this process accumulated must survive the os._exit for the
        # post-mortem merge (`gordo telemetry dump --dir <output_dir>`)
        if telemetry.enabled():
            try:
                telemetry.REGISTRY.write_snapshot(os.path.join(
                    output_dir, telemetry.SNAPSHOT_DIR,
                    f"shard-{dist_cfg.process_id:03d}"
                    f"-of-{dist_cfg.num_processes:03d}.json",
                ))
            except Exception:
                logger.exception("telemetry snapshot write failed")
        doc = result.summary() if result is not None else {}
        doc["resumable"] = {
            "stage": stage,
            "process_id": dist_cfg.process_id,
            "error": str(exc).split("\n")[0][:200],
        }
        click.echo(json.dumps(doc))
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(EXIT_SHARD_RESUMABLE)

    try:
        runtime.barrier("pre-build")
    except BarrierTimeout as exc:
        _resumable_exit("pre-build", exc)
    result = build_project(
        machines,
        output_dir,
        model_register_dir=model_register_dir,
        mesh=runtime.local_mesh(data_parallel),
        replace_cache=replace_cache,
        max_bucket_size=max_bucket_size,
        data_workers=data_workers,
        align_lengths=align_lengths,
        pad_lengths=pad_lengths,
        auto_pad=auto_pad,
        artifact_format=artifact_format,
        shard=shard,
    )
    try:
        runtime.barrier("post-build")
    except BarrierTimeout as exc:
        # THIS shard may be fully built (its state says so); the exit code
        # still signals "re-run the job" because fleet-wide completion is
        # unconfirmed — the re-run cache-hits everything already on disk
        _resumable_exit("post-build", exc, result)
    runtime.shutdown()
    summary = result.summary()
    summary["multihost"] = {
        "process_id": dist_cfg.process_id,
        "num_processes": dist_cfg.num_processes,
        "global_devices": n_global,
    }
    click.echo(json.dumps(summary))
    if result.failed:
        sys.exit(1)


# ---------------------------------------------------------------------------
# servers
# ---------------------------------------------------------------------------

@gordo.command("run-server")
@click.option("--model-dir", envvar="MODEL_LOCATION", required=True,
              help="One machine's artifact dir, or a project dir of them.")
@click.option("--host", default="0.0.0.0", show_default=True)
@click.option("--port", default=5555, show_default=True)
@click.option("--project", envvar="PROJECT_NAME", default="project")
@click.option("--rescan-interval", default=30.0, show_default=True,
              help="Seconds between artifact-dir rescans picking up newly "
                   "built machines (0 disables).")
@click.option("--coalesce-ms", default=0.0, show_default=True,
              help="Micro-batch concurrent single-machine anomaly requests "
                   "into stacked fleet dispatches (0 disables). The drain "
                   "is continuous; this bounds only the single-rider grace "
                   "wait. Big win under concurrent load; requests below "
                   "--coalesce-min-concurrency bypass and dispatch "
                   "directly, and the coalescer stands down to direct "
                   "dispatch when its saturation signal says batching is "
                   "losing.")
@click.option("--coalesce-min-concurrency", default=2, show_default=True,
              help="Coalesce only when at least this many single-machine "
                   "anomaly requests are in flight; below it requests "
                   "score directly (adaptive bypass).")
@click.option("--coalesce-knee", default=0, show_default=True,
              help="Cap coalesced dispatches at this many machines (the "
                   "throughput knee). 0 = auto-estimate from a short "
                   "warmup sweep on first use.")
@click.option("--model-parallel/--no-model-parallel", default=False,
              show_default=True,
              help="Shard stacked serving dispatches over ALL visible "
                   "devices (the 'models' mesh axis): one server process "
                   "drives a whole slice instead of one chip.")
@click.option("--mesh-devices", default=None, envvar="GORDO_MESH_DEVICES",
              help="Fleet-mesh width for --model-parallel: 'all'/'auto' "
                   "(default) uses every visible device, '1' forces the "
                   "single-device path, an integer N takes the first N "
                   "devices. Default: $GORDO_MESH_DEVICES.")
@click.option("--warmup/--no-warmup", default=False, show_default=True,
              help="Precompile the serving programs in the background at "
                   "startup so the first request doesn't pay jit "
                   "compilation (~20-40s cold on TPU).")
@click.option("--shard", default=None, envvar="GORDO_SERVE_SHARD",
              help="'i/N': serve shard i of an N-replica fleet-sharded "
                   "tier — load, warm, and make device-resident ONLY this "
                   "shard's machines (the same deterministic partition "
                   "the client and watchman compute; docs/serving.md "
                   "'Sharded serving tier'). Default: unsharded.")
@click.option("--reload-watch", default=None, type=float,
              help="Seconds between artifact-generation polls for the "
                   "zero-downtime delta hot reload (one tiny sidecar "
                   "read per poll; a flip re-stacks only the changed "
                   "machines while the old generation keeps serving). "
                   "Default: GORDO_RELOAD_WATCH_SECONDS, else 5; 0 "
                   "disables.")
def run_server_cmd(model_dir, host, port, project, rescan_interval,
                   coalesce_ms, coalesce_min_concurrency, coalesce_knee,
                   model_parallel, mesh_devices, warmup, shard,
                   reload_watch):
    """Serve model(s) over the /gordo/v0/<project>/<machine>/ routes."""
    from gordo_tpu.serve.server import run_server
    from gordo_tpu.serve.shard import ShardSpec

    if shard:
        try:
            shard = ShardSpec.parse(shard)
        except ValueError as exc:
            raise click.BadParameter(str(exc), param_hint="--shard")
    run_server(
        model_dir, host=host, port=port, project=project,
        rescan_interval=rescan_interval,
        coalesce_window_ms=coalesce_ms,
        coalesce_min_concurrency=coalesce_min_concurrency,
        coalesce_knee_batch=coalesce_knee,
        model_parallel=model_parallel,
        mesh_devices=mesh_devices,
        warmup=warmup,
        shard=shard or None,
        reload_watch_interval=reload_watch,
    )


@gordo.command("run-watchman")
@click.option("--project", envvar="PROJECT_NAME", default="project")
@click.option("--machines", default=None,
              help="Comma-separated machine names (or use --machine-config).")
@click.option("--machine-config", default=None,
              help="Project YAML to derive the machine list from.")
@click.option("--target", "targets", multiple=True,
              default=("http://localhost:5555",), show_default=True,
              help="ML-server base URL(s) to poll (repeatable).")
@click.option("--host", default="0.0.0.0", show_default=True)
@click.option("--port", default=5556, show_default=True)
@click.option("--poll-interval", default=30.0, show_default=True)
@click.option("--discover/--no-discover", default=True, show_default=True,
              help="Also discover machines from each target's project "
                   "index (new machines appear without reconfig).")
@click.option("--kube-namespace", default=None,
              help="Discover ml-server Services in this k8s namespace "
                   "(requires the kubernetes client package).")
def run_watchman_cmd(project, machines, machine_config, targets, host, port,
                     poll_interval, discover, kube_namespace):
    """Run the fleet-status aggregation service."""
    from gordo_tpu.watchman.server import run_watchman
    from gordo_tpu.workflow.config import NormalizedConfig

    if machines:
        machine_names = [m.strip() for m in machines.split(",") if m.strip()]
    elif machine_config:
        config = NormalizedConfig.from_source(machine_config, project)
        machine_names = [m.name for m in config.machines]
    elif discover:
        machine_names = []  # discovered from the targets' project indexes
    else:
        raise click.ClickException(
            "Provide --machines or --machine-config (or enable --discover)"
        )
    target_discovery = None
    if kube_namespace:
        from gordo_tpu.watchman.kube import KubeTargetDiscovery

        target_discovery = KubeTargetDiscovery(kube_namespace, project=project)
    run_watchman(
        project, machine_names, list(targets),
        host=host, port=port, poll_interval=poll_interval,
        discover=discover, target_discovery=target_discovery,
    )


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------

@gordo.group("client")
@click.option("--project", envvar="PROJECT_NAME", default="project")
@click.option("--host", default="localhost", show_default=True)
@click.option("--port", default=5555, show_default=True)
@click.option("--watchman-url", default=None,
              help="Discover machines from this watchman (healthy only).")
@click.option("--replica-url", "replica_urls", multiple=True,
              help="Fleet-sharded serving tier: replica base URL, ordered "
                   "by shard index (repeatable — give all N). The client "
                   "computes the shard table locally and routes each "
                   "machine's requests straight to its owning replica; "
                   "bulk scoring scatter-gathers across the tier.")
@click.pass_context
def client_group(ctx, project, host, port, watchman_url, replica_urls):
    """Query ML servers: bulk predictions, metadata, model download."""
    ctx.obj = {
        "project": project, "host": host, "port": port,
        "watchman_url": watchman_url,
        "replica_urls": list(replica_urls) or None,
    }


def _make_client(ctx, **kwargs):
    from gordo_tpu.client import Client

    return Client(
        ctx.obj["project"], host=ctx.obj["host"], port=ctx.obj["port"],
        watchman_url=ctx.obj["watchman_url"],
        replica_urls=ctx.obj["replica_urls"], **kwargs
    )


@client_group.command("predict")
@click.argument("start")
@click.argument("end")
@click.option("--machine", "machine_names", multiple=True,
              help="Machine(s) to score; default: every machine.")
@click.option("--output-dir", default=None,
              help="Forward scored frames to this directory.")
@click.option("--parallelism", default=10, show_default=True)
@click.option("--bulk", is_flag=True,
              help="Use the server's stacked bulk route (one vmapped "
                   "dispatch per chunk across all machines).")
@click.pass_context
def client_predict(ctx, start, end, machine_names, output_dir, parallelism,
                   bulk):
    """Score [START, END] for the project's machines."""
    from gordo_tpu.client.forwarders import ForwardPredictionsToDisk

    forwarder = ForwardPredictionsToDisk(output_dir) if output_dir else None
    client = _make_client(
        ctx, prediction_forwarder=forwarder, parallelism=parallelism,
        use_bulk=bulk,
    )
    results = client.predict(start, end, machine_names or None)
    ok = sum(r.ok for r in results)
    for res in results:
        status = "ok" if res.ok else f"FAILED: {'; '.join(res.error_messages)}"
        rows = 0 if res.predictions is None else len(res.predictions)
        click.echo(f"{res.name}: {rows} rows {status}")
    if ok != len(results):
        sys.exit(1)


@client_group.command("metadata")
@click.option("--machine", "machine_names", multiple=True)
@click.option("--output-file", type=click.File("w"), default=None)
@click.pass_context
def client_metadata(ctx, machine_names, output_file):
    """Print (or write) machine metadata JSON."""
    client = _make_client(ctx)
    names = machine_names or client.machine_names()
    meta = {name: client.machine_metadata(name) for name in names}
    out = json.dumps(meta, indent=2, default=str)
    if output_file:
        output_file.write(out)
    else:
        click.echo(out)


@client_group.command("download-model")
@click.argument("output_dir")
@click.option("--machine", "machine_names", multiple=True)
@click.pass_context
def client_download_model(ctx, output_dir, machine_names):
    """Download serialized model(s) into OUTPUT_DIR."""
    from gordo_tpu import serializer

    client = _make_client(ctx)
    names = machine_names or client.machine_names()
    os.makedirs(output_dir, exist_ok=True)
    for name in names:
        model = client.download_model(name)
        serializer.dump(model, os.path.join(output_dir, name))
        click.echo(os.path.join(output_dir, name))


# ---------------------------------------------------------------------------
# warmup (compile plane)
# ---------------------------------------------------------------------------

@gordo.command("warmup")
@click.option("--dir", "model_dir", default=None,
              help="Artifact dir (a machine's, or a project output dir): "
                   "pre-compile its serving programs from the build's "
                   "warmup manifest and print per-program compile seconds. "
                   "Exits non-zero on any compile failure, so an init "
                   "container can gate rollout on it.")
@click.option("--url", "server_url", default=None,
              help="Poll a running server's /healthz until its startup "
                   "warmup reports ready (exit non-zero on timeout or a "
                   "warmup failure) — the remote twin of --dir for pods "
                   "that warm themselves via run-server --warmup.")
@click.option("--rows", "row_sizes", multiple=True, type=int,
              help="Request row bucket(s) to pre-compile for (repeatable); "
                   "default: the manifest's row buckets, else 256 and "
                   "2048.")
@click.option("--shard", default=None, envvar="GORDO_SERVE_SHARD",
              help="--dir mode: 'i/N' — warm only shard i's subset of "
                   "the artifacts (what a sharded replica's init "
                   "container runs: 1/N of the fleet's programs).")
@click.option("--timeout", default=600.0, show_default=True,
              help="--url mode: seconds to wait for the ready state.")
def warmup_cmd(model_dir, server_url, row_sizes, shard, timeout):
    """Pre-compile serving programs (the cold-start gate).

    ``--dir``: AOT-compile every (signature, row bucket) program for the
    artifacts — run it in a kubernetes init container sharing
    ``JAX_COMPILATION_CACHE_DIR`` with the server, and the server's own
    warmup loads every program from the persistent cache in milliseconds.
    ``--url``: wait for a self-warming server to report ready.
    """
    if bool(model_dir) == bool(server_url):
        raise click.UsageError("provide exactly one of --dir or --url")
    if model_dir:
        from gordo_tpu.compile import warmup_collection
        from gordo_tpu.serve.server import ModelCollection
        from gordo_tpu.serve.shard import ShardSpec
        from gordo_tpu.utils.compile_cache import (
            enable_persistent_compile_cache,
        )

        shard_spec = None
        if shard:
            try:
                shard_spec = ShardSpec.parse(shard)
            except ValueError as exc:
                raise click.BadParameter(str(exc), param_hint="--shard")
        enable_persistent_compile_cache()
        try:
            collection = ModelCollection.from_directory(
                model_dir, shard=shard_spec
            )
        except FileNotFoundError as exc:
            raise click.ClickException(str(exc))
        stats = warmup_collection(
            collection, row_sizes=[int(r) for r in row_sizes] or None
        )
        for p in stats["programs"]:
            click.echo(
                f"{p['program']} rows={p['rows']}: {p['seconds']:.3f}s"
                + ("  (cached)" if p["seconds"] == 0.0 else "")
            )
        click.echo(
            f"warmup: {stats['buckets']} bucket(s), "
            f"{len(stats['programs'])} program signature(s), "
            f"dtype={stats.get('dtype', 'float32')}, "
            f"{stats.get('compile_seconds', 0.0):.2f}s compiling, "
            f"{stats['errors']} error(s)"
        )
        if stats["errors"]:
            sys.exit(1)
        return

    # --url: poll /healthz until the server reports ready
    import time as time_mod
    import urllib.error
    import urllib.request

    url = server_url.rstrip("/")
    if not url.endswith("/healthz"):
        url += "/healthz"
    deadline = time_mod.monotonic() + timeout
    last_state = None
    while time_mod.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=10) as resp:
                doc = json.loads(resp.read().decode())
        except (urllib.error.URLError, OSError, ValueError):
            doc = None  # not up yet — keep polling
        state = (doc or {}).get("state")
        if state != last_state and state is not None:
            click.echo(f"{url}: {state}", err=True)
            last_state = state
        if state == "ready":
            if doc.get("warmup_error") or doc.get("warmup_errors"):
                raise click.ClickException(
                    "server is ready but its warmup reported errors: "
                    f"{doc.get('warmup_error') or doc.get('warmup_errors')}"
                )
            click.echo("ready")
            return
        time_mod.sleep(1.0)
    raise click.ClickException(
        f"server at {url} did not report ready within {timeout:.0f}s "
        f"(last state: {last_state})"
    )


# ---------------------------------------------------------------------------
# mesh (device placement plane)
# ---------------------------------------------------------------------------

@gordo.group("mesh")
def mesh_group():
    """Device placement plane: inspect the fleet mesh and bucket placement."""


@mesh_group.command("info")
@click.option("--mesh-devices", default=None, envvar="GORDO_MESH_DEVICES",
              help="Fleet-mesh width: the same 'all'/'auto'/'1'/N spec "
                   "run-server and build-project accept. Default: "
                   "$GORDO_MESH_DEVICES, else all visible devices.")
@click.option("--data-parallel", default=1, show_default=True,
              help="Width of the 'data' mesh axis (build-time row "
                   "sharding; serving uses 1).")
@click.option("--model-dir", default=None,
              help="Also print the per-bucket placement plan for these "
                   "artifacts: stacked machines, padded fleet rows, and "
                   "which model slots each device holds.")
@click.option("--shard", default=None, envvar="GORDO_SERVE_SHARD",
              help="--model-dir mode: 'i/N' replica shard to plan for "
                   "(the subset a sharded replica would stack).")
def mesh_info(mesh_devices, data_parallel, model_dir, shard):
    """Print the resolved device mesh (JSON): devices, mesh shape, and —
    with --model-dir — the per-bucket placement plan a server loading
    those artifacts would use."""
    from gordo_tpu.mesh import FleetMesh

    try:
        fm = FleetMesh.resolve(mesh_devices, data_parallel=data_parallel)
    except ValueError as exc:
        raise click.BadParameter(str(exc), param_hint="--mesh-devices")
    doc = fm.describe()
    if model_dir:
        from gordo_tpu.serve.server import ModelCollection
        from gordo_tpu.serve.shard import ShardSpec

        shard_spec = None
        if shard:
            try:
                shard_spec = ShardSpec.parse(shard)
            except ValueError as exc:
                raise click.BadParameter(str(exc), param_hint="--shard")
        try:
            collection = ModelCollection.from_directory(
                model_dir, serve_mesh=fm.mesh, shard=shard_spec
            )
        except FileNotFoundError as exc:
            raise click.ClickException(str(exc))
        plan = []
        for i, bucket in enumerate(collection.fleet_scorer.buckets):
            shards = (
                bucket.mesh.shape["models"] if bucket.mesh is not None else 1
            )
            entry = {
                "bucket": i,
                "machines": len(bucket.names),
                "fleet-rows-padded": bucket.m_pad,
                "model-shards": shards,
                "sharded": bucket.mesh is not None,
            }
            if bucket.mesh is not None:
                per = bucket.m_pad // shards
                # devices grid is (models, data); every device in models
                # row j holds the same model-slot range
                entry["per-device-slots"] = {
                    str(d.id): [j * per, (j + 1) * per]
                    for j in range(shards)
                    for d in bucket.mesh.devices[j].reshape(-1)
                }
            plan.append(entry)
        doc["buckets"] = plan
    click.echo(json.dumps(doc, indent=1))


# ---------------------------------------------------------------------------
# artifacts (format v2 pack tooling)
# ---------------------------------------------------------------------------

@gordo.group("artifacts")
def artifacts_group():
    """Artifact-plane tooling: inspect, repack (v1 → v2), unpack (v2 → v1)."""


@artifacts_group.command("info")
@click.option("--dir", "output_dir", required=True,
              help="A build output dir (either format, or mixed).")
def artifacts_info(output_dir):
    """Print what backs the artifacts under --dir (format, machine and
    pack counts, pack bytes) as JSON."""
    from gordo_tpu import artifacts

    try:
        click.echo(json.dumps(artifacts.store_info(output_dir), indent=1))
    except artifacts.PackError as exc:
        raise click.ClickException(str(exc))


@artifacts_group.command("repack")
@click.option("--dir", "output_dir", required=True,
              help="A v1 (or mixed) build output dir to convert in place.")
@click.option("--max-bucket-size", default=512, show_default=True,
              help="Max machines per pack (the (signature, bucket) chunk "
                   "size).")
@click.option("--keep-dirs", is_flag=True,
              help="Leave the converted per-machine dirs on disk (the pack "
                   "index is authoritative either way).")
def artifacts_repack(output_dir, max_bucket_size, keep_dirs):
    """Convert v1 per-machine dirs to v2 memory-mapped packs in place.
    Machines whose models can't fuse into a stacked serving chain stay
    as v1 dirs — every reader handles the mixed layout."""
    from gordo_tpu import artifacts

    try:
        summary = artifacts.repack(
            output_dir, max_bucket_size=max_bucket_size, keep_dirs=keep_dirs
        )
    except artifacts.PackError as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps(
        {"packs": summary["packs"],
         "packed": len(summary["packed"]),
         "kept_as_dirs": summary["kept_as_dirs"]}
    ))


@artifacts_group.command("unpack")
@click.option("--dir", "output_dir", required=True,
              help="A v2 build output dir (its pack index is read).")
@click.option("--dest", required=True,
              help="Directory to write v1 per-machine artifact dirs into.")
def artifacts_unpack(output_dir, dest):
    """Export every packed machine back to v1 per-machine dirs (the
    compatibility direction: external tooling that walks artifact dirs
    keeps working against an export)."""
    from gordo_tpu import artifacts

    try:
        written = artifacts.unpack(output_dir, dest)
    except artifacts.PackError as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps({"unpacked": len(written), "dest": dest}))


@artifacts_group.command("gc")
@click.option("--dir", "output_dir", required=True,
              help="A v2 build output dir (its pack index is read).")
@click.option("--keep", default=2, show_default=True,
              help="Generation records to retain (newest first). The "
                   "live generation always survives; retired pack files "
                   "no retained generation references are unlinked.")
def artifacts_gc(output_dir, keep):
    """Prune artifact-generation history and the retired pack files it
    kept reloadable.  Builds and delta writes retire superseded packs
    instead of deleting them (so any retained generation stays loadable
    for rollback); this reclaims the disk once the history is no longer
    wanted.  Refuses --keep 0: the live generation is never collectable.
    Set GORDO_GC_KEEP to auto-prune on every build's generation stamp."""
    from gordo_tpu import artifacts

    try:
        summary = artifacts.gc_generations(output_dir, keep)
    except (artifacts.PackError, ValueError) as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps(summary, indent=1))


@artifacts_group.command("flip")
@click.option("--dir", "output_dir", required=True,
              help="A v2 build output dir (its pack index is read).")
def artifacts_flip(output_dir):
    """Force-publish a new artifact generation, republishing every
    machine row.  The operator heal path when pack bytes were restored
    out-of-band (e.g. copied back from a healthy replica): no build
    wrote pending rows, so the ordinary stamp is a no-op, yet serving
    replicas only re-validate — and drop a quarantine — when the
    published generation advances.  A no-op on stores with no machines."""
    from gordo_tpu import artifacts

    try:
        gen = artifacts.stamp_generation(output_dir, force=True)
    except artifacts.PackError as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps({"generation": gen}))


@artifacts_group.command("fsck")
@click.option("--dir", "output_dir", required=True,
              help="A build output dir (either format, or mixed).")
@click.option("--repair", is_flag=True,
              help="Fix what is safely fixable: unlink orphaned tmp files "
                   "from dead writers, restamp a stale GENERATION sidecar. "
                   "Corrupt packs are never 'repaired' — they are reported "
                   "(and quarantined by a serving load).")
def artifacts_fsck(output_dir, repair):
    """Verify every artifact invariant under --dir — index rows resolve,
    pack files exist with the recorded size, meta sidecars parse, tensor
    extents stay inside the pack — and report findings as JSON.  The
    server runs this automatically (with repair) at startup; exits
    non-zero when unrepaired findings remain."""
    from gordo_tpu import artifacts

    try:
        report = artifacts.fsck(output_dir, repair=repair)
    except artifacts.PackError as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps(report, indent=1))
    if not report["ok"]:
        raise SystemExit(1)


# ---------------------------------------------------------------------------
# scores (score-archive lifecycle tooling)
# ---------------------------------------------------------------------------

@gordo.group("scores")
def scores_group():
    """Score-archive lifecycle: compact, gc, inspect (ls/stat)."""


@scores_group.command("compact")
@click.option("--dir", "archive_dir", required=True,
              help="A backfill output dir (holds .gordo-scores/).")
@click.option("--period", default=None, envvar="GORDO_SCORES_PERIOD",
              help="Time-partition length to merge chunk segments into "
                   "(any pandas Timedelta string). "
                   "[default: GORDO_SCORES_PERIOD or 1d]")
@click.option("--dry-run", is_flag=True,
              help="Report what would merge without writing anything.")
def scores_compact(archive_dir, period, dry_run):
    """Merge small per-chunk score segments into one period file per
    closed time partition.  Crash-safe (write-new-then-flip under the
    index flock): a kill mid-compact never loses a completed period,
    and reads are byte-identical before and after.  Re-run to resume."""
    from gordo_tpu import batch

    try:
        summary = batch.compact_scores(
            archive_dir, period=period, dry_run=dry_run
        )
    except (batch.ArchiveError, ValueError) as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps(summary, indent=1))


@scores_group.command("gc")
@click.option("--dir", "archive_dir", required=True,
              help="A backfill output dir (holds .gordo-scores/).")
@click.option("--keep", default=None, type=float,
              envvar="GORDO_SCORES_KEEP",
              help="Days of score history to retain; segments whose "
                   "entire window is older are deleted. Refuses "
                   "--keep < 1. [default: GORDO_SCORES_KEEP or 90]")
def scores_gc(archive_dir, keep):
    """Prune score segments past the retention window, mirroring
    ``gordo artifacts gc``: the index flips before any unlink (readers
    never follow a record to a missing file) and completion records
    survive as ``pruned`` so a backfill resume does not re-score —
    and resurrect — retired windows."""
    from gordo_tpu import batch

    try:
        summary = batch.gc_scores(archive_dir, keep_days=keep)
    except (batch.ArchiveError, ValueError) as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps(summary, indent=1))


@scores_group.command("ls")
@click.option("--dir", "archive_dir", required=True,
              help="A backfill output dir (holds .gordo-scores/).")
def scores_ls(archive_dir):
    """List every data segment (chunk and compacted period files) with
    rows and on-disk bytes — what compaction and gc actually did."""
    from gordo_tpu import batch

    try:
        listing = batch.ls_scores(archive_dir)
    except batch.ArchiveError as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps(listing, indent=1))


@scores_group.command("stat")
@click.option("--dir", "archive_dir", required=True,
              help="A backfill output dir (holds .gordo-scores/).")
@click.option("--period", default=None, envvar="GORDO_SCORES_PERIOD",
              help="Partition length used to compute pending-compaction."
                   " [default: GORDO_SCORES_PERIOD or 1d]")
def scores_stat(archive_dir, period):
    """One-document archive state: plan, segment/byte totals by kind,
    period coverage, pruned windows, pending compaction work."""
    from gordo_tpu import batch

    try:
        doc = batch.stat_scores(archive_dir, period=period)
    except (batch.ArchiveError, ValueError) as exc:
        raise click.ClickException(str(exc))
    click.echo(json.dumps(doc, indent=1))


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

@gordo.group("telemetry")
def telemetry_group():
    """Observability plane: metric snapshots and scrapes."""


@telemetry_group.command("dump")
@click.option("--dir", "snapshot_dir", default=None,
              help="Merge the shard-local snapshots a project build wrote "
                   "under DIR (a build --output-dir, or its "
                   ".gordo-telemetry/ subdir directly) and print the "
                   "merged result.")
@click.option("--url", "scrape_url", default=None,
              help="Scrape a live server's /metrics (base URL or full "
                   "/metrics URL) and print it.")
@click.option("--format", "output_format",
              type=click.Choice(["prom", "json"]), default="prom",
              show_default=True,
              help="Output format: Prometheus text exposition, or the "
                   "JSON snapshot document (merge-able with "
                   "telemetry.merge_snapshots). A live /metrics scrape "
                   "only speaks prom.")
def telemetry_dump(snapshot_dir, scrape_url, output_format):
    """Print a metrics snapshot.

    Default (no option): this process's own registry — mostly useful under
    ``GORDO_SPAN_LOG``/scripted use.  ``--dir`` merges a (multi-host)
    build's shard-local snapshot files; ``--url`` scrapes a live server.
    ``--format prom`` (default) prints the Prometheus text exposition,
    ``--format json`` the JSON snapshot document.
    """
    if snapshot_dir and scrape_url:
        raise click.UsageError("--dir and --url are mutually exclusive")
    if snapshot_dir:
        candidates = [
            os.path.join(snapshot_dir, telemetry.SNAPSHOT_DIR),
            snapshot_dir,
        ]
        snaps = []
        for directory in candidates:
            snaps = telemetry.load_snapshot_dir(directory)
            if snaps:
                break
        if not snaps:
            raise click.ClickException(
                f"no telemetry snapshots under {candidates}"
            )
        merged = telemetry.merge_snapshots(snaps)
        if output_format == "json":
            click.echo(json.dumps(merged, indent=1, sort_keys=True))
        else:
            click.echo(telemetry.render_snapshot(merged), nl=False)
        return
    if scrape_url:
        if output_format == "json":
            # a /metrics scrape is already-rendered text; recovering the
            # snapshot document from it would be a lossy reparse
            raise click.UsageError(
                "--format json is not available with --url (the scrape "
                "surface speaks Prometheus text); use --dir or the "
                "default registry dump"
            )
        import urllib.request

        url = scrape_url.rstrip("/")
        if not url.endswith("/metrics"):
            url += "/metrics"
        try:
            with urllib.request.urlopen(url, timeout=10) as resp:
                click.echo(resp.read().decode(), nl=False)
        except Exception as exc:
            raise click.ClickException(f"scrape {url} failed: {exc}")
        return
    if output_format == "json":
        click.echo(
            json.dumps(telemetry.REGISTRY.snapshot(), indent=1,
                       sort_keys=True)
        )
        return
    click.echo(telemetry.render(), nl=False)


# ---------------------------------------------------------------------------
# fleet health
# ---------------------------------------------------------------------------

@gordo.command("fleet-health")
@click.option("--url", default=None,
              help="Live surface: an ML-server base URL (the per-replica "
                   "doc; merged fleet-wide when pointed at a watchman) — "
                   "tries /gordo/v0/<project>/fleet-health, then the "
                   "watchman's /fleet-health.")
@click.option("--dir", "rollup_dir", default=None,
              help="File surface: an artifact dir holding the rollup "
                   "JSONL files serving processes append "
                   "(.gordo-fleet-health/); the latest doc per "
                   "process/shard is merged.")
@click.option("--project", envvar="PROJECT_NAME", default="project",
              show_default=True)
@click.option("--top", default=10, show_default=True,
              help="How many machines the drift ranking lists.")
@click.option("--full/--summary", default=False, show_default=True,
              help="--full prints the whole per-machine document "
                   "(sketches included); the default summary prints "
                   "counts by status and the top-drift ranking.")
def fleet_health_cmd(url, rollup_dir, project, top, full):
    """Which machines are drifting, scoring hot, or silent?

    Prints the fleet-health document (docs/observability.md "Fleet
    health"): per-machine live anomaly-score sketches vs their
    training-time baselines, drift scores, and statuses — from a live
    server/watchman (``--url``) or from the rollup files under an
    artifact dir (``--dir``, no HTTP needed).
    """
    if bool(url) == bool(rollup_dir):
        raise click.UsageError("provide exactly one of --url or --dir")
    if rollup_dir:
        doc = telemetry.read_rollups(rollup_dir, top=top)
        if doc is None:
            raise click.ClickException(
                f"no fleet-health rollups under {rollup_dir!r} "
                f"(is the server writing them? GORDO_HEALTH_ROLLUP_SECONDS)"
            )
    else:
        import urllib.error
        import urllib.request

        base = url.rstrip("/")
        candidates = [
            f"{base}/gordo/v0/{project}/fleet-health?top={int(top)}",
            f"{base}/fleet-health?top={int(top)}",  # watchman surface
        ]
        doc = None
        last_err = None
        for candidate in candidates:
            try:
                with urllib.request.urlopen(candidate, timeout=30) as resp:
                    doc = json.loads(resp.read().decode())
                break
            except Exception as exc:  # 404 on a watchman, conn errors
                last_err = exc
        if doc is None:
            raise click.ClickException(
                f"fleet-health fetch failed from {candidates}: {last_err}"
            )
    if full:
        click.echo(json.dumps(doc, indent=1, sort_keys=True))
        return
    by_status: Dict[str, int] = {}
    for entry in (doc.get("machines") or {}).values():
        by_status[entry.get("status", "?")] = (
            by_status.get(entry.get("status", "?"), 0) + 1
        )
    summary = {
        "machines": len(doc.get("machines") or {}),
        "by-status": dict(sorted(by_status.items())),
        "drift-threshold": doc.get("drift-threshold"),
        "top-drift": doc.get("top-drift", []),
    }
    click.echo(json.dumps(summary, indent=1, sort_keys=True))


# ---------------------------------------------------------------------------
# refresh (drift-driven incremental rebuilds)
# ---------------------------------------------------------------------------

@gordo.command("refresh")
@click.option("--machine-config", required=True, envvar="MACHINE_CONFIG",
              help="Project YAML (text or file) with machines/globals — "
                   "the machines this refresh deployment may rebuild.")
@click.option("--project-name", envvar="PROJECT_NAME", default="project")
@click.option("--output-dir", envvar="OUTPUT_DIR", default="./models")
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR",
              default=None)
@click.option("--health-url", default=None,
              help="HTTP health surface (server or watchman base URL). "
                   "Default: the rollup JSONL files under --output-dir "
                   "(.gordo-fleet-health/) — no HTTP needed.")
@click.option("--server-url", default=None,
              help="Server base URL to confirm the rebuilt generation "
                   "went live on (client wait_for_generation handshake). "
                   "Default: publish without confirmation.")
@click.option("--once", is_flag=True,
              help="Run exactly one poll→select→rebuild cycle and exit "
                   "(the CronJob face; hysteresis streaks persist under "
                   "<output-dir>/.gordo-refresh/state.json).")
@click.option("--interval", default=None, type=click.FloatRange(min=0),
              help="Seconds between cycles in the continuous loop "
                   "[default: GORDO_REFRESH_INTERVAL or 300].")
@click.option("--hysteresis", default=None, type=click.IntRange(min=1),
              help="Consecutive drifting observations before a machine "
                   "is rebuilt [default: GORDO_REFRESH_HYSTERESIS or 2].")
@click.option("--cooldown-seconds", default=None,
              type=click.FloatRange(min=0),
              help="Per-machine seconds between rebuilds "
                   "[default: GORDO_REFRESH_COOLDOWN_SECONDS or 900].")
@click.option("--wait-timeout", default=120.0, show_default=True,
              type=click.FloatRange(min=1),
              help="Seconds to wait for the generation flip to be "
                   "confirmed live (--server-url).")
def refresh_cmd(machine_config, project_name, output_dir,
                model_register_dir, health_url, server_url, once, interval,
                hysteresis, cooldown_seconds, wait_timeout):
    """Rebuild ONLY the drifting machines, warm-started from the live
    generation — O(drifted) instead of O(fleet).

    Polls fleet health (rollup files or --health-url), selects machines
    observed ``status=drifting`` on K consecutive polls and outside
    their cooldown, warm-starts a subset rebuild from the previous
    generation's params (per-machine cold fallback under the loss-parity
    gate), and publishes through the artifact plane's delta path so live
    servers hot-reload exactly the touched packs.  One summary JSON line
    per cycle on stdout.
    """
    from gordo_tpu.refresh import RefreshConfig, refresh_once
    from gordo_tpu.workflow.config import NormalizedConfig

    config = NormalizedConfig.from_source(machine_config, project_name)
    cfg = RefreshConfig(
        machines=config.machines,
        output_dir=output_dir,
        model_register_dir=model_register_dir,
        project=project_name,
        health_url=health_url,
        server_url=server_url,
        hysteresis=hysteresis,
        cooldown_seconds=cooldown_seconds,
        wait_timeout=wait_timeout,
    )
    if once:
        summary = refresh_once(cfg)
        click.echo(json.dumps(summary, sort_keys=True))
        if summary.get("outcome") == "failed":
            sys.exit(1)
        return

    import time

    from gordo_tpu.refresh.loop import (
        DEFAULT_INTERVAL,
        ENV_INTERVAL,
        DriftSelector,
        state_path,
    )

    if interval is None:
        try:
            interval = float(os.environ.get(ENV_INTERVAL, "")
                             or DEFAULT_INTERVAL)
        except ValueError:
            interval = DEFAULT_INTERVAL
    # one selector for the whole loop: streaks span cycles in-process
    # (run_refresh does the same; inlined here for the per-cycle echo)
    selector = DriftSelector.load(
        state_path(output_dir), hysteresis=hysteresis,
        cooldown_seconds=cooldown_seconds,
    )
    while True:
        summary = refresh_once(cfg, selector=selector)
        click.echo(json.dumps(summary, sort_keys=True))
        time.sleep(interval)


# ---------------------------------------------------------------------------
# backfill (offline historical scoring → columnar archive)
# ---------------------------------------------------------------------------

@gordo.command("backfill")
@click.option("--model-dir", envvar="MODEL_LOCATION", default="./models",
              show_default=True,
              help="Artifact directory holding the fleet's built models "
                   "(the same directory run-server scans).")
@click.option("--archive-dir", envvar="GORDO_BACKFILL_ARCHIVE_DIR",
              default=None,
              help="Archive destination root (scores land under "
                   "<archive-dir>/.gordo-scores/) [default: --model-dir].")
@click.option("--project-name", envvar="PROJECT_NAME", default="project")
@click.option("--start", required=True,
              help="Inclusive start of the historical range (ISO-8601; "
                   "tz-naive is taken as UTC).")
@click.option("--end", required=True,
              help="Exclusive end of the historical range (ISO-8601).")
@click.option("--machines", default=None,
              help="Comma-separated machine subset [default: every "
                   "machine discovered under --model-dir].")
@click.option("--shard", default=None, envvar="GORDO_BACKFILL_SHARD",
              help="'i/N' — score only this shard's deterministic "
                   "partition of the fleet (same partitioner the serving "
                   "tier shards with). Indexed Jobs wire the pair "
                   "GORDO_BACKFILL_SHARD_INDEX/GORDO_BACKFILL_NUM_SHARDS "
                   "instead.")
@click.option("--chunk-rows", default=None, type=click.IntRange(min=1),
              envvar="GORDO_BACKFILL_CHUNK_ROWS",
              help="Rows per staged chunk (the unit of resumability and "
                   "of host→device transfer) [default: "
                   "GORDO_BACKFILL_CHUNK_ROWS or 2048].")
@click.option("--max-chunks", default=None, type=click.IntRange(min=1),
              help="Stop after N chunks this invocation (checkpoint-and-"
                   "yield for preemptible capacity; exits resumable).")
def backfill_cmd(model_dir, archive_dir, project_name, start, end,
                 machines, shard, chunk_rows, max_chunks):
    """Score a historical time range for the whole fleet offline.

    Loads every model from --model-dir (no server, no HTTP), fetches
    each machine's sensor frame from its dataset provider, stages
    fixed-row chunks through the compile plane's fused fleet programs
    at the configured serving dtype, and appends columnar segments to
    the ``.gordo-scores/`` archive.  Completed chunks are durable: a
    killed run re-invoked with the same range resumes from its
    completion records and converges on a byte-identical archive.
    Exits 75 (EX_TEMPFAIL) when progress was archived but the range is
    not finished — supervisors should simply re-run.
    """
    from gordo_tpu.batch import BackfillConfig, BackfillError, run_backfill
    from gordo_tpu.distributed.partition import EXIT_SHARD_RESUMABLE

    machine_list = None
    if machines:
        machine_list = [m.strip() for m in machines.split(",") if m.strip()]
    cfg = BackfillConfig(
        model_dir=model_dir,
        start=start,
        end=end,
        archive_dir=archive_dir,
        project=project_name,
        machines=machine_list,
        shard=shard,
        chunk_rows=chunk_rows,
        max_chunks=max_chunks,
    )
    try:
        summary = run_backfill(cfg)
    except BackfillError as exc:
        # completed chunks are already fsync'd behind their completion
        # records — a re-run resumes, so this is EX_TEMPFAIL, not a crash
        logger.error("backfill interrupted (resumable): %s", exc)
        _RESUMABLE_EXITS_TOTAL.inc(1.0, "backfill")
        sys.exit(EXIT_SHARD_RESUMABLE)
    click.echo(json.dumps(summary, sort_keys=True))
    if summary.get("remaining", 0) > 0:
        # --max-chunks checkpoint-and-yield: archived progress, more to do
        _RESUMABLE_EXITS_TOTAL.inc(1.0, "backfill")
        sys.exit(EXIT_SHARD_RESUMABLE)


# ---------------------------------------------------------------------------
# workflow
# ---------------------------------------------------------------------------

@gordo.group("workflow")
def workflow_group():
    """Project-config driven orchestration documents."""


@workflow_group.command("generate")
@click.option("--machine-config", required=True, envvar="MACHINE_CONFIG")
@click.option("--project-name", envvar="PROJECT_NAME", default="project")
@click.option("--image", default="gordo-tpu", show_default=True)
@click.option("--server-replicas", default=1, show_default=True)
@click.option("--server-arg", "server_args", multiple=True,
              help="Extra 'gordo run-server' flag for the ml-server "
                   "Deployment; repeatable (e.g. --server-arg=--coalesce-ms "
                   "--server-arg=2 --server-arg=--model-parallel).")
@click.option("--format", "fmt", type=click.Choice(["k8s", "argo"]),
              default="k8s", show_default=True,
              help="k8s: builder Job + server/watchman Deployments. argo: "
                   "an argoproj Workflow DAG (one task per fleet chunk) "
                   "plus the serving manifests — for clusters whose "
                   "tooling consumes Argo documents.")
@click.option("--multihost", default=None, type=click.IntRange(min=1),
              help="Emit the builder as an N-process Indexed Job "
                   "(jax.distributed over N pods, GORDO_* env wiring, "
                   "deterministic machine shards). Refused when N exceeds "
                   "the plan's machine-shard count.")
@click.option("--scrape-annotations/--no-scrape-annotations", default=True,
              show_default=True,
              help="Stamp prometheus.io/{scrape,port,path} discovery "
                   "annotations on the server and watchman pod templates "
                   "so their /metrics endpoints are scraped without extra "
                   "cluster config.")
@click.option("--serve-dtype", default=None,
              help="Serving precision (fp32/bf16; int8 needs the "
                   "GORDO_SERVE_INT8 opt-in at runtime): stamps "
                   "GORDO_SERVE_DTYPE on builder AND server pods so the "
                   "warmup manifest, AOT warmup, and request dispatch all "
                   "agree. Only use after the fp32 parity suite passes "
                   "for this project's model family (docs/perf.md).")
@click.option("--serve-shards", default=None, type=click.IntRange(min=1),
              help="Emit the serving tier fleet-sharded across N "
                   "replicas: one Deployment+Service per shard "
                   "(GORDO_SERVE_SHARD=i/N), an HPA per shard driven by "
                   "the coalescer's queue-wait/service-time ratio gauge, "
                   "and per-machine Mappings routed to the owning shard. "
                   "Refused when N exceeds the machine count.")
@click.option("--hpa-max-replicas", default=4, show_default=True,
              type=click.IntRange(min=1),
              help="maxReplicas of each shard's HPA (--serve-shards).")
@click.option("--refresh-cron", default=None,
              help="5-field cron schedule: additionally emit a CronJob "
                   "running 'gordo refresh --once' against the same "
                   "models PVC + project config as the builder — the "
                   "drift-driven incremental rebuild loop. Refused when "
                   "the builder has no models volume to warm-start "
                   "from, or when the schedule is malformed.")
@click.option("--backfill", nargs=2, default=None, metavar="START END",
              help="Additionally emit an Indexed Job running 'gordo "
                   "backfill' over this half-open [START, END) range "
                   "against the builder's models PVC — offline fleet "
                   "scoring into the .gordo-scores/ archive. Refused "
                   "when the range is malformed or the builder has no "
                   "models volume.")
@click.option("--backfill-shards", default=1, show_default=True,
              type=click.IntRange(min=1),
              help="Fan the backfill Job out across N Indexed pods "
                   "(GORDO_BACKFILL_SHARD_INDEX/NUM_SHARDS env wiring; "
                   "deterministic machine partition). Refused when N "
                   "exceeds the machine count.")
@click.option("--output-file", type=click.File("w"), default="-")
def workflow_generate(machine_config, project_name, image, server_replicas,
                      server_args, fmt, multihost, scrape_annotations,
                      serve_dtype, serve_shards, hpa_max_replicas,
                      refresh_cron, backfill, backfill_shards, output_file):
    """Render the kubernetes manifests + fleet build plan (reference:
    the Argo workflow template render)."""
    from gordo_tpu.workflow import (
        NormalizedConfig,
        generate_workflow,
        workflow_to_yaml,
    )

    config = NormalizedConfig.from_source(machine_config, project_name)
    if multihost and fmt == "argo":
        raise click.BadParameter(
            "--multihost applies to the k8s Indexed-Job builder; the argo "
            "format's DAG already fans out one task per fleet chunk",
            param_hint="--multihost",
        )
    try:
        docs = generate_workflow(
            config, image=image, server_replicas=server_replicas,
            server_args=list(server_args), multihost=multihost,
            scrape_annotations=scrape_annotations,
            serve_dtype=serve_dtype,
            serve_shards=serve_shards,
            hpa_max_replicas=hpa_max_replicas,
            refresh_cron=refresh_cron,
            backfill=tuple(backfill) if backfill else None,
            backfill_shards=backfill_shards,
        )
    except ValueError as exc:
        raise click.ClickException(str(exc))
    if fmt == "argo":
        from gordo_tpu.workflow.generator import generate_argo_workflow

        # the Argo Workflow replaces the builder Job; serving manifests
        # (Deployments/Services/Mappings/plan ConfigMap) stay as-is
        try:
            argo = generate_argo_workflow(
                config, image=image, serve_dtype=serve_dtype
            )
        except ValueError as exc:
            raise click.ClickException(str(exc))
        docs = [argo] + [d for d in docs if d.get("kind") != "Job"]
    output_file.write(workflow_to_yaml(docs))


@workflow_group.command("plan")
@click.option("--machine-config", required=True, envvar="MACHINE_CONFIG")
@click.option("--project-name", envvar="PROJECT_NAME", default="project")
@click.option("--max-bucket-size", default=512, show_default=True)
@click.option("--align-lengths", default=None, type=click.IntRange(min=2),
              help="Plan for a build run with this --align-lengths value "
                   "(cache keys include it; silences the ragged-compile "
                   "warning).")
@click.option("--pad-lengths", default=None, type=click.IntRange(min=2),
              help="Plan for a build run with this --pad-lengths value "
                   "(cache keys include it; silences the ragged-compile "
                   "warning).")
def workflow_plan(machine_config, project_name, max_bucket_size,
                  align_lengths, pad_lengths):
    """Print the bucketed fleet build plan as YAML.

    When the configs predict a ragged fleet (multiple distinct train
    lengths per bucket) and neither --align-lengths nor --pad-lengths is
    planned, prints the estimated per-distinct-length compile bill to
    stderr — the dry run is where that cost should surface, not an hour
    into the build."""
    from gordo_tpu.workflow import NormalizedConfig, build_plan

    config = NormalizedConfig.from_source(machine_config, project_name)
    plan = build_plan(
        config, max_bucket_size=max_bucket_size,
        align_lengths=align_lengths, pad_lengths=pad_lengths,
    )
    click.echo(yaml.safe_dump(plan))
    warning = plan.get("ragged_compile_warning")
    if warning:
        click.echo(
            "WARNING: ragged fleet — ~{n} distinct train lengths predicted "
            "→ ~{extra} extra XLA compiles ≈ {secs}s of compile time. "
            "{hint}".format(
                n=warning["estimated_distinct_lengths"],
                extra=warning["estimated_extra_compiles"],
                secs=warning["estimated_extra_compile_seconds"],
                hint=warning["hint"],
            ),
            err=True,
        )


@workflow_group.command("unique-tags")
@click.option("--machine-config", required=True, envvar="MACHINE_CONFIG")
@click.option("--output-file-tag-list", type=click.File("w"), default="-")
def workflow_unique_tags(machine_config, output_file_tag_list):
    """List distinct sensor tags across the project (reference parity)."""
    from gordo_tpu.workflow import NormalizedConfig, unique_tags

    config = NormalizedConfig.from_source(machine_config)
    for tag in unique_tags(config.machines):
        output_file_tag_list.write(f"{tag}\n")


if __name__ == "__main__":
    gordo()
