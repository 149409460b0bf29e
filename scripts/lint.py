#!/usr/bin/env python
"""Self-contained stdlib linter — the ``make lint`` backend.

This image ships no flake8/ruff/pyflakes and has no network, so the local
lint gate is built on ``ast``: syntax errors, unused imports, wildcard
imports, duplicate function/class definitions in a scope, mutable default
arguments, ``except:`` bare clauses, and telemetry metric names violating
the ``gordo_[a-z_]+`` catalog convention (any literal first argument to a
``counter``/``gauge``/``histogram`` registration call — the same pattern
``telemetry.metrics`` enforces at runtime, caught here before anything
runs).  CI additionally runs flake8 (installable on GitHub runners — see
.github/workflows/ci.yml); this script is the everywhere-runnable subset.

Usage: python scripts/lint.py PATH [PATH ...]   (exit 1 on findings)
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Iterator, List, Tuple

Finding = Tuple[str, int, str]

#: must match gordo_tpu.telemetry.metrics.NAME_RE (kept literal here so
#: the linter stays import-free and runs on any checkout)
METRIC_NAME_RE = re.compile(r"^gordo_[a-z_]+$")
#: registration entrypoints whose first literal argument is a metric name
METRIC_FACTORIES = {"counter", "gauge", "histogram"}

#: latency-critical drive loops and dispatch windows, by file basename →
#: function names: the build-pipeline drive loop, the coalescer's drain
#: thread, and (r23) the fleet-build DISPATCH window — everything between
#: launching chunk k+1's program and collecting chunk k.  A blocking
#: device→host transfer there stalls EVERY stage behind it (the drain
#: thread can't gather the next batch; the drive loop can't stage the
#: next chunk; a fetch inside dispatch serializes the overlap the
#: dispatch/collect split exists to create), so direct D2H calls are
#: design bugs in these scopes — results must flow through the collect
#: side (``PendingFleetBuild.collect`` / ``_finish_bucket``) or the
#: writer/finish pools instead.  ``# noqa`` opts a line out, as
#: elsewhere.
D2H_FORBIDDEN_SCOPES = {
    "fleet_build.py": {"_drive_pipeline", "_dispatch_bucket",
                       "_dispatch_chunk"},
    "coalesce.py": {"_run", "_drain"},
    "anomaly.py": {"dispatch", "_dispatch_group",
                   "_dispatch_exact_length_groups", "_dispatch_padded"},
}
#: attribute calls that force a blocking device→host transfer
D2H_BLOCKING_ATTRS = {"device_get", "block_until_ready"}
#: bare-name calls that do the same (gordo_tpu.utils.trees.to_host)
D2H_BLOCKING_NAMES = {"to_host"}
#: modules whose ``.asarray(...)`` materializes a jax array on host
D2H_ASARRAY_MODULES = {"np", "numpy"}

#: request-path host-math gate (serve/): between decode and dispatch a
#: request's data must not be computed on with host numpy — padding,
#: scaling, windowing, thresholds and confidence all live INSIDE the
#: fused device programs now, and host np compute creeping back in is
#: exactly the regression this PR removed (r11: concatenate/tile padding
#: and a host confidence divide per request).  Scoped to the dispatch/
#: epilogue functions; ``np.asarray`` wraps, buffer fills, and the
#: explicitly-named legacy kill-switch helpers are the decode side and
#: stay allowed.  ``# noqa`` opts a line out, as elsewhere.
HOST_MATH_FORBIDDEN_SCOPES = {
    "scorer.py": {"_run", "predict", "anomaly_arrays"},
    "fleet_scorer.py": {"score", "score_subset", "assemble",
                        "assemble_columnar"},
}
HOST_MATH_MODULES = {"np", "numpy"}
HOST_MATH_CALLS = {
    "concatenate", "tile", "stack", "vstack", "hstack", "repeat", "pad",
    "maximum", "minimum", "clip", "where", "abs", "divide", "multiply",
    "add", "subtract", "median", "percentile", "mean", "sum", "matmul",
    "dot", "einsum",
}
SERVE_DIR = os.path.join("gordo_tpu", "serve")

#: bulk-wire hot-loop contract (r19): the bulk encode/decode paths move
#: stacked blocks and (machine → extent) maps — building a per-machine
#: pandas frame inside them reintroduces the ~35x frame-materialization
#: wall BENCH_r18 measured (264k samples/s against a 9.4M/s wire floor).
#: Frames belong behind the client's LazyFrame (first-access
#: materialization), never inside the bulk request/response loops.
#: ``# noqa`` opts a line out, as elsewhere.
BULK_FRAME_FORBIDDEN_SCOPES = {
    "server.py": {"bulk_anomaly_prediction"},
    "codec.py": {"encode_columnar", "decode_columnar"},
    "fleet_scorer.py": {"assemble", "assemble_columnar"},
    "client.py": {"_predict_bulk"},
}
BULK_FRAME_MODULES = {"pd", "pandas"}
BULK_FRAME_CALLS = {"DataFrame", "concat"}
#: bare-name calls that materialize a frame (the client's own builder)
BULK_FRAME_NAMES = {"DataFrame", "_frame_from_payload"}
BULK_FRAME_DIRS = (
    os.path.join("gordo_tpu", "serve"),
    os.path.join("gordo_tpu", "client"),
)

#: the ONE module family allowed to touch jax.jit directly: the compile
#: plane (gordo_tpu/compile/) owns every jitted program in the stack —
#: register through compile.program (AOT serving path) or compile.jit
#: (passthrough) instead.  Tests are allowlisted (they jit ad-hoc probe
#: functions); ``# noqa`` opts a line out, as elsewhere.
JIT_ALLOWED_DIR = os.path.join("gordo_tpu", "compile")

#: per-machine artifact path construction is owned by the artifact plane:
#: only gordo_tpu/artifacts/ (both formats behind one API), the
#: serializer (which defines the v1 layout) and the builder (the v1
#: write path) may reference the per-machine artifact file names.  Any
#: other product code joining "<dir>/<machine>/model.pkl" bypasses the
#: v2 pack index and silently grows a third layout.
ARTIFACT_PATH_ALLOWED_DIRS = (
    os.path.join("gordo_tpu", "artifacts"),
    os.path.join("gordo_tpu", "serializer"),
    os.path.join("gordo_tpu", "builder"),
)
ARTIFACT_FILE_LITERALS = {"model.pkl", "metadata.json", "definition.yaml"}
ARTIFACT_FILE_ATTRS = {"MODEL_FILE", "METADATA_FILE", "DEFINITION_FILE"}

#: gordo_tpu/artifacts/ load-path contract: packs load ZERO-COPY (memmap
#: views — no host stack/concat copies) and ship to the device through
#: exactly one call site, the function named ``to_device`` (the counted
#: transfer behind the "one device_put per pack" acceptance gate).
ARTIFACTS_DIR = os.path.join("gordo_tpu", "artifacts")
ARTIFACTS_COPY_CALLS = {"stack", "concatenate", "vstack", "hstack"}
ARTIFACTS_DEVICE_PUT_FN = "to_device"

#: placement single-owner contract (r22): device meshes and shardings are
#: owned by gordo_tpu/mesh/ — raw ``jax.device_put`` and any
#: ``jax.sharding.*`` construction/import outside the placement plane
#: (and the artifact plane's ``to_device``, policed separately above)
#: bypasses the counted ``place()`` seam and the mesh the compile plane
#: keys executables on.  Tests are allowlisted (they probe placement
#: directly); ``# noqa`` opts a line out, as elsewhere.
MESH_DIR = os.path.join("gordo_tpu", "mesh")

#: serve-path shard contract: the machine→replica partition has exactly
#: ONE implementation (gordo_tpu/serve/shard.py, wrapping the builder's
#: partition_machines).  Server, client, watchman and the workflow
#: generator all compute it locally, so a second implementation that
#: drifts by one machine silently misroutes that machine forever —
#: reject direct partition_machines use AND ad-hoc shard arithmetic
#: (``... % n_shards``, ``hash(name) % ...``) anywhere on the serve path
#: outside the one module.
SHARD_FN_MODULE = os.path.join("gordo_tpu", "serve", "shard.py")
SHARD_PATH_DIRS = (
    os.path.join("gordo_tpu", "serve"),
    os.path.join("gordo_tpu", "client"),
    os.path.join("gordo_tpu", "watchman"),
    os.path.join("gordo_tpu", "workflow"),
)

#: degraded-mode contract on the serving/artifact planes: a swallowed
#: exception (``except Exception: pass``) there turns a fault into a torn
#: response or a silently-missing machine.  Every failure must either be
#: quarantined (recorded with detail), converted to a typed per-machine
#: error, or re-raised — never dropped.  ``# noqa`` opts a line out.
SWALLOW_FORBIDDEN_DIRS = (
    os.path.join("gordo_tpu", "serve"),
    os.path.join("gordo_tpu", "artifacts"),
)

#: fault-injection overhead contract: ``GORDO_FAULTS`` unset must cost
#: nothing on the latency-critical drive loops, so the injection seams
#: (``faults.check`` / ``faults.plane`` / ``faults.enabled``) may not
#: appear inside these function bodies at all — seams live at the I/O
#: edges (open/read/write/request), never per-batch.
FAULTS_FORBIDDEN_SCOPES = {
    "fleet_build.py": {"_drive_pipeline"},
    "coalesce.py": {"_run", "_drain"},
}

#: refresh-plane boundary contract: gordo_tpu/refresh/ talks to serving
#: ONLY over its file and HTTP interfaces (fleet-health rollup files /
#: the /fleet-health endpoint, the client's generation handshake) —
#: importing server or watchman internals would couple the rebuild loop
#: to in-process scorer state and quietly break the "any health surface,
#: any server" deployment shape.
REFRESH_DIR = os.path.join("gordo_tpu", "refresh")
REFRESH_FORBIDDEN_IMPORT_PREFIXES = (
    "gordo_tpu.serve",
    "gordo_tpu.watchman",
)

#: backfill-plane boundary contract: gordo_tpu/batch/ is the OFFLINE
#: path — models from the artifact plane, data from dataset providers,
#: scores into the archive.  It reuses the serving stack's scorer and
#: compile plane (gordo_tpu.serve.fleet_scorer / precision are fine),
#: but the HTTP tier must never leak in: no serve.server, no client, no
#: watchman, no HTTP library.  A backfill that talks HTTP has silently
#: become a load generator against production replicas.
BATCH_DIR = os.path.join("gordo_tpu", "batch")
BATCH_FORBIDDEN_IMPORT_PREFIXES = (
    "gordo_tpu.serve.server",
    "gordo_tpu.client",
    "gordo_tpu.watchman",
    "aiohttp",
    "requests",
    "httpx",
    "urllib",
    "http",
)


def _jit_allowed(path: str) -> bool:
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if "tests" in parts or os.path.basename(norm).startswith("test_"):
        return True
    return JIT_ALLOWED_DIR in norm


def _jit_findings(path: str, tree: ast.AST, noqa_lines: set) -> List[Finding]:
    """Flag ``jax.jit`` references (decorator, call, or partial argument)
    outside the compile plane: on-first-call jit tracing is exactly the
    cold-start ambush the compile plane exists to schedule away, and a
    program it doesn't know about can't be warmed, counted, or evicted."""
    if _jit_allowed(path):
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Attribute)
            and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id == "jax"
            and node.lineno not in noqa_lines
        ):
            findings.append(
                (path, node.lineno,
                 "bare jax.jit outside gordo_tpu/compile/ — register the "
                 "program with the compile plane (compile.program for the "
                 "AOT serving path, compile.jit as a passthrough)")
            )
    return findings


def _refresh_import_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag server/watchman-internal imports inside gordo_tpu/refresh/:
    the refresh loop's plane boundary is files and HTTP only (rollup
    files, /fleet-health, the client generation handshake)."""
    norm = os.path.normpath(path)
    if REFRESH_DIR not in norm:
        return []
    findings: List[Finding] = []

    def _bad(module: str) -> bool:
        return any(
            module == p or module.startswith(p + ".")
            for p in REFRESH_FORBIDDEN_IMPORT_PREFIXES
        )

    for node in ast.walk(tree):
        bad = None
        if isinstance(node, ast.Import):
            for alias in node.names:
                if _bad(alias.name):
                    bad = alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            if _bad(node.module):
                bad = node.module
            elif node.module == "gordo_tpu":
                hits = [
                    a.name for a in node.names
                    if a.name in ("serve", "watchman")
                ]
                if hits:
                    bad = f"gordo_tpu.{hits[0]}"
        if bad and getattr(node, "lineno", 0) not in noqa_lines:
            findings.append(
                (path, node.lineno,
                 f"import of {bad} inside gordo_tpu/refresh/ — the "
                 "refresh plane talks to serving ONLY over its file and "
                 "HTTP interfaces (telemetry.read_rollups, /fleet-health, "
                 "client.wait_for_generation), never server internals")
            )
    return findings


#: the build-ingest hot path (gordo_tpu/ingest/plane.py) must stay
#: columnar numpy: per-machine pandas assembly verbs are banned outside
#: the ONE sanctioned escape hatch, ``_load_fallback`` (row filters,
#: custom aggregation, subclassed datasets).  ``pd.tseries...to_offset``
#: and type references stay legal — the ban is on per-machine FRAME
#: construction and resampling, the r24 512-sequential-passes wall.
INGEST_PLANE_FILE = os.path.join("gordo_tpu", "ingest", "plane.py")
INGEST_SANCTIONED_SCOPES = {"_load_fallback"}
INGEST_BANNED_ATTR_CALLS = {
    "resample", "to_frame", "iterrows", "get_data",
}
INGEST_BANNED_PD_CALLS = {"DataFrame", "Series", "concat"}


def _ingest_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag per-machine pandas assembly in the ingest hot path: every
    machine routed through :func:`load_chunk`'s vectorized pass must be
    assembled by the shared columnar kernels; a stray ``.resample()`` /
    ``pd.DataFrame`` / ``.get_data()`` reintroduces the per-machine wall
    the plane exists to remove.  ``_load_fallback`` is the sanctioned
    per-machine path; ``# noqa`` opts a line out, as elsewhere."""
    norm = os.path.normpath(path)
    if not norm.endswith(INGEST_PLANE_FILE):
        return []
    sanctioned = [
        (node.lineno, getattr(node, "end_lineno", node.lineno))
        for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in INGEST_SANCTIONED_SCOPES
    ]
    findings: List[Finding] = []
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        func = call.func
        bad = None
        if isinstance(func, ast.Attribute):
            if (
                func.attr in INGEST_BANNED_PD_CALLS
                and isinstance(func.value, ast.Name)
                and func.value.id in ("pd", "pandas")
            ):
                bad = f"{func.value.id}.{func.attr}"
            elif func.attr in INGEST_BANNED_ATTR_CALLS:
                bad = f".{func.attr}"
        if not bad or call.lineno in noqa_lines:
            continue
        if any(a <= call.lineno <= b for a, b in sanctioned):
            continue
        findings.append(
            (path, call.lineno,
             f"per-machine pandas assembly {bad}() in the ingest hot "
             "path — machines assemble through the columnar vectorized "
             "pass; the only sanctioned per-machine route is "
             "_load_fallback")
        )
    return findings


def _batch_import_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag HTTP-tier imports inside gordo_tpu/batch/: the backfill
    plane scores offline through the artifact/dataset/compile planes —
    serve.server, the client, watchman, and HTTP libraries are all on
    the wrong side of its boundary."""
    norm = os.path.normpath(path)
    if BATCH_DIR not in norm:
        return []
    findings: List[Finding] = []

    def _bad(module: str) -> bool:
        return any(
            module == p or module.startswith(p + ".")
            for p in BATCH_FORBIDDEN_IMPORT_PREFIXES
        )

    for node in ast.walk(tree):
        bad = None
        if isinstance(node, ast.Import):
            for alias in node.names:
                if _bad(alias.name):
                    bad = alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            if _bad(node.module):
                bad = node.module
            elif node.module == "gordo_tpu.serve":
                hits = [a.name for a in node.names if a.name == "server"]
                if hits:
                    bad = "gordo_tpu.serve.server"
            elif node.module == "gordo_tpu":
                hits = [
                    a.name for a in node.names
                    if a.name in ("client", "watchman")
                ]
                if hits:
                    bad = f"gordo_tpu.{hits[0]}"
        if bad and getattr(node, "lineno", 0) not in noqa_lines:
            findings.append(
                (path, node.lineno,
                 f"import of {bad} inside gordo_tpu/batch/ — the backfill "
                 "plane is offline by contract: models via "
                 "artifacts.discover, data via dataset providers, scores "
                 "into the archive; never serve.server, the HTTP client, "
                 "or an HTTP library")
            )
    return findings


def _artifact_path_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag per-machine artifact file references (``"model.pkl"`` /
    ``serializer.MODEL_FILE`` and friends) in product code outside the
    artifact plane's allowlisted owners."""
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if "tests" in parts or os.path.basename(norm).startswith("test_"):
        return []
    if os.path.join("gordo_tpu", "") not in norm + os.sep:
        return []  # scripts/bench/examples are operator tooling
    if any(d in norm for d in ARTIFACT_PATH_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        bad = None
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and node.value in ARTIFACT_FILE_LITERALS
        ):
            bad = repr(node.value)
        elif (
            isinstance(node, ast.Attribute)
            and node.attr in ARTIFACT_FILE_ATTRS
        ):
            bad = f"serializer.{node.attr}"
        if bad and getattr(node, "lineno", 0) not in noqa_lines:
            findings.append(
                (path, node.lineno,
                 f"per-machine artifact path construction ({bad}) outside "
                 "gordo_tpu/artifacts/ — go through the artifact plane "
                 "(artifacts.discover / ArtifactRef / write_pack)")
            )
    return findings


def _artifacts_pack_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Enforce the pack load contract inside gordo_tpu/artifacts/: no
    host copy calls (stack/concatenate — loads must stay memmap views)
    and ``device_put`` only inside ``to_device`` (the one counted
    whole-pack transfer)."""
    norm = os.path.normpath(path)
    if ARTIFACTS_DIR not in norm:
        return []
    findings: List[Finding] = []
    # map every node to its enclosing function name
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for child in ast.walk(node):
                child._lint_fn = getattr(  # type: ignore[attr-defined]
                    child, "_lint_fn", node.name
                )
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not isinstance(func, ast.Attribute):
            continue
        if (
            func.attr in ARTIFACTS_COPY_CALLS
            and node.lineno not in noqa_lines
        ):
            findings.append(
                (path, node.lineno,
                 f"host copy call .{func.attr}() inside gordo_tpu/artifacts/"
                 " — pack loads are zero-copy memmap views by contract")
            )
        if func.attr == "device_put" and node.lineno not in noqa_lines:
            fn = getattr(node, "_lint_fn", None)
            if fn != ARTIFACTS_DEVICE_PUT_FN:
                findings.append(
                    (path, node.lineno,
                     "device_put outside to_device() in gordo_tpu/artifacts/"
                     " — the one counted whole-pack transfer is the only "
                     "allowed call site")
                )
    return findings


def _mesh_findings(path: str, tree: ast.AST, noqa_lines: set) -> List[Finding]:
    """Flag raw ``jax.device_put`` calls and ``jax.sharding`` imports /
    attribute chains outside the placement plane (``gordo_tpu/mesh/``):
    device placement has ONE owner — go through ``gordo_tpu.mesh.place``
    for transfers and ``model_sharding``/``PlacementSpec`` (or the
    re-exported ``Mesh``/``NamedSharding`` types) for shardings.  The
    artifact plane's ``to_device`` is the other transfer seam and is
    policed by ``_artifacts_pack_findings``."""
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if "tests" in parts or os.path.basename(norm).startswith("test_"):
        return []
    if MESH_DIR in norm:
        return []
    in_artifacts = ARTIFACTS_DIR in norm
    findings: List[Finding] = []
    for node in ast.walk(tree):
        bad = None
        if (
            isinstance(node, ast.Attribute)
            and node.attr == "device_put"
            and isinstance(node.value, ast.Name)
            and node.value.id == "jax"
            and not in_artifacts  # to_device scoping handled separately
        ):
            bad = (
                "raw jax.device_put outside gordo_tpu/mesh/ — route the "
                "transfer through gordo_tpu.mesh.place (counted, "
                "sharding-aware) or artifacts.to_device (pack loads)"
            )
        elif isinstance(node, ast.Import) and any(
            a.name == "jax.sharding" or a.name.startswith("jax.sharding.")
            for a in node.names
        ):
            bad = (
                "import of jax.sharding outside gordo_tpu/mesh/ — the "
                "placement plane owns mesh/sharding construction; import "
                "Mesh/NamedSharding/model_sharding from gordo_tpu.mesh"
            )
        elif isinstance(node, ast.ImportFrom) and node.module and (
            node.module == "jax.sharding"
            or node.module.startswith("jax.sharding.")
        ):
            bad = (
                "import from jax.sharding outside gordo_tpu/mesh/ — the "
                "placement plane owns mesh/sharding construction; import "
                "Mesh/NamedSharding/model_sharding from gordo_tpu.mesh"
            )
        elif (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Attribute)
            and node.value.attr == "sharding"
            and isinstance(node.value.value, ast.Name)
            and node.value.value.id == "jax"
        ):
            bad = (
                f"jax.sharding.{node.attr} outside gordo_tpu/mesh/ — the "
                "placement plane owns mesh/sharding construction; use the "
                "gordo_tpu.mesh re-exports"
            )
        if bad and getattr(node, "lineno", 0) not in noqa_lines:
            findings.append((path, node.lineno, bad))
    return findings


def _shard_findings(path: str, tree: ast.AST, noqa_lines: set) -> List[Finding]:
    """Flag serve-path shard computation outside the one shared shard
    function (``gordo_tpu/serve/shard.py``): direct
    ``partition_machines`` imports/references, and modulo arithmetic
    involving shard-named operands or ``hash(...)`` (the classic ad-hoc
    consistent-hash shortcut that silently disagrees with the real
    partition)."""
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if "tests" in parts or os.path.basename(norm).startswith("test_"):
        return []
    if norm.endswith(SHARD_FN_MODULE):
        return []
    if not any(d in norm for d in SHARD_PATH_DIRS):
        return []
    findings: List[Finding] = []

    def _mentions_shard_or_hash(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and "shard" in sub.id.lower():
                return True
            if isinstance(sub, ast.Attribute) and "shard" in sub.attr.lower():
                return True
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
                and sub.func.id == "hash"
            ):
                return True
        return False

    for node in ast.walk(tree):
        bad = None
        if isinstance(node, ast.ImportFrom) and any(
            a.name == "partition_machines" for a in node.names
        ):
            bad = "partition_machines import"
        elif (
            isinstance(node, ast.Name)
            and node.id == "partition_machines"
        ) or (
            isinstance(node, ast.Attribute)
            and node.attr == "partition_machines"
        ):
            bad = "partition_machines reference"
        elif (
            isinstance(node, ast.BinOp)
            and isinstance(node.op, ast.Mod)
            and not isinstance(node.left, ast.Constant)  # "%s" formatting
            and _mentions_shard_or_hash(node)
        ):
            bad = "ad-hoc shard arithmetic (modulo)"
        if bad and getattr(node, "lineno", 0) not in noqa_lines:
            findings.append(
                (path, node.lineno,
                 f"{bad} on the serve path — the machine→replica "
                 "partition has ONE implementation: go through "
                 "gordo_tpu.serve.shard (shard_map/shard_of/owned_names)")
            )
    return findings


def _swallow_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag ``except Exception: pass`` (and the bare/``BaseException``
    forms) inside the serve and artifact planes — see
    ``SWALLOW_FORBIDDEN_DIRS``."""
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if "tests" in parts or os.path.basename(norm).startswith("test_"):
        return []
    if not any(d in norm for d in SWALLOW_FORBIDDEN_DIRS):
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.body and not all(isinstance(s, ast.Pass) for s in node.body):
            continue
        broad = node.type is None or (
            isinstance(node.type, ast.Name)
            and node.type.id in ("Exception", "BaseException")
        )
        if broad and node.lineno not in noqa_lines:
            findings.append(
                (path, node.lineno,
                 "swallowed exception (except Exception: pass) on the "
                 "serve/artifact plane — quarantine it, convert it to a "
                 "typed per-machine error, or re-raise")
            )
    return findings


def _faults_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag fault-injection seam calls (``faults.check`` etc.) inside the
    latency-critical scopes of ``FAULTS_FORBIDDEN_SCOPES`` — the chaos
    plane's zero-overhead-when-unset guarantee holds because seams sit at
    I/O edges, never in per-batch loop bodies."""
    scopes = FAULTS_FORBIDDEN_SCOPES.get(os.path.basename(path))
    if not scopes:
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in scopes:
            continue
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Attribute)
                and isinstance(sub.value, ast.Name)
                and sub.value.id == "faults"
                and sub.lineno not in noqa_lines
            ):
                findings.append(
                    (path, sub.lineno,
                     f"faults.{sub.attr} inside {node.name}() — injection "
                     "seams are banned from hot loop bodies (the "
                     "zero-overhead-when-unset contract); put the seam at "
                     "the I/O edge instead")
                )
    return findings


def iter_py_files(paths: List[str]) -> Iterator[str]:
    for path in paths:
        if os.path.isfile(path) and path.endswith(".py"):
            yield path
        elif os.path.isdir(path):
            for root, dirs, files in os.walk(path):
                dirs[:] = [
                    d for d in dirs
                    if d not in ("__pycache__", ".git", ".pytest_cache")
                ]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


class _ImportTracker(ast.NodeVisitor):
    """Collect imported names and every name usage in a module."""

    def __init__(self):
        self.imports: List[Tuple[str, int]] = []  # (bound name, lineno)
        self.used: set = set()

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            self.imports.append((name, node.lineno))

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            return  # compiler directives, used by definition
        for alias in node.names:
            if alias.name == "*":
                continue  # flagged separately
            name = alias.asname or alias.name
            self.imports.append((name, node.lineno))

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self.generic_visit(node)


def _d2h_findings(path: str, tree: ast.AST, noqa_lines: set) -> List[Finding]:
    """Flag blocking device→host calls inside the pipeline drive loop,
    the coalescer drain thread, and the fleet-build dispatch window (see
    ``D2H_FORBIDDEN_SCOPES``): direct ``jax.device_get`` /
    ``.block_until_ready()`` / ``np.asarray`` (which materializes a jax
    array on host) / ``to_host`` calls in those function bodies."""
    scopes = D2H_FORBIDDEN_SCOPES.get(os.path.basename(path))
    if not scopes:
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in scopes:
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            func = call.func
            bad = None
            if isinstance(func, ast.Attribute):
                if func.attr in D2H_BLOCKING_ATTRS:
                    bad = func.attr
                elif (
                    func.attr == "asarray"
                    and isinstance(func.value, ast.Name)
                    and func.value.id in D2H_ASARRAY_MODULES
                ):
                    bad = f"{func.value.id}.asarray"
            elif isinstance(func, ast.Name) and func.id in D2H_BLOCKING_NAMES:
                bad = func.id
            if bad and call.lineno not in noqa_lines:
                findings.append(
                    (path, call.lineno,
                     f"blocking D2H call {bad}() inside {node.name}() — "
                     "this scope is a drive loop/drain thread/dispatch "
                     "window; route results through the collect side or "
                     "the writer/finish pool")
                )
    return findings


def _host_math_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag host numpy COMPUTE calls (``np.concatenate``/``np.tile``/
    arithmetic reductions — see ``HOST_MATH_CALLS``) inside the serve
    plane's request-path scopes (``HOST_MATH_FORBIDDEN_SCOPES``): that
    work belongs inside the fused device program, where it is one
    dispatch instead of a per-request host bill."""
    norm = os.path.normpath(path)
    if SERVE_DIR not in norm:
        return []
    scopes = HOST_MATH_FORBIDDEN_SCOPES.get(os.path.basename(norm))
    if not scopes:
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in scopes:
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            func = call.func
            if (
                isinstance(func, ast.Attribute)
                and func.attr in HOST_MATH_CALLS
                and isinstance(func.value, ast.Name)
                and func.value.id in HOST_MATH_MODULES
                and call.lineno not in noqa_lines
            ):
                findings.append(
                    (path, call.lineno,
                     f"host numpy compute {func.value.id}.{func.attr}() "
                     f"inside {node.name}() — the serve request path is "
                     "decode -> one device dispatch -> encode; fuse this "
                     "into the compiled program (serve/scorer.py)")
                )
    return findings


def _bulk_frame_findings(
    path: str, tree: ast.AST, noqa_lines: set
) -> List[Finding]:
    """Flag per-machine pandas frame construction (``pd.DataFrame`` /
    ``pd.concat`` / ``_frame_from_payload``) inside the bulk wire hot
    loops (``BULK_FRAME_FORBIDDEN_SCOPES``): the server bulk handler,
    the GSB1 encode/decode pair, the stacked assemblers and the
    client's bulk reassembly all move raw blocks — frame building is
    the r18 35x wall and lives behind the LazyFrame's first-access
    materialization instead."""
    norm = os.path.normpath(path)
    if not any(d in norm for d in BULK_FRAME_DIRS):
        return []
    scopes = BULK_FRAME_FORBIDDEN_SCOPES.get(os.path.basename(norm))
    if not scopes:
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in scopes:
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            func = call.func
            bad = None
            if (
                isinstance(func, ast.Attribute)
                and func.attr in BULK_FRAME_CALLS
                and isinstance(func.value, ast.Name)
                and func.value.id in BULK_FRAME_MODULES
            ):
                bad = f"{func.value.id}.{func.attr}"
            elif isinstance(func, ast.Name) and func.id in BULK_FRAME_NAMES:
                bad = func.id
            if bad and call.lineno not in noqa_lines:
                findings.append(
                    (path, call.lineno,
                     f"per-machine frame construction {bad}() inside "
                     f"{node.name}() — the bulk wire hot loop ships raw "
                     "blocks; materialize frames behind LazyFrame.frame "
                     "(first access), never per chunk in the loop")
                )
    return findings


def lint_file(path: str) -> List[Finding]:
    findings: List[Finding] = []
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [(path, exc.lineno or 0, f"syntax error: {exc.msg}")]

    # module docstring-level "# noqa" opt-outs per line
    noqa_lines = {
        i + 1
        for i, line in enumerate(source.splitlines())
        if "# noqa" in line
    }

    tracker = _ImportTracker()
    tracker.visit(tree)
    # names listed in __all__ count as used (re-export surface); other
    # string literals do NOT — a dict key or log message that happens to
    # match an import name must not suppress an unused-import finding
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Assign)
            and any(
                isinstance(t, ast.Name) and t.id == "__all__"
                for t in node.targets
            )
            and isinstance(node.value, (ast.List, ast.Tuple))
        ):
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(
                    elt.value, str
                ):
                    tracker.used.add(elt.value)
    is_package_init = os.path.basename(path) == "__init__.py"
    if not is_package_init:  # __init__ re-export surface is exempt
        for name, lineno in tracker.imports:
            if name not in tracker.used and lineno not in noqa_lines:
                findings.append((path, lineno, f"unused import: {name}"))

    findings.extend(_d2h_findings(path, tree, noqa_lines))
    findings.extend(_faults_findings(path, tree, noqa_lines))
    findings.extend(_swallow_findings(path, tree, noqa_lines))
    findings.extend(_host_math_findings(path, tree, noqa_lines))
    findings.extend(_bulk_frame_findings(path, tree, noqa_lines))
    findings.extend(_shard_findings(path, tree, noqa_lines))
    findings.extend(_jit_findings(path, tree, noqa_lines))
    findings.extend(_mesh_findings(path, tree, noqa_lines))
    findings.extend(_artifact_path_findings(path, tree, noqa_lines))
    findings.extend(_artifacts_pack_findings(path, tree, noqa_lines))
    findings.extend(_refresh_import_findings(path, tree, noqa_lines))
    findings.extend(_batch_import_findings(path, tree, noqa_lines))
    findings.extend(_ingest_findings(path, tree, noqa_lines))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            func = node.func
            fname = (
                func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name)
                else None
            )
            if (
                fname in METRIC_FACTORIES
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and not METRIC_NAME_RE.match(node.args[0].value)
                and node.lineno not in noqa_lines
            ):
                findings.append(
                    (path, node.lineno,
                     f"metric name {node.args[0].value!r} violates the "
                     f"catalog convention {METRIC_NAME_RE.pattern}")
                )
        if isinstance(node, ast.ImportFrom) and any(
            a.name == "*" for a in node.names
        ):
            findings.append((path, node.lineno, "wildcard import"))
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            if node.lineno not in noqa_lines:
                findings.append((path, node.lineno, "bare except:"))
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for default in node.args.defaults + node.args.kw_defaults:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                    findings.append(
                        (path, node.lineno,
                         f"mutable default argument in {node.name}()")
                    )
        if isinstance(node, (ast.Module, ast.ClassDef)):
            seen = {}
            body = node.body
            for child in body:
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
                ):
                    if child.name in seen and not any(
                        isinstance(d, ast.Name)
                        and d.id in ("property", "overload")
                        or isinstance(d, ast.Attribute)
                        for d in child.decorator_list
                    ):
                        findings.append(
                            (path, child.lineno,
                             f"duplicate definition of {child.name} "
                             f"(first at line {seen[child.name]})")
                        )
                    seen.setdefault(child.name, child.lineno)
    return findings


def main(argv: List[str]) -> int:
    paths = argv or ["gordo_tpu", "tests", "bench.py", "chip_smoke.py",
                     "__graft_entry__.py"]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"lint: path(s) do not exist: {missing}", file=sys.stderr)
        return 2
    all_findings: List[Finding] = []
    n_files = 0
    for path in iter_py_files(paths):
        n_files += 1
        all_findings.extend(lint_file(path))
    for path, lineno, msg in all_findings:
        print(f"{path}:{lineno}: {msg}")
    print(
        f"lint: {n_files} files, {len(all_findings)} finding(s)",
        file=sys.stderr,
    )
    if n_files == 0:
        print("lint: no files found — refusing to pass vacuously",
              file=sys.stderr)
        return 2
    return 1 if all_findings else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
