"""Artifact format v2: memory-mapped bucket packs.

Reference equivalent: none — the reference (and this repo's v1 layout)
ships one directory per machine (``model.pkl`` + ``metadata.json`` +
``definition.yaml``), so a 10k-machine project is ~30k small files that
the build's writer pool must create one by one and the server must
re-deserialize one by one to reassemble what was a single stacked
``(m_pad, ...)`` array on device.  The TensorFlow-serving "one loadable
bundle" pattern and the pjit sharded-checkpoint layout (PAPERS.md) both
point the other way: few large, index-addressed parameter packs.

Layout (under a build output dir)::

    <output_dir>/.gordo-packs/
      index.json            machine -> (pack, slot, cache_key); pack ->
                            tensor/skeleton segment table (the ONE file
                            the disk registry's pack refs resolve through)
      <pack>.pack           raw little-endian tensor segments, each
                            page-aligned (4096), one stacked (M, ...)
                            tensor per array leaf, followed by the
                            per-machine pickled skeletons
      <pack>.meta.json      per-machine build metadata + the chunk's
                            shared definition.yaml text

One pack holds one (signature, bucket) chunk of a fleet build: the
machines share one model structure, so each array leaf stacks across the
machine axis into a single contiguous ``(M, *leaf_shape)`` segment.  A
machine's model is a tiny pickled *skeleton* — the object graph with
every array leaf swapped for a ``(pack-leaf, index)`` persistent id —
and loading it materializes zero-copy ``np.memmap`` views into the
stacked segments.  The serve plane goes further: a whole pack's stacked
tensors ship to the device as ONE :func:`to_device` call (the only
``jax.device_put`` the lint gate permits in this package), so server
start pays one transfer per pack instead of one unpickle per machine.

Delta writes: :func:`delta_write` rewrites only the changed machines'
slot segments in place (O(changed-machines) bytes) plus an atomic index
swap — the primitive incremental rebuilds (ROADMAP item 3) need.

Generations: the index carries a monotonic ``generation`` id, and every
machine row records the generation (``gen``) that last rewrote it.  Pack
writes record rows as *pending* (``gen = active + 1``) without touching
the published generation; one flock-serialized
:func:`~gordo_tpu.artifacts.generations.stamp_generation` at the end of
a build flips the id atomically (``delta_write`` stamps inside its own
index flip).  Readers — the server's delta hot reload above all — never
act on pack mtimes: the generation flip is the ONLY reload signal, and
it happens strictly after the pack bytes it publishes are durable, so a
mid-rewrite pack can never be observed as "new".  Superseded packs are
*retired* (entry moved aside, file retained on disk) rather than
unlinked, so previous generations stay loadable until
:func:`~gordo_tpu.artifacts.generations.gc_generations` prunes them.

Durability matches the registry/round-file convention: every rename is
``tmp + os.replace`` followed by a parent-directory fsync, so an index
can never reference a pack that a crash kept off disk.
"""

from __future__ import annotations

import contextlib
import fcntl
import hashlib
import io
import json
import logging
import os
import pickle
import struct
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from gordo_tpu import faults, telemetry
from gordo_tpu.utils.disk_registry import fsync_dir

logger = logging.getLogger(__name__)

#: directory (under a build output dir) holding the pack files + index
PACKS_DIR = ".gordo-packs"
#: pack file magic + format version (little-endian u32 after the magic)
PACK_MAGIC = b"GPK2"
PACK_VERSION = 2
#: tensor segments align to page boundaries so ``np.memmap`` views (and
#: the eventual DMA into device memory) start page-aligned
PAGE = 4096
#: registry values for packed machines: ``pack:<packs_dir>#<machine>``
PACK_REF_PREFIX = "pack:"
#: persistent-id tag marking an extracted array leaf in a skeleton pickle
_LEAF_TAG = "gordo-pack-leaf"

ENV_FORMAT = "GORDO_ARTIFACT_FORMAT"
FORMATS = ("v1", "v2")

#: tiny sidecar holding just the active generation int — the cheap
#: watch-poll target (one small read per poll instead of parsing the
#: whole index); rewritten under the index flock so it can never run
#: ahead of the index it summarizes
GENERATION_FILE = "GENERATION"
#: when set, every generation stamp auto-prunes to the newest N
#: generations (``gordo artifacts gc --keep N`` is the explicit form)
ENV_GC_KEEP = "GORDO_GC_KEEP"

# -- telemetry instruments (docs/observability.md) --------------------------
_PACKS_TOTAL = telemetry.counter(
    "gordo_artifact_packs_total",
    "Pack operations by kind (written | opened | delta | retired | gc)",
    labels=("op",),
)
_GENERATIONS_GAUGE = telemetry.gauge(
    "gordo_artifact_generations",
    "Generation records retained in the pack index (active + history "
    "still reloadable on disk)",
)
_PACK_BYTES_TOTAL = telemetry.counter(
    "gordo_artifact_pack_bytes_total",
    "Bytes written to pack files, by operation (written | delta)",
    labels=("op",),
)
#: the stages of one pack write: ``gordo.build.write.<stage>`` spans and
#: ``gordo_build_pipeline_stage_seconds{stage="write.<stage>"}``
WRITE_SPAN_PREFIX = "gordo.build.write."


class _WriteClock:
    """Where one pack write's seconds go: ``serialize`` (the
    ``leaf.tobytes()`` copies), ``file`` (the ``write`` and ``flush`` calls)
    and ``fsync`` (the pack's, the meta file's, the directory's and the
    index's), each summed over the write.  :meth:`close` gives each stage
    one ``gordo.build.write.<stage>`` span record (first entry to last
    exit, ``seconds`` the busy sum), one observation of
    ``gordo_build_pipeline_stage_seconds{stage="write.<stage>"}`` and, on
    whatever span encloses the write (the builder's ``gordo.build.write``,
    which the chunk timeline keeps), the attribute ``<stage>_s``.  With
    telemetry off it reads no clock and records nothing."""

    def __init__(self, on: bool = True) -> None:
        self.on = on and telemetry.enabled()
        self._wall = time.time() - time.perf_counter()
        # stage -> [busy seconds, first entry, last exit]; a write enters
        # all three: serialize, file, fsync
        self._stages: Dict[str, List[float]] = {}

    def add(self, stage: str, t0: float, t1: float) -> None:
        held = self._stages.setdefault(stage, [0.0, t0, t1])
        held[0] += t1 - t0
        held[2] = t1

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        if not self.on:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, t0, time.perf_counter())

    def write(self, fh, leaf: np.ndarray) -> None:
        """``fh.write(leaf.tobytes())``, the copy and the write apart."""
        if not self.on:
            fh.write(leaf.tobytes())
            return
        t0 = time.perf_counter()
        data = leaf.tobytes()
        t1 = time.perf_counter()
        fh.write(data)
        self.add("serialize", t0, t1)
        self.add("file", t1, time.perf_counter())

    def close(self) -> None:
        if not self.on:
            return
        # the builder's own series; imported here because the builder
        # imports this module
        from gordo_tpu.builder.timeline import STAGE_SECONDS

        for name, (busy, first, last) in self._stages.items():
            telemetry.record_span(WRITE_SPAN_PREFIX + name, self._wall + first,
                                  self._wall + last, seconds=busy)
            STAGE_SECONDS.observe(busy, "write." + name)
            telemetry.add_to_span(**{name + "_s": busy})


#: for the index updates that belong to no pack write (a generation stamp)
_NO_CLOCK = _WriteClock(on=False)


_PACK_DEVICE_PUTS = telemetry.counter(
    "gordo_artifact_pack_device_puts_total",
    "Whole-pack host->device transfers (the v2 load contract: exactly "
    "one per (signature, bucket) pack)",
)
_PACK_LOAD_SECONDS = telemetry.histogram(
    "gordo_artifact_pack_load_seconds",
    "Store open (index validation + memmap) seconds",
)


class PackError(Exception):
    """Base class for v2 artifact failures (always loud, never skipped)."""


class PackCorruptError(PackError):
    """A pack or its index fails validation (truncated segment, offset
    past EOF, bad magic, unreadable index) — refuse to serve from it."""


def resolve_format(fmt: Optional[str] = None) -> str:
    """The artifact format a build writes: an explicit argument wins,
    else ``GORDO_ARTIFACT_FORMAT``, else ``v2`` — memory-mapped bucket
    packs are the library default now that the whole serving tier
    (collection load, fleet prestacking, sharded replicas) consumes
    packs end-to-end.  ``GORDO_ARTIFACT_FORMAT=v1`` is the escape hatch
    for tooling that still walks per-machine directories (or run
    ``gordo artifacts unpack`` to export a v1 view)."""
    fmt = fmt or os.environ.get(ENV_FORMAT, "").strip().lower() or "v2"
    if fmt not in FORMATS:
        raise ValueError(
            f"unknown artifact format {fmt!r}; expected one of {FORMATS}"
        )
    return fmt


def packs_dir(output_dir: str) -> str:
    return os.path.join(output_dir, PACKS_DIR)


def machine_ref(output_dir: str, name: str) -> str:
    """The registry value recorded for a packed machine: the pack index
    is the unit the registry records, so the ref addresses the machine
    THROUGH the index rather than a per-machine path."""
    return f"{PACK_REF_PREFIX}{os.path.abspath(packs_dir(output_dir))}#{name}"


def is_pack_ref(value: str) -> bool:
    return isinstance(value, str) and value.startswith(PACK_REF_PREFIX)


def parse_ref(ref: str) -> Tuple[str, str]:
    """``pack:<packs_dir>#<machine>`` -> (packs_dir, machine)."""
    if not is_pack_ref(ref) or "#" not in ref:
        raise ValueError(f"not a pack ref: {ref!r}")
    body = ref[len(PACK_REF_PREFIX):]
    directory, _, name = body.rpartition("#")
    return directory, name


# ---------------------------------------------------------------------------
# model <-> (skeleton, leaves) flattening
# ---------------------------------------------------------------------------

def flatten_model(model: Any) -> Tuple[bytes, List[np.ndarray]]:
    """Pickle ``model`` with every array leaf swapped for a persistent
    id; returns the skeleton bytes plus the leaves in encounter order.
    Duplicate references to one array collapse to one leaf (and restore
    as one shared view).  jax array leaves pull to host first — packs
    are device-independent, like v1 pickles."""
    leaves: List[np.ndarray] = []
    seen: Dict[int, int] = {}
    keepalive: List[Any] = []  # pin ids against reuse during the dump

    class _Extractor(pickle.Pickler):
        def persistent_id(self, obj):  # noqa: D102
            arr = None
            if isinstance(obj, np.ndarray) and obj.dtype != np.dtype(object):
                arr = obj
            elif isinstance(obj, jax.Array):
                arr = obj
            if arr is None:
                return None
            key = id(arr)
            if key not in seen:
                host = np.ascontiguousarray(
                    np.asarray(jax.device_get(arr))
                    if isinstance(arr, jax.Array) else arr
                )
                if host.dtype.byteorder == ">":
                    host = host.astype(host.dtype.newbyteorder("<"))
                seen[key] = len(leaves)
                leaves.append(host)
                keepalive.append(arr)
            return (_LEAF_TAG, seen[key])

    buf = io.BytesIO()
    _Extractor(buf, protocol=4).dump(model)
    return buf.getvalue(), leaves


class _ViewUnpickler(pickle.Unpickler):
    """Skeleton unpickler: persistent ids resolve to zero-copy views."""

    def __init__(self, data: bytes, resolver: Callable[[int], np.ndarray]):
        super().__init__(io.BytesIO(data))
        self._resolver = resolver

    def persistent_load(self, pid):  # noqa: D102
        if (
            not isinstance(pid, tuple) or len(pid) != 2
            or pid[0] != _LEAF_TAG
        ):
            raise pickle.UnpicklingError(f"unknown persistent id {pid!r}")
        return self._resolver(int(pid[1]))


def _leaf_signature(leaves: Sequence[np.ndarray]) -> List[Tuple]:
    return [(tuple(a.shape), a.dtype.str) for a in leaves]


# ---------------------------------------------------------------------------
# index read/modify/write (flock-serialized: multi-host shards share a dir)
# ---------------------------------------------------------------------------

def _index_path(directory: str) -> str:
    return os.path.join(directory, "index.json")


def _read_index(directory: str) -> Optional[Dict[str, Any]]:
    path = _index_path(directory)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except FileNotFoundError:
        return None
    except (OSError, ValueError) as exc:
        raise PackCorruptError(f"unreadable pack index {path}: {exc}")
    if doc.get("version") != PACK_VERSION:
        raise PackCorruptError(
            f"pack index {path} has version {doc.get('version')!r}; this "
            f"reader speaks version {PACK_VERSION}"
        )
    return doc


def _locked_index_update(
    directory: str,
    mutate: Callable[[Dict[str, Any]], None],
    after: Optional[Callable[[Dict[str, Any]], None]] = None,
    clock: Optional[_WriteClock] = None,
) -> Dict[str, Any]:
    """Read-modify-write the index under an exclusive flock, swapping the
    new index in atomically (tmp + rename + dir fsync).  The lock
    serializes concurrent writers — multi-host build shards write
    disjoint chunks into ONE shared index.  ``after`` runs with the lock
    STILL HELD once the new index is durable (the generation sidecar
    write rides here, so two concurrent stamps can't publish sidecars
    out of order).  ``clock``: a pack write's, for the two fsyncs."""
    clock = clock or _NO_CLOCK
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, ".lock"), "a+") as lock:
        fcntl.flock(lock.fileno(), fcntl.LOCK_EX)
        doc = _read_index(directory) or {
            "version": PACK_VERSION, "packs": {}, "machines": {},
        }
        mutate(doc)
        path = _index_path(directory)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.flush()
            with clock.stage("fsync"):
                os.fsync(fh.fileno())
        os.replace(tmp, path)
        with clock.stage("fsync"):
            fsync_dir(directory)
        if after is not None:
            after(doc)
        return doc


def _write_generation_file(directory: str, generation: int) -> None:
    """Publish the tiny ``GENERATION`` sidecar (tmp + replace + fsync) —
    what the server's watch loop polls instead of re-parsing the index.
    Callers hold the index flock, so sidecars publish in stamp order."""
    path = os.path.join(directory, GENERATION_FILE)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        fh.write(f"{int(generation)}\n")
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)
    fsync_dir(directory)


def _record_generation(
    directory: str, doc: Dict[str, Any], changed: Sequence[str]
) -> int:
    """Flip ``doc`` to the next generation (caller is inside a locked
    index mutate): bump the id, stamp the changed rows, and append a
    generation record carrying the live pack refs — what keeps retired
    pack files reachable (and gc-able) per generation."""
    new_gen = int(doc.get("generation", 0)) + 1
    doc["generation"] = new_gen
    for name in changed:
        row = doc["machines"].get(name)
        if row is not None:
            row["gen"] = new_gen
    doc.setdefault("generations", {})[str(new_gen)] = {
        "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "changed_count": len(changed),
        "packs": sorted(
            {e["file"] for e in doc["packs"].values()}
        ),
    }
    keep = os.environ.get(ENV_GC_KEEP, "").strip()
    if keep:
        try:
            _prune_generations(directory, doc, max(1, int(keep)))
        except ValueError:
            logger.warning("ignoring non-integer %s=%r", ENV_GC_KEEP, keep)
    _GENERATIONS_GAUGE.set(float(len(doc.get("generations", {}))))
    return new_gen


def _prune_generations(
    directory: str, doc: Dict[str, Any], keep: int
) -> List[str]:
    """Drop all but the newest ``keep`` generation records and unlink
    retired pack files no retained record (nor the live index)
    references.  Runs inside a locked index mutate; the active
    generation is always retained (``keep >= 1`` is enforced by
    callers).  Returns the file names actually removed."""
    gens = doc.get("generations", {})
    retained = sorted((int(g) for g in gens), reverse=True)[:keep]
    for g in [g for g in gens if int(g) not in retained]:
        del gens[g]
    referenced = {e["file"] for e in doc["packs"].values()}
    referenced |= {e["meta_file"] for e in doc["packs"].values()}
    for rec in gens.values():
        referenced.update(rec.get("packs", ()))
    removed: List[str] = []
    retired = doc.get("retired", {})
    for pack_id in [
        p for p, e in retired.items() if e["file"] not in referenced
    ]:
        entry = retired.pop(pack_id)
        _PACKS_TOTAL.inc(1.0, "gc")
        for key in ("file", "meta_file"):
            if entry.get(key) and entry[key] not in referenced:
                try:
                    os.unlink(os.path.join(directory, entry[key]))
                    removed.append(entry[key])
                except OSError:
                    pass
    return removed


def _gc_dead_packs(directory: str, doc: Dict[str, Any]) -> None:
    """Retire pack entries whose machines were all superseded by newer
    packs: the entry moves to the index's ``retired`` table but the FILE
    stays on disk — a previous generation's packs must remain loadable
    until :func:`~gordo_tpu.artifacts.generations.gc_generations` (or
    the ``GORDO_GC_KEEP`` auto-prune) decides history is deep enough."""
    live: Dict[str, int] = {}
    for row in doc["machines"].values():
        live[row["pack"]] = live.get(row["pack"], 0) + 1
    for pack_id in [p for p in doc["packs"] if not live.get(p)]:
        entry = doc["packs"].pop(pack_id)
        _PACKS_TOTAL.inc(1.0, "retired")
        doc.setdefault("retired", {})[pack_id] = {
            "file": entry["file"],
            "meta_file": entry["meta_file"],
            "bytes": entry.get("bytes", 0),
            "retired_after": int(doc.get("generation", 0)),
        }


# ---------------------------------------------------------------------------
# writing
# ---------------------------------------------------------------------------

def write_pack(
    output_dir: str,
    names: Sequence[str],
    models: Sequence[Any],
    metadatas: Optional[Sequence[Optional[Dict[str, Any]]]] = None,
    definition: Optional[str] = None,
    cache_keys: Optional[Dict[str, str]] = None,
) -> str:
    """Write one (signature, bucket) chunk as a single pack.

    Every model must flatten to the same leaf signature (shapes +
    dtypes) — true by construction for a fleet chunk; a mismatch raises
    :class:`PackError` so the caller can fall back to per-machine v1
    artifacts instead of silently mis-slicing.  Returns the pack id.
    The index update drops any older rows for these machines and
    garbage-collects packs left with no live machines.
    """
    if not names or len(names) != len(models):
        raise PackError(
            f"write_pack needs aligned names/models (got {len(names)} names, "
            f"{len(models)} models)"
        )
    metadatas = list(metadatas) if metadatas is not None else [None] * len(names)
    flat = [flatten_model(m) for m in models]
    sig0 = _leaf_signature(flat[0][1])
    for name, (_, leaves) in zip(names, flat):
        if _leaf_signature(leaves) != sig0:
            raise PackError(
                f"machine {name!r} breaks the chunk's leaf signature — "
                "packs require one shared model structure per chunk"
            )

    directory = packs_dir(output_dir)
    os.makedirs(directory, exist_ok=True)
    # generation-qualify the pack id: a rebuild of the same chunk in a
    # LATER generation must land in a fresh file so the previous
    # generation's bytes survive until gc — same names + same pending
    # generation still collapse to one file (idempotent re-runs)
    try:
        existing = _read_index(directory)
    except PackCorruptError:
        existing = None
    pending_gen = int((existing or {}).get("generation", 0)) + 1
    pack_id = "pack-" + hashlib.md5(
        ",".join(names).encode()
    ).hexdigest()[:12] + f"-g{pending_gen}"
    pack_file = f"{pack_id}.pack"
    meta_file = f"{pack_id}.meta.json"

    tensors: List[Dict[str, Any]] = []
    skeletons: List[Tuple[int, int]] = []
    clock = _WriteClock()
    tmp = os.path.join(directory, f"{pack_file}.tmp.{os.getpid()}")
    with open(tmp, "wb") as fh:
        fh.write(PACK_MAGIC + struct.pack("<I", PACK_VERSION))
        for leaf_idx, (shape, dtype) in enumerate(sig0):
            offset = -(-fh.tell() // PAGE) * PAGE  # next page boundary
            fh.seek(offset)
            for _, leaves in flat:
                clock.write(fh, leaves[leaf_idx])
            tensors.append(
                {
                    "offset": offset,
                    "shape": [len(names)] + list(shape),
                    "dtype": dtype,
                }
            )
        with clock.stage("file"):
            for skeleton, _ in flat:
                skeletons.append((fh.tell(), len(skeleton)))
                fh.write(skeleton)
            fh.flush()
        with clock.stage("fsync"):
            os.fsync(fh.fileno())
        n_bytes = fh.tell()
    # injection seam: "enospc" surfaces as OSError to the caller, "crash"
    # aborts between the durable tmp write and the rename — exactly the
    # torn state `gordo artifacts fsck` must detect and sweep
    faults.check("artifact.write", op="write_pack", file=pack_file)
    os.replace(tmp, os.path.join(directory, pack_file))

    meta_doc = {
        "definition": definition,
        "machines": {
            name: md for name, md in zip(names, metadatas) if md is not None
        },
    }
    tmp = os.path.join(directory, f"{meta_file}.tmp.{os.getpid()}")
    with open(tmp, "w") as fh:
        json.dump(meta_doc, fh, default=str)
        fh.flush()
        with clock.stage("fsync"):
            os.fsync(fh.fileno())
    os.replace(tmp, os.path.join(directory, meta_file))
    with clock.stage("fsync"):
        fsync_dir(directory)  # both renames durable before the index names them

    entry = {
        "file": pack_file,
        "meta_file": meta_file,
        "bytes": n_bytes,
        "machines": list(names),
        "tensors": tensors,
        "skeletons": [list(s) for s in skeletons],
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }

    def mutate(doc: Dict[str, Any]) -> None:
        doc["packs"][pack_id] = entry
        # rows land PENDING: gen is one past the published generation,
        # so readers gating on the generation id don't reload mid-build;
        # stamp_generation at build end publishes every pending row in
        # one atomic flip (recomputed under the lock — a stamp that
        # slipped in between makes these rows part of the NEXT flip)
        row_gen = int(doc.get("generation", 0)) + 1
        for slot, name in enumerate(names):
            row: Dict[str, Any] = {
                "pack": pack_id, "slot": slot, "gen": row_gen,
            }
            key = (cache_keys or {}).get(name)
            if key:
                row["cache_key"] = key
            doc["machines"][name] = row
        _gc_dead_packs(directory, doc)

    _locked_index_update(directory, mutate, clock=clock)
    clock.close()
    _PACKS_TOTAL.inc(1.0, "written")
    _PACK_BYTES_TOTAL.inc(float(n_bytes), "written")
    return pack_id


def delta_write(
    output_dir: str,
    models: Dict[str, Any],
    metadatas: Optional[Dict[str, Dict[str, Any]]] = None,
) -> List[str]:
    """Rewrite only the named machines inside their existing packs.

    O(changed-machines): each machine's slot segment in every stacked
    tensor is overwritten in place (same shapes/dtypes required — a
    structural change is a rebuild, not a delta), its skeleton is
    appended to the pack tail, and ONE atomic index swap publishes the
    new offsets.  This is the primitive incremental rebuilds compose
    with: changed machines rewrite; the index flip is the generation
    boundary.  Returns the machine names rewritten.
    """
    directory = packs_dir(output_dir)
    faults.check("artifact.write", op="delta_write")
    doc = _read_index(directory)
    if doc is None:
        raise PackError(f"no pack index under {directory}")
    by_pack: Dict[str, List[str]] = {}
    for name in models:
        row = doc["machines"].get(name)
        if row is None:
            raise PackError(f"machine {name!r} is not in the pack index")
        by_pack.setdefault(row["pack"], []).append(name)

    new_skeletons: Dict[str, Dict[int, Tuple[int, int]]] = {}
    delta_bytes = 0
    clock = _WriteClock()
    for pack_id, pack_names in by_pack.items():
        entry = doc["packs"][pack_id]
        sig = [
            (tuple(t["shape"][1:]), t["dtype"]) for t in entry["tensors"]
        ]
        path = os.path.join(directory, entry["file"])
        with open(path, "r+b") as fh:
            for name in pack_names:
                skeleton, leaves = flatten_model(models[name])
                if _leaf_signature(leaves) != sig:
                    raise PackError(
                        f"delta for {name!r} changes the leaf signature — "
                        "structural changes need a full chunk rebuild"
                    )
                slot = doc["machines"][name]["slot"]
                for tensor, leaf in zip(entry["tensors"], leaves):
                    fh.seek(tensor["offset"] + slot * leaf.nbytes)
                    clock.write(fh, leaf)
                    delta_bytes += leaf.nbytes
                fh.seek(0, os.SEEK_END)
                new_skeletons.setdefault(pack_id, {})[slot] = (
                    fh.tell(), len(skeleton),
                )
                with clock.stage("file"):
                    fh.write(skeleton)
                delta_bytes += len(skeleton)
            with clock.stage("file"):
                fh.flush()
            with clock.stage("fsync"):
                os.fsync(fh.fileno())
            entry["bytes"] = fh.seek(0, os.SEEK_END)

        if metadatas:
            meta_path = os.path.join(directory, entry["meta_file"])
            try:
                with open(meta_path) as fh:
                    meta_doc = json.load(fh)
            except (OSError, ValueError):
                meta_doc = {"definition": None, "machines": {}}
            for name in pack_names:
                if name in metadatas:
                    meta_doc["machines"][name] = metadatas[name]
            tmp = f"{meta_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(meta_doc, fh, default=str)
                fh.flush()
                with clock.stage("fsync"):
                    os.fsync(fh.fileno())
            os.replace(tmp, meta_path)

    def mutate(idx: Dict[str, Any]) -> None:
        for pack_id, slots in new_skeletons.items():
            entry = idx["packs"].get(pack_id)
            if entry is None:
                raise PackError(
                    f"pack {pack_id} vanished during delta_write"
                )
            entry["bytes"] = doc["packs"][pack_id]["bytes"]
            for slot, (offset, length) in slots.items():
                entry["skeletons"][slot] = [offset, length]
        # a delta IS a generation: the pack bytes above are already
        # durable (fsync'd before this flip), so stamping here makes the
        # index swap the one atomic publish — readers gating reloads on
        # the generation can never observe the rewrite half-done
        _record_generation(directory, idx, sorted(models))

    _locked_index_update(
        directory, mutate,
        after=lambda idx: _write_generation_file(
            directory, int(idx["generation"])
        ),
        clock=clock,
    )
    clock.close()
    _PACKS_TOTAL.inc(float(len(by_pack)), "delta")
    _PACK_BYTES_TOTAL.inc(float(delta_bytes), "delta")
    return sorted(models)


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

class PackStore:
    """Read surface over one ``.gordo-packs/`` directory.

    Opening validates every pack eagerly — magic, version, and that each
    recorded segment lies inside the file — so corruption (a truncated
    pack, an index offset past EOF) fails LOUDLY at open instead of
    serving garbage views later.  All reads after that are zero-copy:
    one ``np.memmap`` per pack, ``np.ndarray`` views into it per tensor
    and per machine slot.

    ``quarantine=True`` (the serving path) records a failing pack in
    ``quarantined_packs``/``quarantined_machines`` instead of raising:
    the rest of the store stays readable, and the collection layer
    serves 503 ``quarantined`` for only the affected machines.  The
    default stays loud — registry/CLI callers want corruption to stop
    them, not shrink results silently.
    """

    def __init__(self, directory: str, quarantine: bool = False):
        t0 = time.monotonic()
        self.directory = directory
        doc = _read_index(directory)
        if doc is None:
            raise FileNotFoundError(f"no pack index under {directory}")
        self.packs: Dict[str, Dict[str, Any]] = doc["packs"]
        self.machines: Dict[str, Dict[str, Any]] = doc["machines"]
        #: published generation id at open (0 for a pre-generations
        #: index) — the value the server's project index republishes
        self.generation: int = int(doc.get("generation", 0))
        #: retained generation records (newest last), for store_info/gc
        self.generations: Dict[str, Dict[str, Any]] = dict(
            doc.get("generations", {})
        )
        try:
            st = os.stat(_index_path(directory))
            self.index_stat = (st.st_mtime, st.st_size)
        except OSError:
            self.index_stat = (0.0, -1)
        self._mmaps: Dict[str, np.memmap] = {}
        self._stacked: Dict[str, List[np.ndarray]] = {}
        self._meta: Dict[str, Dict[str, Any]] = {}
        self._slot_views: Dict[Tuple[str, int, int], np.ndarray] = {}
        #: id(view or stacked tensor) -> (pack_id, leaf_idx); lets the
        #: fleet scorer map a reconstructed model's array leaves back to
        #: their stacked pack tensors without copying anything
        self._leaf_ids: Dict[int, Tuple[str, int]] = {}
        #: packs that failed open-validation, {pack_id: error} (always
        #: empty without ``quarantine`` — failures raise instead)
        self.quarantined_packs: Dict[str, str] = {}
        #: machines whose pack is quarantined, {name: error}
        self.quarantined_machines: Dict[str, str] = {}
        for pack_id, entry in self.packs.items():
            try:
                self._validate(pack_id, entry)
            except PackCorruptError as exc:
                if not quarantine:
                    raise
                logger.error("quarantining pack %s: %s", pack_id, exc)
                self.quarantined_packs[pack_id] = str(exc)
        if self.quarantined_packs:
            self.quarantined_machines = {
                name: self.quarantined_packs[row["pack"]]
                for name, row in self.machines.items()
                if row["pack"] in self.quarantined_packs
            }
        _PACKS_TOTAL.inc(float(len(self.packs)), "opened")
        _PACK_LOAD_SECONDS.observe(time.monotonic() - t0)

    # -- validation ---------------------------------------------------------
    def _validate(self, pack_id: str, entry: Dict[str, Any]) -> None:
        path = os.path.join(self.directory, entry["file"])
        try:
            faults.check("pack.open", pack=pack_id, path=path)
            size = os.stat(path).st_size
            with open(path, "rb") as fh:
                header = fh.read(8)
        except faults.InjectedFault as exc:
            raise PackCorruptError(f"pack {pack_id}: {exc}")
        except OSError as exc:
            raise PackCorruptError(f"pack {pack_id} unreadable: {exc}")
        if header[:4] != PACK_MAGIC:
            raise PackCorruptError(
                f"pack {pack_id} has bad magic {header[:4]!r}"
            )
        ends = [
            t["offset"]
            + int(np.prod(t["shape"])) * np.dtype(t["dtype"]).itemsize
            for t in entry["tensors"]
        ] + [off + length for off, length in entry["skeletons"]]
        if ends and max(ends) > size:
            raise PackCorruptError(
                f"pack {pack_id} is truncated: index addresses byte "
                f"{max(ends)} but the file has {size}"
            )

    # -- zero-copy views ----------------------------------------------------
    def _mmap(self, pack_id: str) -> np.memmap:
        mm = self._mmaps.get(pack_id)
        if mm is None:
            path = os.path.join(
                self.directory, self.packs[pack_id]["file"]
            )
            mm = self._mmaps[pack_id] = np.memmap(
                path, dtype=np.uint8, mode="r"
            )
        return mm

    def stacked(self, pack_id: str) -> List[np.ndarray]:
        """The pack's stacked ``(M, *leaf_shape)`` tensors as memmap
        views — what ships to the device in one :func:`to_device`."""
        out = self._stacked.get(pack_id)
        if out is None:
            mm = self._mmap(pack_id)
            out = []
            for leaf_idx, t in enumerate(self.packs[pack_id]["tensors"]):
                dt = np.dtype(t["dtype"])
                n = int(np.prod(t["shape"])) * dt.itemsize
                view = (
                    mm[t["offset"]: t["offset"] + n]
                    .view(dt)
                    .reshape(t["shape"])
                )
                self._leaf_ids[id(view)] = (pack_id, leaf_idx)
                out.append(view)
            self._stacked[pack_id] = out
        return out

    def _slot_view(self, pack_id: str, slot: int, leaf_idx: int) -> np.ndarray:
        key = (pack_id, slot, leaf_idx)
        view = self._slot_views.get(key)
        if view is None:
            view = self.stacked(pack_id)[leaf_idx][slot]
            self._slot_views[key] = view
            self._leaf_ids[id(view)] = (pack_id, leaf_idx)
        return view

    def leaf_of(self, array: Any) -> Optional[Tuple[str, int]]:
        """(pack_id, leaf_idx) when ``array`` is a view this store handed
        out (per-slot or stacked), else None."""
        return self._leaf_ids.get(id(array))

    # -- per-machine surface ------------------------------------------------
    def names(self) -> List[str]:
        """Loadable machine names (quarantined packs' machines excluded —
        the collection layer reports those separately)."""
        if not self.quarantined_machines:
            return sorted(self.machines)
        return sorted(
            n for n in self.machines if n not in self.quarantined_machines
        )

    def __contains__(self, name: str) -> bool:
        return name in self.machines

    def location(self, name: str) -> Tuple[str, int]:
        row = self.machines[name]
        return row["pack"], row["slot"]

    def cache_key(self, name: str) -> Optional[str]:
        row = self.machines.get(name)
        return row.get("cache_key") if row else None

    def machines_of(self, pack_id: str) -> List[str]:
        """Live machines of a pack in slot order (superseded slots —
        machines a newer pack took over — are skipped)."""
        return [
            n for n in self.packs[pack_id]["machines"]
            if self.machines.get(n, {}).get("pack") == pack_id
        ]

    def load_model(self, name: str) -> Any:
        """Reconstruct one machine's model: unpickle its tiny skeleton,
        resolving each array leaf to a zero-copy view of the stacked
        memmap — no per-machine file opens, no array copies."""
        if name in self.quarantined_machines:
            raise PackCorruptError(
                f"machine {name!r} is quarantined: "
                f"{self.quarantined_machines[name]}"
            )
        pack_id, slot = self.location(name)
        try:
            faults.check("pack.read", pack=pack_id, machine=name)
        except (faults.InjectedFault, OSError) as exc:
            raise PackCorruptError(f"machine {name!r}: {exc}")
        offset, length = self.packs[pack_id]["skeletons"][slot]
        data = bytes(self._mmap(pack_id)[offset: offset + length])
        try:
            return _ViewUnpickler(
                data, lambda leaf: self._slot_view(pack_id, slot, leaf)
            ).load()
        except PackError:
            raise
        except Exception as exc:
            raise PackCorruptError(
                f"machine {name!r} skeleton in pack {pack_id} failed to "
                f"load: {exc}"
            )

    def _meta_doc(self, pack_id: str) -> Dict[str, Any]:
        doc = self._meta.get(pack_id)
        if doc is None:
            path = os.path.join(
                self.directory, self.packs[pack_id]["meta_file"]
            )
            try:
                with open(path) as fh:
                    doc = json.load(fh)
            except FileNotFoundError:
                doc = {"definition": None, "machines": {}}
            except (OSError, ValueError) as exc:
                raise PackCorruptError(
                    f"pack {pack_id} metadata unreadable: {exc}"
                )
            self._meta[pack_id] = doc
        return doc

    def load_metadata(self, name: str) -> Dict[str, Any]:
        pack_id, _ = self.location(name)
        return self._meta_doc(pack_id)["machines"].get(name, {})

    def definition(self, name: str) -> Optional[str]:
        pack_id, _ = self.location(name)
        return self._meta_doc(pack_id).get("definition")

    def row_generation(self, name: str) -> int:
        """The generation that last (re)wrote this machine's slot —
        what the server's delta reload compares against its own
        generation to build the changed-machine set.  0 for rows written
        before the generations layer existed."""
        row = self.machines.get(name)
        return int(row.get("gen", 0)) if row else 0

    def changed_since(self, generation: int) -> List[str]:
        """Machines whose rows were rewritten after ``generation`` —
        the O(changed) set a delta hot reload re-stacks."""
        return sorted(
            name for name, row in self.machines.items()
            if int(row.get("gen", 0)) > int(generation)
        )

    def stat(self, name: str) -> Tuple[float, int]:
        """(mtime, size) of the machine's pack file.  Historical reload
        signal, kept for v1-parity surfaces only: a ``delta_write``
        mutates the pack in place, so mtime can tick while the rewrite
        is still torn — rescan gates pack reloads on
        :meth:`row_generation` instead."""
        pack_id, _ = self.location(name)
        try:
            st = os.stat(
                os.path.join(self.directory, self.packs[pack_id]["file"])
            )
            return st.st_mtime, st.st_size
        except OSError:
            return 0.0, -1

    def total_bytes(self) -> int:
        return sum(int(e.get("bytes", 0)) for e in self.packs.values())


def to_device(host_tree: Any, shardings: Any = None, dtype: Any = None) -> Any:
    """ONE whole-pack host→device transfer (counted; the v2 load contract
    is exactly one of these per (signature, bucket) pack — the lint gate
    keeps ``device_put`` out of everywhere else in this package).

    ``dtype``: optional storage dtype (the serving-precision plane —
    ``gordo_tpu/serve/precision.py``): float leaves are cast host-side
    before the transfer, so a bf16 serving configuration ships HALF the
    pack bytes over the wire and resides at half the device footprint.
    ``None`` (the fp32 default) preserves the zero-copy memmap path —
    a cast necessarily materializes a host copy, so it only happens when
    reduced precision was explicitly configured.
    """
    _PACK_DEVICE_PUTS.inc(1.0)
    if dtype is not None:
        dt = np.dtype(dtype)
        host_tree = jax.tree.map(
            lambda a: (
                a.astype(dt)
                if getattr(getattr(a, "dtype", None), "kind", "") == "f"
                else a
            ),
            host_tree,
        )
    if shardings is None:
        return jax.device_put(host_tree)
    return jax.device_put(host_tree, shardings)


def device_put_count() -> float:
    """Current value of the pack-transfer counter (telemetry attestation
    for tests and the artifact_io bench)."""
    return _PACK_DEVICE_PUTS.value()
