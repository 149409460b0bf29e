"""Stacked multi-machine serving: many models resident per chip, scored in
one dispatch.

Reference equivalent: none — the reference serves one model per pod, so
aggregate project throughput is bounded by per-request Python/Flask
overhead times N pods.  SURVEY.md §8 step 6 calls for the TPU-native
answer: stack every (structurally identical) machine's params on device
and score a whole project's stream as ONE vmapped fused program — a
bucket of tiny per-tag scoring programs becomes MXU-filling batched GEMMs,
exactly like the fleet trainer.

Used by the bulk serving route (``POST .../_bulk/anomaly/prediction``) and
the replayed-stream benchmark (BASELINE config 5).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu import artifacts
from gordo_tpu import compile as compile_plane
from gordo_tpu.anomaly.diff import scores_fn
from gordo_tpu.ops.windows import make_windows
from gordo_tpu.serve import precision
from gordo_tpu.serve.scorer import (
    SMOOTH_ONE_SHOT_BOUND,
    CompiledScorer,
    _DISPATCHES,
    _H2D,
    _bucket_rows,
    _extract_chain,
    refuse_sequence_model,
    _rolling_median,
    short_rows_message,
)

#: the ONE windows-tensor ceiling (scorer.SMOOTH_ONE_SHOT_BOUND; not
#: re-measured on an attached chip), applied here across the stacked
#: machine axis.  NOTE: a source-level alias — editing the
#: scorer constant updates both, but a *runtime* rebind of
#: scorer.SMOOTH_ONE_SHOT_BOUND (monkeypatch, dynamic re-probe) does not
#: propagate here; rebind both names in that case.
SMOOTH_ELEMENT_BOUND = SMOOTH_ONE_SHOT_BOUND


def _fleet_score_core(
    module,
    scaler_classes,
    mode,
    lookback,
    det_cls,
    with_thresholds,
    smooth_window,
    dtype,           # serving precision (static: keys the executable)
    scaler_stats,    # tuple of stacked stats pytrees, leaves (M, ...)
    params,          # stacked params pytree, leaves (M, ...)
    det_stats,       # stacked detector-scaler stats
    agg_thresholds,  # (M,) stacked aggregate thresholds (or None)
    X,               # (M, N, F)
):
    """The fused anomaly program of ``serve.scorer``, vmapped over the
    machine axis, at serving precision ``dtype`` (casts are identity for
    float32 and for leaves already stored reduced).  Outputs leave the
    program as float32 — the response schema is dtype-invariant; the
    confidence divide runs f32 against never-quantized thresholds."""
    scaler_stats = precision.cast_params(scaler_stats, dtype)
    params = precision.cast_params(params, dtype)
    det_stats = precision.cast_params(det_stats, dtype)
    Xc = precision.cast_input(X, dtype)

    def one(stats_i, params_i, det_i, x):
        xs = x
        for cls, st in zip(scaler_classes, stats_i):
            xs = cls.apply(st, xs)
        if mode == "none":
            inputs = xs
        elif mode == "ae":
            inputs = make_windows(xs, lookback)
        else:  # forecast
            inputs = make_windows(xs[:-1], lookback)
        pred = module.apply({"params": params_i}, inputs)
        offset = x.shape[0] - pred.shape[0]
        tag, total = scores_fn(det_cls, det_i, x[offset:], pred)
        if smooth_window:
            tag = _rolling_median(tag, smooth_window)
            total = _rolling_median(total, smooth_window)
        return pred, tag, total

    pred, tag, total = jax.vmap(one)(scaler_stats, params, det_stats, Xc)
    total = total.astype(jnp.float32)
    out = {
        "model-output": pred.astype(jnp.float32),
        "tag-anomaly-scores": tag.astype(jnp.float32),
        "total-anomaly-score": total,
    }
    if with_thresholds:
        out["anomaly-confidence"] = total / jnp.maximum(
            agg_thresholds[:, None].astype(jnp.float32), 1e-12
        )
    return out


_STATIC_ARGS = (
    "module", "scaler_classes", "mode", "lookback", "det_cls",
    "with_thresholds", "smooth_window", "dtype",
)

#: the full-bucket stacked program, compile-plane-owned: warmup
#: AOT-compiles it per (bucket signature, row bucket) before readiness
_fleet_score_program = compile_plane.program(
    "serve.fleet", _fleet_score_core, static_argnames=_STATIC_ARGS
)


def _fleet_score_subset_core(
    module,
    scaler_classes,
    mode,
    lookback,
    det_cls,
    with_thresholds,
    smooth_window,
    dtype,
    scaler_stats,
    params,
    det_stats,
    agg_thresholds,
    idx,             # (m_sub,) int32 positions into the stacked machine axis
    X,               # (m_sub, N, F)
):
    """Score a SUBSET of a bucket's machines: gather their stacked slots on
    device, then run the same fused program at the subset size.

    ``idx`` is a traced array, so which machines are requested never
    recompiles — only the subset SIZE does, and callers pad that to a power
    of two.  This is what makes small coalesced rounds cheap: an 8-machine
    dispatch against a 512-machine bucket computes (and transfers back)
    8 slots, not 512.
    """
    take = lambda t: jax.tree.map(lambda a: a[idx], t)  # noqa: E731
    return _fleet_score_core(
        module, scaler_classes, mode, lookback, det_cls, with_thresholds,
        smooth_window, dtype,
        take(scaler_stats),
        take(params),
        take(det_stats),
        None if agg_thresholds is None else agg_thresholds[idx],
        X,
    )


_fleet_score_subset_program = compile_plane.program(
    "serve.fleet_subset", _fleet_score_subset_core,
    static_argnames=_STATIC_ARGS,
)


class _Bucket:
    """One structurally identical group of machines, params stacked.

    With ``mesh`` (a ``("models", "data")`` fleet mesh spanning >1 device),
    the stacked machine axis is padded to a multiple of the model-shard
    count and placed with a ``models``-axis ``NamedSharding`` — the fused
    program is a pure map over machines, so XLA partitions one serving
    dispatch across every chip with zero collectives.  This is the serving
    twin of the fleet trainer's sharding (``parallel/fleet.py``).
    """

    def __init__(
        self,
        names: List[str],
        chains: List[Dict[str, Any]],
        mesh: Optional[Any] = None,
        prestacked: Optional[Dict[str, Any]] = None,
        dtype: Optional[str] = None,
    ):
        self.names = names
        c0 = chains[0]
        self.module = c0["module"]
        self.scaler_classes = tuple(cls for cls, _ in c0["scalers"])
        self.mode = c0["mode"]
        self.lookback = c0["lookback"]
        det0 = c0["detector"]
        self.det_cls = det0["scaler_cls"]
        self.smooth_window = det0["window"]
        #: the serving precision this bucket's stacked programs dispatch
        #: at; its stacked float tensors are STORED at the matching
        #: storage dtype (bf16 halves residency and the pack transfer)
        self.dtype = (
            precision.canonical(dtype) if dtype else precision.serve_dtype()
        )
        self.with_thresholds = all(
            c["detector"]["feature_thresholds"] is not None for c in chains
        )

        from gordo_tpu.mesh import MODEL_AXIS

        self.mesh = (
            mesh
            if mesh is not None and mesh.shape.get(MODEL_AXIS, 1) > 1
            else None
        )
        #: stacked machine-axis length on device (== len(names) without a
        #: mesh; padded to a shard multiple with one)
        self.m_pad = len(names)

        if prestacked is not None:
            self._init_prestacked(prestacked)
        else:
            self._init_stacking(chains)
        #: authoritative input width (detector scaler stats are per-feature
        #: arrays), used to reject malformed requests per machine instead
        #: of letting one bad array sink a whole stacked dispatch
        det_leaves = jax.tree.leaves(self.det_stats)
        self.n_features = (
            int(det_leaves[0].shape[-1]) if det_leaves else None
        )
        #: pinned host stacking buffers keyed by (machines, rows, features),
        #: reused across score_all calls while request shapes repeat;
        #: LRU-bounded so a long-lived server with varied request shapes
        #: can't accumulate unbounded host memory; guarded by _lock —
        #: concurrent bulk requests run score_all from executor threads
        self._stack_bufs: "OrderedDict[Tuple[int, int, int], np.ndarray]" = (
            OrderedDict()
        )
        self._lock = threading.Lock()

    def _init_stacking(self, chains: List[Dict[str, Any]]) -> None:
        """The v1 path: per-machine chain arrays stack leaf by leaf (one
        host gather + implicit transfer per leaf).

        With a mesh the stack/cast/pad all stay host-side (numpy) so the
        sharded ``jax.device_put`` at the end is the ONLY host->device
        copy per leaf; stacking through jnp would first place every leaf
        on the default device, then copy it again for the sharded layout.
        """
        mesh = self.mesh
        if mesh is None:
            stack = lambda trees: jax.tree.map(  # noqa: E731
                lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *trees
            )
        else:
            stack = lambda trees: jax.tree.map(  # noqa: E731
                lambda *xs: np.stack([np.asarray(x) for x in xs]), *trees
            )
        self.params = stack([c["params"] for c in chains])
        self.scaler_stats = tuple(
            stack([c["scalers"][i][1] for c in chains])
            for i in range(len(self.scaler_classes))
        )
        self.det_stats = stack([c["detector"]["scaler_stats"] for c in chains])
        if self.dtype != "float32":
            # reduced-precision serving stores the stacked float tensors
            # at the storage dtype (bf16): half the device residency, and
            # the in-program compute cast becomes an identity
            if mesh is None:
                self.params = precision.cast_storage(self.params, self.dtype)
                self.scaler_stats = precision.cast_storage(
                    self.scaler_stats, self.dtype
                )
                self.det_stats = precision.cast_storage(
                    self.det_stats, self.dtype
                )
            else:
                # host-side equivalent of cast_storage — casting through
                # jnp here would defeat the single-transfer property
                store = precision.storage_np_dtype(self.dtype)
                cast = lambda tree: jax.tree.map(  # noqa: E731
                    lambda a: (
                        a.astype(store)
                        if np.issubdtype(a.dtype, np.floating) else a
                    ),
                    tree,
                )
                self.params = cast(self.params)
                self.scaler_stats = cast(self.scaler_stats)
                self.det_stats = cast(self.det_stats)
        if self.with_thresholds:
            # host copies kept alongside the device arrays: per-machine
            # response assembly reads thresholds once per call per machine,
            # and a device-array index there would issue hundreds of tiny
            # device->host transfers per bulk request (not re-measured on
            # an attached chip)
            self.thresholds_np = np.stack(
                [
                    np.asarray(c["detector"]["feature_thresholds"])
                    for c in chains
                ]
            )
            self.agg_thresholds_np = np.asarray(
                [
                    float(c["detector"]["aggregate_threshold"])
                    for c in chains
                ],
                np.float32,
            )
            # only the aggregate goes to device (the program's confidence
            # divide); per-feature thresholds are response-assembly-only and
            # a device copy would just pin unused memory.  With a mesh the
            # device copy happens sharded in the block below instead.
            self.agg_thresholds = (
                jnp.asarray(self.agg_thresholds_np) if mesh is None else None
            )
        else:
            self.thresholds_np = None
            self.agg_thresholds_np = None
            self.agg_thresholds = None
        if mesh is not None:
            from gordo_tpu.mesh import (
                MODEL_AXIS,
                model_sharding,
                pad_to_multiple,
                place,
            )

            shards = mesh.shape[MODEL_AXIS]
            self.m_pad = pad_to_multiple(len(self.names), shards)
            pad = self.m_pad - len(self.names)

            def shard(tree):
                def one(a):
                    if pad:
                        a = np.concatenate(
                            [a, np.repeat(a[:1], pad, axis=0)]
                        )
                    return place(a, model_sharding(mesh, a.ndim - 1))

                return jax.tree.map(one, tree)

            self.params = shard(self.params)
            self.scaler_stats = shard(self.scaler_stats)
            self.det_stats = shard(self.det_stats)
            if self.agg_thresholds_np is not None:
                agg = np.asarray(self.agg_thresholds_np)
                if pad:
                    agg = np.concatenate([agg, np.repeat(agg[:1], pad)])
                self.agg_thresholds = place(agg, model_sharding(mesh, 0))
            self._x_sharding = model_sharding(self.mesh, 2)

    def _init_prestacked(self, prestacked: Dict[str, Any]) -> None:
        """The v2 pack path: the artifact store already holds this
        bucket's arrays stacked (M_pack, ...) and memory-mapped per
        (signature, bucket) pack, so each pack ships to the device as
        ONE ``artifacts.to_device`` call — zero host copies — and a
        multi-pack bucket concatenates the transferred trees on device.
        Dispatch geometry (the stacked machine-axis length) is identical
        to the v1 stacking path's, so scoring stays bitwise-equal to a
        v1 load of the same models.
        """
        self.thresholds_np = (
            prestacked["feature_thresholds"] if self.with_thresholds else None
        )
        self.agg_thresholds_np = (
            prestacked["agg"] if self.with_thresholds else None
        )
        pack_hosts = prestacked["packs"]
        if self.mesh is not None:
            from gordo_tpu.mesh import (
                MODEL_AXIS,
                model_sharding,
                pad_to_multiple,
                place,
            )

            shards = self.mesh.shape[MODEL_AXIS]
            self.m_pad = pad_to_multiple(len(self.names), shards)
            pad = self.m_pad - len(self.names)

            def stitch(*parts):
                # load-time pack stitching (NOT the request path — the
                # host-math lint gate scopes a request-path "assemble")
                a = (
                    parts[0] if len(parts) == 1
                    else np.concatenate(parts, axis=0)
                )
                if pad:
                    a = np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
                return a

            # sharded placement needs host-side pad/concat copies anyway;
            # still ONE counted transfer for the whole bucket
            host = jax.tree.map(stitch, *pack_hosts)
            shardings = jax.tree.map(
                lambda a: model_sharding(self.mesh, a.ndim - 1), host
            )
            dev = artifacts.to_device(
                host, shardings,
                dtype=precision.storage_np_dtype(self.dtype),
            )
            self._x_sharding = model_sharding(self.mesh, 2)
            self.params, self.scaler_stats, self.det_stats = dev
            self.agg_thresholds = None
            if self.with_thresholds:
                agg = self.agg_thresholds_np
                if pad:
                    agg = np.concatenate([agg, np.repeat(agg[:1], pad)])
                self.agg_thresholds = place(
                    jnp.asarray(agg), model_sharding(self.mesh, 0)
                )
            return
        devs = [
            artifacts.to_device(
                h, dtype=precision.storage_np_dtype(self.dtype)
            )
            for h in pack_hosts
        ]
        dev = devs[0] if len(devs) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *devs
        )
        self.params, self.scaler_stats, self.det_stats = dev
        self.agg_thresholds = (
            jnp.asarray(self.agg_thresholds_np)
            if self.with_thresholds else None
        )

    #: max retained stacking buffers per bucket (power-of-two shape
    #: bucketing keeps distinct shapes few; 4 covers a steady mix of bulk +
    #: coalesced sizes while bounding worst-case host residency)
    MAX_STACK_BUFS = 4

    @staticmethod
    def fill_slot(stacked: np.ndarray, i: int, a: np.ndarray) -> None:
        """Write machine rows into dispatch slot ``i`` with repeat-last row
        padding — the ONE padding scheme both subset and full-bucket
        dispatches must share (divergence would make partial- and
        full-bucket results differ for the same machine)."""
        stacked[i, : a.shape[0]] = a
        stacked[i, a.shape[0]:] = a[-1:]

    def stack_buffer(self, shape: Tuple[int, int, int]) -> np.ndarray:
        """Pinned stacking buffer for ``shape`` (call with ``_lock`` held)."""
        buf = self._stack_bufs.get(shape)
        if buf is None:
            buf = self._stack_bufs[shape] = np.empty(shape, np.float32)
            while len(self._stack_bufs) > self.MAX_STACK_BUFS:
                self._stack_bufs.popitem(last=False)
        else:
            self._stack_bufs.move_to_end(shape)
        return buf

    def _program_prefix(self) -> Tuple:
        """The stacked programs' leading arguments — dispatch and AOT
        warmup must assemble them identically (same objects, same static
        values) or warmed executables would never be looked up."""
        return (
            self.module,
            self.scaler_classes,
            self.mode,
            self.lookback,
            self.det_cls,
            self.with_thresholds,
            self.smooth_window,
            self.dtype,
            self.scaler_stats,
            self.params,
            self.det_stats,
            self.agg_thresholds,
        )

    def score(self, X_stack: np.ndarray) -> Dict[str, np.ndarray]:
        if self.mesh is not None:
            # host array straight to its shards (committed sharding -> XLA
            # partitions the whole fused program over the fleet axis, a
            # pure map with no collectives); going via jnp.asarray first
            # would stage the full array on device 0 and pay a second
            # device-to-device scatter
            from gordo_tpu.mesh import place

            _H2D.inc(1.0, "serve.fleet")
            X = place(np.asarray(X_stack, np.float32), self._x_sharding)
        else:
            _H2D.inc(1.0, "serve.fleet")
            X = jnp.asarray(X_stack, jnp.float32)
        _DISPATCHES.inc(1.0, "serve.fleet")
        return _fleet_score_program(*self._program_prefix(), X)

    def score_subset(
        self, X_stack: np.ndarray, idx: np.ndarray
    ) -> Dict[str, np.ndarray]:
        _H2D.inc(1.0, "serve.fleet_subset")
        _DISPATCHES.inc(1.0, "serve.fleet_subset")
        return _fleet_score_subset_program(
            *self._program_prefix(),
            jnp.asarray(idx, jnp.int32),
            jnp.asarray(X_stack, jnp.float32),
        )

    def warm_programs(
        self, row_sizes: "List[int]"
    ) -> "List[Tuple[str, int, float]]":
        """AOT-compile this bucket's stacked dispatch family for each row
        bucket: the full-bucket program (the ``_bulk`` route) and — for
        multi-machine buckets — the 1-machine subset gather (the
        coalescer's common case).  Shape structs only; nothing executes.
        Returns ``[(label, rows, compile_seconds), ...]``."""
        n_feat = self.n_features or 1
        out: "List[Tuple[str, int, float]]" = []
        for rows in row_sizes:
            x_kw = {}
            if self.mesh is not None:
                x_kw["sharding"] = self._x_sharding
            X_full = jax.ShapeDtypeStruct(
                (self.m_pad, int(rows), n_feat), jnp.float32, **x_kw
            )
            out.append((
                "serve.fleet/full", int(rows),
                _fleet_score_program.warm(*self._program_prefix(), X_full),
            ))
            if len(self.names) > 1:
                idx = jax.ShapeDtypeStruct((1,), jnp.int32)
                X_sub = jax.ShapeDtypeStruct(
                    (1, int(rows), n_feat), jnp.float32
                )
                out.append((
                    "serve.fleet/subset", int(rows),
                    _fleet_score_subset_program.warm(
                        *self._program_prefix(), idx, X_sub
                    ),
                ))
        return out


class _PrestackMiss(Exception):
    """A chain leaf did not map back to its pack's stacked tensors —
    fall back to the generic per-leaf stacking path."""


def _delta_bucket(
    bucket: _Bucket, store, touched: List[str], dtype: str
) -> _Bucket:
    """One bucket's successor after a delta generation flip: re-stack
    ONLY the touched pack runs into the prestacked device buffers.

    The delta contract (``artifacts.delta_write``) guarantees stable
    membership, slots, and leaf shapes, so the new bucket's device
    tensors are assembled from the OLD bucket's device arrays (zero-copy
    device slices for every untouched pack run) plus one
    ``artifacts.to_device`` per TOUCHED pack — host→device traffic is
    O(changed packs), and identical shapes mean the compile plane
    resolves every program of the new bucket from cache (zero compiles).
    Raises :class:`_PrestackMiss` whenever the geometry drifted (members
    moved packs, slots went non-contiguous, sharded placement) — the
    caller falls back to a full restack, never serves a misaligned view.
    """
    if bucket.mesh is not None:
        # sharded buckets interleave pad slots — rebuild wholesale
        raise _PrestackMiss()
    member_set = set(bucket.names)
    pack_ids: List[str] = []
    for n in bucket.names:
        if n not in store:
            raise _PrestackMiss()
        pid = store.location(n)[0]
        if pid not in pack_ids:
            pack_ids.append(pid)
    runs: Dict[str, Tuple[int, int, int]] = {}
    expect: List[str] = []
    for pid in pack_ids:
        live = store.machines_of(pid)
        owned = [i for i, m in enumerate(live) if m in member_set]
        lo, hi = owned[0], owned[-1] + 1
        if owned != list(range(lo, hi)):
            raise _PrestackMiss()
        runs[pid] = (lo, hi, len(live))
        expect.extend(live[lo:hi])
    if expect != list(bucket.names):
        raise _PrestackMiss()
    touched_packs: List[str] = []
    for n in touched:
        pid = store.location(n)[0]
        if pid not in touched_packs:
            touched_packs.append(pid)

    def lift(pid, live_count, a):
        loc = store.leaf_of(a)
        if loc is None or loc[0] != pid:
            raise _PrestackMiss()
        stacked = store.stacked(pid)[loc[1]]
        if stacked.shape[0] != live_count:
            raise _PrestackMiss()
        lo, hi, _ = runs[pid]
        return stacked[lo:hi]

    new_parts: Dict[str, Any] = {}
    thr_rows: Dict[str, np.ndarray] = {}
    for pid in touched_packs:
        lo, hi, n_live = runs[pid]
        rep = _extract_chain(store.load_model(store.machines_of(pid)[lo]))
        if rep is None:
            raise _PrestackMiss()
        take = lambda a, p=pid, m=n_live: lift(p, m, a)  # noqa: E731
        host = (
            jax.tree.map(take, rep["params"]),
            tuple(jax.tree.map(take, st) for _, st in rep["scalers"]),
            jax.tree.map(take, rep["detector"]["scaler_stats"]),
        )
        new_parts[pid] = artifacts.to_device(
            host, dtype=precision.storage_np_dtype(dtype)
        )
        if bucket.with_thresholds:
            ft = rep["detector"]["feature_thresholds"]
            if ft is None:
                raise _PrestackMiss()
            thr_rows[pid] = np.asarray(take(ft))

    old_leaves, treedef = jax.tree.flatten(
        (bucket.params, bucket.scaler_stats, bucket.det_stats)
    )
    parts_leaves = {
        pid: jax.tree.flatten(t)[0] for pid, t in new_parts.items()
    }
    offsets: Dict[str, Tuple[int, int]] = {}
    pos = 0
    for pid in pack_ids:
        lo, hi, _ = runs[pid]
        offsets[pid] = (pos, pos + (hi - lo))
        pos += hi - lo
    new_leaves = []
    for i, old_leaf in enumerate(old_leaves):
        pieces = []
        for pid in pack_ids:
            start, stop = offsets[pid]
            if pid in parts_leaves:
                pieces.append(parts_leaves[pid][i])
            else:
                # untouched run: a device slice of the resident stacked
                # tensor — no host copy, no transfer
                pieces.append(old_leaf[start:stop])
        new_leaves.append(
            pieces[0] if len(pieces) == 1
            else jnp.concatenate(pieces, axis=0)
        )
    params, scaler_stats, det_stats = jax.tree.unflatten(
        treedef, new_leaves
    )

    thresholds_np = bucket.thresholds_np
    agg_np = bucket.agg_thresholds_np
    if bucket.with_thresholds:
        # COPIES, never in-place: in-flight dispatches against the old
        # bucket assemble from its threshold arrays after their device
        # work completes — mutating them would mix generations within
        # one response
        thresholds_np = np.array(bucket.thresholds_np, copy=True)
        agg_np = np.array(bucket.agg_thresholds_np, copy=True)
        for pid in touched_packs:
            start, stop = offsets[pid]
            thresholds_np[start:stop] = thr_rows[pid]
        pos_of = {n: i for i, n in enumerate(bucket.names)}
        for n in touched:
            c = _extract_chain(store.load_model(n))
            if c is None:
                raise _PrestackMiss()
            agg_np[pos_of[n]] = float(
                c["detector"]["aggregate_threshold"] or 0.0
            )

    nb = _Bucket.__new__(_Bucket)
    for attr in (
        "names", "module", "scaler_classes", "mode", "lookback",
        "det_cls", "smooth_window", "dtype", "with_thresholds", "mesh",
        "m_pad", "n_features",
    ):
        setattr(nb, attr, getattr(bucket, attr))
    nb.params, nb.scaler_stats, nb.det_stats = (
        params, scaler_stats, det_stats
    )
    nb.thresholds_np = thresholds_np
    nb.agg_thresholds_np = agg_np
    nb.agg_thresholds = (
        jnp.asarray(agg_np) if bucket.with_thresholds else None
    )
    # share the dispatch lock + pinned stacking buffers with the
    # predecessor: old-scorer and new-scorer dispatches against "the
    # same" bucket must serialize on one lock or a shared buffer could
    # be overwritten mid-transfer during the handover window
    nb._lock = bucket._lock
    nb._stack_bufs = bucket._stack_bufs
    return nb


def _prestack_group(
    store, names: List[str], chains: List[Dict[str, Any]]
):
    """Zero-copy stacked arrays for a pack-backed signature group.

    Bucketing stays at the v1 granularity (one bucket per structural
    signature — dispatch geometry, and therefore XLA codegen and bitwise
    outputs, must not depend on how the build chunked its packs).  Each
    pack contributes its stacked ``(M_pack, ...)`` memmap tensors as ONE
    whole-pack device transfer; a multi-pack bucket concatenates the
    transferred trees on device.

    A pack may also contribute a CONTIGUOUS RUN of its slots — the
    fleet-sharded serving case: shard slices and pack chunks are both
    name-sorted, so a replica's boundary cuts a pack into a basic numpy
    slice of the stacked tensors (still a zero-copy view, still one
    ``to_device`` for that pack's contribution).  A pack whose in-group
    machines are NOT slot-contiguous (interleaved bucketing) falls back
    to the generic stacking path, as before.

    Succeeds only when every machine of the group is pack-backed and
    every chain array of each contributed run's first machine maps back
    to a stacked tensor.  Returns ``(prestacked, names, chains)``
    reordered to pack-slot order, or ``(None, names, chains)`` unchanged.
    """
    by_name = dict(zip(names, chains))
    group = set(names)
    pack_ids: List[str] = []
    for n in names:
        if n not in store:
            return None, names, chains
        pid = store.location(n)[0]
        if pid not in pack_ids:
            pack_ids.append(pid)
    slot_orders: Dict[str, List[str]] = {}
    slot_runs: Dict[str, Tuple[int, int]] = {}
    for pid in pack_ids:
        live = store.machines_of(pid)
        owned_pos = [i for i, m in enumerate(live) if m in group]
        lo, hi = owned_pos[0], owned_pos[-1] + 1
        if owned_pos != list(range(lo, hi)):
            # in-group slots are interleaved with foreign ones — a view
            # can't express that; stacked rows would not align
            return None, names, chains
        slot_orders[pid] = live[lo:hi]
        slot_runs[pid] = (lo, hi)
    pack_ids.sort(key=lambda p: slot_orders[p][0])

    def lift(pid, live_count, a):
        loc = store.leaf_of(a)
        if loc is None or loc[0] != pid:
            raise _PrestackMiss()
        stacked = store.stacked(pid)[loc[1]]
        if stacked.shape[0] != live_count:
            # superseded slots still occupy stacked rows — row i would
            # no longer be machine i of this bucket
            raise _PrestackMiss()
        lo, hi = slot_runs[pid]
        return stacked[lo:hi]  # basic slice: still a zero-copy view

    pack_hosts = []
    thr_parts: List[Any] = []
    want_thr = all(
        c["detector"]["feature_thresholds"] is not None for c in chains
    )
    try:
        for pid in pack_ids:
            live = slot_orders[pid]
            c0 = by_name[live[0]]
            n_live = len(store.machines_of(pid))
            take = lambda a, p=pid, m=n_live: lift(p, m, a)  # noqa: E731
            pack_hosts.append((
                jax.tree.map(take, c0["params"]),
                tuple(
                    jax.tree.map(take, stats) for _, stats in c0["scalers"]
                ),
                jax.tree.map(take, c0["detector"]["scaler_stats"]),
            ))
            if want_thr:
                thr_parts.append(take(c0["detector"]["feature_thresholds"]))
    except _PrestackMiss:
        return None, names, chains

    names = [n for pid in pack_ids for n in slot_orders[pid]]
    chains = [by_name[n] for n in names]
    thr = None
    if want_thr:
        # single pack: the memmap view itself (zero copy); multi-pack:
        # one bounded host concat of the (M, n_tags) threshold rows
        thr = thr_parts[0] if len(thr_parts) == 1 else np.concatenate(
            thr_parts
        )
    prestacked = {
        "packs": pack_hosts,
        "feature_thresholds": thr,
        "agg": np.asarray(
            [
                float(c["detector"]["aggregate_threshold"] or 0.0)
                for c in chains
            ],
            np.float32,
        ),
    }
    return prestacked, names, chains


def _hint_group(
    hint: Dict[str, Any], names: List[str], chains: List[Dict]
) -> Tuple[Optional[Dict[str, Any]], List[str], List[Dict]]:
    """Adopt a builder-supplied prestack for this signature group.

    The fleet builder's collect side fetches each chunk's results as
    stacked ``(M, ...)`` host arrays and hands the per-machine detectors
    zero-copy views; ``hint`` re-exposes those stacked arrays whole
    (``PendingFleetBuild.prestacked``).  When this group is exactly the
    hinted fleet, the bucket initializes through the prestacked path —
    one ``to_device`` per pack — instead of re-stacking the per-machine
    views leaf by leaf.  Row order follows the hint (group-dispatch
    order); bucket semantics don't depend on name order.  Any mismatch —
    a subset fleet, mixed signatures splitting the models across groups —
    falls back to the generic stacking path unchanged.
    """
    hinted = hint.get("names")
    if (
        hinted is None
        or len(hinted) != len(names)
        or set(hinted) != set(names)
    ):
        return None, names, chains
    by_name = dict(zip(names, chains))
    names = list(hinted)
    return (
        {k: hint[k] for k in ("packs", "feature_thresholds", "agg")},
        names,
        [by_name[n] for n in names],
    )


def _signature(chain: Dict[str, Any]) -> Optional[Tuple]:
    det = chain["detector"]
    if det is None:
        return None
    if det["feature_thresholds"] is None and det["require_thresholds"]:
        # the per-machine path refuses to serve this model; route it through
        # the fallback so the same per-machine error surfaces here
        return None
    return (
        chain["module"],                      # flax modules: frozen, hashable
        tuple(cls for cls, _ in chain["scalers"]),
        chain["mode"],
        chain["lookback"],
        det["scaler_cls"],
        det["window"],
        det["feature_thresholds"] is not None,
    )


class FleetDispatch:
    """Completed device dispatches whose per-machine result assembly is
    deferred.

    ``FleetScorer.dispatch_all`` returns one of these after the device
    work (stacking, dispatch, device→host transfer) is done;
    :meth:`assemble` performs the remaining host-side numpy slicing and
    dict building.  The split exists for the coalescer's drain thread:
    assembly of round N must not delay the gather of round N+1, so the
    drain thread calls ``dispatch_all`` and hands the ``FleetDispatch``
    to a finish pool.  ``assemble`` touches only host arrays already
    fetched from the device, so it is safe on any thread and needs no
    bucket lock.
    """

    def __init__(self):
        #: results already final at dispatch time: per-machine validation
        #: errors, fallback-path machines, windows-bound per-machine scores
        self.results: Dict[str, Dict[str, Any]] = {}
        #: (host outputs, bucket, [(name, slot, stack_pos, n_valid), ...])
        self._pending: List[Tuple[Dict[str, np.ndarray], Any, List[Tuple]]] = []

    @property
    def n_device_dispatches(self) -> int:
        """Stacked device dispatches gathered into this result (one per
        bucket program actually run — each staged exactly one host→device
        input transfer).  Read it BEFORE :meth:`assemble` drains the
        pending list; the backfill plane's per-chunk device-transfer
        attestation consumes it."""
        return len(self._pending)

    def assemble(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Slice each machine's rows out of the stacked host outputs and
        attach its thresholds; idempotent-safe (pending entries drain)."""
        from gordo_tpu import telemetry

        pending, self._pending = self._pending, []
        for out, bucket, slots in pending:
            for name, slot, stack_pos, n_valid in slots:
                res = {
                    k: np.asarray(v[slot])[:n_valid]
                    for k, v in out.items()
                }
                if bucket.with_thresholds:
                    res["tag-anomaly-thresholds"] = bucket.thresholds_np[
                        stack_pos
                    ].copy()
                    res["total-anomaly-threshold"] = float(
                        bucket.agg_thresholds_np[stack_pos]
                    )
                # fleet-health sketch per stacked machine: the output is
                # already host numpy (device_get happened at dispatch),
                # so recording here adds one bincount and no D2H.  The
                # windows-bound and fallback paths record through their
                # own named CompiledScorers instead — results landing
                # directly in ``self.results`` never reach this loop, so
                # nothing double-counts.
                telemetry.FLEET_HEALTH.record(
                    name, res.get("total-anomaly-score")
                )
                self.results[name] = res
        return self.results

    def assemble_columnar(self) -> "codec.ColumnarResult":
        """The columnar sibling of :meth:`assemble`: keep the stacked
        host outputs STACKED and return a :class:`codec.ColumnarResult`
        of per-bucket blocks plus a (machine → block/slot/row-extent)
        map, instead of splitting into per-machine dicts.

        The blocks are zero-copy views into the dispatch outputs (a
        leading-slot prefix of a C-contiguous array is still
        contiguous), so the bulk encode path — ``encode_columnar`` over
        this result — never materializes a per-machine array.  Error
        and fallback machines (everything already final in
        ``self.results``) ride the result's ``rest`` dict with exact
        msgpack semantics.  Value parity with :meth:`assemble` is
        bitwise: both slice the same stacked host bytes.  Fleet-health
        sketches are recorded here exactly as ``assemble`` does, so the
        drift plane sees the same stream regardless of wire format.
        """
        from gordo_tpu import telemetry
        from gordo_tpu.serve import codec

        pending, self._pending = self._pending, []
        blocks: List[np.ndarray] = []
        machines: Dict[str, Dict[str, Tuple[int, int, Optional[int]]]] = {}
        scalar_blocks: set = set()
        for out, bucket, slots in pending:
            # ship only the occupied slot prefix: subset dispatches use a
            # contiguous prefix and full dispatches pad with duplicate
            # slot-0 rows, so wire waste stays bounded and no padding slot
            # carries anything a real slot doesn't
            n_slots = max(slot for _, slot, _, _ in slots) + 1
            key_block: Dict[str, int] = {}
            for k, v in out.items():
                key_block[k] = len(blocks)
                blocks.append(np.asarray(v)[:n_slots])
            thr_block = agg_block = None
            if bucket.with_thresholds:
                thr_block = len(blocks)
                blocks.append(np.asarray(bucket.thresholds_np))
                agg_block = len(blocks)
                blocks.append(np.asarray(bucket.agg_thresholds_np))
                # decodes to a python float — dtype= must not cast it
                scalar_blocks.add(agg_block)
            total_block = key_block.get("total-anomaly-score")
            for name, slot, stack_pos, n_valid in slots:
                entry: Dict[str, Tuple[int, int, Optional[int]]] = {
                    k: (b, slot, n_valid) for k, b in key_block.items()
                }
                if thr_block is not None:
                    entry["tag-anomaly-thresholds"] = (
                        thr_block, stack_pos, None,
                    )
                    entry["total-anomaly-threshold"] = (
                        agg_block, stack_pos, None,
                    )
                machines[name] = entry
                telemetry.FLEET_HEALTH.record(
                    name,
                    None if total_block is None
                    else blocks[total_block][slot][:n_valid],
                )
        return codec.ColumnarResult(
            blocks=blocks,
            machines=machines,
            scalar_blocks=scalar_blocks,
            rest=dict(self.results),
        )


class FleetScorer:
    """Serve MANY machines' anomaly scoring as stacked device programs.

    ``from_models`` buckets machines whose fused chains are structurally
    identical; ``score_all`` runs one vmapped dispatch per bucket.
    Machines that cannot fuse (or bucket alone) still work — they fall
    back to their own ``CompiledScorer`` path.
    """

    def __init__(self):
        self.buckets: List[_Bucket] = []
        self.fallbacks: Dict[str, CompiledScorer] = {}
        self.machine_bucket: Dict[str, Tuple[int, int]] = {}
        self.models: Dict[str, Any] = {}
        self._machine_scorers: Dict[str, CompiledScorer] = {}
        self.dtype: str = "float32"

    def _machine_scorer(self, name: str) -> CompiledScorer:
        if name not in self._machine_scorers:
            self._machine_scorers[name] = CompiledScorer(
                self.models[name], dtype=self.dtype, machine=name
            )
        return self._machine_scorers[name]

    @classmethod
    def from_models(
        cls,
        models: Dict[str, Any],
        mesh: Optional[Any] = None,
        pack_store: Optional[Any] = None,
        dtype: Optional[str] = None,
        prestacked_hint: Optional[Dict[str, Any]] = None,
    ) -> "FleetScorer":
        """``mesh``: optional ``("models", "data")`` fleet mesh; buckets
        shard their stacked machine axis over it so one serving dispatch
        spans every chip (single-device behavior is unchanged without it).

        ``pack_store``: the v2 :class:`gordo_tpu.artifacts.PackStore`
        the models came from, when they did.  Pack-backed machines group
        one bucket per pack and the bucket's stacked arrays ship as ONE
        whole-pack device transfer instead of a per-leaf ``jnp.stack``
        over per-machine copies — the v2 load contract.

        ``dtype``: serving precision for every bucket and fallback scorer
        (``None`` resolves ``GORDO_SERVE_DTYPE``); one fleet, one
        precision — per-machine mixing would make bulk responses depend
        on bucketing accidents.

        ``prestacked_hint``: already-stacked host arrays for the whole
        fleet (``PendingFleetBuild.prestacked``) — the builder's
        baseline-sketch call adopts them via :func:`_hint_group` instead
        of re-stacking its freshly assembled detectors' views leaf by
        leaf.  Ignored (generic stacking) on any mismatch.
        """
        self = cls()
        self.models = dict(models)
        self.dtype = (
            precision.canonical(dtype) if dtype else precision.serve_dtype()
        )
        groups: Dict[Tuple, Tuple[List[str], List[Dict]]] = {}
        for name, model in sorted(models.items()):
            refuse_sequence_model(model, name, "FleetScorer")
            chain = _extract_chain(model)
            sig = _signature(chain) if chain else None
            if sig is None:
                self.fallbacks[name] = CompiledScorer(
                    model, dtype=self.dtype, machine=name
                )
                continue
            names, chains = groups.setdefault(sig, ([], []))
            names.append(name)
            chains.append(chain)
        for names, chains in groups.values():
            prestacked = None
            if pack_store is not None:
                prestacked, names, chains = _prestack_group(
                    pack_store, names, chains
                )
            elif prestacked_hint is not None:
                prestacked, names, chains = _hint_group(
                    prestacked_hint, names, chains
                )
            bucket = _Bucket(
                names, chains, mesh=mesh, prestacked=prestacked,
                dtype=self.dtype,
            )
            idx = len(self.buckets)
            self.buckets.append(bucket)
            for pos, name in enumerate(names):
                self.machine_bucket[name] = (idx, pos)
        return self

    def delta_restack(
        self,
        models: Dict[str, Any],
        pack_store: Optional[Any],
        changed: List[str],
        mesh: Optional[Any] = None,
    ) -> "FleetScorer":
        """O(changed-machines) successor scorer after a generation flip.

        Buckets with no changed member are REUSED wholesale — same
        ``_Bucket`` object, same resident device arrays, zero transfers.
        Buckets with changed members rebuild through
        :func:`_delta_bucket`: one ``to_device`` per touched pack, device
        slices for everything else.  Every bucket (reused or rebuilt)
        keeps its dispatch shapes, so the compile plane serves all of the
        successor's programs from cache — a delta reload compiles
        nothing.

        The delta contract is checked, not assumed: membership drift
        (machines added/removed), signature drift, or geometry drift in
        any touched bucket falls back to a full :meth:`from_models`
        restack.  The old scorer is never mutated — callers keep serving
        it until they swap the returned one in.
        """
        def full() -> "FleetScorer":
            return FleetScorer.from_models(
                models, mesh=mesh, pack_store=pack_store, dtype=self.dtype
            )

        changed_set = set(changed)
        if set(models) != set(self.models):
            return full()
        if pack_store is None and changed_set:
            return full()
        known = set(self.machine_bucket) | set(self.fallbacks)
        if not changed_set <= known:
            return full()
        new = FleetScorer()
        new.dtype = self.dtype
        new.models = dict(models)
        new.fallbacks = dict(self.fallbacks)
        new._machine_scorers = {
            n: s for n, s in self._machine_scorers.items()
            if n not in changed_set
        }
        try:
            for n in changed_set & set(new.fallbacks):
                new.fallbacks[n] = CompiledScorer(
                    models[n], dtype=self.dtype, machine=n
                )
            for bucket in self.buckets:
                touched = [n for n in bucket.names if n in changed_set]
                nb = (
                    bucket if not touched
                    else _delta_bucket(
                        bucket, pack_store, touched, self.dtype
                    )
                )
                idx = len(new.buckets)
                new.buckets.append(nb)
                for pos, name in enumerate(nb.names):
                    new.machine_bucket[name] = (idx, pos)
        except _PrestackMiss:
            return full()
        return new

    @property
    def n_stacked(self) -> int:
        return sum(len(b.names) for b in self.buckets)

    def score_all(
        self, X_by_name: Dict[str, np.ndarray]
    ) -> Dict[str, Dict[str, np.ndarray]]:
        """Score every machine's rows in as few dispatches as buckets.

        Rows are padded (repeat-last) to a shared power-of-two bucket per
        program; outputs are sliced back per machine.
        """
        return self.dispatch_all(X_by_name).assemble()

    def dispatch_all(self, X_by_name: Dict[str, np.ndarray]) -> FleetDispatch:
        """The device half of :meth:`score_all`: run every stacked (and
        fallback) dispatch, defer the per-machine host-side slicing to the
        returned :class:`FleetDispatch` — callable from another thread."""
        dispatch = FleetDispatch()
        results = dispatch.results
        for bucket in self.buckets:
            wanted = [n for n in bucket.names if n in X_by_name]
            if not wanted:
                continue
            # rows a windowed model consumes: validation bound AND output
            # slicing offset (one expression — they must never diverge)
            offset_rows = (
                bucket.lookback - 1
                if bucket.mode == "ae"
                else bucket.lookback if bucket.mode == "forecast" else 0
            )
            ok_names = []
            for n in wanted:
                arr = np.asarray(X_by_name[n])
                # report malformed requests per machine; one bad machine
                # must not sink the whole stacked dispatch.  "client-error"
                # lets transports map these to 400 instead of 500.
                if arr.ndim != 2:
                    results[n] = {
                        "error": (
                            f"X must be 2-dimensional, got shape {arr.shape}"
                        ),
                        "client-error": True,
                    }
                elif arr.shape[0] <= offset_rows:
                    results[n] = {
                        "error": short_rows_message(
                            offset_rows, arr.shape[0]
                        ),
                        "client-error": True,
                    }
                elif (
                    bucket.n_features is not None
                    and arr.shape[1] != bucket.n_features
                ):
                    results[n] = {
                        "error": (
                            f"X has {arr.shape[1]} columns; model expects "
                            f"{bucket.n_features}"
                        ),
                        "client-error": True,
                    }
                else:
                    ok_names.append(n)
            wanted = ok_names
            if not wanted:
                continue
            arrays = {n: np.asarray(X_by_name[n], np.float32) for n in wanted}
            n_rows = _bucket_rows(max(a.shape[0] for a in arrays.values()))
            n_feat = next(iter(arrays.values())).shape[1]
            # A request covering only part of the bucket dispatches at the
            # SUBSET size (padded to a power of two so the jit cache stays
            # log-sized): compute and device->host transfer scale with the
            # machines actually requested, not the bucket's resident count.
            # This is what keeps coalesced rounds (~8 machines of a 64+
            # bucket) from paying full-bucket cost per dispatch.
            n_bucket = len(bucket.names)
            m_full = 1 << (len(wanted) - 1).bit_length()
            if m_full < n_bucket:
                m_eff = m_full  # subset dispatch (unsharded gather)
            else:
                # full dispatch; with a mesh the windows tensor shards
                # along the machine axis, so the PER-DEVICE bound sees
                # only each shard's machines
                m_eff = bucket.m_pad
                if bucket.mesh is not None:
                    from gordo_tpu.mesh import MODEL_AXIS

                    m_eff = -(-m_eff // bucket.mesh.shape[MODEL_AXIS])
            chunks = [wanted]
            # every per-machine windows tensor the fused program
            # materializes one-shot: the MODEL-INPUT windows of lookback
            # models (n, lookback, tags) and the smoothing windows
            # (n, smooth_window, tags) — summed, since both can be live
            win_factor = (bucket.smooth_window or 0) + (
                bucket.lookback if bucket.mode != "none" else 0
            )
            if win_factor:
                per_machine_elems = n_rows * win_factor * n_feat
                if per_machine_elems > SMOOTH_ELEMENT_BOUND:
                    # ONE machine's windows tensors alone exceed the bound
                    # — score each through its own scorer (blocked
                    # on-device median for smoothing overflow; host path
                    # for lookback overflow)
                    for n in wanted:
                        try:
                            results[n] = self._machine_scorer(
                                n
                            ).anomaly_arrays(arrays[n])
                        except Exception as exc:
                            # same per-machine isolation as the fallbacks
                            # loop: one machine's model-internal error must
                            # not 500 the whole bulk request
                            results[n] = {
                                "error": str(exc),
                                "client-error": isinstance(exc, ValueError),
                            }
                    continue
                if m_eff * per_machine_elems > SMOOTH_ELEMENT_BOUND:
                    # the windows tensor at the full dispatch size would
                    # blow device memory — split the MACHINE axis into
                    # bound-respecting subset dispatches instead of falling
                    # back to sequential per-machine scoring (one dispatch
                    # round-trip per machine; not re-measured on an attached
                    # chip)
                    cap = 1 << (
                        (SMOOTH_ELEMENT_BOUND // per_machine_elems)
                        .bit_length() - 1
                    )
                    chunks = [
                        wanted[i: i + cap]
                        for i in range(0, len(wanted), cap)
                    ]
            for chunk in chunks:
                pos = [self.machine_bucket[n][1] for n in chunk]
                m_sub = 1 << (len(pos) - 1).bit_length()
                subset = m_sub < n_bucket
                # reuse the pinned stacking buffer while shapes repeat (the
                # replayed-stream case).  The lock spans stack -> dispatch
                # -> device_get: concurrent bulk requests score from
                # executor threads, and an unguarded shared buffer would
                # let one request's rows overwrite another's mid-transfer.
                # Holding it through the dispatch costs nothing — the
                # device serializes same-bucket programs anyway.
                with bucket._lock:
                    if subset:
                        # slot i holds chunk[i]'s rows; padding slots
                        # repeat slot 0 (their outputs are discarded).  idx
                        # is traced, so machine choice never recompiles —
                        # only m_sub does.
                        idx = np.asarray(
                            pos + [pos[0]] * (m_sub - len(pos)), np.int32
                        )
                        stacked = bucket.stack_buffer(
                            (m_sub, n_rows, n_feat)
                        )
                        for i, name in enumerate(chunk):
                            bucket.fill_slot(stacked, i, arrays[name])
                        stacked[len(chunk): m_sub] = stacked[0]
                        out = jax.device_get(
                            bucket.score_subset(stacked, idx)
                        )
                        slot_of = {n: i for i, n in enumerate(chunk)}
                    else:
                        # full-bucket dispatch in bucket.names order:
                        # requested machines get repeat-last row padding;
                        # absent slots (and mesh shard-padding slots past
                        # n_bucket) score a dummy copy whose output is
                        # discarded
                        spare = next(iter(arrays.values()))
                        stacked = bucket.stack_buffer(
                            (bucket.m_pad, n_rows, n_feat)
                        )
                        for i, name in enumerate(bucket.names):
                            bucket.fill_slot(stacked, i, arrays.get(name, spare))
                        stacked[n_bucket: bucket.m_pad] = stacked[0]
                        # ONE device->host transfer per output array;
                        # slicing per machine afterwards is pure numpy
                        # (per-machine indexing of device arrays would
                        # issue hundreds of tiny transfers)
                        out = jax.device_get(bucket.score(stacked))
                        # full dispatch: output slots ARE stack positions
                        slot_of = None
                # device work done (out is host numpy after device_get);
                # record the slicing plan and defer the copies to assemble()
                slots = []
                for name in chunk:
                    stack_pos = self.machine_bucket[name][1]
                    slot = stack_pos if slot_of is None else slot_of[name]
                    n_valid = arrays[name].shape[0] - offset_rows
                    slots.append((name, slot, stack_pos, n_valid))
                dispatch._pending.append((out, bucket, slots))

        for name, scorer in self.fallbacks.items():
            if name in X_by_name:
                X = np.asarray(X_by_name[name], np.float32)
                if X.ndim != 2:
                    # same clean client error as the bucketed machines get
                    results[name] = {
                        "error": (
                            f"X must be 2-dimensional, got shape {X.shape}"
                        ),
                        "client-error": True,
                    }
                    continue
                try:
                    if scorer.is_anomaly:
                        results[name] = scorer.anomaly_arrays(X)
                    else:
                        # non-anomaly model: serve its plain prediction
                        # (mirrors the client's 422 -> /prediction fallback)
                        results[name] = {"model-output": scorer.predict(X)}
                except Exception as exc:
                    # missing thresholds, short rows, model-internal errors —
                    # report per machine instead of sinking the bulk request
                    results[name] = {
                        "error": str(exc),
                        "client-error": isinstance(exc, ValueError),
                    }
        return dispatch
