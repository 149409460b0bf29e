"""Sharding construction + the one counted ``device_put`` seam.

Everything that ships host data to devices outside the artifact plane's
``to_device`` goes through :func:`place` here, and every
``jax.sharding.NamedSharding`` in the stack is built by this module —
``scripts/lint.py`` rejects raw ``jax.device_put`` / ``jax.sharding.*``
construction anywhere else, the same single-owner contract the shard
function and the compile plane already enforce.

Placement layout for a fleet-stacked program: every operand with a leading
``models`` axis (params, opt-state, X/y/w stacks, thresholds) shards that
axis over the mesh fleet axis and replicates the rest; scalars replicate.
The shardings are donation-compatible — a donated input buffer and its
matching output share a layout, so the compile plane's ``donate_argnums``
keep working unchanged on the sharded path.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Set

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gordo_tpu.telemetry import metrics as telemetry
from gordo_tpu.telemetry.spans import add_to_span

from .fleet import DATA_AXIS, MODEL_AXIS, FleetMesh

_PLACEMENTS = telemetry.counter(
    "gordo_fleet_placements_total",
    "Fleet-stack device placements by kind (sharded mesh vs single device)",
    labels=("kind",),
)
_DEVICE_TRANSFERS = telemetry.counter(
    "gordo_mesh_device_transfers_total",
    "Array leaves transferred to each device by the placement plane",
    labels=("device",),
)


def model_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    """Sharding placing a leading ``models`` axis over the mesh fleet axis."""
    return NamedSharding(mesh, P(MODEL_AXIS, *([None] * extra_dims)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    """Sharding placing a leading rows axis over the mesh ``data`` axis
    (the data-parallel single-model fit path)."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * extra_dims)))


class PlacementSpec:
    """The sharding plan for one fleet-stacked program's operands.

    Wraps an optional mesh (a raw :class:`Mesh`, a :class:`FleetMesh`, or
    ``None``) and answers "what sharding does THIS operand get".  With no
    mesh every method returns ``None`` — which ``jax.device_put`` and the
    compile plane both read as "default single-device placement", keeping
    the degenerate case today's code path exactly.
    """

    __slots__ = ("mesh",)

    def __init__(self, mesh: Optional[Any] = None):
        if isinstance(mesh, FleetMesh):
            mesh = mesh.mesh
        self.mesh: Optional[Mesh] = mesh

    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    def stacked(self, extra_dims: int = 0) -> Optional[NamedSharding]:
        """Leading ``models`` axis sharded, ``extra_dims`` trailing axes
        replicated (params/opt-state/X/y/w stacks)."""
        if self.mesh is None:
            return None
        return model_sharding(self.mesh, extra_dims)

    def replicated(self) -> Optional[NamedSharding]:
        """Fully replicated (scalars, shared configuration arrays)."""
        if self.mesh is None:
            return None
        return replicated_sharding(self.mesh)

    def leaf(self, a: Any) -> Optional[NamedSharding]:
        """The stacked sharding matched to ``a``'s rank (leading axis is
        the fleet axis, everything after replicates)."""
        if self.mesh is None:
            return None
        ndim = getattr(a, "ndim", 0)
        return model_sharding(self.mesh, max(int(ndim) - 1, 0))

    def tree(self, host_tree: Any) -> Optional[Any]:
        """Per-leaf stacked shardings for a whole pytree (params stacks)."""
        if self.mesh is None:
            return None
        return jax.tree_util.tree_map(self.leaf, host_tree)


def array_devices(tree: Any) -> Set[jax.Device]:
    """The devices that hold ``tree``'s jax arrays, read from the arrays
    themselves — what was placed, not what a sharding asked for."""
    held: Set[jax.Device] = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            held.update(leaf.devices())
    return held


def device_doc(held: Iterable[jax.Device] = ()) -> Dict[str, Any]:
    """The ``device`` object every result carries (build summary,
    ``/healthz``): ``platform`` and ``device_kind`` of the devices in
    ``held`` — pass :func:`array_devices` of the arrays the work ran on —
    ``count`` of the devices jax sees in this process, and ``used``, how
    many of them ``held`` names.  With nothing held (a fully cached build)
    platform and kind are the first visible device's and ``used`` is 0."""
    visible = jax.devices()
    held = set(held)
    first = min(held, key=lambda d: d.id) if held else visible[0]
    return {
        "platform": first.platform,
        "device_kind": first.device_kind,
        "count": len(visible),
        "used": len(held),
    }


def place(tree: Any, sharding: Any = None) -> Any:
    """THE device transfer of the placement plane.

    ``sharding`` may be ``None`` (default single-device placement — the
    degenerate path), one sharding broadcast over the tree, or a pytree of
    shardings matching ``tree``.  Counts one placement per call
    (``gordo_fleet_placements_total{kind}``) and, per device, the leaves
    that landed on it (``gordo_mesh_device_transfers_total{device}``) —
    both read from the placed arrays' own devices — and adds the
    transfer's ``bytes`` and ``leaves`` onto the span the caller has open
    (``gordo.build.stage`` in a project build).
    """
    if sharding is None:
        out = jax.device_put(tree)
    else:
        out = jax.device_put(tree, sharding)
    if telemetry.enabled():
        per_device: Dict[int, int] = {}
        leaves = jax.tree_util.tree_leaves(out)
        for leaf in leaves:
            for d in leaf.devices():
                per_device[d.id] = per_device.get(d.id, 0) + 1
        add_to_span(bytes=sum(leaf.nbytes for leaf in leaves),
                    leaves=len(leaves))
        _PLACEMENTS.inc(1.0, "sharded" if len(per_device) > 1 else "single")
        for device_id, n_leaves in per_device.items():
            _DEVICE_TRANSFERS.inc(float(n_leaves), str(device_id))
    return out
