"""The placement plane: the ONE owner of device meshes and shardings.

Everything mesh- or sharding-shaped lives here (or is re-exported from
here): :class:`FleetMesh` resolves which devices participate
(``GORDO_MESH_DEVICES`` / ``--mesh-devices`` / auto), :class:`PlacementSpec`
decides what sharding each operand gets, and :func:`place` is the single
``jax.device_put`` seam outside the artifact plane's ``to_device``.
``scripts/lint.py`` bans raw ``jax.device_put`` / ``jax.sharding.*``
construction everywhere else, so the rest of the stack imports the
``Mesh`` / ``NamedSharding`` / ``PartitionSpec`` types from HERE when it
needs them for annotations or cache keys.
"""

from jax.sharding import Mesh, NamedSharding, PartitionSpec

from gordo_tpu.mesh.fleet import (
    DATA_AXIS,
    ENV_MESH_DEVICES,
    MODEL_AXIS,
    FleetMesh,
    fleet_mesh,
    global_fleet_mesh,
    pad_to_multiple,
)
from gordo_tpu.mesh.placement import (
    PlacementSpec,
    array_devices,
    data_sharding,
    device_doc,
    model_sharding,
    place,
    replicated_sharding,
)

__all__ = [
    "DATA_AXIS",
    "ENV_MESH_DEVICES",
    "MODEL_AXIS",
    "Mesh",
    "NamedSharding",
    "PartitionSpec",
    "FleetMesh",
    "PlacementSpec",
    "array_devices",
    "data_sharding",
    "device_doc",
    "fleet_mesh",
    "global_fleet_mesh",
    "model_sharding",
    "pad_to_multiple",
    "place",
    "replicated_sharding",
]
