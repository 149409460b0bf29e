"""Build-ingest plane: fleet-level dataset assembly.

The r23 stage attribution showed the build loop ingest-bound: per
512-machine chunk, the host ``load`` stage (512 sequential per-machine
``dataset.get_data()`` pandas passes) cost more than the device compute
it feeds.  This package is the tf.data move for the fleet builder — keep
the input pipeline off the accelerator's critical path:

- :mod:`gordo_tpu.ingest.fingerprint` — dataset/provider fingerprints,
  hoisted from the r18 backfill runner into the ONE shared definition of
  "these machines fetch the same data" used by the builder, refresh, and
  batch planes.
- :mod:`gordo_tpu.ingest.plane` — :func:`~gordo_tpu.ingest.plane.load_chunk`:
  one chunk of machines assembled as a fleet.  Machines sharing a dataset
  fingerprint fetch once; machines sharing (index, resolution, window)
  geometry resample/join as ONE columnar numpy pass across the machine
  axis, written straight into a preallocated ``(m_pad, n, tags)`` float32
  stacked buffer the dispatch path adopts without re-stacking.  Anything
  the vectorized path cannot express takes the sanctioned per-machine
  ``get_data()`` fallback with byte-identical results.
"""

from gordo_tpu.ingest.fingerprint import (  # noqa: F401
    dataset_fingerprint,
    provider_fingerprint,
)
from gordo_tpu.ingest.plane import (  # noqa: F401
    load_chunk,
    owned_stack_base,
    stack_live_slots,
)
