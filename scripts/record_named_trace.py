"""Record ``benchmark/testdata/tiny_named.xplane.pb`` on the chip: two whole
executions of the fleet program (three folds' fits and forecasts, the final
fit) of the ``lfm2_moe`` backbone's tiny preset, as
``tests/test_backbone_lfm2.py`` builds it, with the names the program gives
its operations.

    python scripts/record_named_trace.py

Chip only (two minutes).  Keeps of the recording what the readers under
``benchmark/readers/`` and ``scripts/sequence_trace_split.py`` read: the
first device plane's ``XLA Modules`` and ``XLA Ops`` lines, an
instruction's name without its operands, and the fields ``tf_op``,
``source``, ``hlo_category``, ``flops``, ``bytes_accessed``.  Writes
``chiprun_out/tiny_named.xplane.pb`` and prints what the readers read in it.
"""

from __future__ import annotations

import glob
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

KEPT_LINES = ("XLA Modules", "XLA Ops")
KEPT_FIELDS = ("tf_op", "source", "hlo_category", "flops", "bytes_accessed")
TINY = dict(hidden_size=64, num_heads=8, num_kv_heads=2, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8, num_experts_per_token=2,
            experts_held=2, experts_held_from=0, num_layers=5,
            context=32, stride=16, batch_size=4)
TAGS, ROWS, SEED = 5, 217, 13
DEST = os.path.join(ROOT, "chiprun_out", "tiny_named.xplane.pb")


def reduced(space, messages):
    """The first device plane with the lines and fields the readers read."""
    plane = sorted((p for p in space.planes if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)[0]
    keep = messages.XSpace()
    out = keep.planes.add()
    out.id, out.name = plane.id, plane.name
    fields = {i for i, meta in plane.stat_metadata.items() if meta.name in KEPT_FIELDS}
    refs = set()
    used = set()
    for line in plane.lines:
        if line.name in KEPT_LINES:
            out.lines.add().CopyFrom(line)
            used.update(ev.metadata_id for ev in line.events)
    for line in out.lines:
        for ev in line.events:
            del ev.stats[:]
    for i in used:
        meta = plane.event_metadata[i]
        kept = out.event_metadata[i]
        kept.id, kept.name = meta.id, meta.name.split(" ")[0]
        for stat in meta.stats:
            if stat.metadata_id in fields:
                kept.stats.add().CopyFrom(stat)
                if stat.WhichOneof("value") == "ref_value":
                    refs.add(stat.ref_value)
    for i in fields | refs:
        out.stat_metadata[i].CopyFrom(plane.stat_metadata[i])
    return keep


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sequence_trace_split
    from benchmark.kinds import backbone_build as kind
    from benchmark.readers import trace_scope_seconds
    from gordo_tpu import serializer
    from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition

    config = {
        "detector": "DiffBasedAnomalyDetector", "scalers": ["MinMaxScaler"],
        "estimator": "SequenceForecast",
        "model": {"kind": "lfm2_moe", "epochs": 1, "learning_rate": 0.001,
                  "compute_dtype": "bfloat16", **TINY},
        "cv": {"splitter": "TimeSeriesSplit", "n_splits": 3},
        "dataset": {"type": "RandomDataset", "resolution": "10min", "n_tags": TAGS,
                    "train_start_date": "2017-01-01T00:00:00+00:00",
                    "train_end_date": "2017-01-02T12:00:00+00:00", "rows": ROWS},
    }
    doc = kind.project_doc(config, SEED, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    builder = FleetDiffBuilder(spec)
    program = builder._group_program(builder._group_context(ROWS, TAGS, TAGS),
                                     padded=False, warm=False)
    rows = np.random.default_rng(SEED).normal(size=(1, ROWS, TAGS)).astype(np.float32)
    args = (jnp.asarray(rows), jnp.asarray(rows), jnp.asarray([SEED], jnp.uint32))
    jax.block_until_ready(program(*args))
    out = tempfile.mkdtemp()
    with jax.profiler.trace(out):
        for _ in range(2):
            jax.block_until_ready(program(*args))
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    messages = trace_scope_seconds.xplane_messages()
    space = messages.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    os.makedirs(os.path.dirname(DEST), exist_ok=True)
    with open(DEST, "wb") as fh:
        fh.write(reduced(space, messages).SerializeToString())
    found = sequence_trace_split.split(DEST, sources=True)
    record = {"trace_dir": os.path.dirname(DEST), "device_kind": jax.devices()[0].device_kind,
              "work_per_chunk": {"steps_per_model": 1}, "snap_start": {}, "snap_end": {}}
    print(os.path.getsize(DEST), "bytes;", found["programs"], "whole programs of",
          found["program_s"], "s")
    for scope, row in found["by_scope"].items():
        print(f"  {scope:28s} {row['s']:.6f} s {100 * row['share']:5.1f} %")
    for row in found["unscoped"][:30]:
        print(f"    {row['primitive']:28s} {row['hlo']:36s} {row['s']:.6f} s "
              f"{100 * row['share']:5.2f} % x{row['events']} {row['source']}")
    print(sequence_trace_split.readings(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
