"""Compile the fleet program of each ``sequence_build`` cell at its real
shape for a described (not attached) v5e and print what the compiler says
it needs: ``rehearse_memory.py`` for the kind whose project document it
cannot write (it imports ``kinds.fleet_build``).

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse_sequence_memory [--workload <name>]

A rehearsal, not a chip run: nothing executes, and the figures are the
compiler's ``memory_analysis()`` for one program on one device.  The chip's
own reading decides a cell's size (PERF.md puts the two side by side).  A
compile takes minutes here, so this is a script and not a test; the
topology is described inside ``main``, never at import.

It reaches below the program's public entry points (the builder's group
context and the closure's jitted function): there is no public way to lower
the fleet program for a device that is not attached.  ``compute_dtype`` is
pinned to what ``auto`` resolves to on a TPU, because under
``JAX_PLATFORMS=cpu`` it would resolve to float32.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

GIB = 2.0 ** 30


def rehearse(manifest, cell) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.kinds import sequence_build as kind
    from gordo_tpu import serializer
    from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition

    config = manifest.config(cell["config"])
    pinned = json.loads(json.dumps(config))
    if pinned["model"].get("compute_dtype", "auto") == "auto":
        pinned["model"]["compute_dtype"] = "bfloat16"
    doc = kind.project_doc(pinned, 0, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    builder = FleetDiffBuilder(spec)
    ds = config["dataset"]
    m, rows, tags = int(config["deployment"]["max_bucket_size"]), int(ds["rows"]), int(ds["n_tags"])
    ctx = builder._group_context(rows, tags, tags)
    program = builder._group_program(ctx, padded=False, warm=False)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    t0 = time.time()
    compiled = program._jitted.lower(
        aval((m, rows, tags), jnp.float32), aval((m, rows, tags), jnp.float32),
        aval((m,), jnp.uint32),
    ).compile()
    ma = compiled.memory_analysis()
    out = {
        "workload": cell["name"], "machines": m, "rows": rows, "tags": tags,
        "compile_s": time.time() - t0,
        "argument_gib": ma.argument_size_in_bytes / GIB,
        "output_gib": ma.output_size_in_bytes / GIB,
        "temp_gib": ma.temp_size_in_bytes / GIB,
        "alias_gib": ma.alias_size_in_bytes / GIB,
    }
    out["arguments_plus_temporaries_gib"] = out["argument_gib"] + out["temp_gib"]
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    parser = argparse.ArgumentParser(prog="benchmark.rehearse_sequence_memory")
    parser.add_argument("--workload", default=None)
    args = parser.parse_args(argv)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import manifest as manifest_mod

    manifest = manifest_mod.Manifest()
    for cell in manifest.doc["workloads"]:
        if manifest.traffic(cell["traffic"])["kind"] != "sequence_build":
            continue
        if args.workload in (None, cell["name"]):
            print(json.dumps(rehearse(manifest, cell)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
