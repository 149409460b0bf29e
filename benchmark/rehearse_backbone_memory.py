"""Compile the fleet program of a ``backbone_build`` cell at its real shape
for a described (not attached) v5e and print what the compiler says it needs:
``rehearse_sequence_memory.py`` for the cells its ``main`` passes over (it
takes ``sequence_build`` cells alone; its ``rehearse`` reads any
``SequenceForecast`` configuration, and is what runs here).

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse_backbone_memory [--workload <name>]

A rehearsal, not a chip run: nothing executes.  For what the chip will
reserve, run it under ``XLA_FLAGS="--xla_dump_to=<dir>
--xla_dump_hlo_as_text=true"`` and read ``*jit_program*memory-usage-report.txt``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    parser = argparse.ArgumentParser(prog="benchmark.rehearse_backbone_memory")
    parser.add_argument("--workload", default=None)
    args = parser.parse_args(argv)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import manifest as manifest_mod
    from benchmark.rehearse_sequence_memory import rehearse

    manifest = manifest_mod.Manifest()
    for cell in manifest.doc["workloads"]:
        if manifest.traffic(cell["traffic"])["kind"] != "backbone_build":
            continue
        if args.workload in (None, cell["name"]):
            print(json.dumps(rehearse(manifest, cell)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
