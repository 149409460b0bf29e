"""What the run sits on: the chip requirement, the device report and the
table of peaks.  A device that is not in the table is an error, never a
default."""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> List[Any]:
    """The devices a cell runs on; raises :class:`NoChip` when jax found
    no TPU or fewer than ``chips``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(
            f"jax found platform {devices[0].platform!r}, not a TPU: "
            "this benchmark has no CPU fallback"
        )
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax found {len(devices)}")
    return list(devices[:chips])


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"{PEAKS_FILE} with its source before quoting a share of them"
        )
    return table[device_kind]


def loaded_programs(devices: List[Any]) -> List[Dict[str, Any]]:
    """Every executable the backend holds loaded, largest temporaries first:
    ``name``, ``temp_bytes``, ``argument_bytes``, ``output_bytes`` as the
    compiler states them (``get_compiled_memory_stats``)."""
    out = []
    for exe in devices[0].client.live_executables():
        stats = exe.get_compiled_memory_stats()
        modules = exe.hlo_modules()
        out.append({
            "name": modules[0].name if modules else "?",
            "temp_bytes": int(stats.temp_size_in_bytes),
            "argument_bytes": int(stats.argument_size_in_bytes),
            "output_bytes": int(stats.output_size_in_bytes),
        })
    return sorted(out, key=lambda e: -e["temp_bytes"])


def report(devices: List[Any]) -> Dict[str, Any]:
    """The ``device`` object of the result line.

    ``peak_bytes_in_use`` counts live buffers only: on a TPU a program's
    temporaries are not buffers but a reservation that the device holds
    while the program is loaded (``peak_bytes_reserved``).  So
    ``memory_peak_bytes`` is the buffers' peak plus the temporaries of the
    largest loaded program, and only as far as the device reserved them:
    the smaller of the chip's own ``peak_bytes_reserved`` and that
    program's ``temp_size_in_bytes``.  A reservation that no loaded
    program accounts for is not counted.  The parts stand beside the sum
    under keys of their own."""
    programs = loaded_programs(devices)
    temp = programs[0]["temp_bytes"] if programs else 0
    best: Dict[str, Any] = {"memory_peak_bytes": -1}
    for dev in devices:
        stats = dev.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        peak = in_use + min(reserved, temp)
        if peak > best["memory_peak_bytes"]:
            best = {
                "memory_peak_bytes": peak,
                "peak_bytes_in_use": in_use,
                "peak_bytes_reserved": reserved,
                "largest_program_temp_bytes": temp,
                "largest_program": programs[0]["name"] if programs else None,
            }
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        **best,
    }
