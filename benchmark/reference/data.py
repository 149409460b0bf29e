"""The benchmark's own copy of the seeded plant data.

A machine's sensor matrix is a pure function of its tag names: per tag a
random walk on a 5-minute grid, seeded by a digest of the tag name, then
the mean over each 10-minute bucket.  This is the recipe of upstream's
``RandomDataProvider`` behind a ``TimeSeriesDataset`` at its default
resolution, written out here so that the reference trains on data it made
itself.  Nothing of the program is imported.
"""

from __future__ import annotations

import zlib
from typing import Sequence

import numpy as np

_SOURCE_STEP_S = 300          # the provider's grid: one sample per 5 minutes
_PROVIDER_MAX_POINTS = 50_000  # past this the provider's grid stops being regular


def machine_rows(tags: Sequence[str], start_s: int, end_s: int,
                 resolution_s: int = 600, provider_seed: int = 0) -> np.ndarray:
    """``(rows, len(tags))`` float32: the training matrix of one machine.

    ``start_s``/``end_s`` are epoch seconds of the train window (both ends
    sampled, as the provider does)."""
    n = (end_s - start_s) // _SOURCE_STEP_S + 1
    if n > _PROVIDER_MAX_POINTS:
        raise ValueError(
            f"{n} source points: past {_PROVIDER_MAX_POINTS} the provider "
            "thins its grid and this copy no longer describes it"
        )
    if resolution_s % _SOURCE_STEP_S:
        raise ValueError("resolution must be a multiple of the 5-minute grid")
    per = resolution_s // _SOURCE_STEP_S
    # both ends are sampled, so the end point opens a last bucket of its own
    full = (end_s - start_s) // resolution_s
    rows = full + 1
    out = np.empty((rows, len(tags)), dtype=np.float32)
    for j, tag in enumerate(tags):
        rng = np.random.default_rng(zlib.crc32(f"{tag}:{provider_seed}".encode()))
        walk = rng.standard_normal(n).cumsum() * 0.1 + rng.uniform(-1, 1)
        out[:full, j] = walk[: full * per].reshape(full, per).mean(axis=1)
        out[full, j] = walk[full * per:].mean()
    return out
