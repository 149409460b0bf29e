"""Operations that one chunk of the fleet build needs, from its shapes
alone.  Kept with the benchmark so that no later change to the
program can move the yardstick.

The count is the algorithm's: 2 operations per weight per window step in
the forward pass and 4 in the backward pass, for every window a fit trains
on, plus the forward pass over each fold's held-out rows.  Work the program
does beyond that (padding rows, recomputation) is not counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple


def kernel_params(dims: Sequence[int], n_features: int, n_out: int) -> int:
    """Weights that enter a matmul: per LSTM layer ``4H(in + H)``, then the
    dense head (biases left out)."""
    total, prev = 0, n_features
    for h in dims:
        total += 4 * h * (prev + h)
        prev = h
    return total + prev * n_out


def time_series_folds(n_rows: int, n_splits: int) -> List[Tuple[int, int]]:
    """``(train_rows, test_rows)`` of each expanding fold: blocks of
    ``n_rows // (n_splits + 1)`` rows, fold k trains on the first k blocks
    and is tested on the next; the last test block takes the rows left
    over."""
    block = n_rows // (n_splits + 1)
    return [
        (block * k, (block if k < n_splits else n_rows - block * n_splits))
        for k in range(1, n_splits + 1)
    ]


def chunk_work(*, machines: int, n_rows: int, n_features: int,
               dims: Sequence[int], lookback: int, epochs: int,
               n_splits: int) -> Dict[str, Any]:
    """FLOPs for one chunk: ``n_splits`` fold fits with their held-out
    predictions, then the final fit."""
    kp = kernel_params(dims, n_features, n_features)
    windows = lambda rows: max(rows - lookback + 1, 0)  # noqa: E731
    trained, predicted = 0, 0
    for train_rows, test_rows in time_series_folds(n_rows, n_splits):
        trained += windows(train_rows) * epochs
        predicted += windows(test_rows)
    trained += windows(n_rows) * epochs
    per_model = kp * lookback * (6.0 * trained + 2.0 * predicted)
    return {
        "kernel_params": kp,
        "trained_windows": trained,
        "predicted_windows": predicted,
        "flops_per_model": per_model,
        "flops": per_model * machines,
    }
