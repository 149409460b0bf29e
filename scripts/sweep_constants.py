#!/usr/bin/env python
"""Hardware sweeps for device-side tuning constants and perf scenarios
(results recorded in docs/perf.md).  Each sweep is sized to finish well
inside a 10-minute window:

- ``minbucket``: fused-scorer latency vs padded row-bucket size
  (→ ``serve/scorer.py::MIN_BUCKET``)
- ``bucket``: fleet-build rate vs ``max_bucket_size``
  (→ ``builder/fleet_build.py::DEFAULT_MAX_BUCKET``)
- ``smooth``: stacked smoothing-window scoring vs the windows-tensor size
  (→ ``serve/fleet_scorer.py::SMOOTH_ELEMENT_BOUND``)
- ``multibucket``: mixed-tag-width project vs a uniform one (per-bucket
  compile/dispatch overhead)
- ``sustained``: one 4096-machine memory-bounded project build
- ``lstmdtype``: LSTM fleet build rate, bfloat16 vs float32 compute
- ``lstmbucket``: LSTM fleet build rate vs machines-per-bucket, 64→512
  (→ ``builder/fleet_build.py::DEFAULT_MAX_BUCKET_LSTM``)

Usage: python scripts/sweep_constants.py
           {minbucket|bucket|smooth|multibucket|sustained|lstmdtype|lstmbucket} [n]
(``n`` — machine count — applies to bucket/sustained/lstmdtype only.)
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time

import numpy as np


def build_one(n_tags: int = 10, window: int = 0):
    from gordo_tpu.builder.build_model import build_model
    from gordo_tpu.workflow.config import Machine

    mc = {
        "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
            **({"window": window} if window else {}),
            "base_estimator": {
                "gordo_tpu.pipeline.Pipeline": {
                    "steps": [
                        "gordo_tpu.ops.scalers.MinMaxScaler",
                        {
                            "gordo_tpu.models.estimator.AutoEncoder": {
                                "kind": "feedforward_hourglass",
                                "epochs": 10,
                                "batch_size": 64,
                            }
                        },
                    ]
                }
            },
        }
    }
    m = Machine.from_config(
        {
            "name": "sweep-m",
            "dataset": {
                "type": "RandomDataset",
                "tag_list": [f"t-{j}" for j in range(n_tags)],
            },
            "model": mc,
        }
    )
    model, _ = build_model(m.name, m.model, m.dataset, {}, m.evaluation)
    return model


def sweep_minbucket() -> None:
    """Latency vs padded bucket rows: if flat up to 256+, MIN_BUCKET can
    rise to cut jit-cache entries; if it climbs, small buckets pay off."""
    from gordo_tpu.serve.scorer import CompiledScorer

    sc = CompiledScorer(build_one())
    rng = np.random.default_rng(0)
    for rows in (32, 64, 128, 256, 512, 1024, 2048):
        X = rng.standard_normal((rows, 10)).astype(np.float32)
        sc.anomaly_arrays(X)  # compile this bucket
        t0 = time.perf_counter()
        for _ in range(30):
            sc.anomaly_arrays(X)
        dt = (time.perf_counter() - t0) / 30
        print(
            f"rows={rows:5d}: {dt * 1000:6.2f} ms/call "
            f"({rows * 10 / dt / 1e3:,.0f}k samples/s)",
            flush=True,
        )


def sweep_bucket(n_machines: int = 512) -> None:
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import Machine

    machines = [
        Machine.from_config(
            {
                "name": f"swp-{i:04d}",
                "dataset": {
                    "type": "RandomDataset",
                    "tag_list": [f"t-{i}-{j}" for j in range(10)],
                },
            }
        )
        for i in range(n_machines)
    ]
    for bucket in (128, 256, 512):
        _timed_build(
            machines, f"max_bucket={bucket:5d}", max_bucket_size=bucket
        )


def _timed_build(machines, label: str, **build_kwargs) -> None:
    """Cold + warm timed ``build_project`` runs; prints one result line —
    the ONE measurement harness every build-rate sweep shares."""
    from gordo_tpu.builder.fleet_build import build_project

    rates = []
    for _run in range(2):
        out = tempfile.mkdtemp()
        t0 = time.perf_counter()
        res = build_project(machines, out, **build_kwargs)
        dt = time.perf_counter() - t0
        shutil.rmtree(out, ignore_errors=True)
        assert not res.failed, list(res.failed.items())[:2]
        rates.append(len(res.artifacts) / dt * 3600)
    print(
        f"{label}: warm {rates[-1]:,.0f} models/h (cold {rates[0]:,.0f})",
        flush=True,
    )


def _machines(n: int, n_tags: int = 10, prefix: str = "swp"):
    from gordo_tpu.workflow.config import Machine

    return [
        Machine.from_config(
            {
                "name": f"{prefix}-{i:04d}",
                "dataset": {
                    "type": "RandomDataset",
                    "tag_list": [f"t-{i}-{j}" for j in range(n_tags)],
                },
            }
        )
        for i in range(n)
    ]


def sweep_multibucket() -> None:
    """Bench-diversity scenario: a project whose machines split across 4
    tag widths (4 buckets, 4 programs) vs a uniform project of the same
    size — measures the per-bucket compile+dispatch overhead."""
    from gordo_tpu.builder.fleet_build import build_project
    import shutil as sh
    import tempfile as tf

    uniform = _machines(512, 10, "uni")
    mixed = (
        _machines(128, 8, "w8") + _machines(128, 12, "w12")
        + _machines(128, 16, "w16") + _machines(128, 24, "w24")
    )
    for label, machines in (("uniform-1-bucket", uniform),
                            ("mixed-4-buckets", mixed)):
        _timed_build(machines, label)


def sweep_sustained(n: int = 4096) -> None:
    """Bench-diversity scenario: one sustained 4096-machine project build
    (8 chunks of 512) — the memory-bounded stream at scale, warm rate."""
    from gordo_tpu.builder.fleet_build import build_project
    import shutil as sh
    import tempfile as tf

    machines = _machines(n, 10, "sus")
    for run in range(2):
        out = tf.mkdtemp()
        t0 = time.perf_counter()
        res = build_project(machines, out)
        dt = time.perf_counter() - t0
        sh.rmtree(out, ignore_errors=True)
        assert not res.failed, list(res.failed.items())[:2]
        print(f"run {run}: {len(res.artifacts)} machines in {dt:.1f}s "
              f"({len(res.artifacts) / dt * 3600:,.0f} models/h, "
              f"peak_loaded={res.peak_loaded})", flush=True)


def sweep_lstmdtype(n_machines: int = 32) -> None:
    """The r4 pending measurement (docs/perf.md): LSTM fleet build rate
    with bfloat16 vs float32 recurrent compute.  The LSTM scenario is the
    only FLOP-heavy path, so the MXU-native dtype should move it; run on a
    healthy TPU (each dtype compiles its own program — cold run first,
    warm run is the number)."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import Machine

    for dtype in ("bfloat16", "float32"):
        machines = [
            Machine.from_config(
                {
                    "name": f"dt-{dtype[:4]}-{i:03d}",
                    "dataset": {
                        "type": "RandomDataset",
                        "tag_list": [f"t-{i}-{j}" for j in range(50)],
                    },
                    "model": {
                        "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
                            "base_estimator": {
                                "gordo_tpu.pipeline.Pipeline": {
                                    "steps": [
                                        "gordo_tpu.ops.scalers.MinMaxScaler",
                                        {
                                            "gordo_tpu.models.estimator"
                                            ".LSTMAutoEncoder": {
                                                "kind": "lstm_hourglass",
                                                "lookback_window": 12,
                                                "epochs": 10,
                                                "batch_size": 64,
                                                "compute_dtype": dtype,
                                            }
                                        },
                                    ]
                                }
                            }
                        }
                    },
                }
            )
            for i in range(n_machines)
        ]
        _timed_build(machines, f"compute_dtype={dtype}")


def sweep_lstmbucket(n_unused: int = 0, epochs: int = 2) -> None:
    """Machines-per-bucket sweep for the LSTM fleet CV+fit program
    (→ ``builder/fleet_build.py::DEFAULT_MAX_BUCKET_LSTM``).

    Per bucket size b in 64→512: build exactly b machines as ONE chunk
    (``max_bucket_size=b``) — a big project's steady-state rate IS its
    per-chunk rate, since chunks run sequentially — cold then warm, so
    the table carries both the per-size compile cost and the amortized
    rate.  ``epochs=2`` (vs the bench's 10) keeps the 512-point tractable
    on CPU; dispatch-amortization differences between bucket sizes only
    get MORE visible with less compute per machine, so the knee the sweep
    finds is conservative.  Peak host/device memory scales with b via the
    stacked (b, rows, 50) arrays and the windows tensors — the smoothing
    bound (`docs/perf.md`) is the other half of the decision."""
    from gordo_tpu.workflow.config import Machine

    for b in (64, 128, 256, 512):
        machines = [
            Machine.from_config(
                {
                    "name": f"lb-{b}-{i:03d}",
                    "dataset": {
                        "type": "RandomDataset",
                        "tag_list": [f"t-{i}-{j}" for j in range(50)],
                    },
                    "model": {
                        "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
                            "base_estimator": {
                                "gordo_tpu.pipeline.Pipeline": {
                                    "steps": [
                                        "gordo_tpu.ops.scalers.MinMaxScaler",
                                        {
                                            "gordo_tpu.models.estimator"
                                            ".LSTMAutoEncoder": {
                                                "kind": "lstm_hourglass",
                                                "lookback_window": 12,
                                                "epochs": epochs,
                                                "batch_size": 64,
                                            }
                                        },
                                    ]
                                }
                            }
                        }
                    },
                }
            )
            for i in range(b)
        ]
        _timed_build(machines, f"lstm_bucket={b:4d}", max_bucket_size=b)


def sweep_smooth() -> None:
    """Probe the smoothing windows-tensor guard: disable it and drive
    stacked scoring at sizes spanning the current 2^27-element bound."""
    import gordo_tpu.serve.fleet_scorer as fs_mod
    from gordo_tpu.serve.fleet_scorer import FleetScorer

    model = build_one(window=144)
    rng = np.random.default_rng(0)
    fs_mod.SMOOTH_ELEMENT_BOUND = 2 ** 40  # hardware probe: guard off
    for m_count, rows in ((32, 2048), (64, 2048), (64, 4096)):
        elems = m_count * rows * 144 * 10
        fleet = FleetScorer.from_models(
            {f"m-{i}": model for i in range(m_count)}
        )
        X_by = {
            f"m-{i}": rng.standard_normal((rows, 10)).astype(np.float32)
            for i in range(m_count)
        }
        try:
            fleet.score_all(X_by)  # compile
            t0 = time.perf_counter()
            for _ in range(3):
                fleet.score_all(X_by)
            dt = (time.perf_counter() - t0) / 3
            print(
                f"M={m_count} rows={rows} window=144 "
                f"elems=2^{np.log2(elems):.1f}: OK {dt * 1000:,.0f} ms/call "
                f"({m_count * rows * 10 / dt / 1e6:.2f}M samples/s)",
                flush=True,
            )
        except Exception as exc:
            print(
                f"M={m_count} rows={rows} elems=2^{np.log2(elems):.1f}: "
                f"FAILED {type(exc).__name__}: {str(exc)[:160]}",
                flush=True,
            )


if __name__ == "__main__":
    sweeps = {
        "minbucket": sweep_minbucket,
        "bucket": sweep_bucket,
        "smooth": sweep_smooth,
        "multibucket": sweep_multibucket,
        "sustained": sweep_sustained,
        "lstmdtype": sweep_lstmdtype,
        "lstmbucket": sweep_lstmbucket,
    }
    which = sys.argv[1] if len(sys.argv) > 1 else ""
    if which not in sweeps:
        print(
            f"usage: {sys.argv[0]} {{{'|'.join(sweeps)}}} [n]",
            file=sys.stderr,
        )
        sys.exit(2)
    sized = {"bucket", "sustained", "lstmdtype"}
    if len(sys.argv) > 2:
        if which not in sized:
            print(
                f"sweep {which!r} takes no size argument "
                f"(sized sweeps: {sorted(sized)})",
                file=sys.stderr,
            )
            sys.exit(2)
        sweeps[which](int(sys.argv[2]))
    else:
        sweeps[which]()
