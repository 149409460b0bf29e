"""Fleet engine tests: stacked vmapped training must reproduce the
single-machine path bit-for-bit (same RNG derivation, same padding), and
shard cleanly over the 8-virtual-device CPU mesh.

Reference test-strategy parity (SURVEY.md §5): "distributed" behavior is
asserted via single-host multi-device simulation, mirroring how the
reference asserts on generated Argo documents rather than live clusters.
"""

import numpy as np
import pytest

import jax

from gordo_tpu.models.estimator import AutoEncoder
from gordo_tpu.ops.scalers import MinMaxScaler
from gordo_tpu.parallel import (
    FleetDiffBuilder,
    fleet_apply,
    fleet_fit,
    fleet_mesh,
    stack_rows,
)
from gordo_tpu.parallel.anomaly import analyze_definition
from gordo_tpu.parallel.fleet import fit_data_parallel
from gordo_tpu.pipeline import Pipeline
from gordo_tpu.registry import lookup_factory
from gordo_tpu.serializer import from_definition
from gordo_tpu.train.fit import TrainConfig, fit as single_fit

# heavy integration module: excluded from the fast CI lane
pytestmark = pytest.mark.slow


CFG = TrainConfig(epochs=3, batch_size=64, learning_rate=1e-3)


def _make_fleet_data(m=3, n=120, f=5, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((n, f)).astype(np.float32) for _ in range(m)]


def _hourglass(f):
    return lookup_factory("AutoEncoder", "feedforward_hourglass")(
        n_features=f, n_features_out=f
    )


class TestFleetFit:
    def test_matches_single_model_fits_exactly(self):
        Xs = _make_fleet_data()
        module = _hourglass(5)
        X, w, _ = stack_rows(Xs)
        res = fleet_fit(module, X, X, w, CFG, seeds=np.arange(3, dtype=np.uint32))

        per_model = res.unstack_params()
        for i, Xi in enumerate(Xs):
            params_i, hist_i = single_fit(
                module, Xi, Xi, CFG, rng=jax.random.PRNGKey(i)
            )
            for a, b in zip(
                jax.tree.leaves(per_model[i]), jax.tree.leaves(params_i)
            ):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(res.history[i], hist_i, rtol=1e-5)

    def test_sharded_over_mesh_matches_unsharded(self):
        Xs = _make_fleet_data(m=5)  # deliberately not divisible by 8
        module = _hourglass(5)
        X, w, _ = stack_rows(Xs)
        seeds = np.arange(5, dtype=np.uint32)
        plain = fleet_fit(module, X, X, w, CFG, seeds=seeds)
        mesh = fleet_mesh()
        sharded = fleet_fit(module, X, X, w, CFG, seeds=seeds, mesh=mesh)
        assert sharded.n_models == 5
        for a, b in zip(
            jax.tree.leaves(plain.params), jax.tree.leaves(sharded.params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b)[:5], rtol=1e-5, atol=1e-6)

    def test_ragged_lengths_are_masked(self):
        rng = np.random.default_rng(1)
        Xs = [
            rng.standard_normal((n, 4)).astype(np.float32) for n in (100, 80, 60)
        ]
        X, w, lengths = stack_rows(Xs)
        assert X.shape == (3, 100, 4)
        assert w.sum() == sum(lengths)
        module = _hourglass(4)
        res = fleet_fit(module, X, X, w, CFG)
        preds = fleet_apply(module, res.params, X)
        assert preds.shape == (3, 100, 4)
        assert np.isfinite(res.history).all()

    def test_data_parallel_single_model(self):
        rng = np.random.default_rng(2)
        X = rng.standard_normal((200, 6)).astype(np.float32)
        module = _hourglass(6)
        mesh = fleet_mesh(data_parallel=8)
        params, history = fit_data_parallel(module, X, X, CFG, mesh)
        assert np.isfinite(history).all()
        single_params, _ = single_fit(module, X, X, CFG)
        # same program, different sharding — results agree to float tolerance
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(single_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


DETECTOR_DEF = {
    "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "gordo_tpu.pipeline.Pipeline": {
                "steps": [
                    "gordo_tpu.ops.scalers.MinMaxScaler",
                    {
                        "gordo_tpu.models.estimator.AutoEncoder": {
                            "kind": "feedforward_hourglass",
                            "epochs": 3,
                            "batch_size": 64,
                        }
                    },
                ]
            }
        }
    }
}


class TestFleetDiffBuilder:
    def test_analyze_definition_accepts_canonical_config(self):
        model = from_definition(DETECTOR_DEF)
        spec = analyze_definition(model)
        assert spec is not None
        assert spec.train_cfg.epochs == 3
        assert isinstance(spec.signature, tuple)

    def test_analyze_definition_rejects_non_detector(self):
        assert analyze_definition(AutoEncoder()) is None
        assert analyze_definition(Pipeline([MinMaxScaler(), AutoEncoder()])) is None

    def test_fleet_build_matches_single_builds(self, sine_tags):
        m = 3
        rng = np.random.default_rng(7)
        Xs = [
            (sine_tags + 0.01 * rng.standard_normal(sine_tags.shape)).astype(
                np.float32
            )
            for _ in range(m)
        ]

        spec = analyze_definition(from_definition(DETECTOR_DEF))
        builder = FleetDiffBuilder(spec)
        detectors = builder.build(Xs)
        assert len(detectors) == m

        for i, Xi in enumerate(Xs):
            single = from_definition(DETECTOR_DEF)
            single.cross_validate(Xi)
            single.fit(Xi)

            fleet_det = detectors[i]
            # CV-fold statistics are EXACT: the fleet program materializes
            # each fold with the single path's own geometry and RNG (see
            # parallel/anomaly.py module docstring) — only float scheduling
            # noise remains.
            np.testing.assert_allclose(
                fleet_det.feature_thresholds_,
                single.feature_thresholds_,
                rtol=1e-4,
                atol=1e-6,
            )
            assert fleet_det.aggregate_threshold_ == pytest.approx(
                single.aggregate_threshold_, rel=1e-4
            )
            for name, stats in single.cv_metadata_["scores"].items():
                fleet_scores = fleet_det.cv_metadata_["scores"][name]
                np.testing.assert_allclose(
                    fleet_scores["folds"], stats["folds"], rtol=1e-3, atol=1e-5
                )
                assert fleet_scores["mean"] == pytest.approx(
                    stats["mean"], rel=1e-3, abs=1e-5
                )
            # The FINAL model is bit-identical: anomaly frames must agree.
            fa = fleet_det.anomaly(Xi)
            sa = single.anomaly(Xi)
            np.testing.assert_allclose(
                fa[("total-anomaly-score", "")].to_numpy(),
                sa[("total-anomaly-score", "")].to_numpy(),
                rtol=1e-4,
                atol=1e-5,
            )
            np.testing.assert_allclose(
                fa["model-output"].to_numpy(),
                sa["model-output"].to_numpy(),
                rtol=1e-4,
                atol=1e-5,
            )

    def test_fleet_build_on_mesh(self, sine_tags):
        spec = analyze_definition(from_definition(DETECTOR_DEF))
        mesh = fleet_mesh()
        detectors = FleetDiffBuilder(spec, mesh=mesh).build(
            [sine_tags, sine_tags * 1.1, sine_tags * 0.9]
        )
        assert len(detectors) == 3
        for det in detectors:
            assert np.isfinite(det.feature_thresholds_).all()
            assert det.aggregate_threshold_ > 0

    def test_fleet_build_lstm(self, sine_tags):
        definition = {
            "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "gordo_tpu.pipeline.Pipeline": {
                        "steps": [
                            "gordo_tpu.ops.scalers.MinMaxScaler",
                            {
                                "gordo_tpu.models.estimator.LSTMAutoEncoder": {
                                    "kind": "lstm_hourglass",
                                    "lookback_window": 6,
                                    "epochs": 2,
                                    "batch_size": 64,
                                }
                            },
                        ]
                    }
                }
            }
        }
        X = sine_tags[:200]
        spec = analyze_definition(from_definition(definition))
        assert spec is not None
        detectors = FleetDiffBuilder(spec).build([X, X * 1.05])

        single = from_definition(definition)
        single.cross_validate(X)
        single.fit(X)
        np.testing.assert_allclose(
            detectors[0].feature_thresholds_,
            single.feature_thresholds_,
            rtol=1e-3,
            atol=1e-5,
        )
        assert detectors[0].aggregate_threshold_ == pytest.approx(
            single.aggregate_threshold_, rel=1e-3
        )
        # final model bit-identical (windowed path included)
        fa = detectors[0].anomaly(X)
        sa = single.anomaly(X)
        np.testing.assert_allclose(
            fa[("total-anomaly-score", "")].to_numpy(),
            sa[("total-anomaly-score", "")].to_numpy(),
            rtol=1e-3,
            atol=1e-4,
        )


def test_model_axis_pad_targets():
    """Machine-axis padding collapses counts onto log-many compiled
    shapes (pow2, then the mesh 'models'-axis multiple)."""
    from gordo_tpu.parallel.anomaly import _model_axis_pad

    assert [_model_axis_pad(m, None) for m in (1, 2, 3, 5, 272, 512)] == [
        1, 2, 4, 8, 512, 512,
    ]
    mesh = fleet_mesh()  # 8 virtual devices
    assert _model_axis_pad(3, mesh) == 8   # pow2 4, then mesh multiple 8
    assert _model_axis_pad(12, mesh) == 16


def test_pad_lengths_parity_on_already_aligned_data(sine_tags):
    """pad-up mode with machines ALREADY at the aligned length runs with
    all-ones masks — results must match the exact per-length program
    (same folds, same geometry, same RNG)."""
    Xs = [sine_tags[:400], (sine_tags[:400] * 1.1).astype(np.float32)]
    spec = analyze_definition(from_definition(DETECTOR_DEF))
    exact = FleetDiffBuilder(spec).build(Xs)
    padded = FleetDiffBuilder(spec, pad_lengths=100).build(Xs)

    for Xi, de, dp in zip(Xs, exact, padded):
        np.testing.assert_allclose(
            dp.feature_thresholds_, de.feature_thresholds_,
            rtol=1e-4, atol=1e-6,
        )
        assert dp.aggregate_threshold_ == pytest.approx(
            de.aggregate_threshold_, rel=1e-4
        )
        for name, stats in de.cv_metadata_["scores"].items():
            np.testing.assert_allclose(
                dp.cv_metadata_["scores"][name]["folds"], stats["folds"],
                rtol=1e-3, atol=1e-5,
            )
        np.testing.assert_allclose(
            dp.anomaly(Xi)[("total-anomaly-score", "")].to_numpy(),
            de.anomaly(Xi)[("total-anomaly-score", "")].to_numpy(),
            rtol=1e-4, atol=1e-5,
        )


def test_pad_lengths_ragged_one_program_zero_rows_dropped(
    sine_tags, monkeypatch
):
    """16 distinct row counts inside one pad boundary -> ONE masked
    program (not 16 exact ones), with every real row trained and sane
    finite thresholds for every machine."""
    from gordo_tpu.parallel import anomaly as anomaly_mod

    lengths = [400 - 6 * i for i in range(16)]       # 400..310, all -> 400
    Xs = [sine_tags[:L] for L in lengths]
    spec = analyze_definition(from_definition(DETECTOR_DEF))

    calls = []
    orig = FleetDiffBuilder._dispatch_group

    def counting(self, stacked, warm=None):
        def seeing():
            X, y, lens = stacked()
            calls.append((X.shape, None if lens is None else tuple(lens)))
            return X, y, lens

        return orig(self, seeing, warm=warm)

    monkeypatch.setattr(
        anomaly_mod.FleetDiffBuilder, "_dispatch_group", counting
    )

    detectors = FleetDiffBuilder(spec, pad_lengths=100).build(Xs)
    assert len(calls) == 1                            # O(1) compiles
    shape, lens = calls[0]
    assert shape == (16, 400, sine_tags.shape[1])
    assert sorted(lens) == sorted(lengths)            # zero rows dropped

    for Xi, det in zip(Xs, detectors):
        assert np.all(np.isfinite(det.feature_thresholds_))
        assert det.feature_thresholds_.min() > 0
        assert np.isfinite(det.aggregate_threshold_)
        scores = det.anomaly(Xi)
        assert len(scores) == len(Xi)                 # all rows score


def test_pad_lengths_too_short_machine_demotes_to_exact(sine_tags, caplog):
    """A machine whose real rows would miss an entire CV test block at the
    padded length must NOT get silently-zero thresholds — it builds through
    the exact per-length path instead (with a warning)."""
    import logging

    # 600-row pad boundary: TimeSeriesSplit(3) test blocks start at 150/
    # 300/450 — an 80-row machine would contribute no real test rows
    Xs = [sine_tags[:600], sine_tags[:80]]
    spec = analyze_definition(from_definition(DETECTOR_DEF))
    with caplog.at_level(logging.WARNING, logger="gordo_tpu.parallel.anomaly"):
        detectors = FleetDiffBuilder(spec, pad_lengths=600).build(Xs)
    assert any("exact per-length path" in r.message for r in caplog.records)

    # the short machine matches its single-machine build exactly
    single = from_definition(DETECTOR_DEF)
    single.cross_validate(Xs[1])
    single.fit(Xs[1])
    np.testing.assert_allclose(
        detectors[1].feature_thresholds_, single.feature_thresholds_,
        rtol=1e-4, atol=1e-6,
    )
    assert detectors[1].feature_thresholds_.min() > 0
    assert detectors[0].feature_thresholds_.min() > 0


def test_pad_lengths_shuffled_splitter_demotes_to_exact(sine_tags, caplog):
    """Pad-up exactness requires contiguous fold blocks; a shuffled
    splitter must demote the group to the exact path, not silently train
    on windows interleaved with padding."""
    import logging

    from sklearn.model_selection import KFold

    Xs = [sine_tags[:350], sine_tags[:400]]
    spec = analyze_definition(from_definition(DETECTOR_DEF))
    builder = FleetDiffBuilder(
        spec, cv=KFold(n_splits=3, shuffle=True, random_state=0),
        pad_lengths=100,
    )
    with caplog.at_level(logging.WARNING, logger="gordo_tpu.parallel.anomaly"):
        detectors = builder.build(Xs)
    assert any("non-contiguous" in r.message for r in caplog.records)
    for det in detectors:
        assert np.all(np.isfinite(det.feature_thresholds_))
        assert not getattr(det, "pad_built_", False)  # exact-path builds


def test_fleet_build_ragged_lengths_exact(sine_tags):
    """Machines of DIFFERENT lengths in one bucket: each length-group runs
    its own exact program, so every machine (not just the longest) matches
    its single-machine build."""
    Xs = [sine_tags[:400], sine_tags[:280], sine_tags[:400] * 1.1]
    spec = analyze_definition(from_definition(DETECTOR_DEF))
    detectors = FleetDiffBuilder(spec).build(Xs)

    for Xi, fleet_det in zip(Xs, detectors):
        single = from_definition(DETECTOR_DEF)
        single.cross_validate(Xi)
        single.fit(Xi)
        np.testing.assert_allclose(
            fleet_det.feature_thresholds_,
            single.feature_thresholds_,
            rtol=1e-4,
            atol=1e-6,
        )
        assert fleet_det.aggregate_threshold_ == pytest.approx(
            single.aggregate_threshold_, rel=1e-4
        )
        np.testing.assert_allclose(
            fleet_det.anomaly(Xi)[("total-anomaly-score", "")].to_numpy(),
            single.anomaly(Xi)[("total-anomaly-score", "")].to_numpy(),
            rtol=1e-4,
            atol=1e-5,
        )
