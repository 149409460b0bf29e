"""r23 dispatch/collect split: the async build plane must be
byte-equivalent to a serial one.

Pins, in order of blast radius:

- end-to-end: pipelined (dispatch k+1 before collect k) vs serial (one
  ``build_project`` call per chunk) drives of the same project produce
  byte-identical artifacts and registry entries, across BOTH artifact layouts (v1 dirs, v2 packs), exact and
  pad-up grouping, cold and warm-start builds;
- builder-level: the collect side's LAZY/partial D2H fetch (device-side
  fold slicing, zero-copy view handout) returns exactly the values an
  eager ``to_host`` of the full result tree yields — ``cv_metadata_``,
  ``history_``, thresholds;
- the drive loop's dispatch window and the builder's dispatch family are
  lint-enforced D2H-free (scripts/lint.py gate, tested on synthesized
  sources).

Slow lane (CI test-full job), alongside tests/test_build_pipeline.py.
"""

import pickle

import jax
import numpy as np
import pytest

from gordo_tpu import artifacts
from gordo_tpu.builder import build_project
from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition
from gordo_tpu.serializer import from_definition
from gordo_tpu.utils import disk_registry
from gordo_tpu.utils.trees import to_host
from gordo_tpu.workflow.config import Machine

from tests.test_build_pipeline import (
    _build_chunk_by_chunk,
    _machines,
    _scrub_timings,
    _strip_meta,
)

pytestmark = pytest.mark.slow

DETECTOR_DEF = {
    "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "gordo_tpu.pipeline.Pipeline": {
                "steps": [
                    "gordo_tpu.ops.scalers.MinMaxScaler",
                    {
                        "gordo_tpu.models.estimator.AutoEncoder": {
                            "kind": "feedforward_hourglass",
                            "epochs": 2,
                            "batch_size": 64,
                        }
                    },
                ]
            }
        }
    }
}


def _ragged_machines(n, prefix):
    """n machines whose train windows differ by an hour each — distinct
    row counts, so pad-up mode actually pads."""
    out = []
    for i in range(n):
        hours = 20 + i
        day = 25 + (6 + hours) // 24
        hh = (6 + hours) % 24
        out.append(Machine.from_config({
            "name": f"{prefix}-{i}",
            "dataset": {
                "type": "RandomDataset",
                "tag_list": ["a", "b", "c"],
                "train_start_date": "2017-12-25T06:00:00Z",
                "train_end_date": f"2017-12-{day}T{hh:02d}:10:00Z",
            },
        }))
    return out


def _assert_v1_parity(machines, a_out, b_out):
    for m in machines:
        a, b = a_out / m.name, b_out / m.name
        assert (a / "definition.yaml").read_bytes() == (
            b / "definition.yaml"
        ).read_bytes()
        with open(a / "model.pkl", "rb") as f:
            ma = pickle.load(f)
        with open(b / "model.pkl", "rb") as f:
            mb = pickle.load(f)
        _scrub_timings(ma)
        _scrub_timings(mb)
        assert pickle.dumps(ma) == pickle.dumps(mb), m.name
        import json

        meta_a = json.loads((a / "metadata.json").read_text())
        meta_b = json.loads((b / "metadata.json").read_text())
        assert _strip_meta(meta_a) == _strip_meta(meta_b), m.name


def _assert_v2_parity(machines, a_out, b_out):
    sa = artifacts.open_store(str(a_out))
    sb = artifacts.open_store(str(b_out))
    assert sorted(sa.names()) == sorted(sb.names())
    for m in machines:
        ma, mb = sa.load_model(m.name), sb.load_model(m.name)
        _scrub_timings(ma)
        _scrub_timings(mb)
        assert pickle.dumps(ma) == pickle.dumps(mb), m.name
        assert _strip_meta(sa.load_metadata(m.name)) == _strip_meta(
            sb.load_metadata(m.name)
        ), m.name


class TestAsyncSerialParity:
    """The acceptance contract: for every layout and grouping mode, the
    overlapped drive (dispatch chunk k+1 before collecting chunk k) and
    a serial drive (``_build_chunk_by_chunk``: nothing is dispatched
    before the previous chunk is collected) produce the same bytes."""

    @pytest.mark.parametrize(
        "fmt,ragged",
        [("v1", False), ("v2", False), ("v1", True), ("v2", True)],
        ids=["v1-exact", "v2-exact", "v1-padded", "v2-padded"],
    )
    def test_cold_build_parity(self, tmp_path, fmt, ragged):
        if ragged:
            machines = _ragged_machines(4, prefix=f"dcp-{fmt}")
            kwargs = {"pad_lengths": 72}
        else:
            machines = _machines(4, prefix=f"dc-{fmt}")
            kwargs = {}
        a_out, a_reg = tmp_path / "out-serial", tmp_path / "reg-serial"
        b_out, b_reg = tmp_path / "out-async", tmp_path / "reg-async"
        serial = _build_chunk_by_chunk(
            machines, a_out, 2, model_register_dir=str(a_reg),
            artifact_format=fmt, **kwargs,
        )
        overlapped = build_project(
            machines, str(b_out), model_register_dir=str(b_reg),
            max_bucket_size=2, artifact_format=fmt, **kwargs,
        )
        for result in serial + [overlapped]:
            assert not result.failed
        assert sorted(overlapped.fleet_built) == sorted(
            name for result in serial for name in result.fleet_built
        ) == sorted(m.name for m in machines)
        if fmt == "v1":
            _assert_v1_parity(machines, a_out, b_out)
        else:
            _assert_v2_parity(machines, a_out, b_out)
        assert sorted(disk_registry.list_keys(str(a_reg))) == sorted(
            disk_registry.list_keys(str(b_reg))
        )

    def test_warm_start_build_parity(self, tmp_path):
        """Warm-start rebuilds (v2 in-place delta writes) land the same
        bytes whether the drive loop overlaps or not — the warm path runs
        synchronously inside the dispatch window, and its ordering
        relative to cold chunks must not matter."""
        machines = _machines(4, prefix="dcw")
        stores = {}
        for label in ("serial", "async"):
            out = tmp_path / f"out-{label}"
            cold = build_project(
                machines, str(out), max_bucket_size=2, artifact_format="v2",
            )
            assert not cold.failed
            if label == "serial":
                warms = _build_chunk_by_chunk(
                    machines, out, 2, artifact_format="v2", warm_start=True,
                )
            else:
                warms = [build_project(
                    machines, str(out), max_bucket_size=2,
                    artifact_format="v2", warm_start=True,
                )]
            assert not any(warm.failed for warm in warms)
            assert sorted(
                name for warm in warms
                for name in warm.warm_started + list(warm.warm_fallbacks)
            ) == sorted(m.name for m in machines)
            stores[label] = out
        _assert_v2_parity(machines, stores["serial"], stores["async"])

    def test_device_idle_seconds_reported(self, tmp_path):
        """The new occupancy instrument rides the build summary (and is
        sane: bounded by wall clock, non-negative)."""
        result = build_project(
            _machines(4, prefix="idle"), str(tmp_path / "m"),
            max_bucket_size=2,
        )
        assert not result.failed
        idle = result.summary()["device_idle_seconds"]
        assert 0.0 <= idle <= result.seconds


class TestLazyFetchParity:
    """Regression pin for the collect side's partial fetch: slicing the
    scaler-stat fold axis on device and handing out zero-copy views must
    yield exactly what an eager full-tree ``to_host`` yields."""

    def test_collect_matches_eager_to_host(self):
        rng = np.random.default_rng(11)
        t = np.linspace(0, 20, 300, dtype=np.float32)
        base = np.stack([np.sin(t), np.cos(t), np.sin(2 * t)], axis=1)
        Xs = [
            (base + 0.01 * rng.standard_normal(base.shape)).astype(
                np.float32
            )
            for _ in range(3)
        ]
        spec = analyze_definition(from_definition(DETECTOR_DEF))
        builder = FleetDiffBuilder(spec)
        X = np.stack(Xs)
        g = builder._dispatch_group(lambda: (X, X, None))

        # eager reference: the FULL device tree, fetched before collect
        # runs its partial reads (fetch is idempotent — same buffers)
        eager = to_host(g.out)
        dets = builder._collect_group(g)

        for i, det in enumerate(dets):
            np.testing.assert_array_equal(
                det.feature_thresholds_,
                eager["feature_thresholds"][i],
            )
            assert det.aggregate_threshold_ == float(
                eager["aggregate_threshold"][i]
            )
            est = det.base_estimator
            if hasattr(est, "steps"):
                est = est.steps[-1]
                if isinstance(est, tuple):
                    est = est[-1]
            np.testing.assert_array_equal(
                np.asarray(est.history_), eager["final_history"][i]
            )
            for name, stats in det.cv_metadata_["scores"].items():
                folds = eager["metrics"][name][i]
                assert stats["folds"] == [float(v) for v in folds]
                assert stats["mean"] == float(folds.mean())
                assert stats["std"] == float(folds.std())

    def test_whole_leaves_start_for_the_host_before_the_sliced_reads(
        self, monkeypatch
    ):
        """The sliced reads are device operations, which on the chip queue
        behind the next chunk's program: what is fetched whole has to be on
        its way before the first of them blocks."""
        from gordo_tpu.parallel import anomaly as anomaly_mod
        from gordo_tpu.utils import trees

        rng = np.random.default_rng(13)
        X = rng.standard_normal((2, 250, 3)).astype(np.float32)
        spec = analyze_definition(from_definition(DETECTOR_DEF))
        builder = FleetDiffBuilder(spec)
        g = builder._dispatch_group(lambda: (X, X, None))
        whole = {k: v for k, v in g.out.items() if k != "scaler_stats"}
        order = []

        def started(tree):
            order.append(("start", len(jax.tree.leaves(tree))))
            trees.start_fetch(tree)

        real_asarray = np.asarray

        def read(x, *args, **kwargs):
            if isinstance(x, jax.Array):
                order.append(("read",))
            return real_asarray(x, *args, **kwargs)

        monkeypatch.setattr(anomaly_mod, "start_fetch", started)
        monkeypatch.setattr(anomaly_mod.np, "asarray", read)
        builder._collect_group(g)
        assert order[0] == ("start", len(jax.tree.leaves(whole)))
        assert ("read",) in order[1:]

    def test_start_fetch_takes_any_tree_and_changes_no_value(self):
        from gordo_tpu.utils.trees import start_fetch

        tree = {"a": jax.numpy.arange(6.0).reshape(2, 3), "b": [np.ones(2), 3, None]}
        assert start_fetch(tree) is None
        np.testing.assert_array_equal(
            to_host(tree)["a"], np.arange(6.0).reshape(2, 3))

    def test_collect_frees_device_tree_and_is_idempotent(self):
        rng = np.random.default_rng(12)
        Xs = [
            rng.standard_normal((250, 3)).astype(np.float32)
            for _ in range(2)
        ]
        spec = analyze_definition(from_definition(DETECTOR_DEF))
        pending = FleetDiffBuilder(spec).dispatch(Xs)
        dets = pending.collect()
        assert all(g.out is None for g in pending._groups)  # buffers freed
        assert pending.collect() is dets  # cached, no second fetch


class TestPrestackedBaselines:
    """The collect side's stacked host arrays double as the fleet-health
    baseline scorer's prestack (``PendingFleetBuild.prestacked`` →
    ``FleetScorer.from_models(prestacked_hint=...)``): the scorer adopts
    them whole instead of re-stacking per-machine views leaf by leaf.
    Sketch docs must be identical either way, and any fleet/hint mismatch
    must fall back to the generic stacking path, not mis-stack."""

    def _built(self, n=3, rows=240):
        rng = np.random.default_rng(21)
        names = [f"pre-{i}" for i in range(n)]
        Xs = [
            rng.standard_normal((rows, 3)).astype(np.float32)
            for _ in names
        ]
        spec = analyze_definition(from_definition(DETECTOR_DEF))
        pending = FleetDiffBuilder(spec).dispatch(Xs)
        dets = pending.collect()
        return names, Xs, dets, pending

    def test_hint_docs_match_stacking_path(self):
        from gordo_tpu.serve.fleet_scorer import FleetScorer
        from gordo_tpu.telemetry import fleet_health

        names, Xs, dets, pending = self._built()
        hint = pending.prestacked(names)
        assert hint is not None
        assert hint["names"] == names
        models = dict(zip(names, dets))
        X_by = dict(zip(names, Xs))
        with_hint = fleet_health.training_baselines(
            models, X_by, prestacked_hint=hint
        )
        plain = fleet_health.training_baselines(models, X_by)
        assert set(with_hint) == set(names)
        assert with_hint == plain

        # the hint must actually engage: the bucket's threshold rows are
        # the hint's own array, not a restacked copy
        scorer = FleetScorer.from_models(models, prestacked_hint=hint)
        assert (
            scorer.buckets[0].thresholds_np is hint["feature_thresholds"]
        )

    def test_hint_mismatch_falls_back(self):
        from gordo_tpu.telemetry import fleet_health

        names, Xs, dets, pending = self._built()
        hint = pending.prestacked(names)
        # a subset fleet (one machine's load failed upstream) no longer
        # matches the hinted names — stacking path, same docs, no error
        sub = dict(list(zip(names, dets))[:-1])
        X_by = dict(zip(names, Xs))
        docs = fleet_health.training_baselines(
            sub, X_by, prestacked_hint=hint
        )
        assert set(docs) == set(names[:-1])

    def test_prestacked_requires_collect(self):
        rng = np.random.default_rng(22)
        Xs = [
            rng.standard_normal((240, 3)).astype(np.float32)
            for _ in range(2)
        ]
        spec = analyze_definition(from_definition(DETECTOR_DEF))
        pending = FleetDiffBuilder(spec).dispatch(Xs)
        assert pending.prestacked(["a", "b"]) is None  # not collected yet
        pending.collect()  # leave no dangling device futures


class TestDispatchWindowLint:
    """The scripts/lint.py D2H gate covers the r23 dispatch window: a
    blocking fetch sneaking into the dispatch family is a lint error, on
    real sources and on synthesized regressions."""

    def _findings(self, basename, source, tmp_path):
        import ast
        import importlib.util
        import pathlib

        lint_path = (
            pathlib.Path(__file__).resolve().parents[1]
            / "scripts" / "lint.py"
        )
        lint_spec = importlib.util.spec_from_file_location("_lint", lint_path)
        lint = importlib.util.module_from_spec(lint_spec)
        lint_spec.loader.exec_module(lint)
        path = tmp_path / basename
        path.write_text(source)
        return lint._d2h_findings(str(path), ast.parse(source), set())

    def test_blocking_fetch_in_dispatch_scope_flagged(self, tmp_path):
        source = (
            "import numpy as np\n"
            "def dispatch(self, Xs):\n"
            "    return np.asarray(Xs[0])\n"
            "def _dispatch_group(self, X, y):\n"
            "    out = self._program(X, y)\n"
            "    return to_host(out)\n"
        )
        findings = self._findings("anomaly.py", source, tmp_path)
        assert len(findings) == 2
        assert "np.asarray" in findings[0][2]
        assert "to_host" in findings[1][2]

    def test_drive_loop_dispatch_scopes_flagged(self, tmp_path):
        source = (
            "def _dispatch_bucket(key, chunk, loaded):\n"
            "    loaded[0].block_until_ready()\n"
            "def _dispatch_chunk(spec, cv, ok, loaded):\n"
            "    import jax\n"
            "    jax.device_get(loaded)\n"
        )
        findings = self._findings("fleet_build.py", source, tmp_path)
        assert len(findings) == 2

    def test_collect_scopes_stay_unflagged(self, tmp_path):
        source = (
            "import numpy as np\n"
            "def _collect_group(self, g):\n"
            "    return to_host(g.out)\n"
            "def _finish_bucket(rec):\n"
            "    return np.asarray(rec.out)\n"
        )
        assert self._findings("anomaly.py", source, tmp_path) == []
        assert self._findings("fleet_build.py", source, tmp_path) == []

    def test_shipped_sources_pass_the_gate(self):
        import ast
        import importlib.util
        import pathlib

        root = pathlib.Path(__file__).resolve().parents[1]
        lint_path = root / "scripts" / "lint.py"
        lint_spec = importlib.util.spec_from_file_location("_lint", lint_path)
        lint = importlib.util.module_from_spec(lint_spec)
        lint_spec.loader.exec_module(lint)
        for rel in (
            "gordo_tpu/parallel/anomaly.py",
            "gordo_tpu/builder/fleet_build.py",
        ):
            src = (root / rel).read_text()
            noqa = {
                i + 1
                for i, line in enumerate(src.splitlines())
                if "# noqa" in line
            }
            assert lint._d2h_findings(
                str(root / rel), ast.parse(src), noqa
            ) == [], rel
