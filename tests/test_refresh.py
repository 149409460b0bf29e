"""Drift-driven incremental refresh (ISSUE 13).

Fast lane: the pure pieces — hysteresis/cooldown selection, warm-param
resolution off a written pack, the shared rollup reader, the workflow
CronJob emission + refusals, the refresh-plane lint gate, and a
refresh_once cycle against stubbed health/build seams.

Slow lane (``TestRefreshAcceptance``): the end-to-end pin — build a
fleet, shift live inputs to a subset, let the refresh loop rebuild
exactly those machines warm, assert the generation flips, a live
serving collection delta-reloads only the touched pack, and the drift
signal returns to ok without any restart.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from gordo_tpu import artifacts, telemetry
from gordo_tpu.refresh import DriftSelector, RefreshConfig, refresh_once
from gordo_tpu.refresh import loop as refresh_loop
from gordo_tpu.telemetry import fleet_health as fh


def _doc(statuses):
    """A status-only health doc — enough for DriftSelector.observe."""
    return {
        "gordo-fleet-health": 1,
        "machines": {n: {"status": s} for n, s in statuses.items()},
    }


def _sketch_doc(shift, n=2000):
    rng = np.random.default_rng(42)
    return fh.sketch_from_scores(rng.lognormal(shift, 1, n), ts=0.0).to_doc()


def _health_doc(statuses):
    """A health doc with REAL score sketches behind each status —
    ``merge_health_docs`` (what ``read_rollups`` applies) recomputes
    drift/status from the sketches, so rollup-file tests need the
    distributions, not just labels."""
    baseline = _sketch_doc(0.0, n=4000)
    machines = {}
    for name, status in statuses.items():
        live = _sketch_doc(3.0 if status == "drifting" else 0.0)
        machines[name] = {"baseline": baseline, "live": live}
    return {"gordo-fleet-health": 1, "machines": machines}


# ---------------------------------------------------------------------------
# selection: hysteresis + cooldown
# ---------------------------------------------------------------------------

class TestDriftSelector:
    def test_hysteresis_requires_consecutive_observations(self):
        sel = DriftSelector(hysteresis=2, cooldown_seconds=0)
        assert sel.observe(_doc({"m-a": "drifting", "m-b": "ok"}), 0.0) == []
        assert sel.observe(_doc({"m-a": "drifting", "m-b": "ok"}), 1.0) == [
            "m-a"
        ]

    def test_non_drifting_observation_resets_the_streak(self):
        sel = DriftSelector(hysteresis=2, cooldown_seconds=0)
        sel.observe(_doc({"m-a": "drifting"}), 0.0)
        sel.observe(_doc({"m-a": "ok"}), 1.0)  # one quiet window resets
        assert sel.observe(_doc({"m-a": "drifting"}), 2.0) == []
        assert sel.observe(_doc({"m-a": "drifting"}), 3.0) == ["m-a"]

    def test_absent_machine_keeps_its_streak(self):
        """A silent shard is not evidence the drift cleared."""
        sel = DriftSelector(hysteresis=2, cooldown_seconds=0)
        sel.observe(_doc({"m-a": "drifting"}), 0.0)
        assert sel.observe(_doc({"m-b": "ok"}), 1.0) == []
        assert sel.observe(_doc({"m-a": "drifting"}), 2.0) == ["m-a"]

    def test_cooldown_suppresses_rebuilds_until_it_expires(self):
        sel = DriftSelector(hysteresis=1, cooldown_seconds=100)
        assert sel.observe(_doc({"m-a": "drifting"}), 0.0) == ["m-a"]
        sel.mark_rebuilt(["m-a"], 0.0)
        assert sel.observe(_doc({"m-a": "drifting"}), 50.0) == []
        assert sel.observe(_doc({"m-a": "drifting"}), 150.0) == ["m-a"]

    def test_state_round_trips_through_the_state_file(self, tmp_path):
        path = str(tmp_path / "state.json")
        sel = DriftSelector(hysteresis=3, cooldown_seconds=0)
        sel.observe(_doc({"m-a": "drifting"}), 0.0)
        sel.observe(_doc({"m-a": "drifting"}), 1.0)
        sel.save(path)
        # the next --once invocation resumes the streak at 2/3
        again = DriftSelector.load(path, hysteresis=3, cooldown_seconds=0)
        assert again.observe(_doc({"m-a": "drifting"}), 2.0) == ["m-a"]

    def test_corrupt_state_file_starts_fresh(self, tmp_path):
        path = tmp_path / "state.json"
        path.write_text("{torn")
        sel = DriftSelector.load(str(path), hysteresis=1, cooldown_seconds=0)
        assert sel.observe(_doc({"m-a": "drifting"}), 0.0) == ["m-a"]

    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv(refresh_loop.ENV_HYSTERESIS, "5")
        monkeypatch.setenv(refresh_loop.ENV_COOLDOWN_SECONDS, "12.5")
        sel = DriftSelector()
        assert sel.hysteresis == 5
        assert sel.cooldown_seconds == 12.5


# ---------------------------------------------------------------------------
# warm-start material: previous-generation params off the pack store
# ---------------------------------------------------------------------------

class _FakeEstimator:
    def __init__(self, seed, with_history=True):
        rng = np.random.default_rng(seed)
        self.params_ = {
            "dense": {
                "w": rng.standard_normal((4, 3)).astype(np.float32),
                "b": rng.standard_normal(3).astype(np.float32),
            }
        }
        if with_history:
            self.history_ = np.asarray(
                [0.9, 0.5, 0.25 + seed], np.float32
            )


class _FakeDetector:
    def __init__(self, seed, with_history=True):
        self.base_estimator = _FakeEstimator(seed, with_history)


class TestWarmParamResolution:
    def test_resolves_params_and_previous_loss_from_the_pack(self, tmp_path):
        from gordo_tpu.builder.fleet_build import _resolve_warm_params

        names = ["wm-0", "wm-1"]
        artifacts.write_pack(
            str(tmp_path), names, [_FakeDetector(0), _FakeDetector(1)],
        )
        resolved = _resolve_warm_params(str(tmp_path), names + ["wm-miss"])
        assert sorted(resolved) == names  # unknown machine simply absent
        params, prev_loss = resolved["wm-1"]
        assert prev_loss == pytest.approx(1.25)
        np.testing.assert_array_equal(
            params["dense"]["w"],
            _FakeDetector(1).base_estimator.params_["dense"]["w"],
        )

    def test_no_store_resolves_empty(self, tmp_path):
        from gordo_tpu.builder.fleet_build import _resolve_warm_params

        assert _resolve_warm_params(str(tmp_path), ["wm-0"]) == {}

    def test_missing_history_resolves_none_loss(self, tmp_path):
        from gordo_tpu.builder.fleet_build import _resolve_warm_params

        artifacts.write_pack(
            str(tmp_path), ["wm-0"], [_FakeDetector(0, with_history=False)],
        )
        _, prev_loss = _resolve_warm_params(str(tmp_path), ["wm-0"])["wm-0"]
        assert prev_loss is None

    def test_warm_epoch_budget_and_env_override(self, monkeypatch):
        from gordo_tpu.builder.fleet_build import _warm_epochs
        from gordo_tpu.parallel.fleet import TrainConfig

        assert _warm_epochs(TrainConfig(epochs=8)) == 2  # 0.25 default
        monkeypatch.setenv("GORDO_REFRESH_EPOCH_FRACTION", "0.5")
        assert _warm_epochs(TrainConfig(epochs=8)) == 4
        monkeypatch.setenv("GORDO_REFRESH_EPOCH_FRACTION", "0.0")
        assert _warm_epochs(TrainConfig(epochs=8)) == 1  # never below 1

    def test_mismatched_leaf_signature_is_a_loud_error(self):
        from gordo_tpu.parallel.anomaly import _stack_warm_params

        good = {"w": np.zeros((4, 3), np.float32)}
        bad = {"w": np.zeros((4, 2), np.float32)}  # config changed
        with pytest.raises(ValueError, match="leaf signature"):
            _stack_warm_params([good, bad], 2)


# ---------------------------------------------------------------------------
# the shared rollup reader
# ---------------------------------------------------------------------------

class TestReadRollups:
    def test_empty_dir_reads_none(self, tmp_path):
        assert telemetry.read_rollups(str(tmp_path)) is None

    def test_reads_and_merges_rollups(self, tmp_path):
        d = str(tmp_path)
        fh.write_rollup(d, _health_doc({"rr-a": "drifting"}))
        doc = telemetry.read_rollups(d)
        assert doc["machines"]["rr-a"]["status"] == "drifting"


# ---------------------------------------------------------------------------
# one refresh cycle against stubbed seams
# ---------------------------------------------------------------------------

class _FakeBuildResult:
    def __init__(self, built, failed=None):
        self.fleet_built = list(built)
        self.single_built = []
        self.warm_started = list(built)
        self.warm_fallbacks = {}
        self.failed = dict(failed or {})
        self.generation = 7
        self.ingest = {}


class _Machine:
    def __init__(self, name):
        self.name = name


class TestRefreshOnce:
    @pytest.fixture
    def cfg(self, tmp_path):
        return RefreshConfig(
            machines=[_Machine("m-a"), _Machine("m-b")],
            output_dir=str(tmp_path),
            hysteresis=2,
            cooldown_seconds=0,
        )

    def test_no_health_is_a_noop_cycle(self, cfg):
        assert refresh_once(cfg)["outcome"] == "no-health"

    def test_streaks_accumulate_across_once_invocations(
        self, cfg, monkeypatch
    ):
        """The CronJob face: two separate ``--once`` processes — the
        state file carries the streak, the second cycle rebuilds, and
        only the drifted machine is handed to the builder."""
        import gordo_tpu.builder as builder_mod

        fh.write_rollup(
            cfg.output_dir, _health_doc({"m-a": "drifting", "m-b": "ok"})
        )
        calls = []

        def fake_build(machines, output_dir, **kwargs):
            calls.append(([m.name for m in machines], kwargs))
            return _FakeBuildResult([m.name for m in machines])

        monkeypatch.setattr(builder_mod, "build_project", fake_build)

        first = refresh_once(cfg)
        assert first["outcome"] == "idle"
        assert first["drifting"] == ["m-a"]
        assert not calls

        second = refresh_once(cfg)  # fresh selector — loads the state file
        assert second["outcome"] == "rebuilt"
        assert second["rebuilt"] == ["m-a"]
        assert second["generation"] == 7
        assert calls == [(["m-a"], {
            "model_register_dir": None, "warm_start": True,
        })]
        # cooldown: an immediately-following cycle stays idle
        cfg2 = RefreshConfig(
            machines=cfg.machines, output_dir=cfg.output_dir,
            hysteresis=2, cooldown_seconds=3600,
        )
        refresh_once(cfg2)
        third = refresh_once(cfg2)
        assert third["outcome"] == "idle" and len(calls) == 1

    def test_build_failure_reports_failed_outcome(self, cfg, monkeypatch):
        import gordo_tpu.builder as builder_mod

        cfg = RefreshConfig(
            machines=cfg.machines, output_dir=cfg.output_dir,
            hysteresis=1, cooldown_seconds=0,
        )
        fh.write_rollup(cfg.output_dir, _health_doc({"m-a": "drifting"}))
        monkeypatch.setattr(
            builder_mod, "build_project",
            lambda machines, output_dir, **kw: _FakeBuildResult(
                [], failed={"m-a": "boom"}
            ),
        )
        summary = refresh_once(cfg)
        assert summary["outcome"] == "failed"
        assert summary["failed"] == {"m-a": "boom"}

    def test_unknown_drifting_machine_is_reported_not_built(
        self, cfg, monkeypatch
    ):
        cfg = RefreshConfig(
            machines=[_Machine("m-a")], output_dir=cfg.output_dir,
            hysteresis=1, cooldown_seconds=0,
        )
        fh.write_rollup(cfg.output_dir,
                        _health_doc({"m-elsewhere": "drifting"}))
        summary = refresh_once(cfg)
        assert summary["outcome"] == "idle"
        assert summary["unknown"] == ["m-elsewhere"]


# ---------------------------------------------------------------------------
# CLI face
# ---------------------------------------------------------------------------

_PROJECT_YAML = """
machines:
  - name: cli-m-a
    dataset:
      type: RandomDataset
      tags: [t1, t2, t3]
      train_start_date: "2017-12-25T06:00:00Z"
      train_end_date: "2017-12-26T06:00:00Z"
"""


class TestRefreshCli:
    def test_once_with_no_health_exits_clean(self, tmp_path):
        from click.testing import CliRunner

        from gordo_tpu.cli.cli import gordo

        result = CliRunner().invoke(gordo, [
            "refresh", "--machine-config", _PROJECT_YAML,
            "--output-dir", str(tmp_path), "--once",
        ])
        assert result.exit_code == 0, result.output
        summary = json.loads(result.output.strip().splitlines()[-1])
        assert summary["outcome"] == "no-health"


# ---------------------------------------------------------------------------
# workflow CronJob emission
# ---------------------------------------------------------------------------

class TestRefreshCron:
    def _generate(self, schedule):
        from gordo_tpu.workflow import (
            NormalizedConfig,
            generate_workflow,
            load_machine_config,
        )

        config = NormalizedConfig(
            load_machine_config(_PROJECT_YAML), "cronproj"
        )
        return generate_workflow(config, refresh_cron=schedule)

    def test_cronjob_mirrors_the_builder_wiring(self):
        docs = self._generate("*/30 * * * *")
        jobs = [d for d in docs if d["kind"] == "CronJob"]
        assert len(jobs) == 1
        cj = jobs[0]
        assert cj["spec"]["schedule"] == "*/30 * * * *"
        assert cj["spec"]["concurrencyPolicy"] == "Forbid"
        pod = cj["spec"]["jobTemplate"]["spec"]["template"]["spec"]
        container = pod["containers"][0]
        assert container["command"] == ["gordo", "refresh"]
        assert "--once" in container["args"]
        volumes = {v["name"] for v in pod["volumes"]}
        assert {"models", "project-config", "compile-cache"} <= volumes
        env = {e["name"] for e in container["env"]}
        assert {"PROJECT_NAME", "JAX_COMPILATION_CACHE_DIR",
                "GORDO_REFRESH_HYSTERESIS"} <= env

    def test_malformed_schedule_is_refused(self):
        with pytest.raises(ValueError, match="5-field cron"):
            self._generate("hourly")
        with pytest.raises(ValueError, match=r"\[0-9\*/,-\]"):
            self._generate("* * * * mon")

    def test_builder_without_models_volume_is_refused(self):
        from gordo_tpu.workflow.generator import _refresh_cronjob

        stripped = {
            "spec": {"template": {"spec": {
                "containers": [{"name": "b", "env": []}],
                "volumes": [{"name": "project-config"}],
            }}}
        }
        with pytest.raises(ValueError, match="models"):
            _refresh_cronjob("p", "img", "0 * * * *", stripped)


# ---------------------------------------------------------------------------
# the plane-boundary lint gate
# ---------------------------------------------------------------------------

class TestRefreshLintGate:
    @staticmethod
    def _lint(path):
        spec = importlib.util.spec_from_file_location(
            "gordo_lint", os.path.join(
                os.path.dirname(os.path.dirname(__file__)),
                "scripts", "lint.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.lint_file(path)

    def test_server_internal_imports_rejected_in_refresh_plane(
        self, tmp_path
    ):
        bad = tmp_path / "gordo_tpu" / "refresh" / "thing.py"
        bad.parent.mkdir(parents=True)
        bad.write_text(
            "from gordo_tpu.serve.scorer import Scorer\n"
            "from gordo_tpu import watchman\n"
            "Scorer, watchman\n"
        )
        msgs = [f[2] for f in self._lint(str(bad))]
        assert sum("refresh plane" in m for m in msgs) == 2

    def test_refresh_plane_is_clean_under_the_gate(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for rel in (
            os.path.join("gordo_tpu", "refresh", "loop.py"),
            os.path.join("gordo_tpu", "refresh", "__init__.py"),
        ):
            assert self._lint(os.path.join(repo, rel)) == [], rel


# ---------------------------------------------------------------------------
# end-to-end acceptance (slow lane — CI test-full job)
# ---------------------------------------------------------------------------

def _acceptance_yaml():
    machines = "\n".join(
        f"""
  - name: rf-{i}
    dataset:
      type: RandomDataset
      tags: [rf{i}-a, rf{i}-b, rf{i}-c]
      train_start_date: "2017-12-25T06:00:00Z"
      train_end_date: "2017-12-27T06:00:00Z"
"""
        for i in range(4)
    )
    return f"""
machines:{machines}
globals:
  model:
    gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector:
      base_estimator:
        gordo_tpu.pipeline.Pipeline:
          steps:
            - gordo_tpu.ops.scalers.MinMaxScaler
            - gordo_tpu.models.estimator.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 4
                batch_size: 64
"""


@pytest.mark.slow
class TestRefreshAcceptance:
    """Build fleet → shift a subset's inputs → refresh rebuilds exactly
    those machines → generation flips → a live collection delta-reloads
    only the touched pack → drift returns to ok.  No restarts."""

    SHIFTED = "rf-1"

    def _machine_matrix(self, name):
        from gordo_tpu.dataset.base import GordoBaseDataset

        i = int(name.split("-")[1])
        ds = GordoBaseDataset.from_dict({
            "type": "RandomDataset",
            "tags": [f"rf{i}-a", f"rf{i}-b", f"rf{i}-c"],
            "train_start_date": "2017-12-25T06:00:00Z",
            "train_end_date": "2017-12-27T06:00:00Z",
        })
        X, _ = ds.get_data()
        return np.asarray(X, np.float32)

    def test_drift_to_live_cycle(self, tmp_path, monkeypatch):
        from gordo_tpu.builder import build_project
        from gordo_tpu.dataset import datasets as ds_mod
        from gordo_tpu.serve.server import ModelCollection
        from gordo_tpu.workflow import NormalizedConfig, load_machine_config

        monkeypatch.setenv("GORDO_REFRESH_PARITY_FACTOR", "1e6")
        out = str(tmp_path / "models")
        cfg = NormalizedConfig(
            load_machine_config(_acceptance_yaml()), "refreshproj"
        )
        names = [m.name for m in cfg.machines]
        result = build_project(
            cfg.machines, out, max_bucket_size=2, artifact_format="v2",
        )
        assert not result.failed
        gen0 = artifacts.read_generation(out)
        assert gen0 >= 1

        # a live serving collection (adopts training baselines) sees
        # shifted traffic on ONE machine, in-range traffic on the rest
        reg = telemetry.FLEET_HEALTH
        reg.clear(names)
        coll = ModelCollection.from_directory(out, project="refreshproj")
        for name in names:
            X = self._machine_matrix(name)
            scale = 8.0 if name == self.SHIFTED else 1.0
            coll.get(name).scorer.anomaly_arrays(X * scale)
        doc = reg.doc(machines=names)
        statuses = {n: e["status"] for n, e in doc["machines"].items()}
        assert statuses[self.SHIFTED] == "drifting", statuses
        assert all(
            s == "ok" for n, s in statuses.items() if n != self.SHIFTED
        ), statuses
        fh.write_rollup(out, doc)

        # the refresh build must train the drifted machine on the NEW
        # (shifted) regime — shift that machine's dataset rows
        shifted_prefix = f"rf{self.SHIFTED.split('-')[1]}-"
        orig_get_data = ds_mod.RandomDataset.get_data

        def shifted_get_data(ds_self):
            X, y = orig_get_data(ds_self)
            tag0 = ds_self.tag_list[0]
            tag_name = getattr(tag0, "name", tag0)
            if str(tag_name).startswith(shifted_prefix):
                return X * 8.0, y * 8.0
            return X, y

        monkeypatch.setattr(
            ds_mod.RandomDataset, "get_data", shifted_get_data
        )

        # two health polls (hysteresis) → exactly the drifted machine
        # rebuilds warm; the suspended() guard keeps the refresh build's
        # own training scores out of the live window
        rcfg = RefreshConfig(
            machines=cfg.machines, output_dir=out,
            hysteresis=2, cooldown_seconds=0,
        )
        with reg.suspended():
            first = refresh_once(rcfg)
            assert first["outcome"] == "idle"
            assert first["drifting"] == [self.SHIFTED]
            second = refresh_once(rcfg)
        assert second["outcome"] == "rebuilt", second
        assert second["selected"] == [self.SHIFTED]
        assert second["rebuilt"] == [self.SHIFTED]
        assert second["warm_started"] == [self.SHIFTED], (
            "previous-generation params must warm-start the rebuild "
            f"(fallbacks: {second['warm_fallbacks']})"
        )
        gen1 = artifacts.read_generation(out)
        assert gen1 == second["generation"] == gen0 + 1

        # the live collection follows the flip with ONE whole-pack
        # transfer — only the touched machine reloads, no restart.
        # Materialize the stacked serving programs first so the reload's
        # device transfer is observable (lazy scorers defer it).
        with reg.suspended():
            _ = coll.fleet_scorer
        d0 = artifacts.device_put_count()
        changes = coll.maybe_delta_reload()
        assert changes["reloaded"] == [self.SHIFTED]
        assert artifacts.device_put_count() - d0 == 1
        assert coll.generation == gen1

        # warm attestation rides the artifact metadata
        store = artifacts.open_store(out)
        meta = store.load_metadata(self.SHIFTED)
        warm_meta = meta["model"]["warm_start"]
        assert warm_meta["warm"] is True
        assert warm_meta["epochs"] == 1  # ceil(4 * 0.25)

        # drift clears against the rebuilt baseline: fresh live window,
        # rebuilt model, same shifted regime → ok
        reg.clear([self.SHIFTED])
        reg.load_baselines({self.SHIFTED: meta})
        # get_data is monkeypatched for this machine by now, so the
        # matrix is already in the shifted regime — no extra scale
        X = self._machine_matrix(self.SHIFTED)
        coll.get(self.SHIFTED).scorer.anomaly_arrays(X)
        cleared = reg.doc(machines=[self.SHIFTED])
        entry = cleared["machines"][self.SHIFTED]
        assert entry["status"] == "ok", entry["drift"]

        # ... and the next refresh cycle goes back to idle
        fh.write_rollup(out, reg.doc(machines=names))
        with reg.suspended():
            after = refresh_once(rcfg)
        assert after["outcome"] == "idle"
        assert self.SHIFTED not in after["drifting"]
        reg.clear(names)


@pytest.mark.slow
class TestRefreshLongHorizonSoak:
    """ISSUE 14 satellite: the refresh plane under sustained drift — 20
    compressed drift→refresh→flip cycles against ONE live serving
    collection.  Pins the long-horizon invariants a single-cycle test
    can't: generations stay strictly monotone, the persisted selector
    state stays bounded (it must not accrete per-cycle entries), no
    machine is ever quarantined, and the live collection follows every
    flip by delta reload alone (no restart, no full rescan)."""

    CYCLES = 20

    def _soak_yaml(self):
        machines = "\n".join(
            f"""
  - name: soak-{i}
    dataset:
      type: RandomDataset
      tags: [soak{i}-a, soak{i}-b, soak{i}-c]
      train_start_date: "2017-12-25T06:00:00Z"
      train_end_date: "2017-12-26T06:00:00Z"
"""
            for i in range(2)
        )
        return f"""
machines:{machines}
globals:
  model:
    gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector:
      base_estimator:
        gordo_tpu.pipeline.Pipeline:
          steps:
            - gordo_tpu.ops.scalers.MinMaxScaler
            - gordo_tpu.models.estimator.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 2
                batch_size: 64
"""

    def test_twenty_cycle_soak(self, tmp_path, monkeypatch):
        from gordo_tpu.builder import build_project
        from gordo_tpu.serve.server import ModelCollection
        from gordo_tpu.workflow import NormalizedConfig, load_machine_config

        # the soak drives rebuild mechanics, not loss quality — a huge
        # parity factor keeps every warm rebuild on the warm path
        monkeypatch.setenv("GORDO_REFRESH_PARITY_FACTOR", "1e6")
        out = str(tmp_path / "models")
        cfg = NormalizedConfig(
            load_machine_config(self._soak_yaml()), "soakproj"
        )
        names = [m.name for m in cfg.machines]
        result = build_project(cfg.machines, out, max_bucket_size=2)
        assert not result.failed
        generation = artifacts.read_generation(out)

        reg = telemetry.FLEET_HEALTH
        reg.clear(names)
        coll = ModelCollection.from_directory(out, project="soakproj")
        rcfg = RefreshConfig(
            machines=cfg.machines, output_dir=out,
            hysteresis=1, cooldown_seconds=0,
        )
        state_file = refresh_loop.state_path(out)
        state_size_early = None

        for cycle in range(self.CYCLES):
            target = names[cycle % len(names)]
            statuses = {
                n: ("drifting" if n == target else "ok") for n in names
            }
            fh.write_rollup(out, _health_doc(statuses))

            # the CronJob face: a fresh selector per cycle, streaks and
            # cooldowns riding state.json — the growth-bounded artifact
            with reg.suspended():
                summary = refresh_once(rcfg)
            assert summary["outcome"] == "rebuilt", (cycle, summary)
            assert summary["rebuilt"] == [target], (cycle, summary)
            assert not summary["failed"], (cycle, summary)

            # strictly monotone generations, one flip per cycle
            assert summary["generation"] == generation + 1, (cycle, summary)
            generation = summary["generation"]

            # the live collection follows by delta reload alone
            changes = coll.maybe_delta_reload()
            assert changes["reloaded"] == [target], (cycle, changes)
            assert changes["added"] == changes["removed"] == []
            assert coll.generation == generation
            assert coll.quarantined == {}, (cycle, coll.quarantined)

            if cycle == 1:
                state_size_early = os.path.getsize(state_file)

        # bounded state: one entry per fleet machine, not per cycle —
        # the file must not grow past its steady-state size (small slack
        # for float-digit jitter in last_rebuild timestamps)
        with open(state_file) as fh_state:
            state = json.load(fh_state)
        assert sorted(state["machines"]) == sorted(names)
        final_size = os.path.getsize(state_file)
        assert final_size <= state_size_early + 64, (
            state_size_early, final_size,
        )

        # the fleet survived 20 rebuild generations intact
        _, refs = artifacts.discover(out, quarantine=True)
        assert sorted(r.name for r in refs) == sorted(names)
        reg.clear(names)
