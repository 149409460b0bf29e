"""Build-ingest plane (r24): fingerprint dedup correctness, vectorized
parity with the per-machine path, stacked zero-copy handoff, the config
fast path, and the hot-path lint gate.

The central contract — pinned here in BOTH directions — is that the
fleet-vectorized assembly is an invisible optimization: machines with
IDENTICAL dataset fingerprints share one fetch and get byte-identical
frames, machines with ANY differing dataset field (tags, resolution,
row filter, window, ...) must miss the dedup cache, and every machine's
``(X, y, metadata)`` matches what ``dataset.get_data()`` produces: the
arrays and the metadata to the bit, except ``summary_statistics``, whose
floats come from two summation orders (numpy's pairwise sum in the plane,
pandas' in ``get_data()``) and are held to a relative 1e-12.
"""

import importlib.util
import os
import pickle
import types

import numpy as np
import pytest

from gordo_tpu.dataset.base import GordoBaseDataset
from gordo_tpu.ingest.fingerprint import (
    dataset_fingerprint,
    provider_fingerprint,
)
from gordo_tpu.ingest.plane import (
    DEDUP_HITS_TOTAL,
    load_chunk,
    owned_stack_base,
    stack_live_slots,
)

WINDOW = {
    "train_start_date": "2017-12-25 06:00:00Z",
    "train_end_date": "2017-12-26 06:00:00Z",
}


def _m(name, n_tags=3, **over):
    cfg = {
        "type": "RandomDataset",
        "tag_list": [f"{name}-t{j}" for j in range(n_tags)],
        "resolution": "10min",
        **WINDOW,
    }
    cfg.update(over)
    return types.SimpleNamespace(name=name, dataset=cfg)


def _classic(machine):
    """The per-machine reference path the vectorized pass must match."""
    ds = GordoBaseDataset.from_dict(dict(machine.dataset))
    X, y = ds.get_data()
    return np.asarray(X, np.float32), ds.get_metadata()


def _without_summary(meta):
    return {k: v for k, v in meta.items() if k != "summary_statistics"}


def _assert_metadata_parity(meta, ref, label):
    """Pickle-identical dataset metadata apart from ``summary_statistics``,
    which :func:`_assert_summary_parity` holds."""
    assert pickle.dumps(_without_summary(meta)) == pickle.dumps(
        _without_summary(ref)
    ), label
    _assert_summary_parity(
        meta["summary_statistics"], ref["summary_statistics"], label
    )


def _assert_summary_parity(stats, stats_ref, label):
    """Same tags and statistics, each float within a relative 1e-12 (two
    summation orders differ in the last ulps, 3e-16 measured)."""
    assert list(stats) == list(stats_ref), label
    for tag, per_tag in stats.items():
        assert list(per_tag) == list(stats_ref[tag]), (label, tag)
        for stat, value in per_tag.items():
            assert value == pytest.approx(
                stats_ref[tag][stat], rel=1e-12, abs=0.0
            ), (label, tag, stat)


class TestFingerprint:
    def test_identical_configs_equal(self):
        a = _m("a").dataset
        b = dict(_m("a").dataset)
        assert dataset_fingerprint(a) == dataset_fingerprint(b)

    @pytest.mark.parametrize(
        "override",
        [
            {"tag_list": ["a-t0", "a-t1"]},
            {"resolution": "5min"},
            {"row_filter": "`a-t0` > 0"},
            {"row_filter_buffer_size": 3},
            {"train_start_date": "2017-12-24 06:00:00Z"},
            {"train_end_date": "2017-12-27 06:00:00Z"},
            {"target_tag_list": ["a-t0"]},
            {"aggregation_methods": "max"},
            {"n_samples_threshold": 5},
            {"asset": "other"},
            {"some_future_knob": 1},  # unknown keys can only MISS
        ],
    )
    def test_any_differing_field_misses(self, override):
        base = _m("a").dataset
        other = dict(base)
        other.update(override)
        assert dataset_fingerprint(base) != dataset_fingerprint(other)

    def test_tag_spelling_normalizes(self):
        """str / dict / SensorTag spellings of the same tags must HIT —
        the fingerprint is over tag NAMES, not config syntax."""
        a = dict(_m("a").dataset)
        b = dict(a)
        b["tag_list"] = [{"name": t} for t in a["tag_list"]]
        assert dataset_fingerprint(a) == dataset_fingerprint(b)

    def test_batch_plane_uses_the_hoisted_fingerprint(self):
        """r18's backfill fetch dedup and the r24 ingest plane must share
        ONE fingerprint implementation (the hoist this PR performed)."""
        from gordo_tpu.batch.runner import _dataset_fingerprint

        assert _dataset_fingerprint is provider_fingerprint

    def test_provider_grain_ignores_window(self):
        """The fetch grain (backfill) shares frames across scoring
        windows; the output grain (build ingest) must not."""
        a = _m("a").dataset
        b = dict(a, train_end_date="2017-12-27 06:00:00Z")
        assert provider_fingerprint(a) == provider_fingerprint(b)
        assert dataset_fingerprint(a) != dataset_fingerprint(b)


class TestDedup:
    def test_twins_fetch_once_and_share_bytes(self):
        leader = _m("lead")
        twin = types.SimpleNamespace(name="twin", dataset=dict(leader.dataset))
        before = DEDUP_HITS_TOTAL.value()
        stats = {}
        out = load_chunk([leader, twin], stats=stats)
        Xl, yl, ml, _ = out["lead"]
        Xt, yt, mt, _ = out["twin"]
        assert Xl.tobytes() == Xt.tobytes()
        assert pickle.dumps(ml) == pickle.dumps(mt)
        assert stats["fetches"] == 1
        assert stats["dedup_hits"] == 1
        assert DEDUP_HITS_TOTAL.value() == before + 1

    def test_twin_metadata_is_isolated(self):
        """Dedup copies must not alias: the builder mutates metadata
        per machine downstream."""
        leader = _m("lead")
        twin = types.SimpleNamespace(name="twin", dataset=dict(leader.dataset))
        out = load_chunk([leader, twin])
        ml, mt = out["lead"][2], out["twin"][2]
        assert ml is not mt
        mt["tag_loading_metadata"]["poisoned"] = True
        assert "poisoned" not in ml["tag_loading_metadata"]

    def test_differing_window_fetches_twice(self):
        a = _m("a")
        b = types.SimpleNamespace(
            name="b",
            dataset=dict(a.dataset, train_end_date="2017-12-27 06:00:00Z"),
        )
        stats = {}
        out = load_chunk([a, b], stats=stats)
        assert stats["fetches"] == 2
        assert stats["dedup_hits"] == 0
        assert out["a"][0].shape != out["b"][0].shape

    def test_row_filter_routes_to_fallback(self):
        m = _m("filt", row_filter="`filt-t0` > -100")
        stats = {}
        out = load_chunk([m], stats=stats)
        assert stats["fallback"] == 1
        assert stats["vectorized"] == 0
        X, _, meta, _ = out[m.name]
        Xc, mc = _classic(m)
        assert X.tobytes() == Xc.tobytes()
        assert pickle.dumps(meta) == pickle.dumps(mc)


class TestVectorizedParity:
    @pytest.fixture(scope="class")
    def mixed_chunk(self):
        """One chunk mixing tag widths, a fingerprint twin and a fallback
        machine, loaded once: ``({kind: machines}, load_chunk's output)``."""
        plain = [_m("a"), _m("b")]
        kinds = {
            "plain": plain,
            "wide": [_m("wide", n_tags=5)],
            "twin": [types.SimpleNamespace(
                name="twin-a", dataset=dict(plain[0].dataset)
            )],
            "fallback": [_m("filt", row_filter="`filt-t0` > -100")],
        }
        chunk = [m for members in kinds.values() for m in members]
        return kinds, load_chunk(chunk)

    @pytest.mark.parametrize("kind", ["plain", "wide", "twin", "fallback"])
    def test_mixed_chunk_matches_per_machine_path(self, mixed_chunk, kind):
        """The acceptance contract at the array level: every kind of
        machine in a mixed chunk gets byte-identical X and the same
        metadata (``_assert_metadata_parity``) as from get_data()."""
        kinds, out = mixed_chunk
        for m in kinds[kind]:
            entry = out[m.name]
            assert not isinstance(entry, Exception), (m.name, entry)
            X, y, meta, secs = entry
            Xc, mc = _classic(m)
            assert X.tobytes() == Xc.tobytes(), m.name
            _assert_metadata_parity(meta, mc, m.name)
            assert secs >= 0.0

    def test_y_is_x_for_untargeted_machines(self):
        """No target_tag_list → y shares X's buffer outright, so the
        dispatch plane stages ONE stacked array, not two."""
        out = load_chunk([_m("a"), _m("b")])
        for name in ("a", "b"):
            X, y, _, _ = out[name]
            assert y is X

    def test_bad_config_is_a_per_machine_value(self):
        """One broken machine must not poison the chunk."""
        good = _m("good")
        bad = types.SimpleNamespace(name="bad", dataset={"type": "NoSuch"})
        out = load_chunk([good, bad])
        assert isinstance(out["bad"], Exception)
        X, _, _, _ = out["good"]
        assert X.tobytes() == _classic(good)[0].tobytes()


class TestStackedHandoff:
    def test_capacity_buffer_is_adopted(self):
        machines = [_m(f"s{i}") for i in range(4)]
        out = load_chunk(machines, capacity=lambda m: m + 2)
        X0 = out["s0"][0]
        base = owned_stack_base(X0)
        assert base is not None
        assert base.shape[0] == 6  # 4 live + 2 padding slots
        assert stack_live_slots(base) == 4
        for i in range(4):
            assert np.shares_memory(out[f"s{i}"][0], base)

    def test_stack_machine_axis_is_a_view(self):
        from gordo_tpu.parallel.anomaly import _stack_machine_axis

        machines = [_m(f"s{i}") for i in range(4)]
        out = load_chunk(machines, capacity=lambda m: m)
        arrs = [out[f"s{i}"][0] for i in range(4)]
        stacked = _stack_machine_axis(arrs)
        base = owned_stack_base(arrs[0])
        assert np.shares_memory(stacked, base)
        assert np.array_equal(stacked, np.stack(arrs))

    def test_stack_machine_axis_copies_foreign_arrays(self):
        from gordo_tpu.parallel.anomaly import _stack_machine_axis

        arrs = [np.ones((5, 3), np.float32), np.zeros((5, 3), np.float32)]
        stacked = _stack_machine_axis(arrs)
        assert owned_stack_base(stacked) is None
        assert np.array_equal(stacked, np.stack(arrs))

    def test_pad_models_capacity_in_place(self):
        from gordo_tpu.parallel.anomaly import (
            _pad_models_capacity,
            _stack_machine_axis,
        )

        machines = [_m(f"s{i}") for i in range(3)]
        out = load_chunk(machines, capacity=lambda m: m + 1)
        arrs = [out[f"s{i}"][0] for i in range(3)]
        X = _stack_machine_axis(arrs)
        base = owned_stack_base(arrs[0])
        padded = _pad_models_capacity(X, 4)
        assert np.shares_memory(padded, base)
        assert padded.shape[0] == 4
        assert np.array_equal(padded[3], X[2])  # replicated last machine

    def test_pad_models_capacity_copies_foreign_arrays(self):
        from gordo_tpu.parallel.anomaly import _pad_models_capacity

        X = np.arange(24, dtype=np.float32).reshape(2, 4, 3)
        padded = _pad_models_capacity(X, 3)
        assert not np.shares_memory(padded, X)
        assert np.array_equal(padded[2], X[1])


PROJECT_YAML = """
machines:
  - name: cfg-a
    dataset:
      type: RandomDataset
      tags: [a-t0, a-t1]
  - name: cfg-b
    dataset:
      type: RandomDataset
      tags: [b-t0]
    model:
      gordo_tpu.ops.scalers.MinMaxScaler: {}
globals:
  dataset:
    resolution: 5min
"""


class TestConfigFastPath:
    def test_from_source_matches_legacy_path(self):
        from gordo_tpu.workflow.config import (
            NormalizedConfig,
            load_machine_config,
        )

        legacy = NormalizedConfig(load_machine_config(PROJECT_YAML), "p")
        fast = NormalizedConfig.from_source(PROJECT_YAML, "p")
        assert [m.to_dict() for m in legacy.machines] == [
            m.to_dict() for m in fast.machines
        ]
        assert legacy.config_globals == fast.config_globals

    def test_cache_hit_skips_the_parse(self, tmp_path, monkeypatch):
        import gordo_tpu.workflow.config as config_mod

        cold = config_mod.NormalizedConfig.from_source(
            PROJECT_YAML, "p", cache_dir=str(tmp_path)
        )
        assert list(tmp_path.glob("config-*.json"))

        def boom(_source):
            raise AssertionError("cache hit must not re-parse")

        monkeypatch.setattr(config_mod, "load_machine_config", boom)
        warm = config_mod.NormalizedConfig.from_source(
            PROJECT_YAML, "p", cache_dir=str(tmp_path)
        )
        assert [m.to_dict() for m in warm.machines] == [
            m.to_dict() for m in cold.machines
        ]
        assert warm.config_globals == cold.config_globals
        assert warm.project_name == "p"

    def test_project_name_is_part_of_the_key(self, tmp_path):
        from gordo_tpu.workflow.config import NormalizedConfig

        NormalizedConfig.from_source(PROJECT_YAML, "p1", cache_dir=str(tmp_path))
        NormalizedConfig.from_source(PROJECT_YAML, "p2", cache_dir=str(tmp_path))
        assert len(list(tmp_path.glob("config-*.json"))) == 2

    def test_corrupt_cache_entry_falls_back_cold(self, tmp_path):
        from gordo_tpu.workflow.config import NormalizedConfig

        NormalizedConfig.from_source(PROJECT_YAML, "p", cache_dir=str(tmp_path))
        (entry,) = tmp_path.glob("config-*.json")
        entry.write_text("{not json")
        cfg = NormalizedConfig.from_source(
            PROJECT_YAML, "p", cache_dir=str(tmp_path)
        )
        assert [m.name for m in cfg.machines] == ["cfg-a", "cfg-b"]

    def test_unjsonable_config_never_caches(self, tmp_path):
        """A YAML date parses to datetime.date — not JSON-representable,
        so the entry must simply not cache (correctness over speed)."""
        from gordo_tpu.workflow.config import NormalizedConfig

        text = PROJECT_YAML.replace(
            "resolution: 5min",
            "resolution: 5min\n  metadata:\n    dated: 2017-12-25",
        )
        cfg = NormalizedConfig.from_source(text, "p", cache_dir=str(tmp_path))
        assert not list(tmp_path.glob("config-*.json"))
        assert len(cfg.machines) == 2

    def test_duplicate_names_still_rejected(self):
        from gordo_tpu.workflow.config import NormalizedConfig

        dup = PROJECT_YAML.replace("cfg-b", "cfg-a")
        with pytest.raises(ValueError, match="Duplicate"):
            NormalizedConfig.from_source(dup, "p")


class TestIngestLintGate:
    @staticmethod
    def _lint(path):
        spec = importlib.util.spec_from_file_location(
            "gordo_lint",
            os.path.join(
                os.path.dirname(os.path.dirname(__file__)),
                "scripts",
                "lint.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.lint_file(path)

    def test_per_machine_pandas_banned_outside_fallback(self, tmp_path):
        bad = tmp_path / "gordo_tpu" / "ingest" / "plane.py"
        bad.parent.mkdir(parents=True)
        bad.write_text(
            "import pandas as pd\n"
            "def hot(df, ds):\n"
            "    a = df.resample('10min').mean()\n"
            "    b = pd.DataFrame({'x': [1]})\n"
            "    c = ds.get_data()\n"
            "    return pd.concat([a, b]), c\n"
            "def _load_fallback(dataset, align_lengths):\n"
            "    X, y = dataset.get_data()\n"
            "    return X.to_frame()\n"
        )
        msgs = [f[2] for f in self._lint(str(bad))]
        hits = [m for m in msgs if "ingest hot path" in m]
        assert len(hits) == 4  # resample, DataFrame, get_data, concat
        # _load_fallback's get_data/to_frame are sanctioned
        assert not any("to_frame" in m for m in hits)

    def test_shipping_plane_is_clean(self):
        plane_py = os.path.join(
            os.path.dirname(os.path.dirname(__file__)),
            "gordo_tpu",
            "ingest",
            "plane.py",
        )
        msgs = [f[2] for f in self._lint(plane_py)]
        assert not any("ingest hot path" in m for m in msgs)


@pytest.mark.slow
class TestBuildParity:
    def test_ingest_build_byte_identical_to_classic(self, tmp_path):
        """The end-to-end acceptance contract: build_project through the
        plane's columnar pass produces byte-identical artifacts (definition
        bytes, metadata modulo volatile timings and summary_statistics'
        last ulps, model pickles modulo zeroed wall-clock) and registry
        keys vs the per-machine path, ``_load_fallback``, which every
        machine takes once nothing counts as vectorizable."""
        import json

        from test_build_pipeline import _machines, _scrub_timings, _strip_meta

        from gordo_tpu.builder import build_project
        from gordo_tpu.ingest import plane
        from gordo_tpu.workflow.config import Machine

        machines = _machines(6)
        machines.append(
            Machine.from_config(
                {"name": "twin-1", "dataset": dict(machines[1].dataset)}
            )
        )
        dirs = {}
        for label in ("classic", "ingest"):
            out = tmp_path / f"out-{label}"
            reg = tmp_path / f"reg-{label}"
            with pytest.MonkeyPatch.context() as patch:
                if label == "classic":
                    patch.setattr(plane, "_vectorizable", lambda dataset: False)
                result = build_project(
                    machines,
                    str(out),
                    model_register_dir=str(reg),
                    max_bucket_size=4,
                    artifact_format="v1",
                )
            assert not result.failed, result.failed
            ingest = result.summary()["ingest"]
            assert ingest["dedup_hits"] >= 1
            took, other = (
                ("fallback", "vectorized") if label == "classic"
                else ("vectorized", "fallback")
            )
            assert ingest[took] == ingest["fetches"] and ingest[other] == 0
            dirs[label] = (out, reg)
        c_out, c_reg = dirs["classic"]
        i_out, i_reg = dirs["ingest"]
        for m in machines:
            a, b = c_out / m.name, i_out / m.name
            assert (a / "definition.yaml").read_bytes() == (
                b / "definition.yaml"
            ).read_bytes(), m.name
            meta_a = _strip_meta(json.loads((a / "metadata.json").read_text()))
            meta_b = _strip_meta(json.loads((b / "metadata.json").read_text()))
            _assert_summary_parity(
                meta_b["dataset"]["summary_statistics"],
                meta_a["dataset"]["summary_statistics"],
                m.name,
            )
            for meta in (meta_a, meta_b):
                meta["dataset"] = _without_summary(meta["dataset"])
            assert meta_a == meta_b, m.name
            pa = pickle.loads((a / "model.pkl").read_bytes())
            pb = pickle.loads((b / "model.pkl").read_bytes())
            _scrub_timings(pa)
            _scrub_timings(pb)
            assert pickle.dumps(pa) == pickle.dumps(pb), m.name
        assert sorted(p.name for p in c_reg.iterdir()) == sorted(
            p.name for p in i_reg.iterdir()
        )
