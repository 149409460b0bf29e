"""What PR 39 named: the fleet program's operations outside the mixers
(``fit.*``, ``backbone.embed`` / ``.head`` / ``.norm`` / ``.stack`` /
``.residual``), the
jit under its registry name, and the pack write's three stages.  The names
are debug information and nothing else: the same program lowered without
it holds none of them (no digest of a program is pinned here)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.kinds import sequence_build as kind  # noqa: E402  (the project document)
from gordo_tpu import artifacts, compile as compile_plane, telemetry  # noqa: E402
from gordo_tpu.builder.timeline import STAGE_SECONDS, BuildTimeline  # noqa: E402

TINY = dict(hidden_size=64, num_heads=8, num_kv_heads=2, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8, num_experts_per_token=2,
            experts_held=2, experts_held_from=0, num_layers=5,
            context=32, stride=16, batch_size=4)
F, ROWS, SEED = 5, 217, 13
FIT_NAMES = ("fit.optimizer", "fit.loss", "fit.forecast", "fit.draw")
BACKBONE_NAMES = ("backbone.embed", "backbone.head", "backbone.norm", "backbone.stack",
                  "backbone.residual")
WRITE_STAGES = ("write.serialize", "write.file", "write.fsync")


@pytest.fixture(scope="module")
def lowered():
    """The tiny ``lfm2_moe`` backbone's fleet program (three folds' fits and
    forecasts, the final fit), lowered as ``build_project`` would."""
    from gordo_tpu import serializer
    from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition

    config = {
        "detector": "DiffBasedAnomalyDetector", "scalers": ["MinMaxScaler"],
        "estimator": "SequenceForecast",
        "model": {"kind": "lfm2_moe", "epochs": 1, "learning_rate": 0.001,
                  "compute_dtype": "auto", **TINY},
        "cv": {"splitter": "TimeSeriesSplit", "n_splits": 3},
        "dataset": {"type": "RandomDataset", "resolution": "10min", "n_tags": F,
                    "train_start_date": "2017-01-01T00:00:00+00:00",
                    "train_end_date": "2017-01-02T12:00:00+00:00", "rows": ROWS},
    }
    doc = kind.project_doc(config, SEED, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    builder = FleetDiffBuilder(spec)
    program = builder._group_program(builder._group_context(ROWS, F, F),
                                     padded=False, warm=False)
    data = jax.ShapeDtypeStruct((1, ROWS, F), jnp.float32)
    return program._jitted.lower(data, data, jax.ShapeDtypeStruct((1,), jnp.uint32))


@pytest.mark.parametrize("name", FIT_NAMES + BACKBONE_NAMES)
def test_the_name_is_debug_information_and_nothing_else(lowered, name):
    assert name in lowered.as_text(debug_info=True)
    assert name not in lowered.as_text()


@pytest.mark.parametrize("path", [
    "transpose(jvp(fit.loss))/", "transpose(jvp(backbone.norm))/",
    "transpose(jvp(SequenceBackbone))/backbone.head/",
    "transpose(jvp(SequenceBackbone))/backbone.embed/",
    "transpose(jvp(SequenceBackbone))/backbone.stack/",
    # a forecast's operations keep their own innermost names under the pass's
    "fit.forecast/SequenceBackbone/backbone.conv/", "fit.forecast/SequenceBackbone/backbone.norm/",
])
def test_the_backward_and_the_forecast_inherit_the_names(lowered, path):
    assert path in lowered.as_text(debug_info=True)


def test_no_new_name_wraps_a_mixer(lowered):
    """A leaf names arithmetic; ``fit.forecast`` alone marks a pass.  Were
    ``fit.optimizer``, ``fit.loss``, ``fit.draw`` or a new ``backbone.*``
    name around a loop that holds mixers, a mixer's path would hold it."""
    named = lowered.as_text(debug_info=True)
    for leaf in ("fit.optimizer", "fit.loss", "fit.draw") + BACKBONE_NAMES:
        for mixer in ("backbone.conv", "backbone.gqa", "backbone.moe", "backbone.ffn"):
            assert f"{leaf}/{mixer}" not in named and f"{leaf})/{mixer}" not in named


@pytest.mark.parametrize("name, module", [
    ("fleet.exact", "jit_fleet_exact"), ("fleet.exact_warm", "jit_fleet_exact_warm"),
    ("fleet.padded", "jit_fleet_padded"), ("closure", "jit_closure")])
def test_a_closure_program_lowers_a_module_named_for_it(name, module):
    program = compile_plane.closure_program(lambda x: x + 1.0, name=name)
    text = program._jitted.lower(jnp.ones(3)).as_text()
    assert f"module @{module} " in text
    np.testing.assert_allclose(program(jnp.ones(3)), 2.0)


def test_the_fleet_program_is_named_for_its_registry_name(lowered):
    assert "module @jit_fleet_exact " in lowered.as_text()


# -- the pack write's stages -------------------------------------------------

def _models(n=2):
    rng = np.random.default_rng(3)
    return [{"w": rng.normal(size=(64, 32)).astype(np.float32),
             "b": rng.normal(size=(32,)).astype(np.float32)} for _ in range(n)]


def _counts():
    series = {name: STAGE_SECONDS.snapshot_series(name) for name in WRITE_STAGES}
    return {name: (state["count"], state["sum"]) for name, state in series.items()}


def test_write_pack_observes_its_three_stages_once_a_pack(tmp_path, monkeypatch):
    """One observation of each ``write.*`` stage per pack, their sum no more
    than the span around the write, the three on that span (where the chunk
    timeline finds them), one span-log record each; the index names the pack
    only after its bytes and both renames were synced."""
    log = tmp_path / "spans.jsonl"
    monkeypatch.setenv("GORDO_SPAN_LOG", str(log))
    synced = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(os, "fsync", lambda fd: (synced.append("fsync"), real_fsync(fd))[1])
    monkeypatch.setattr(os, "replace", lambda a, b: (
        synced.append("rename " + os.path.basename(b).split(".", 1)[-1]), real_replace(a, b))[1])
    before = _counts()
    out = str(tmp_path / "out")
    with telemetry.span("gordo.build.write") as sp:
        artifacts.write_pack(out, ["m-0", "m-1"], _models())
    after = _counts()
    for name in WRITE_STAGES:
        assert after[name][0] - before[name][0] == 1
    busy = sum(after[name][1] - before[name][1] for name in WRITE_STAGES)
    assert 0 < busy <= sp["seconds"]
    assert busy == pytest.approx(sp["serialize_s"] + sp["file_s"] + sp["fsync_s"])
    assert sp["fsync_s"] > 0 and sp["file_s"] > 0 and sp["serialize_s"] > 0
    # the order of the guarantee: pack bytes synced, renamed; meta synced,
    # renamed; the directory synced; only then the index written and synced
    assert synced == ["fsync", "rename pack", "fsync", "rename meta.json", "fsync",
                      "fsync", "rename json", "fsync"]
    records = [json.loads(line) for line in log.read_text().splitlines()]
    stages = [r for r in records if r["span"].startswith("gordo.build.write.")]
    assert sorted(r["span"].rsplit(".", 1)[-1] for r in stages) == ["file", "fsync", "serialize"]
    for r in stages:
        assert r["parent"] == sp["id"] and r["seconds"] <= r["end"] - r["start"] + 1e-6
    # a second pack: one more observation each, and a machine reads back
    artifacts.write_pack(out, ["m-2"], _models(1))
    assert all(_counts()[name][0] - before[name][0] == 2 for name in WRITE_STAGES)
    assert sorted(ref.name for ref in artifacts.discover(out)[1]) == ["m-0", "m-1", "m-2"]


def test_the_chunk_timeline_keeps_the_stages_on_the_write_row(tmp_path):
    timeline = BuildTimeline(0.0)
    with timeline.phase("write", 3):
        artifacts.write_pack(str(tmp_path), ["m-0"], _models(1))
    (row,) = timeline.rows()
    counts = row["counts"]["write"]
    assert set(counts) == {"serialize_s", "file_s", "fsync_s"}
    (start, end), = row["phases"]["write"]
    assert 0 < sum(counts.values()) <= end - start


def test_delta_write_observes_the_stages_too(tmp_path):
    models = _models()
    artifacts.write_pack(str(tmp_path), ["m-0", "m-1"], models)
    before = _counts()
    artifacts.delta_write(str(tmp_path), {"m-1": models[0]})
    after = _counts()
    assert all(after[name][0] - before[name][0] == 1 for name in WRITE_STAGES)


def test_nothing_is_observed_with_telemetry_off(tmp_path):
    before = _counts()
    telemetry.set_enabled(False)
    try:
        with telemetry.span("gordo.build.write") as sp:
            artifacts.write_pack(str(tmp_path), ["m-0"], _models(1))
    finally:
        telemetry.set_enabled(True)
    assert _counts() == before and "serialize_s" not in sp
    assert [ref.name for ref in artifacts.discover(str(tmp_path))[1]] == ["m-0"]
