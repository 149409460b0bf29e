"""Pipelined project builds (ISSUE 4): the loader-pool → device →
artifact-writer-pool drive loop must be byte-equivalent to a serial
drive — same artifact bytes, same registry entries — and the writer pool
must fully drain before the resumable exit-75 path records its shard
state.  The serial drive is a reference that lives here: one
``build_project`` call per chunk (:func:`_build_chunk_by_chunk`).  Slow
lane, alongside tests/test_distributed.py (wired into the
CI test-full job, .github/workflows/ci.yml)."""

import json
import os
import pickle

import pytest

from gordo_tpu import telemetry
from gordo_tpu.builder import build_project
from gordo_tpu.builder import fleet_build as fb
from gordo_tpu.distributed.partition import ShardState, process_shard
from gordo_tpu.utils import disk_registry
from gordo_tpu.workflow.config import Machine

# heavy integration module: excluded from the fast CI lane
pytestmark = pytest.mark.slow

#: metadata fields that legitimately differ between two builds of the
#: same config (wall-clock measurements) — same set the multihost dryrun
#: byte-identity check uses (scripts/multihost_dryrun.py)
VOLATILE_META = {
    "model_creation_date",
    "data_query_duration_sec",
    "cross_validation_duration_sec",
    "model_builder_duration_sec",
    "fit_samples_per_second",
    "fit_seconds",
    "fleet_seconds",
    "bucket_size",
}


def _machines(n, prefix="pipe", hours=24):
    return [
        Machine.from_config(
            {
                "name": f"{prefix}-{i}",
                "dataset": {
                    "type": "RandomDataset",
                    "tag_list": ["a", "b", "c"],
                    "train_start_date": "2017-12-25T06:00:00Z",
                    "train_end_date": "2017-12-26T06:00:00Z",
                },
            }
        )
        for i in range(n)
    ]


def _build_chunk_by_chunk(machines, out, size, **kwargs):
    """The serial reference drive: one ``build_project`` call per chunk of
    ``size`` machines, all into ``out`` (and whatever registry ``kwargs``
    names).  A one-chunk call dispatches and then finishes, so no program
    is dispatched before the previous chunk is collected and written; it
    runs the same programs at the same padded width as one call over all
    of ``machines`` at ``max_bucket_size=size``."""
    results = []
    for start in range(0, len(machines), size):
        result = build_project(
            machines[start:start + size], str(out), max_bucket_size=size,
            **kwargs,
        )
        assert len(result.timeline) <= 1  # one chunk: nothing to overlap
        results.append(result)
    return results


def _scrub_timings(obj, seen=None):
    """Zero wall-clock attributes through a pickled object graph (the
    multihost dryrun's technique): everything else must match to the bit."""
    if seen is None:
        seen = set()
    if id(obj) in seen:
        return
    seen.add(id(obj))
    if isinstance(obj, dict):
        for key, zero in (("fleet_seconds", 0.0), ("bucket_size", 0)):
            if key in obj:
                obj[key] = zero
        for v in obj.values():
            _scrub_timings(v, seen)
        return
    if isinstance(obj, (list, tuple)):
        for v in obj:
            _scrub_timings(v, seen)
        return
    d = getattr(obj, "__dict__", None)
    if d is None:
        return
    if "fit_seconds_" in d:
        d["fit_seconds_"] = 0.0
    for v in d.values():
        _scrub_timings(v, seen)


def _strip_meta(v):
    if isinstance(v, dict):
        return {k: _strip_meta(x) for k, x in v.items() if k not in VOLATILE_META}
    if isinstance(v, list):
        return [_strip_meta(x) for x in v]
    return v


class TestPipelineParity:
    def test_artifacts_and_registry_byte_identical_to_serial(self, tmp_path):
        """The acceptance contract: the pipelined drive and a serial
        drive (a call per chunk) of the same project produce
        byte-identical artifacts (model.pkl modulo zeroed wall-clock
        timings, definition.yaml byte-for-byte, metadata.json modulo
        timing fields) and the same registry keys."""
        machines = _machines(6)
        s_out, s_reg = tmp_path / "out-serial", tmp_path / "reg-serial"
        p_out, p_reg = tmp_path / "out-pipelined", tmp_path / "reg-pipelined"
        # v1 on purpose: this test's byte-identity contract is defined on
        # the per-machine-dir layout (v2 pack parity is
        # tests/test_artifacts.py::TestV1V2Parity's job)
        serial = _build_chunk_by_chunk(
            machines, s_out, 2, model_register_dir=str(s_reg),
            artifact_format="v1",
        )
        pipelined = build_project(
            machines, str(p_out), model_register_dir=str(p_reg),
            max_bucket_size=2, artifact_format="v1",
        )
        assert len(pipelined.timeline) == 3  # chunks to overlap
        for result in serial + [pipelined]:
            assert not result.failed
        assert sorted(pipelined.artifacts) == sorted(
            name for result in serial for name in result.artifacts
        ) == sorted(m.name for m in machines)
        for m in machines:
            a, b = s_out / m.name, p_out / m.name
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
            meta_a = json.loads((a / "metadata.json").read_text())
            meta_b = json.loads((b / "metadata.json").read_text())
            assert _strip_meta(meta_a) == _strip_meta(meta_b), m.name
        # registry entries: same keys, each resolving to the machine dir
        keys_s = sorted(disk_registry.list_keys(str(s_reg)))
        keys_p = sorted(disk_registry.list_keys(str(p_reg)))
        assert keys_s == keys_p and len(keys_s) == len(machines)
        # no scratch residue
        assert not list(p_out.glob(".gordo-tmp*"))

    def test_pipelined_artifacts_cache_hit_a_rerun(self, tmp_path):
        """Registry parity the way it matters: artifacts the writer pool
        registered satisfy a re-run's cache lookups."""
        machines = _machines(3, prefix="xcache")
        out, reg = str(tmp_path / "m"), str(tmp_path / "r")
        first = build_project(machines, out, model_register_dir=reg)
        assert sorted(first.fleet_built) == sorted(m.name for m in machines)
        rerun = build_project(
            machines, str(tmp_path / "m2"), model_register_dir=reg,
        )
        assert sorted(rerun.cached) == sorted(m.name for m in machines)


class TestPipelineTelemetry:
    def test_pipeline_telemetry_present_after_pipelined_build(self, tmp_path):
        build_project(_machines(2, prefix="tel"), str(tmp_path / "m"))
        scrape = telemetry.render()
        for name in (
            "gordo_build_pipeline_stage_seconds",
            "gordo_build_pipeline_stall_seconds",
            "gordo_build_pipeline_writer_queue_depth",
            "gordo_build_pipeline_chunks_total",
        ):
            assert name in scrape, name


class TestWriterDrainOnResumablePath:
    def test_queued_artifacts_land_before_shard_goes_resumable(
        self, tmp_path, monkeypatch
    ):
        """exit-75 contract: when a machine failure marks the shard
        resumable, every artifact the writer pool had queued is FULLY on
        disk, registered, and recorded in the shard state before the
        state transitions — a re-run must cache-hit them, and the state
        file must never reference a half-written artifact."""
        from gordo_tpu.dataset import datasets as ds_mod
        from gordo_tpu.ingest import plane

        machines = _machines(6, prefix="drain")
        for i, m in enumerate(machines):
            # a fetch each: machines of one fingerprint share one load
            m.dataset["tag_list"] = [f"drain-{i}-{c}" for c in "abc"]
        # get_data() is the plane's per-machine path, for what its
        # columnar pass cannot express: send every machine there
        monkeypatch.setattr(plane, "_vectorizable", lambda dataset: False)
        orig = ds_mod.RandomDataset.get_data
        calls = {"n": 0}

        def failing_get_data(self):
            calls["n"] += 1
            if calls["n"] == 5:  # one mid-stream load fails
                raise RuntimeError("synthetic data outage")
            return orig(self)

        monkeypatch.setattr(ds_mod.RandomDataset, "get_data", failing_get_data)
        out = str(tmp_path / "m")
        reg = str(tmp_path / "r")
        shard = process_shard(machines, 1, 0, output_dir=out)
        # v1: this test inspects per-machine dirs and the v1 writer
        # pool's drain semantics directly
        result = build_project(
            machines, out, model_register_dir=reg, max_bucket_size=2,
            data_workers=1, shard=shard, artifact_format="v1",
        )
        assert len(result.failed) == 1
        ok_names = sorted(result.artifacts)
        assert len(ok_names) == 5

        state = ShardState.load(out, 0, 1)
        assert state.status == "resumable"
        # every completed machine was recorded AND is complete on disk
        assert sorted(state.completed) == ok_names
        for name in state.completed:
            art = os.path.join(out, name)
            assert os.path.exists(os.path.join(art, "model.pkl"))
            meta = json.loads(
                open(os.path.join(art, "metadata.json")).read()
            )
            assert meta["name"] == name
        # no half-written scratch artifacts survive the drain
        assert not [d for d in os.listdir(out) if d.startswith(".gordo-tmp")]
        # and the registered artifacts satisfy the resumed run's lookups
        monkeypatch.setattr(ds_mod.RandomDataset, "get_data", orig)
        shard2 = process_shard(machines, 1, 0, output_dir=out)
        rerun = build_project(
            machines, out, model_register_dir=reg, max_bucket_size=2,
            shard=shard2, artifact_format="v1",
        )
        assert not rerun.failed
        assert sorted(rerun.cached) == ok_names
        assert ShardState.load(out, 0, 1).status == "done"

    def test_write_failure_fails_one_machine_loudly(self, tmp_path, monkeypatch):
        """A broken artifact write must fail that machine (recorded in
        result.failed) without sinking the drain or the other writes."""
        machines = _machines(4, prefix="wfail")
        orig = fb._write_artifact
        target = f"{machines[1].name}"

        def breaking_write(detector, metadata, dest, *args, **kwargs):
            if os.path.basename(dest) == target:
                raise OSError("disk full (synthetic)")
            return orig(detector, metadata, dest, *args, **kwargs)

        monkeypatch.setattr(fb, "_write_artifact", breaking_write)
        # v1: the synthetic failure targets the v1 per-machine writer
        # (_write_artifact); the pack writer's failure fallback is covered
        # by tests/test_artifacts.py
        result = build_project(
            machines, str(tmp_path / "m"), max_bucket_size=2,
            artifact_format="v1",
        )
        assert list(result.failed) == [target]
        assert result.failed[target].startswith("write:")
        assert sorted(result.artifacts) == sorted(
            m.name for m in machines if m.name != target
        )
