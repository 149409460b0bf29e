"""chip_smoke.py on CPU: the same phase functions the chip run goes
through, at 2 machines x 3 tags, plus the refusal to run without a chip.

Sizes and the expected platform are ARGUMENTS of the phases — there is no
flag or environment variable that turns the smoke into a CPU run.  Two
forced host devices make the children take the several-device path too
(sharded build, ``--model-parallel`` server, ``mesh info`` plan).
"""

import json
import os

import pytest

import chip_smoke


def _two_device_env():
    env = dict(os.environ)
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=2")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def test_phases_end_to_end_on_cpu(tmp_path):
    """build -> serve (single, bulk, stream) -> mesh plan -> second cold
    build, through ``build-project`` / ``run-server`` children."""
    env = _two_device_env()
    device = chip_smoke.probe_device(str(tmp_path), env, timeout=120)
    assert device == {"platform": "cpu", "device_kind": "cpu", "count": 2}
    sizes = chip_smoke.Sizes(
        dense_machines=2, dense_tags=3, lstm_machines=0,
        single_rows=64, bulk_machines=1, bulk_rows=128, stream_rows=5,
    )
    result = chip_smoke.run_smoke(
        str(tmp_path), sizes, device, env=env, deadline_seconds=600
    )
    phases = result["phases"]
    assert list(phases) == [
        "dense_build", "dense_serve", "dense_mesh", "dense_rebuild",
    ]
    for name in ("dense_build", "dense_rebuild"):
        build = phases[name]
        assert build["fleet_built"] == 2 and build["single_built"] == 0
        assert build["demoted"] == 0 and build["aot_fallbacks"] == 0
        assert build["device"] == {
            "platform": "cpu", "device_kind": "cpu", "count": 2, "used": 2,
        }
        assert build["compile_seconds"]["backend"] > 0
    serve = phases["dense_serve"]
    assert serve["model_parallel"] is True
    assert serve["device"]["used"] == 2
    # 2 single + subset bulk + full bulk + ingest + poll + 2 scrapes +
    # the closing /healthz
    assert serve["requests_by_status"] == {"200": 9}
    assert serve["dispatches"] == serve["input_transfers"] == 4
    assert serve["bulk_shapes"] == [[1, 128], [2, 64]]
    assert phases["dense_mesh"]["mesh_shape"] == {"models": 2, "data": 1}


def test_main_refuses_to_run_without_a_chip(capfd):
    """conftest pins JAX_PLATFORMS=cpu (as the sandbox does): main() must
    exit non-zero, say why, and print no result line."""
    rc = chip_smoke.main()
    out, err = capfd.readouterr()
    assert rc not in (0, None)
    assert out == ""
    assert "no accelerator" in err


def test_result_line_has_exactly_the_contract_keys():
    """The checker that runs the smoke reads the LAST stdout line and
    accepts no key beyond these; the evidence goes on the line before."""
    line = chip_smoke.result_line(
        {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_main_prints_the_result_last_and_the_evidence_before_it(
    tmp_path, monkeypatch, capfd
):
    """main() on a (pretended) chip: exit 0, the evidence line, then the
    contract line as the last line of stdout."""
    found = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    (tmp_path / "gordo_tpu").mkdir()
    monkeypatch.setattr(chip_smoke, "REPO", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "probe_device", lambda *a, **k: found)
    monkeypatch.setattr(
        chip_smoke, "run_smoke",
        lambda *a, **k: {"phases": {}, "total_seconds": 0.0},
    )
    assert chip_smoke.main() == 0
    out, _ = capfd.readouterr()
    evidence, last = out.splitlines()
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    doc = json.loads(evidence)["evidence"]
    assert doc["device"] == found and doc["claim"] is None
    with open(tmp_path / "chiprun_out" / "chip_smoke" / "result.json") as fh:
        assert json.load(fh) == doc


def test_a_platform_other_than_expected_fails_the_build_phase(tmp_path):
    """Assert, do not log: a child that ran somewhere else than expected
    raises out of the phase."""
    config = str(tmp_path / "project.yaml")
    names = chip_smoke.write_project(config, "dense", 2, 3)
    with pytest.raises(chip_smoke.SmokeFailure, match="expected platform tpu"):
        chip_smoke.build_phase(
            "dense", str(tmp_path), config, names, str(tmp_path / "out"),
            "tpu", _two_device_env(), timeout=300,
        )


@pytest.mark.parametrize("marker", chip_smoke.FALLBACK_MARKERS)
def test_fallback_messages_in_a_child_log_fail_the_run(tmp_path, marker):
    log = tmp_path / "child.log"
    log.write_text(f"INFO fine\nERROR gordo_tpu.x: {marker} (details)\n")
    with pytest.raises(chip_smoke.SmokeFailure, match="fallback message"):
        chip_smoke.scan_for_fallbacks(str(log))
    log.write_text("INFO fine\n")
    chip_smoke.scan_for_fallbacks(str(log))
