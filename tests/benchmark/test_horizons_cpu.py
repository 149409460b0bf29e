"""``benchmark.run`` end to end on the CPU for the ``horizons_build`` kind at
the tiny preset (hidden 64, 4 heads, low-rank queries of 24, 8 experts of
which 2 held, three layers and the multi-token-prediction module, sequences
of 32 rows), with the look for a chip lifted only here.  On the CPU
``compute_dtype: auto`` is float32, so the program has to agree with the
float32 reference closely, and every planted fault has to fail the same
limits.
"""

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.kinds import horizons_build as kind  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import glm_moe_lite as reference  # noqa: E402

SEED = 3000000043  # more than 32 signed bits hold
CELL = "glm-flash-tiny.build-two"
# float32 against float32 (measured here: loss 1e-6, update gap 2e-4,
# thresholds 1e-5); the faults read far above (the test below)
LIMITS = {"loss_first_gap": 1e-5, "loss_last_gap": 1e-5,
          "update_norm_gap": 3e-3, "threshold_gap": 1e-4, "nonfinite": 0}
TINY = dict(hidden_size=64, num_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            num_layers=3)
PER_LAYER = ["glm.program_s_per_model", "glm.write_s_per_model",
             "glm.expert_load_max_over_mean", "glm.compile_backend_s",
             "glm.mla_s_per_step", "glm.mla_attn_roofline", "glm.mtp_s_per_step"]


def tiny_config():
    config = Manifest(ROOT).config("glm-flash-plant")
    config["name"] = "glm-flash-tiny"
    config["model"].update(context=32, stride=16, batch_size=4, **TINY)
    config.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
                  qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
                  num_experts_per_tok=2)
    config["depth"]["layers_here"] = [0, 1, 2]
    config["experts"]["held_here"] = 2
    config["dataset"].update(
        n_tags=5, train_end_date="2017-01-02T12:00:00+00:00", rows=217)
    config["deployment"].update(max_bucket_size=1, project_machines=4)
    config["check"] = {"machines": 1, "fold_machines": 1, "limits": LIMITS}
    return config


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-horizons")
    base = root / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (base / sub).mkdir(parents=True)
    config = tiny_config()
    (base / "configs" / "glm-flash-tiny.json").write_text(json.dumps(config))
    (base / "traffic" / "build-two.json").write_text(json.dumps({
        "kind": "horizons_build", "trace_seconds": 0.2}))
    for name in ["build.models_per_h_per_chip", "setup_s", *PER_LAYER]:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics", name + ".json"),
                    base / "metrics" / (name + ".json"))
    real = {m["name"]: m for m in Manifest(ROOT).doc["per_layer"]}
    manifest = {
        "command": ["python3", "-m", "benchmark.run"], "paths": ["extra"],
        "run_seconds": 2,
        "configs": [{"name": "glm-flash-tiny", "source": config["source"],
                     "file": "extra/configs/glm-flash-tiny.json",
                     "reduced": ["depth"], "why": "CPU test size"}],
        "workloads": [{"name": CELL, "config": "glm-flash-tiny",
                       "traffic": "build-two", "chips": 1, "why": "CPU test"}],
        "end_to_end": [
            {"name": "build.models_per_h_per_chip", "unit": "models/h",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [{**real[name], "workloads": [CELL]} for name in PER_LAYER],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def drive(checkout, trace, seed=SEED):
    out = io.StringIO()
    with redirect_stdout(out):
        code = bench_run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
             "--trace", str(trace)],
            require_chip=False, root=checkout,
        )
    return code, out.getvalue()


def last_line(text):
    return json.loads(text.strip().splitlines()[-1])


def evidence(text):
    word, _, doc = text.strip().splitlines()[-2].partition(" ")
    assert word == "evidence"
    return json.loads(doc)


@pytest.fixture(scope="module")
def sound_run(checkout):
    code, text = drive(checkout, trace=0)
    assert code == 0, text
    return text


def test_program_agrees_with_the_reference_in_float32(sound_run):
    line = last_line(sound_run)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 4  # 4 chunks of one machine
    assert set(line["metrics"]) == {"build.models_per_h_per_chip", "setup_s"}
    checks = evidence(sound_run)["checks"]
    assert set(checks) == set(LIMITS)
    for name, limit in LIMITS.items():
        assert checks[name]["value"] <= limit
        assert f"check {name}: value=" in sound_run
    # the module's parameters are compared as a layer of their own
    assert "horizons_build: 4 chunks of 1 machines" in sound_run
    assert "the row after next trained at weight 0.3" in sound_run


def test_one_machine_a_chunk_and_the_last_is_never_counted(sound_run, checkout):
    window = evidence(sound_run)["window"]
    assert window["models"] == 2 and len(window["completions"]) == 2
    assert os.listdir(os.path.join(checkout, bench_run.SCRATCH_DIR)) == []


def test_a_completion_is_the_hand_over_and_not_the_write(sound_run):
    """The window counts ``gordo_build_pipeline_chunks_total{pipelined}``, which
    the drive raises as it hands a chunk to the writer; the count of packs
    written, which the other kinds watch, is not read."""
    assert "chunk 1 handed over" in sound_run and "chunk 2 handed over" in sound_run
    assert "written" not in "".join(
        line for line in sound_run.splitlines() if " into the window" in line)
    assert not hasattr(kind, "_completed")
    from gordo_tpu import telemetry
    before = kind._handed()
    telemetry.REGISTRY.get(kind.HANDOFFS_SERIES).inc(1.0, kind.HANDOFFS_LABEL)
    assert kind._handed() == before + 1


def test_traced_run_reports_what_the_cpu_can_read(checkout):
    """The counter and histogram readers find their series; the trace
    readers find no device plane on the CPU and leave their metrics out."""
    code, text = drive(checkout, trace=1, seed=SEED + 1)
    assert code == 0, text
    metrics = last_line(text)["metrics"]
    assert metrics["glm.program_s_per_model"]["value"] > 0
    assert metrics["glm.write_s_per_model"]["value"] > 0
    assert metrics["glm.compile_backend_s"]["value"] > 0
    assert metrics["glm.expert_load_max_over_mean"]["value"] >= 1.0
    for name in ("glm.mla_s_per_step", "glm.mla_attn_roofline", "glm.mtp_s_per_step"):
        assert name not in metrics
        assert f"metric {name}: nothing to read, left out" in text


def test_the_distances_name_the_modules_parameters(checkout):
    config = Manifest(checkout).config("glm-flash-tiny")
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])
    seed = kind.model_seed(SEED)
    ref = kind.reference_of(config, rows, seed, folds=False)
    d = reference.distances(ref["params"], ref["params"], seed, ref["shape"])
    assert "mtp.weh" in d["names"] and "l0.dense_wg" in d["names"] and "l2.moe_wg" in d["names"]
    assert "in_proj" in d["names"] and max(d["apart"]) == 0.0
    assert all(m > 0 for m in d["moved_ours"])  # one epoch moves every parameter
    messages = []
    ok, _ = kind.judge(kind.middle([kind.compare(ref, ref, messages.append)]),
                       LIMITS, lambda _: None)
    assert ok and any("update_norm_gap" in m for m in messages)


def test_every_planted_fault_comes_out_not_correct_through_judge(checkout, capsys):
    """``benchmark.horizons_control`` at the tiny size: float8 operands, half
    of every minibatch left out, a matrix left at its start, the rotation
    left out, lambda 0; each through the harness's ``judge`` against the
    cell's limits, none of them correct."""
    from benchmark import horizons_control

    code = horizons_control.main(
        ["--workload", CELL, "--seeds", str(SEED)], require_chip=False, root=checkout)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert code == 0
    by_fault = {row["fault"]: row for row in lines if "fault" in row}
    assert set(by_fault) == set(horizons_control.FAULTS)
    for fault, row in by_fault.items():
        assert row["correct"] is False, fault
        assert "update_norm_gap" in row["failed_limits"], fault
    assert by_fault["frozen_leaf"]["numbers"]["update_norm_gap"] == pytest.approx(1.0)
    # lambda 0: the module's matrices are where they started, and the trained
    # loss lacks its second term
    assert by_fault["no_mtp"]["numbers"]["update_norm_gap"] == pytest.approx(1.0)
    assert "loss_first_gap" in by_fault["no_mtp"]["failed_limits"]
    assert by_fault["half_batch"]["numbers"]["update_norm_gap"] > 0.3
    assert "threshold_gap" in by_fault["float8"]["failed_limits"]
    assert "threshold_gap" in by_fault["no_rotation"]["failed_limits"]
    assert lines[-1]["passed_as_correct"] == []
