"""``benchmark.run`` end to end on the CPU for the ``sequence_build`` kind at
the tiny preset (hidden 64, 2 heads of 16, 8 experts of which 2 held,
sequences of 32 rows, KDA chunks of 8), with the look for a chip lifted only
here.  On the CPU ``compute_dtype: auto`` is float32, so the program has to
agree with the float32 reference closely, and a bfloat16 model has to fail
the same limits.
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
from benchmark.kinds import sequence_build as kind  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import kimi_linear as reference  # noqa: E402

SEED = 3000000043  # more than 32 signed bits hold
CELL = "kimi-linear-tiny.build-two"
# float32 against float32, chunked against step by step (measured here:
# loss 3e-6, update gap 1e-5, thresholds 2e-6); a bfloat16 model reads an
# update gap of 0.036, a fit on half of every minibatch 0.34 (its norms
# alone 0.10), a matrix left at its start 1
LIMITS = {"loss_first_gap": 1e-5, "loss_last_gap": 1e-5,
          "update_norm_gap": 3e-3, "threshold_gap": 1e-4, "nonfinite": 0}
TINY = dict(hidden_size=64, num_heads=2, kda_head_dim=16, kda_gate_rank=16,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            kda_chunk=8, num_layers=2, full_attn_every=2)


def tiny_config():
    config = Manifest(ROOT).config("kimi-linear-plant")
    config["name"] = "kimi-linear-tiny"
    config["model"].update(context=32, stride=16, batch_size=4, **TINY)
    config.update(hidden_size=64, num_attention_heads=2, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=128, moe_intermediate_size=32, num_experts=8,
                  num_experts_per_token=2)
    config["linear_attn_config"] = {
        "full_attn_layers": [2], "kda_layers": [1], "head_dim": 16, "num_heads": 2,
        "short_conv_kernel_size": 4}
    config["depth"]["layers_here"] = [1, 2]
    config["experts"]["held_here"] = 2
    config["dataset"].update(
        n_tags=5, train_end_date="2017-01-02T12:00:00+00:00", rows=217)
    config["deployment"].update(max_bucket_size=1, project_machines=4)
    config["check"] = {"machines": 1, "fold_machines": 1, "limits": LIMITS}
    return config


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-sequence")
    base = root / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (base / sub).mkdir(parents=True)
    config = tiny_config()
    (base / "configs" / "kimi-linear-tiny.json").write_text(json.dumps(config))
    (base / "traffic" / "build-two.json").write_text(json.dumps({
        "kind": "sequence_build", "trace_seconds": 0.2}))
    per_layer = ["seq.program_s_per_model", "seq.write_s_per_model",
                 "seq.expert_load_max_over_mean", "seq.kda_s_per_step",
                 "seq.kda_scan_roofline"]
    for name in ["build.models_per_h_per_chip", "setup_s", *per_layer]:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics", name + ".json"),
                    base / "metrics" / (name + ".json"))
    real = {m["name"]: m for m in Manifest(ROOT).doc["per_layer"]}
    manifest = {
        "command": ["python3", "-m", "benchmark.run"], "paths": ["extra"],
        "run_seconds": 2,
        "configs": [{"name": "kimi-linear-tiny", "source": config["source"],
                     "file": "extra/configs/kimi-linear-tiny.json",
                     "reduced": ["depth"], "why": "CPU test size"}],
        "workloads": [{"name": CELL, "config": "kimi-linear-tiny",
                       "traffic": "build-two", "chips": 1, "why": "CPU test"}],
        "end_to_end": [
            {"name": "build.models_per_h_per_chip", "unit": "models/h",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [{**real[name], "workloads": [CELL]} for name in per_layer],
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


def test_one_machine_a_chunk_and_the_last_is_never_counted(sound_run):
    window = evidence(sound_run)["window"]
    assert window["models"] == 2 and len(window["completions"]) == 2
    assert "4 chunks of 1 machines" in sound_run


def test_nothing_leaks_out_of_the_scratch_directory(sound_run, checkout):
    assert os.listdir(os.path.join(checkout, bench_run.SCRATCH_DIR)) == []


def test_traced_run_reports_what_the_cpu_can_read(checkout):
    """The counter and histogram readers find their series; the two trace
    readers find no device plane on the CPU and leave their metrics out."""
    code, text = drive(checkout, trace=1, seed=SEED + 1)
    assert code == 0, text
    metrics = last_line(text)["metrics"]
    assert metrics["seq.program_s_per_model"]["value"] > 0
    assert metrics["seq.write_s_per_model"]["value"] > 0
    assert metrics["seq.expert_load_max_over_mean"]["value"] >= 1.0
    assert "seq.kda_s_per_step" not in metrics
    assert "seq.kda_scan_roofline" not in metrics
    assert "metric seq.kda_s_per_step: nothing to read, left out" in text


def test_a_bfloat16_model_fails_the_same_limits(checkout):
    """The control at a size a test can hold: the reference put in the
    program's place with matmul operands rounded to bfloat16."""
    config = Manifest(checkout).config("kimi-linear-tiny")
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])
    seed = kind.model_seed(SEED)
    ref = kind.reference_of(config, rows, seed, folds=True)
    low = kind.reference_of(config, rows, seed, folds=True, quantize=reference.bfloat16)
    ok, table = kind.judge(kind.middle([kind.compare(low, ref)]), LIMITS, lambda _: None)
    assert not ok and not table["update_norm_gap"]["ok"]
    ok, _ = kind.judge(kind.middle([kind.compare(ref, ref)]), LIMITS, lambda _: None)
    assert ok


def test_every_planted_fault_comes_out_not_correct_through_judge(checkout, capsys):
    """``benchmark.sequence_control`` at the tiny size: float8 operands, half
    of every minibatch left out, a matrix left at its start; each through the
    harness's ``judge`` against the cell's limits, none of them correct.  The
    norms of the changes alone would pass the half batch (they differ by a
    tenth: Adam moves a parameter by the learning rate a step whatever the
    gradient); the distance between the two changes does not."""
    from benchmark import sequence_control

    code = sequence_control.main(
        ["--workload", CELL, "--seeds", str(SEED)], require_chip=False, root=checkout)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert code == 0
    by_fault = {row["fault"]: row for row in lines if "fault" in row}
    assert set(by_fault) == set(sequence_control.FAULTS)
    for fault, row in by_fault.items():
        assert row["correct"] is False, fault
        assert "update_norm_gap" in row["failed_limits"], fault
    assert by_fault["frozen_leaf"]["numbers"]["update_norm_gap"] == pytest.approx(1.0)
    assert by_fault["half_batch"]["numbers"]["update_norm_gap"] > 0.3
    assert "threshold_gap" in by_fault["float8"]["failed_limits"]
    assert lines[-1]["passed_as_correct"] == []
