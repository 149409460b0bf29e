"""``benchmark.run`` end to end on the CPU for the ``backbone_build`` kind at
the tiny preset of ``lfm2_moe`` (hidden 64, 8 query heads over 2 key/value
heads of 8, 8 experts of which 2 held and no shared one, five layers: conv +
dense, attention + experts, three times conv + experts; sequences of 32
rows), with the look for a chip lifted only here.  On the CPU
``compute_dtype: auto`` is float32, so the program has to agree with the
float32 reference closely, and every planted fault has to fail the same
limits.  The kind names no reference and no work count: both come from the
configuration's file, the completion series from the traffic's.
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

from benchmark import backbone_control, hybrid_work, run as bench_run  # noqa: E402
from benchmark.kinds import backbone_build as kind  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import lfm2_moe as reference  # noqa: E402

SEED = 3000000061  # more than 32 signed bits hold
CELL = "lfm2-moe-tiny.build-two"
# float32 against float32 (measured here: loss 1e-6, update gap 3e-4,
# thresholds 1e-5); the faults read far above (the test below)
LIMITS = {"loss_first_gap": 1e-5, "loss_last_gap": 1e-5,
          "update_norm_gap": 3e-3, "threshold_gap": 1e-4, "nonfinite": 0}
TINY = dict(hidden_size=64, num_heads=8, num_kv_heads=2,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            num_layers=5)
PER_LAYER = ["lfm.program_s_per_model", "lfm.write_s_per_model",
             "lfm.expert_load_max_over_mean", "lfm.compile_backend_s",
             "lfm.conv_s_per_step", "lfm.conv_mixer_roofline", "lfm.gqa_attn_roofline"]


def tiny_config():
    config = Manifest(ROOT).config("lfm2-moe-plant")
    config["name"] = "lfm2-moe-tiny"
    config["model"].update(context=32, stride=16, batch_size=4, **TINY)
    config.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
                  intermediate_size=128, moe_intermediate_size=32, num_experts=8,
                  num_experts_per_tok=2)
    config["experts"]["held_here"] = 2
    config["dataset"].update(
        n_tags=5, train_end_date="2017-01-02T12:00:00+00:00", rows=217)
    config["deployment"].update(max_bucket_size=1, project_machines=4)
    config["check"].update(machines=1, fold_machines=1, limits=LIMITS)
    return config


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-backbone")
    base = root / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (base / sub).mkdir(parents=True)
    config = tiny_config()
    (base / "configs" / "lfm2-moe-tiny.json").write_text(json.dumps(config))
    traffic = Manifest(ROOT).traffic("build-fortnight")
    (base / "traffic" / "build-two.json").write_text(json.dumps({
        "kind": traffic["kind"], "completion": traffic["completion"],
        "trace_seconds": 0.2}))
    for name in ["build.models_per_h_per_chip", "setup_s", *PER_LAYER]:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics", name + ".json"),
                    base / "metrics" / (name + ".json"))
    real = {m["name"]: m for m in Manifest(ROOT).doc["per_layer"]}
    manifest = {
        "command": ["python3", "-m", "benchmark.run"], "paths": ["extra"],
        "run_seconds": 2,
        "configs": [{"name": "lfm2-moe-tiny", "source": config["source"],
                     "file": "extra/configs/lfm2-moe-tiny.json",
                     "reduced": ["depth"], "why": "CPU test size"}],
        "workloads": [{"name": CELL, "config": "lfm2-moe-tiny",
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
    assert "backbone_build: 4 chunks of 1 machines" in sound_run
    assert "a completion is gordo_build_pipeline_chunks_total['pipelined']" in sound_run


def test_one_machine_a_chunk_and_the_last_is_never_counted(sound_run, checkout):
    window = evidence(sound_run)["window"]
    assert window["models"] == 2 and len(window["completions"]) == 2
    assert os.listdir(os.path.join(checkout, bench_run.SCRATCH_DIR)) == []
    assert "chunk 1 handed over" in sound_run and "chunk 2 handed over" in sound_run


def test_the_kind_reads_its_reference_work_and_completion_from_data(checkout):
    """Nothing in the kind's source names a configuration: the reference and
    the work count are the modules the configuration's ``check`` names, the
    completion is the series the traffic names."""
    with open(kind.__file__) as fh:
        source = fh.read().partition('"""\n\nfrom __future__')[2]
    for word in ("lfm2", "glm", "kimi", "hybrid_work", "latent_work", "pipelined"):
        assert word not in source, word
    config = Manifest(checkout).config("lfm2-moe-tiny")
    assert kind.reference_module(config) is reference
    assert kind._module("", config["check"]["work"]) is hybrid_work
    with pytest.raises(ValueError, match="not a module's name"):
        kind._module("reference.", "lfm2_moe.os")
    from gordo_tpu import telemetry
    completion = Manifest(ROOT).traffic("build-fortnight")["completion"]
    before = kind._completed(completion)
    telemetry.REGISTRY.get(completion["series"]).inc(1.0, *completion["labels"])
    assert kind._completed(completion) == before + 1
    assert kind._completed({"series": "gordo_no_such_series", "labels": []}) == 0.0


def test_traced_run_reports_what_the_cpu_can_read(checkout):
    """The counter and histogram readers find their series; the trace
    readers find no device plane on the CPU and leave their metrics out."""
    code, text = drive(checkout, trace=1, seed=SEED + 1)
    assert code == 0, text
    metrics = last_line(text)["metrics"]
    assert metrics["lfm.program_s_per_model"]["value"] > 0
    assert metrics["lfm.write_s_per_model"]["value"] > 0
    assert metrics["lfm.compile_backend_s"]["value"] > 0
    assert metrics["lfm.expert_load_max_over_mean"]["value"] >= 1.0
    for name in ("lfm.conv_s_per_step", "lfm.conv_mixer_roofline", "lfm.gqa_attn_roofline"):
        assert name not in metrics
        assert f"metric {name}: nothing to read, left out" in text


def test_the_distances_name_every_layers_parameters(checkout):
    config = Manifest(checkout).config("lfm2-moe-tiny")
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])
    seed = kind.model_seed(SEED)
    ref = kind.reference_of(config, rows, seed, folds=False)
    d = reference.distances(ref["params"], ref["params"], seed, ref["shape"])
    for name in ("l0.conv_win", "l0.dense_wg", "l1.gqa_wk", "l1.gqa_q_norm", "l1.moe_wg",
                 "l4.conv_taps", "l4.moe_router", "in_proj"):
        assert name in d["names"], name
    assert not [n for n in d["names"] if "shared" in n]
    assert max(d["apart"]) == 0.0
    assert all(m > 0 for m in d["moved_ours"])  # one epoch moves every parameter
    messages = []
    ok, _ = kind.judge(kind.middle([kind.compare(ref, ref, messages.append)]),
                       LIMITS, lambda _: None)
    assert ok and any("update_norm_gap" in m for m in messages)


@pytest.fixture(scope="module")
def faults(checkout):
    """``benchmark.backbone_control`` at the tiny size, every fault once."""
    out = io.StringIO()
    with redirect_stdout(out):
        code = backbone_control.main(
            ["--workload", CELL, "--seeds", str(SEED)], require_chip=False, root=checkout)
    lines = [json.loads(line) for line in out.getvalue().splitlines()
             if line.startswith("{")]
    return code, {row["fault"]: row for row in lines if "fault" in row}, lines[-1]


def test_the_control_plants_the_common_faults_and_the_references_own(faults):
    code, by_fault, summary = faults
    assert code == 0 and summary["passed_as_correct"] == []
    assert set(by_fault) == set(backbone_control.COMMON_FAULTS) | set(reference.FORWARD_FAULTS)
    assert set(reference.FORWARD_FAULTS) == {"no_taps", "no_qk_norm", "no_rotation", "wrong_group"}


@pytest.mark.parametrize("fault", backbone_control.COMMON_FAULTS + reference.FORWARD_FAULTS)
def test_every_planted_fault_comes_out_not_correct_through_judge(faults, fault):
    """float8 operands, half of every minibatch left out, a matrix left at
    its start, and one fault per new mechanism (the taps, the heads' norms,
    the rotation, the grouping); each through the harness's ``judge`` against
    the cell's limits, none of them correct."""
    row = faults[1][fault]
    assert row["correct"] is False
    assert "update_norm_gap" in row["failed_limits"]
    # a parameter left at its start reads 1 whatever its size: the frozen
    # matrix, and the two norm vectors of 8 that no gradient reaches where the
    # heads' norms are left out
    if fault in ("frozen_leaf", "no_qk_norm"):
        assert row["numbers"]["update_norm_gap"] >= 1.0 - 1e-6
    if fault == "frozen_leaf":
        assert row["numbers"]["update_norm_gap"] == pytest.approx(1.0)
    elif fault == "half_batch":
        assert row["numbers"]["update_norm_gap"] > 0.3
    else:  # the forward pass itself is another: the thresholds see it too
        assert "threshold_gap" in row["failed_limits"]
