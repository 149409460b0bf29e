"""``benchmark.run`` end to end on the CPU for the files of
``trinity-mini.build-longcontext`` at the tiny preset of ``afmoe`` (hidden 64,
8 query heads over 2 key/value heads of 16, a window of 12 rows in sequences
of 32, 8 experts of which 2 held and a shared one, five layers): the cases of
``test_backbone_build_cpu.py`` over this configuration, its traffic file, its
reference and its work count, with the look for a chip lifted only here.  On
the CPU ``compute_dtype: auto`` is float32, so the program has to agree with
the float32 reference closely, and every planted fault has to fail the same
limits."""

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import backbone_control, run as bench_run, window_work  # noqa: E402
from benchmark.kinds import backbone_build as kind  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import afmoe as reference  # noqa: E402
from gordo_tpu import compile as compile_plane  # noqa: E402
from gordo_tpu.models.factories import backbone  # noqa: E402

SEED = 3000000061  # more than 32 signed bits hold
CELL = "trinity-mini-tiny.build-two"
# float32 against float32 (measured here: loss 1e-6, update gap 5e-4,
# thresholds 1e-5); the faults read far above (the test below)
LIMITS = {"loss_first_gap": 1e-5, "loss_last_gap": 1e-5,
          "update_norm_gap": 3e-3, "threshold_gap": 1e-4, "nonfinite": 0}
TINY = dict(hidden_size=64, num_heads=8, num_kv_heads=2, head_dim=16, attn_window=12,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            num_layers=5)
PER_LAYER = ["trinity.program_s_per_model", "trinity.write_s_per_model",
             "trinity.write_fsync_s_per_model", "trinity.expert_load_max_over_mean",
             "trinity.compile_backend_s", "trinity.swa_s_per_step", "trinity.gqa_s_per_step",
             "trinity.swa_attn_roofline", "trinity.gqa_attn_roofline",
             "trinity.ragged_dot_roofline", "trinity.unnamed_share"]
FAULTS = backbone_control.COMMON_FAULTS + reference.FORWARD_FAULTS


def tiny_config():
    config = Manifest(ROOT).config("trinity-mini-plant")
    config["name"] = "trinity-mini-tiny"
    config["model"].update(context=32, stride=16, batch_size=2, **TINY)
    config.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                  sliding_window=12, intermediate_size=128, moe_intermediate_size=32,
                  num_experts=8, num_experts_per_tok=2)
    config["experts"]["held_here"] = 2
    config["dataset"].update(
        n_tags=5, train_end_date="2017-01-02T12:00:00+00:00", rows=217)
    config["deployment"].update(max_bucket_size=1, project_machines=4)
    config["check"].update(machines=1, fold_machines=1, limits=LIMITS)
    return config


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-longcontext")
    base = root / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (base / sub).mkdir(parents=True)
    config = tiny_config()
    (base / "configs" / "trinity-mini-tiny.json").write_text(json.dumps(config))
    traffic = Manifest(ROOT).traffic("build-longcontext")
    (base / "traffic" / "build-two.json").write_text(json.dumps({
        "kind": traffic["kind"], "completion": traffic["completion"],
        "trace_seconds": 0.2}))
    for name in ["build.models_per_h_per_chip", "setup_s", *PER_LAYER]:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics", name + ".json"),
                    base / "metrics" / (name + ".json"))
    # the entries as BENCHMARK.json would hold them (benchmark/pending says
    # why it cannot yet)
    with open(os.path.join(ROOT, "benchmark", "pending", "trinity.per_layer.json")) as fh:
        real = {m["name"]: m for m in json.load(fh)["per_layer"]}
    manifest = {
        "command": ["python3", "-m", "benchmark.run"], "paths": ["extra"],
        "run_seconds": 2,
        "configs": [{"name": "trinity-mini-tiny", "source": config["source"],
                     "file": "extra/configs/trinity-mini-tiny.json",
                     "reduced": ["depth"], "why": "CPU test size"}],
        "workloads": [{"name": CELL, "config": "trinity-mini-tiny",
                       "traffic": "build-two", "chips": 1, "why": "CPU test"}],
        "end_to_end": [
            {"name": "build.models_per_h_per_chip", "unit": "models/h",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [{**real[name], "workloads": [CELL]} for name in PER_LAYER],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    # sequences of four blocks: two leading ones and two trips of the window's loop
    patch = pytest.MonkeyPatch()
    patch.setattr(backbone, "MLA_BLOCK", 8)
    patch.setattr(reference, "QUERY_ROWS", 16)
    compile_plane.REGISTRY.clear()
    yield str(root)
    patch.undo()
    compile_plane.REGISTRY.clear()


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
    assert "backbone_build: 4 chunks of 1 machines" in sound_run
    assert "sequences of 32 at stride 16, 2 a step" in sound_run
    assert "a completion is gordo_build_pipeline_chunks_total['pipelined']" in sound_run
    window = evidence(sound_run)["window"]
    assert window["models"] == 2 and len(window["completions"]) == 2


def test_the_cells_files_name_its_reference_and_its_work_count(checkout):
    config = Manifest(checkout).config("trinity-mini-tiny")
    assert kind.reference_module(config) is reference
    assert kind._module("", config["check"]["work"]) is window_work
    work = window_work.chunk_work(config, 1)
    assert work["steps_per_model"] > 0
    assert set(work["per_step"]) >= {"backbone.swa.attn", "backbone.gqa.attn"}
    real = Manifest(ROOT).config("trinity-mini-plant")
    assert real["check"]["reference"] == "afmoe" and real["check"]["work"] == "window_work"
    assert real["check"]["machines"] == 9 and real["check"]["fold_machines"] == 1
    assert real["deployment"]["project_machines"] >= 11


def test_traced_run_reports_what_the_cpu_can_read(checkout):
    """The counter and histogram readers find their series; the trace
    readers find no device plane on the CPU and leave their metrics out."""
    code, text = drive(checkout, trace=1, seed=SEED + 1)
    assert code == 0, text
    metrics = last_line(text)["metrics"]
    assert metrics["trinity.program_s_per_model"]["value"] > 0
    assert metrics["trinity.write_s_per_model"]["value"] > 0
    assert metrics["trinity.write_fsync_s_per_model"]["value"] >= 0
    assert metrics["trinity.compile_backend_s"]["value"] > 0
    assert metrics["trinity.expert_load_max_over_mean"]["value"] >= 1.0
    for name in PER_LAYER[5:]:
        assert name not in metrics
        assert f"metric {name}: nothing to read, left out" in text


def test_the_distances_name_every_layers_parameters(checkout):
    config = Manifest(checkout).config("trinity-mini-tiny")
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])
    seed = kind.model_seed(SEED)
    ref = kind.reference_of(config, rows, seed, folds=False)
    d = reference.distances(ref["params"], ref["params"], seed, ref["shape"])
    for name in ("l0.swa_wq", "l0.swa_wz", "l0.swa_post_norm", "l0.dense_wg",
                 "l0.dense_post_norm", "l2.gqa_wk", "l2.gqa_q_norm", "l2.gqa_post_norm",
                 "l1.moe_shared_wg", "l4.moe_router", "l4.moe_post_norm", "in_proj"):
        assert name in d["names"], name
    assert max(d["apart"]) == 0.0
    # one epoch moves every parameter but those of an expert layer none of
    # whose held experts a position selected (the absent experts' terms are
    # left out, so the router then has no gradient either)
    routed = ("moe_router", "moe_wg", "moe_wu", "moe_wd")
    assert all(m > 0 for n, m in zip(d["names"], d["moved_ours"])
               if not n.endswith(routed))
    ok, _ = kind.judge(kind.middle([kind.compare(ref, ref)]), LIMITS, lambda _: None)
    assert ok


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
    assert set(by_fault) == set(FAULTS)
    assert set(reference.FORWARD_FAULTS) == {
        "no_window", "rotated_full", "no_rotation", "no_gate", "no_post_norm",
        "no_qk_norm", "wrong_group"}


@pytest.mark.parametrize("fault", FAULTS)
def test_every_planted_fault_comes_out_not_correct_through_judge(faults, fault):
    """float8 operands, half of every minibatch left out, a matrix left at
    its start, and one fault per mechanism (the window, the full layer's lack
    of a position, the rotation, the gate, the output norms, the heads'
    norms, the grouping); each through the harness's ``judge`` against the
    cell's limits, none of them correct."""
    row = faults[1][fault]
    assert row["correct"] is False
    assert "update_norm_gap" in row["failed_limits"]
    # a parameter left at its start reads 1 whatever its size: the frozen
    # matrix, and the norm vectors that no gradient reaches where a norm is
    # left out
    if fault in ("frozen_leaf", "no_qk_norm", "no_post_norm"):
        assert row["numbers"]["update_norm_gap"] >= 1.0 - 1e-6
    elif fault == "half_batch":
        assert row["numbers"]["update_norm_gap"] > 0.3
    else:  # the forward pass itself is another: the thresholds see it too
        assert "threshold_gap" in row["failed_limits"]
