"""``benchmark.run`` end to end on the CPU at 2 machines x 217 rows, with
the look for a chip lifted only here.

The cell it runs is added the way a later PR would add one: a new
configuration file, a new traffic mix over the ``fleet_build`` kind and a
new per-layer metric over an existing reader kind, all as new files in a
temporary directory plus entries in a manifest of its own — no file of
``benchmark/`` is edited.  On the CPU ``compute_dtype: auto`` is float32,
so the program has to agree with the float32 reference closely, and a
bfloat16 model has to fail the same limits.
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
from benchmark.kinds import fleet_build as kind  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import lstm_ae  # noqa: E402

SEED = 3000000019  # more than 32 signed bits hold
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# float32 against float32: the gaps are rounding (measured here: 0, 1e-7,
# 8e-7); a bfloat16 model moves the update norms by 1.6-4 % (measured)
LIMITS = {"loss_first_gap": 1e-5, "loss_last_gap": 1e-5,
          "update_norm_gap": 1e-3, "threshold_gap": 1e-4, "nonfinite": 0}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A directory with a manifest of its own and one new file of each kind."""
    root = tmp_path_factory.mktemp("bench-checkout")
    base = root / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (base / sub).mkdir(parents=True)
    config = Manifest(ROOT).config("lstm-hourglass-plant")
    config["name"] = "lstm-hourglass-tiny"
    config["model"].update(epochs=2, batch_size=32)
    config["dataset"].update(
        n_tags=6, train_end_date="2017-01-02T12:00:00+00:00", rows=217)
    config["layer_units"] = [5, 4, 3, 3, 4, 5]
    config["deployment"].update(max_bucket_size=2, project_machines=8)
    config["check"] = {"machines": 1, "fold_machines": 1, "limits": LIMITS}
    (base / "configs" / "lstm-hourglass-tiny.json").write_text(json.dumps(config))
    (base / "traffic" / "build-two.json").write_text(json.dumps({
        "kind": "fleet_build", "trace_seconds": 0.2}))
    (base / "metrics" / "build.assemble_s_per_model.json").write_text(json.dumps({
        "reader": "histogram_sum_delta",
        "series": "gordo_build_pipeline_stage_seconds",
        "label_values": [["assemble"]]}))
    for name in ("build.models_per_h_per_chip", "setup_s", "build.load_s_per_model"):
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics", name + ".json"),
                    base / "metrics" / (name + ".json"))
    cell = "lstm-hourglass-tiny.build-two"
    manifest = {
        "command": ["python3", "-m", "benchmark.run"], "paths": ["extra"],
        "run_seconds": 2,
        "configs": [{"name": "lstm-hourglass-tiny", "source": config["source"],
                     "file": "extra/configs/lstm-hourglass-tiny.json",
                     "reduced": ["dataset"], "why": "CPU test size"}],
        "workloads": [{"name": cell, "config": "lstm-hourglass-tiny",
                       "traffic": "build-two", "chips": 1, "why": "CPU test"}],
        "end_to_end": [
            {"name": "build.models_per_h_per_chip", "unit": "models/h",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "build.load_s_per_model", "unit": "s", "better": "lower",
             "source": "program_span", "layer": "ingest load",
             "moves": "build.models_per_h_per_chip"},
            {"name": "build.assemble_s_per_model", "unit": "s",
             "better": "lower", "source": "program_span",
             "layer": "fetch and assemble", "moves": "build.models_per_h_per_chip",
             "workloads": [cell]}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root), cell


def drive(checkout, trace, seed=SEED):
    root, cell = checkout
    out = io.StringIO()
    with redirect_stdout(out):
        code = bench_run.main(
            ["--workload", cell, "--seed", str(seed), "--seconds", "2",
             "--trace", str(trace)],
            require_chip=False, root=root,
        )
    return code, out.getvalue()


@pytest.fixture(scope="module")
def sound_run(checkout):
    code, text = drive(checkout, trace=0)
    assert code == 0, text
    return text


def last_line(text):
    return json.loads(text.strip().splitlines()[-1])


def evidence(text):
    """The line before the last: ``evidence {...}``."""
    word, _, doc = text.strip().splitlines()[-2].partition(" ")
    assert word == "evidence"
    return json.loads(doc)


def test_last_line_has_exactly_the_contracts_keys(sound_run):
    line = last_line(sound_run)
    assert set(line) == CONTRACT_KEYS
    assert set(line["metrics"]) == {"build.models_per_h_per_chip", "setup_s"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["attempted"] == 8 and line["failed"] == 0  # 4 chunks of 2


def test_the_last_chunk_is_never_counted(sound_run):
    # warm-up, two that count, the last: the window holds at most two chunks
    window = evidence(sound_run)["window"]
    assert window["models"] == 4 and len(window["completions"]) == 2
    assert "the project is too small for this window" in sound_run


def test_program_agrees_with_the_reference_in_float32(sound_run):
    assert last_line(sound_run)["correct"] is True
    checks = evidence(sound_run)["checks"]
    assert set(checks) == set(LIMITS)
    for name, limit in LIMITS.items():
        assert checks[name]["limit"] == limit
        assert checks[name]["value"] <= limit
    # every number compared is printed beside its limit
    for name in LIMITS:
        assert f"check {name}: value=" in sound_run


def test_nothing_leaks_out_of_the_scratch_directory(sound_run, checkout):
    scratch = os.path.join(checkout[0], bench_run.SCRATCH_DIR)
    assert os.listdir(scratch) == []


def test_traced_run_reports_the_new_per_layer_metric(checkout):
    code, text = drive(checkout, trace=1, seed=SEED + 1)
    assert code == 0, text
    line = last_line(text)
    # no device plane on the CPU, so no busy_s and no breakdown
    assert set(line) == CONTRACT_KEYS
    assert line["metrics"]["build.assemble_s_per_model"]["value"] > 0
    assert "setup_s" not in line["metrics"]
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_a_run_without_a_chip_prints_no_result(checkout, capsys):
    root, cell = checkout
    code = bench_run.main(
        ["--workload", cell, "--seed", "5", "--seconds", "2", "--trace", "0"],
        root=root)
    captured = capsys.readouterr()
    assert code == bench_run.EXIT_NO_CHIP
    assert captured.out.strip() == "" and "not a TPU" in captured.err


def test_a_bfloat16_model_fails_the_same_limits(checkout):
    """The control, at a size a test can hold: the reference put in the
    program's place with matmul operands rounded to bfloat16."""
    root, cell = checkout
    manifest = Manifest(root)
    config = manifest.config("lstm-hourglass-tiny")
    name = kind.machine_names(SEED, 1)[0]
    rows = kind.reference_rows(config, name)
    seed = kind.model_seed(SEED)
    ref = kind.reference_of(config, rows, seed, folds=True)
    low = kind.reference_of(config, rows, seed, folds=True, quantize=lstm_ae.bfloat16)
    ok, table = kind.judge(kind.middle([kind.compare(low, ref)]), LIMITS, lambda _: None)
    assert not ok and not table["update_norm_gap"]["ok"]
    # the thresholds hardly move under a lower precision: their limit is
    # held against a wrong fold or a wrong smoothing instead (next tests)
    assert "threshold_gap" in table
    ok, _ = kind.judge(kind.middle([kind.compare(ref, ref)]), LIMITS, lambda _: None)
    assert ok


def test_the_middle_machine_is_judged_and_the_worst_is_shown():
    """One fit in fifty leaves its plateau where rounding decides: such a
    machine among the sampled ones is printed and does not decide; a fault
    in every machine does, and a non-finite weight in any one does."""
    sound = {"loss_last_gap": 1e-6, "update_norm_gap": 0.01, "nonfinite": 0.0}
    chaotic = {"loss_last_gap": 0.4, "update_norm_gap": 0.7, "nonfinite": 0.0}
    limits = {"loss_last_gap": 1e-5, "update_norm_gap": 0.1, "nonfinite": 0}
    ok, table = kind.judge(kind.middle([sound] * 4 + [chaotic]), limits, lambda _: None)
    assert ok and table["loss_last_gap"]["worst"] == 0.4
    assert table["loss_last_gap"]["value"] == 1e-6 and table["loss_last_gap"]["machines"] == 5
    ok, _ = kind.judge(kind.middle([chaotic] * 5), limits, lambda _: None)
    assert not ok
    ok, table = kind.judge(
        kind.middle([sound] * 4 + [{**sound, "nonfinite": 1.0}]), limits, lambda _: None)
    assert not ok and table["nonfinite"]["value"] == 1.0
    # thresholds are compared for the first machines only: their middle
    some = [{**sound, "threshold_gap": g} for g in (1e-4, 2e-4, 9e-1)] + [sound] * 2
    assert kind.middle(some)["threshold_gap"] == {
        "value": 2e-4, "worst": 9e-1, "machines": 3}


def test_a_stack_of_machines_fits_as_each_alone(checkout):
    """The reference fits the sampled machines in one stack: every machine
    of it comes out as it does alone, thresholds included."""
    import numpy as np

    config = Manifest(checkout[0]).config("lstm-hourglass-tiny")
    names = kind.machine_names(SEED, 3)
    stack = np.stack([kind.reference_rows(config, name) for name in names])
    seed = kind.model_seed(SEED)
    together = kind.reference_of(config, stack, seed, folds=2)
    assert together["thresholds"].shape[0] == 2
    for i in range(3):
        alone = kind.reference_of(config, stack[i], seed, folds=True)
        numbers = kind.compare(kind.machine_of(together, i), alone)
        assert ("threshold_gap" in numbers) == (i < 2)
        assert all(value <= 1e-6 for value in numbers.values()), numbers


def test_a_broken_timed_path_comes_out_not_correct(checkout, monkeypatch):
    """The rest of a run with the optimizer step returning its state
    unchanged underneath: ``correct`` has to be false."""
    import optax
    from gordo_tpu.train import fit as fit_mod

    monkeypatch.setattr(
        fit_mod.optax, "apply_updates", lambda params, updates: params)
    assert optax.apply_updates is fit_mod.optax.apply_updates
    import jax
    jax.clear_caches()
    try:
        code, text = drive(checkout, trace=0, seed=SEED + 2)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert code == 0, text
    assert last_line(text)["correct"] is False
    assert evidence(text)["checks"]["update_norm_gap"]["ok"] is False


def test_a_wrong_threshold_comes_out_not_correct(checkout, monkeypatch):
    """The rest of a run with the program smoothing the held-out errors
    over another window than the detector states: the fits agree, the
    thresholds do not, and ``correct`` has to be false."""
    import jax
    from gordo_tpu.parallel import anomaly

    monkeypatch.setattr(anomaly, "SMOOTHING_WINDOW", 2)
    jax.clear_caches()
    try:
        code, text = drive(checkout, trace=0, seed=SEED + 3)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert code == 0, text
    checks = evidence(text)["checks"]
    assert last_line(text)["correct"] is False
    assert checks["threshold_gap"]["ok"] is False and checks["update_norm_gap"]["ok"]
