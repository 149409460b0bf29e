"""``BENCHMARK.json`` checks itself: the rules a manifest was refused for
(PR 22: a per-layer metric on a cell that does not report what it moves)
and the limits of names, units, lengths and files."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection")

MANIFEST = Manifest(ROOT)
DOC = MANIFEST.doc
CELLS = [c["name"] for c in DOC["workloads"]]
E2E = {m["name"]: m for m in DOC["end_to_end"]}


def cells_of(metric):
    return set(metric.get("workloads") or CELLS)


def test_top_level_keys_are_exactly_the_contracts():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(DOC)) < 64 * 1024
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51


def test_command_and_paths():
    assert 1 <= len(DOC["paths"]) <= 16
    for path in DOC["paths"]:
        assert PATH.match(path) and not path.startswith("/") and ".." not in path
        assert os.path.isdir(os.path.join(ROOT, path))
    assert 1 <= len(DOC["command"]) <= 32
    for word in DOC["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda e: e["name"])
def test_configuration_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert 1 <= len(entry["source"]) <= 200 and "\n" not in entry["source"]
    assert 1 <= len(entry["why"]) <= 200
    assert any(entry["file"].startswith(p + "/") for p in DOC["paths"])
    assert os.path.isfile(os.path.join(ROOT, entry["file"]))
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank")) and not any(
            w in key for w in WIDTH_WORDS), f"reduced may not name a width: {key}"
    assert any(c["config"] == entry["name"] for c in DOC["workloads"]), \
        "every configuration has a cell"
    body = MANIFEST.config(entry["name"])
    assert body["source"] == entry["source"]
    for key in entry["reduced"]:
        assert key in body["reduced"], "the file says why each key was reduced"


def test_configuration_files_and_names_are_distinct():
    assert len({c["file"] for c in DOC["configs"]}) == len(DOC["configs"])
    assert len({c["name"] for c in DOC["configs"]}) == len(DOC["configs"])
    assert 1 <= len(DOC["configs"]) <= 24


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda c: c["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in DOC["configs"]}
    traffic = MANIFEST.traffic(cell["traffic"])
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "kinds", traffic["kind"] + ".py"))
    # the cell reports set-up, another end-to-end metric and a per-layer one
    mine = [m["name"] for m in DOC["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in mine and len(mine) >= 2
    assert any(cell["name"] in cells_of(m) for m in DOC["per_layer"])


def test_cells_are_distinct_and_few_take_four_chips():
    assert 1 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    pairs = [(c["config"], c["traffic"]) for c in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for c in DOC["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", DOC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert cells_of(metric) <= set(CELLS)
    spec = MANIFEST.metric_spec(metric["name"])
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers", spec["reader"] + ".py"))


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    assert "setup_s" in E2E and cells_of(E2E["setup_s"]) == set(CELLS)
    assert 1 <= len(DOC["end_to_end"]) <= 16


@pytest.mark.parametrize("metric", DOC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {
        "name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    assert metric["moves"] in E2E
    # PR 22's refusal: reported only where the metric it moves is reported
    assert cells_of(metric) <= cells_of(E2E[metric["moves"]]), (
        f"{metric['name']} is reported on cells where {metric['moves']} is not")
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    spec = MANIFEST.metric_spec(metric["name"])
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers", spec["reader"] + ".py"))


def test_metric_names_are_distinct():
    names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(set(names)) == len(names) and 1 <= len(DOC["per_layer"]) <= 128


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda e: e["name"])
def test_every_compared_number_has_a_limit(entry):
    limits = MANIFEST.config(entry["name"])["check"]["limits"]
    assert set(limits) == {"loss_first_gap", "loss_last_gap", "update_norm_gap",
                           "threshold_gap", "nonfinite"}
    assert limits["nonfinite"] == 0
    config = MANIFEST.config(entry["name"])
    assert 1 <= config["check"]["fold_machines"] <= config["check"]["machines"]
    # the middle machine is judged: an odd sample, large enough that the
    # one fit in fifty that leaves its plateau cannot be the middle one
    assert config["check"]["machines"] % 2 == 1 and config["check"]["machines"] >= 9
    assert config["check"]["fold_machines"] % 2 == 1
    # a measured period is not a deployment's: the project's size is
    assert "chunk_seconds" not in config["deployment"]
    assert config["deployment"]["project_machines"] % config["deployment"]["max_bucket_size"] == 0


def test_files_under_paths_use_only_the_allowed_characters():
    for base in DOC["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, base)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert PATH.match(rel), rel
