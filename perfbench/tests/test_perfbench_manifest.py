"""BENCHMARK.json against the benchmark's contract, and every name in it
resolving to its file."""
import json
import re

import pytest

from perfbench import harness

MAN = json.loads((harness.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "perfbench/run.py"]
    assert MAN["paths"] == ["perfbench"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    texts = [entry[k] for k in ("why", "layer") if k in entry]
    if "file" in entry:
        texts.append(entry["source"])
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t


def test_names_unique():
    for group in (MAN["configs"], MAN["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports(cell):
    c = harness.load_cell(cell)
    assert c.chips == 1
    e2e = [m["name"] for m in c.metrics if m["kind"] == "end_to_end"]
    layer = [m["name"] for m in c.metrics if m["kind"] == "per_layer"]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in c.metrics:
        assert harness.reader(m["name"]) is not None, m["name"]
    training = {"ft_loss_gap", "ft_grad_gap", "ft_grad_diff", "ft_change_gap"}
    assert set(c.setup["limits"]) == {"token_gap"} | (
        training if "trainer" in c.traffic else set())


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_its_cells_report(metric):
    moved = [m for m in MAN["end_to_end"] if m["name"] == metric["moves"]]
    assert moved and moved[0]["name"] != "setup_s"
    for cell in metric["workloads"]:
        assert cell in CELLS and reports(moved[0], cell)


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(cfg):
    spec = json.loads((harness.REPO / cfg["file"]).read_text())
    assert spec["name"] == cfg["name"]
    assert sorted(spec["reduced"]) == sorted(cfg["reduced"])
    assert set(spec["published"]) == set(spec["reduced"])
    for k in ("source", "deployment", "assumed", "registry_differences"):
        assert spec[k]
    assert any(w["config"] == cfg["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_config_names_its_architecture(cfg):
    spec = json.loads((harness.REPO / cfg["file"]).read_text())
    arch = harness.architecture(spec["model_type"])
    for name in ("model_config", "Reference", "step_flops", "NORMS"):
        assert hasattr(arch, name), (spec["model_type"], name)
