"""BENCHMARK.json and the files it names: the contract's characters and
limits, every name found as a file, each per-layer metric reported only in
cells that report the end-to-end metric it moves, and each configuration
file agreeing with itself and with the program's own configuration."""

import json
import re
from pathlib import Path

import pytest

import tinycell
from bench import harness, traffic

REPO = tinycell.REPO
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTHS = ("hidden", "intermediate", "latent", "state", "projection", "head", "expansion",
          "experts_per_tok", "kv_channels", "ffn")


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_sizes():
    assert set(MANIFEST) == KEYS["top"]
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for kind in ("config", "workload", "end_to_end", "per_layer"):
        entries = MANIFEST[kind + "s" if kind in ("config", "workload") else kind]
        for e in entries:
            assert KEYS[kind] <= set(e) <= KEYS[kind] | {"workloads"}, e
    assert 1 <= len(MANIFEST["configs"]) <= 24 and 1 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= MANIFEST["run_seconds"] <= 51
    # a full check: 2 + 14 runs a cell, each run_seconds + 60, 180 s of
    # compiling a cell, 1200 s spare, with all 24 cells
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_text():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in MANIFEST[k]]
    assert len(names) == len(set(names))
    for w in MANIFEST["workloads"]:
        names += [w["config"], w["traffic"]]
        assert _one_line(w["why"]) and w["chips"] in (1, 4)
    for c in MANIFEST["configs"]:
        names += c["reduced"]
        assert _one_line(c["why"]) and _one_line(c["source"]) and len(c["reduced"]) <= 16
        assert not any(w in k for k in c["reduced"] for w in WIDTHS)
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(_one_line(word) for word in MANIFEST["command"])


def test_paths_and_command_stay_inside():
    for p in MANIFEST["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and not p.startswith("/")
        assert ".." not in Path(p).parts and (REPO / p).is_dir()
    for word in MANIFEST["command"][1:]:
        assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


def test_bounds():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def _reports(metric, cell):
    return cell in metric.get("workloads", [w["name"] for w in MANIFEST["workloads"]])


def test_each_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    for w in MANIFEST["workloads"]:
        e2e = [m["name"] for m in MANIFEST["end_to_end"] if _reports(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(_reports(m, w["name"]) for m in MANIFEST["per_layer"])


def test_per_layer_metrics_move_an_end_to_end_metric_their_cells_report():
    layers = {}
    for m in MANIFEST["per_layer"]:
        moved = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
        for cell in (w["name"] for w in MANIFEST["workloads"] if _reports(m, w["name"])):
            assert _reports(moved, cell), (m["name"], cell)
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _one_line(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert (REPO / "bench" / "metrics" / f"{metric['name']}.py").is_file()


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_from_its_files(w):
    cell = harness.load_cell(w["name"])
    assert cell.mix.name == w["traffic"]
    assert set(cell.check["limits"]) == {"logit_gap"}
    assert all(v is not None and v > 0 for v in cell.check["limits"].values())
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2 and cell.per_layer


@pytest.mark.parametrize("path", sorted((REPO / "bench" / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_configuration_files(path):
    """Every configuration file, listed in the manifest or kept for a cell
    still to come, agrees with itself and with the program."""
    config = json.loads(path.read_text())
    harness.check_published(config)  # model block = the published keys it names
    harness.program_config(config)  # = the program's repro.configs entry
    assert sorted(config.get("published", {})) == sorted(config["reduced"])
    assert (REPO / "bench" / "reference" / f"{config['reference']}.py").is_file()
    for c in MANIFEST["configs"]:
        if c["file"] == str(path.relative_to(REPO)):
            assert config["source"] == c["source"] and config["reduced"] == c["reduced"]


@pytest.mark.parametrize("path", sorted((REPO / "bench" / "cells").glob("*.json")),
                         ids=lambda p: p.stem)
def test_cell_files(path):
    check = json.loads(path.read_text())
    assert check["sample_requests"] >= 1 and set(check["limits"]) == {"logit_gap"}
    assert all(v is not None and v > 0 for v in check["limits"].values())


def test_configuration_files_are_not_shared():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    assert {w["config"] for w in MANIFEST["workloads"]} == {c["name"] for c in MANIFEST["configs"]}
    assert len({(w["config"], w["traffic"]) for w in MANIFEST["workloads"]}) == len(
        MANIFEST["workloads"])


def test_traffic_files_load(tmp_path):
    for f in (REPO / "bench" / "traffic").glob("*.json"):
        mix = traffic.load(f)
        a = mix.prompts(2**40 + 1, 3, 1000)
        assert a.shape == (mix.batch, mix.prompt_len) and 0 <= a.min() and a.max() < 1000
        assert (a == mix.prompts(2**40 + 1, 3, 1000)).all()
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"loop": "open", "prompt_ids": "uniform", "batch": 1,
                               "prompt_len": 1, "output_len": 1}))
    with pytest.raises(ValueError):
        traffic.load(bad)
