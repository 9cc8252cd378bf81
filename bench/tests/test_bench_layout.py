"""Every cell, configuration, traffic mix, limit and metric of
BENCHMARK.json loads by name, and the file keeps to its contract."""

import importlib
import json
import re
from pathlib import Path

import pytest

from bench import counts, run

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_entries_have_just_their_keys():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    cells = {w["name"] for w in SPEC["workloads"]}
    for section, allowed in keys.items():
        for e in SPEC[section]:
            extra = set(e) - allowed
            assert extra <= {"workloads"} and set(e) >= allowed - {"why"}
            assert set(e.get("workloads", cells)) <= cells
            for text in (e.get("why"), e.get("layer"), e.get("source")):
                assert text is None or (0 < len(text) <= 200
                                        and "\n" not in text)
    assert len({(w["config"], w["traffic"])
                for w in SPEC["workloads"]}) == len(cells)


def test_names_units_and_bounds():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_loads_by_name(cell):
    c = run.load_cell(cell)
    assert c["chips"] in (1, 4)
    assert c["config_data"]["name"] == c["config"]
    importlib.import_module(
        f"bench.references.{c['config_data']['reference']}")
    assert c["per_layer"] and "setup_s" in c["end_to_end"]
    numbers = {"first_update_gap", "rates_gap", "budget_gap",
               "global_change_gap", "clients_change_gap"}
    assert {"first_update_gap", "budget_gap", "global_change_gap"} \
        <= set(c["limits"]) <= numbers


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_loads_by_name(metric):
    reader = importlib.import_module(f"bench.metrics.{metric}")
    assert callable(reader.read)


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_file_is_under_paths(config):
    entry = {c["name"]: c for c in SPEC["configs"]}[config]
    assert entry["file"].startswith("bench/")
    data = json.loads((ROOT / entry["file"]).read_text())
    assert data["name"] == config and data["reduced"] == entry["reduced"]


def test_unknown_device_kind_is_an_error():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
