"""BENCHMARK.json keeps the benchmark's contract, and every name in it
has the file the harness looks for."""
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_top_level_keys():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_command_and_paths_stay_inside_the_benchmark():
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p
    assert len(SPEC["command"]) <= 32
    assert all(_line(w) for w in SPEC["command"])
    script = SPEC["command"][1]
    assert any(script.startswith(p + "/") for p in SPEC["paths"])
    assert (REPO / script).is_file()


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
    assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
    body = json.loads((REPO / c["file"]).read_text())
    assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
    assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and _line(w["why"])
    assert w["config"] in {c["name"] for c in SPEC["configs"]}
    traffic = json.loads((REPO / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    assert (REPO / "bench" / "drivers" / f"{traffic['driver']}.py").is_file()
    reported = [m for m in SPEC["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    assert any(w["name"] in m.get("workloads", []) for m in SPEC["per_layer"])


def test_cells_are_unique_and_few_take_four_chips():
    names = [w["name"] for w in SPEC["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    assert 1 <= len(names) <= 24
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(names) // 2)


@pytest.mark.parametrize("m", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert _line(m["layer"])
    moved = {e["name"]: e for e in SPEC["end_to_end"]}[m["moves"]]
    cells = {w["name"] for w in SPEC["workloads"]}
    for cell in m.get("workloads", []):
        assert cell in cells and cell in moved.get("workloads", [cell])
    assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_names_are_unique():
    for key in ("configs", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[key]]
        assert len(set(names)) == len(names)
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metrics)) == len(metrics)
