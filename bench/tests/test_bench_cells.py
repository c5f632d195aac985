"""CPU rehearsal of every cell of BENCHMARK.json, at the configurations'
``reduced()`` sizes: each runs end to end through the harness (without
its look for a chip) and gives a result that the contract's line can
carry. A new configuration, traffic mix and per-layer metric are then
added as files and entries only, and run."""
import json

import pytest

from bench.tests import rehearsal

SPEC = json.loads((rehearsal.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.checkout(tmp_path_factory.mktemp("bench"))


def _end_to_end(cell: str) -> set:
    return {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_prints_a_contract_line(root, cell):
    res = json.loads(json.dumps(rehearsal.run(root, cell)))
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == _end_to_end(cell)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["whisper-base.coldstart",
                                  "whisper-base.publish"])
def test_traced_cpu_run_reports_no_device_metric(root, cell):
    res = rehearsal.run(root, cell, trace=True)
    assert res["correct"] is True
    # counters and the program's own clocks are read; nothing from a
    # device trace, since no chip is in it
    device_read = {m["name"] for m in SPEC["per_layer"]
                   if m["source"] == "device_trace"}
    assert res["metrics"] and not device_read & set(res["metrics"])
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path):
    root = rehearsal.checkout(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "xlstm-350m.json").read_text())
    cfg["name"] = "xlstm-350m-twin"
    (bench / "configs" / "xlstm-350m-twin.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "coldstart_serial.json")
                         .read_text())
    traffic["new_tokens"] = 2
    (bench / "traffic" / "coldstart_two_tokens.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "units_in_window.py").write_text(
        "def read(run):\n    return float(len(run.records))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="xlstm-350m-twin",
                                file="bench/configs/xlstm-350m-twin.json"))
    spec["workloads"].append({
        "name": "xlstm-350m-twin.two_tokens", "config": "xlstm-350m-twin",
        "traffic": "coldstart_two_tokens", "chips": 1, "why": "test cell"})
    spec["end_to_end"][1]["workloads"].append("xlstm-350m-twin.two_tokens")
    spec["per_layer"].append({
        "name": "units_in_window", "unit": "units", "better": "higher",
        "source": "program_counter", "layer": "serve/coldstart",
        "moves": "coldstart_s", "workloads": ["xlstm-350m-twin.two_tokens"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    plain = rehearsal.run(root, "xlstm-350m-twin.two_tokens")
    assert plain["correct"] is True
    assert set(plain["metrics"]) == {"setup_s", "coldstart_s"}
    traced = rehearsal.run(root, "xlstm-350m-twin.two_tokens", trace=True)
    assert traced["metrics"]["units_in_window"]["value"] >= 1
