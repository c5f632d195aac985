"""The per-layer metrics that read the loader's ``repro.*`` spans: None
without a trace and on a program that opens no such span, the known
value on a synthetic capture, and a number from a real CPU capture of a
traced cell run through the harness."""
import json

import pytest

from bench import harness
from bench.tests import rehearsal
from bench.trace import Trace

SPEC = json.loads((rehearsal.REPO / "BENCHMARK.json").read_text())
READERS = ["fetch_busy_s", "fetch_blocked_s", "decode_starved_s",
           "marshal_s.coldstart", "place_s", "marshal_s.publish"]
MS = 1_000_000


def reader(name):
    return harness.load_module(rehearsal.REPO / "bench" / "metrics"
                               / f"{name}.py")


def synthetic() -> Trace:
    """Two cold starts (0-100 ms, 200-300 ms) and one publish (400-500
    ms). Program spans on two threads, one running past the first cold
    start's end (clipped there); an XLA host event that is not a program
    span."""
    spans = [("bench.window", 0, 100 * MS), ("bench.coldstart", 0, 100 * MS),
             ("bench.window", 200 * MS, 300 * MS),
             ("bench.coldstart", 200 * MS, 300 * MS),
             ("bench.window", 400 * MS, 500 * MS),
             ("bench.publish", 400 * MS, 500 * MS)]
    host = [
        # cold start 1: origin GETs on two pool threads overlap 10-30 ms
        ("repro.fetch.origin", 10 * MS, 30 * MS),
        ("repro.fetch.origin", 20 * MS, 40 * MS),
        ("repro.fetch.l1", 5 * MS, 6 * MS),
        ("repro.stream.put_wait", 40 * MS, 60 * MS),
        ("repro.stream.get_wait", 60 * MS, 62 * MS),
        ("repro.kernel.pack", 62 * MS, 65 * MS),
        ("repro.kernel.split", 70 * MS, 71 * MS),
        ("repro.coldstart.place", 90 * MS, 110 * MS),    # clipped at 100
        ("PjitFunction(_fused_device)", 65 * MS, 70 * MS),
        # cold start 2
        ("repro.fetch.origin", 210 * MS, 220 * MS),
        ("repro.kernel.pack", 230 * MS, 234 * MS),
        ("repro.coldstart.place", 280 * MS, 290 * MS),
        # publish
        ("repro.kernel.pack", 410 * MS, 420 * MS),
        ("repro.kernel.split", 430 * MS, 435 * MS),
    ]
    return Trace({}, spans, host)


EXPECTED = {                # means per unit, in seconds
    "fetch_busy_s": (0.031 + 0.010) / 2,
    "fetch_blocked_s": 0.020 / 2,
    "decode_starved_s": 0.002 / 2,
    "marshal_s.coldstart": (0.004 + 0.004) / 2,
    "place_s": (0.010 + 0.010) / 2,
    "marshal_s.publish": 0.015,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_is_listed_for_the_cells_that_run_its_spans(name):
    (m,) = [m for m in SPEC["per_layer"] if m["name"] == name]
    assert m["source"] == "program_span"
    cells = (["whisper-base.publish"] if name.endswith(".publish")
             else ["xlstm-350m.coldstart", "whisper-base.coldstart"])
    assert m["workloads"] == cells


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_program_spans(name):
    read = reader(name).read
    assert read(harness.RunData(None, [], None)) is None
    bare = synthetic()
    bare.host = [ev for ev in bare.host if not ev[0].startswith("repro.")]
    assert read(harness.RunData(None, [], bare)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_synthetic_capture(name):
    got = reader(name).read(harness.RunData(None, [], synthetic()))
    assert got == pytest.approx(EXPECTED[name], abs=1e-12)


def test_a_wait_never_taken_reads_zero():
    trace = synthetic()
    trace.host = [ev for ev in trace.host
                  if ev[0] != "repro.stream.put_wait"]
    run = harness.RunData(None, [], trace)
    assert reader("fetch_blocked_s").read(run) == 0.0


def test_traced_cpu_cold_start_reads_the_program_spans(tmp_path):
    """A traced rehearsal of the whisper cold start: the reduction keeps
    the program's spans, and each cold-start reader finds them (the CPU
    decodes through numpy and hashlib, so no kernel adapter runs and
    marshalling reads 0)."""
    root = rehearsal.checkout(tmp_path)
    res = rehearsal.run(root, "whisper-base.coldstart", trace=True)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["fetch_busy_s"] > 0 and got["place_s"] > 0
    assert got["fetch_blocked_s"] >= 0 and got["decode_starved_s"] >= 0
    assert got["marshal_s.coldstart"] == 0.0
    assert got["fetch_busy_s"] < got["load_s"]
