"""The trace reduction reads known numbers from a small trace recorded on
a v5e (three fused verify+decrypt calls of 2 x 512 KiB chunks and one
jitted reduction, under one ``bench.probe`` host span), the metric
readers stay silent where there is nothing to read, and the harness
refuses to run, and prints nothing, without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, peaks, trace
from bench.harness import RunData

DATA = Path(__file__).resolve().parent / "data" / "probe.xplane.pb"
REPO = Path(__file__).resolve().parents[2]

# read off the recorded trace with nothing but the raw events
FUSED_NS = 32_773_236.0          # three %fused_verify_decrypt.1 events
BUSY_NS = 33_962_568.0           # union of the 31 XLA Ops events
SPAN = (43_775_959.0, 141_995_197.0)   # bench.probe


@pytest.fixture(scope="module")
def recorded():
    import jax
    tr = trace.from_xspace(jax.profiler.ProfileData.from_file(str(DATA)))
    tr.spans.append((trace.WINDOW_SPAN,) + SPAN)
    return tr


def test_reduction_reads_the_recorded_numbers(recorded):
    assert list(recorded.ops) == [0] and len(recorded.ops[0]) == 31
    assert recorded.spans_named("bench.probe") == [SPAN]
    assert recorded.busy_ns() == BUSY_NS
    assert recorded.window_s() == (SPAN[1] - SPAN[0]) / 1e9
    assert recorded.op_ns(["fused_verify_decrypt"]) == FUSED_NS
    assert recorded.op_ns(["no_such_kernel"]) is None
    assert trace.idle_pct(recorded) == pytest.approx(
        100 * (1 - BUSY_NS / (SPAN[1] - SPAN[0])))


def test_breakdown_names_ops_and_idle_gaps(recorded):
    b = recorded.breakdown()
    assert b["device_ops"][0] == ["fused_verify_decrypt", FUSED_NS / 1e9]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(t for _, t in b["device_ops"]) == pytest.approx(BUSY_NS / 1e9)
    idle = (SPAN[1] - SPAN[0] - BUSY_NS) / 1e9
    assert sum(t for _, t in b["idle_gaps"]) == pytest.approx(idle)
    # the host waited on the device's result for most of the idle time
    assert b["idle_gaps"][0][0] == "bench.probe: np.asarray(jax.Array)"


@pytest.mark.parametrize("event, name", [
    ("%fused_verify_decrypt.1 = (s32[8,8]{1,0:T(8,128)}, s32[16]) "
     "custom-call(s32[16] %a)", "fused_verify_decrypt"),
    ("%copy-start = (s32[1,8]{1,0}) copy-start(s32[1,8] %p)", "copy-start"),
    ("%shift-right-logical_or_fusion = s32[8]{0} fusion(s32[8] %x)",
     "shift-right-logical_or_fusion"),
    ("%reshape.2.3 = s32[8] reshape(s32[8] %x)", "reshape"),
])
def test_op_name(event, name):
    assert trace.op_name(event) == name


def _reader(name):
    return harness.load_module(REPO / "bench" / "metrics" / f"{name}.py")


DEVICE_METRICS = ["decode_kernel_ms", "publish_kernel_ms",
                  "restore_hbm_roofline_pct", "idle_pct.coldstart",
                  "idle_pct.publish"]


@pytest.mark.parametrize("name", DEVICE_METRICS)
def test_device_metric_is_silent_without_a_trace(name):
    run = RunData(cell=None, records=[], trace=None, device_kind="cpu")
    assert _reader(name).read(run) is None


def test_roofline_from_the_recorded_trace(recorded):
    recorded.spans.append(("bench.coldstart",) + SPAN)
    try:
        run = RunData(cell=None, trace=recorded, device_kind="TPU v5 lite",
                      records=[{"load_seconds": 1.0, "image_bytes": 2 << 20}])
        got = _reader("restore_hbm_roofline_pct").read(run)
        want = 100 * (2 * (2 << 20) / 819e9 * 1e9) / BUSY_NS
        assert got == pytest.approx(want) and 0 < got < 100
        ms = _reader("decode_kernel_ms").read(run)
        assert ms == pytest.approx(FUSED_NS / 1e6)
    finally:
        recorded.spans.pop()


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_main_refuses_a_cpu_run_and_prints_nothing(capsys):
    rc = harness.main(["--workload", "whisper-base.coldstart", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs a TPU" in out.err


def test_command_refuses_a_cpu_run_and_prints_nothing():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload",
         spec["workloads"][0]["name"], "--seed", str(2**40 + 3),
         "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
