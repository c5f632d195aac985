"""CPU rehearsal of ``chip_smoke.py``: its inner function runs the whole
publish -> cold start -> serve path at the reduced ``smollm-360m``, and
its entry point refuses to run anywhere but on a TPU."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.configs import get_config

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    return chip_smoke.run(get_config("smollm-360m").reduced(),
                          str(tmp_path_factory.mktemp("smoke")),
                          log=lambda m: None)


def test_smoke_restores_byte_identical_and_serves_reference_tokens(report):
    # run() raises SmokeFailure on a restore that is not byte-identical,
    # on tokens that differ from the original parameters', and on a leaf
    # that cold_start left as a host array
    assert report["served"] == chip_smoke.REQUESTS
    assert report["leaf_platforms"] == [jax.devices()[0].platform]
    assert report["chunks"] >= 1 and report["image_bytes"] > 0
    assert report["coldstart_s"] > 0 and report["setup_s"] > 0


@pytest.mark.parametrize("change, ok", [
    ({}, True),
    ({"decode_backend": "python", "routes": {}}, False),
    ({"routes": {"fused": {"pallas": 3, "xla-jit": 1}}}, False),
    ({"routes": {"fused": {"pallas-interpret": 4}}}, False),
    ({"routes": {"fused": {"pallas": 4}, "aes": {"pallas-interpret": 1}}},
     False),
    ({"leaf_platforms": ["cpu"]}, False),
])
def test_require_device_decode(change, ok):
    rep = {"decode_backend": "bitsliced-fused",
           "routes": {"fused": {"pallas": 4}}, "leaf_platforms": ["tpu"],
           **change}
    if ok:
        chip_smoke.require_device_decode(rep)
    else:
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.require_device_decode(rep)


def test_cpu_rehearsal_fails_the_device_decode_check(report):
    # on the CPU the auto backend is the host one: the chip check refuses it
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.require_device_decode(report)


def test_main_exits_nonzero_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
