"""Fused verify+decrypt probe: time and check one decode tile per shape.

For each ``lanes x chunk_bytes`` shape it decodes ``lanes`` random
chunks, each under its own random AES-256 key, through
``fused_verify_decrypt`` and reports two host-clock times per tile:

* ``call_ms``: the jitted device program alone (the keystream kernel
  and its transpose, the word swap and transpose, the SHA walk), inputs
  already on the device, timed to ``block_until_ready``; median of
  ``REPEATS``;
* ``adapter_ms``: the whole adapter, host marshalling and the copy of
  plaintext back to the host included; median of ``REPEATS``.

Every digest is compared with hashlib, and the plaintext of the first
and last lane of every kernel lane tile with the serial AES-CTR oracle;
a mismatch exits non-zero. The routes each launch took are printed.
One JSON object per shape goes to standard output.

    python benchmarks/kernel_probe.py                  # the default shapes
    python benchmarks/kernel_probe.py 8x524288,256x4096

A shape wider than ``LANE_BLOCK`` lanes runs the kernel's lane-tile
grid axis (256 x 4 KiB is the 1 MiB tile of 4 KiB chunks). Off a TPU the
adapter takes its XLA-jit route; the times then say nothing of a chip.
"""
from __future__ import annotations

import hashlib
import json
import statistics
import sys
import time

import jax
import numpy as np

from repro.core.crypto import aes
from repro.core.telemetry import COUNTERS
from repro.kernels import on_tpu, route_counts
from repro.kernels.fused import fused_verify_decrypt
from repro.kernels.fused.ops import _fused_device, round_key_planes
from repro.kernels.sha256.ops import pack_messages
from repro.kernels.sha256.sha256p import LANE_BLOCK

DEFAULT_SHAPES = "8x524288,32x524288,128x524288,256x4096"
REPEATS = 3
SEED = 0


def _median_ms(fn) -> float:
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def probe(lanes: int, chunk: int, rng) -> dict:
    cts = [rng.integers(0, 256, chunk, dtype=np.uint8).tobytes()
           for _ in range(lanes)]
    keys = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
            for _ in range(lanes)]
    digests, plains = fused_verify_decrypt(cts, keys)        # compiles
    bad = [i for i, (d, c) in enumerate(zip(digests, cts))
           if d != hashlib.sha256(c).digest()]
    edges = sorted({j for t in range(0, lanes, LANE_BLOCK)
                    for j in (t, min(t + LANE_BLOCK, lanes) - 1)})
    bad += [i for i in edges if plains[i] != aes.ctr_decrypt(cts[i], keys[i])]

    buf, nb = pack_messages(cts)
    rk = round_key_planes(keys, buf.shape[0])
    args = jax.device_put((buf, nb, rk))
    pallas = on_tpu()

    def call():
        jax.block_until_ready(_fused_device(
            *args, rounds=rk.shape[1] - 1, pallas=pallas, interpret=False))

    call()
    call_ms = _median_ms(call)
    adapter_ms = _median_ms(lambda: fused_verify_decrypt(cts, keys))
    mb = lanes * chunk / 1e6
    return {"lanes": lanes, "chunk_bytes": chunk, "call_ms": call_ms,
            "call_MBps": mb / call_ms * 1e3, "adapter_ms": adapter_ms,
            "adapter_MBps": mb / adapter_ms * 1e3,
            "lanes_checked_plaintext": len(edges), "mismatches": sorted(bad)}


def main(shapes: str = DEFAULT_SHAPES) -> int:
    """Probe each ``LANESxCHUNK_BYTES`` of the comma-separated `shapes`;
    non-zero when any digest or checked plaintext is wrong."""
    dev = jax.devices()[0]
    print(f"jax {jax.__version__}; platform {dev.platform}; device_kind "
          f"{dev.device_kind}", flush=True)
    rng = np.random.default_rng(SEED)
    failed = False
    for shape in shapes.split(","):
        lanes, chunk = (int(x) for x in shape.split("x"))
        row = probe(lanes, chunk, rng)
        failed |= bool(row["mismatches"])
        print(json.dumps(row), flush=True)
    print(f"routes: {json.dumps(route_counts(COUNTERS.snapshot()))}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
