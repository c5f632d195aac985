"""Decode-kernel microbenchmark: keystream GB/s + verify GB/s per
registered decode backend (the two halves of the paper §3.1
verify-then-decrypt pass), recorded into BENCH_e2e.json.

Each backend from the ``core.decode`` registry runs the SAME batch —
one decode tile's worth of independently-keyed AES-256-CTR keystreams
through its ``encrypt_many`` kernel, and the ciphertext batch through
its ``sha_many`` verify — byte-identity-checked against the serial
per-chunk oracles (``aes.ctr_keystream`` / hashlib) before any number
is reported. A ``serial`` row (pure per-chunk python loop) anchors the
scale.

``--smoke`` is the CI gate (wired into ``scripts/test.sh`` / ``make
verify``): a small shape, hard non-zero exit when ANY registered
backend diverges from the serial oracle or regresses below
``REGRESSION_FRACTION`` of its recorded BENCH baseline. The perf
comparison is ANCHORED and INTERLEAVED: each repeat times the backend
and the serial oracle back-to-back and the median RATIO is compared
against the recorded ratio (the ``smoke`` sub-keys in BENCH_e2e.json,
refreshed by every full ``run()``) — absolute GB/s would hard-fail a
fresh clone on any machine slower than the one that recorded the
baseline.
"""
from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

from repro.core.crypto import aes
from repro.core.decode import get_backend, registered_backends
from repro.kernels.aes import bitslice

BENCH_JSON = os.environ.get("BENCH_E2E_JSON", "BENCH_e2e.json")
FULL_SHAPE = (64, 4096)        # one default 256 KiB decode tile
SMOKE_SHAPE = (16, 4096)
# fail smoke below half the recorded backend/serial ratio: interpret-
# mode Pallas timings swing ~±25% BETWEEN processes on a loaded 2-core
# box even with interleaved-median measurement, so a tighter gate
# flakes; a real kernel regression (e.g. silently falling back to the
# python path) shifts the ratio 2-10x and still trips this
REGRESSION_FRACTION = 0.5
MIN_GATE_SECONDS = 1e-3        # don't perf-gate sub-ms timings (jitter)


def _mk_batch(nchunks: int, chunk_bytes: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    keys = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
            for _ in range(nchunks)]
    datas = [rng.integers(0, 256, chunk_bytes, dtype=np.uint8).tobytes()
             for _ in range(nchunks)]
    return keys, datas


def _serial_keystreams(keys: list, sizes: list) -> list:
    return [aes.ctr_keystream(k, b"\x00" * 16, (s + 15) // 16)
            .reshape(-1)[:s] for k, s in zip(keys, sizes)]


def _backend_fns(name: str, keys: list, datas: list, sizes: list):
    """(keystream_fn, verify_fn) for a backend name (``serial`` = the
    per-chunk oracle loops)."""
    if name == "serial":
        return (lambda: _serial_keystreams(keys, sizes),
                lambda: [hashlib.sha256(d).digest() for d in datas])
    be = get_backend(name)
    enc, sha = be.encrypt_many, be.sha_many
    return (lambda: aes.ctr_keystream_many(keys, sizes, encrypt_many=enc),
            (lambda: sha(datas)) if sha is not None else
            (lambda: [hashlib.sha256(d).digest() for d in datas]))


def _check_identity(name: str, ks_fn, sha_fn, keys, datas, sizes) -> None:
    """Byte-identity vs the serial oracles (also warms jit caches so
    later timings exclude compile). Raises AssertionError on divergence."""
    got_ks = ks_fn()
    want_ks = _serial_keystreams(keys, sizes)
    for i, (g, w) in enumerate(zip(got_ks, want_ks)):
        assert np.array_equal(g, w), \
            f"{name}: keystream diverged from serial oracle at chunk {i}"
    assert sha_fn() == [hashlib.sha256(d).digest() for d in datas], \
        f"{name}: verify digests diverged from hashlib"


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def measure_backend(name: str, nchunks: int, chunk_bytes: int,
                    repeats: int = 3, seed: int = 0) -> dict:
    """Best-of-`repeats` keystream and verify throughput for one
    backend name, identity-checked against the serial oracles."""
    keys, datas = _mk_batch(nchunks, chunk_bytes, seed)
    sizes = [len(d) for d in datas]
    total = float(sum(sizes))
    ks_fn, sha_fn = _backend_fns(name, keys, datas, sizes)
    _check_identity(name, ks_fn, sha_fn, keys, datas, sizes)
    ks_s = min(_timed(ks_fn) for _ in range(repeats))
    sha_s = min(_timed(sha_fn) for _ in range(repeats))
    return {
        "chunks": nchunks,
        "chunk_bytes": chunk_bytes,
        "keystream_s": ks_s,
        "verify_s": sha_s,
        "keystream_gbps": total / ks_s / 1e9,
        "verify_gbps": total / sha_s / 1e9,
    }


def measure_ratios(name: str, nchunks: int, chunk_bytes: int,
                   repeats: int = 5, seed: int = 1) -> dict:
    """The smoke gate's metric: this backend's throughput RELATIVE to
    the serial oracle, measured INTERLEAVED (backend and oracle timed
    back-to-back within each repeat, median ratio) so load spikes hit
    both sides of the division — stable where absolute GB/s on a noisy
    shared box is not. The same procedure produces the recorded
    baseline and the smoke measurement, so they are comparable."""
    keys, datas = _mk_batch(nchunks, chunk_bytes, seed)
    sizes = [len(d) for d in datas]
    total = float(sum(sizes))
    ks_fn, sha_fn = _backend_fns(name, keys, datas, sizes)
    ks_ser, sha_ser = _backend_fns("serial", keys, datas, sizes)
    _check_identity(name, ks_fn, sha_fn, keys, datas, sizes)
    ks_r, sha_r, ks_t, sha_t, ks_st, sha_st = [], [], [], [], [], []
    for _ in range(repeats):
        tb = _timed(ks_fn)
        ts = _timed(ks_ser)
        ks_r.append(ts / tb)
        ks_t.append(tb)
        ks_st.append(ts)
        tb = _timed(sha_fn)
        ts = _timed(sha_ser)
        sha_r.append(ts / tb)
        sha_t.append(tb)
        sha_st.append(ts)
    return {
        "chunks": nchunks,
        "chunk_bytes": chunk_bytes,
        "keystream_x_serial": float(np.median(ks_r)),
        "verify_x_serial": float(np.median(sha_r)),
        "keystream_s": float(np.median(ks_t)),
        "verify_s": float(np.median(sha_t)),
        # the ratio denominators: a gate is only meaningful when BOTH
        # sides of the division are above timer-jitter resolution
        "keystream_serial_s": float(np.median(ks_st)),
        "verify_serial_s": float(np.median(sha_st)),
        "keystream_gbps": total / float(np.median(ks_t)) / 1e9,
        "verify_gbps": total / float(np.median(sha_t)) / 1e9,
    }


def measure_fused(nchunks: int, chunk_bytes: int, repeats: int = 5,
                  seed: int = 2) -> dict | None:
    """The fused verify+decrypt pass vs the bitsliced TWO-PASS decode
    (sha verify + keystream decrypt as separate kernel launches) and vs
    the serial per-chunk oracle, interleaved-median like
    ``measure_ratios``. ``fused_x_twopass`` is the acceptance metric for
    the single-walk kernel: digests AND plaintexts from one pass must
    beat verify-then-decrypt as two. Returns None when no fused backend
    is registered."""
    be = get_backend("bitsliced-fused")
    fused = be.fused
    if fused is None:
        return None
    keys, datas = _mk_batch(nchunks, chunk_bytes, seed)
    sizes = [len(d) for d in datas]
    total = float(sum(sizes))
    two = get_backend("bitsliced")

    def fused_fn():
        return fused(datas, keys)

    def twopass_fn():
        digs = two.sha_many(datas)
        return digs, aes.ctr_decrypt_many(datas, keys,
                                          encrypt_many=two.encrypt_many)

    def serial_fn():
        return ([hashlib.sha256(d).digest() for d in datas],
                [aes.ctr_decrypt(d, k) for d, k in zip(datas, keys)])

    # byte-identity against the serial oracle (and jit warm-up)
    want_d, want_p = serial_fn()
    got_d, got_p = fused_fn()
    assert got_d == want_d, "fused: digests diverged from hashlib"
    assert got_p == want_p, "fused: plaintexts diverged from serial CTR"
    td, tp = twopass_fn()
    assert td == want_d and tp == want_p, \
        "bitsliced two-pass diverged from serial oracle"
    f_t, t_t, s_t = [], [], []
    for _ in range(repeats):
        f_t.append(_timed(fused_fn))
        t_t.append(_timed(twopass_fn))
        s_t.append(_timed(serial_fn))
    f_s = float(np.median(f_t))
    t_s = float(np.median(t_t))
    s_s = float(np.median(s_t))
    return {
        "chunks": nchunks,
        "chunk_bytes": chunk_bytes,
        "fused_s": f_s,
        "twopass_s": t_s,
        "serial_s": s_s,
        "fused_gbps": total / f_s / 1e9,
        "fused_x_twopass": t_s / f_s,
        "fused_x_serial": s_s / f_s,
    }


def measure_pack(nchunks: int, chunk_bytes: int, repeats: int = 5,
                 seed: int = 3) -> dict:
    """Host-side cost of plane packing, before vs after the on-device
    move. ``host_legacy_s`` replays what the bitsliced path used to do
    on the CPU per tile: transpose every AES block into 8x16 bit planes
    plus a per-BLOCK ``np.repeat`` + transposition of the round-key
    schedules. ``host_prep_s`` is the host work that remains on today's
    hot path — stack the per-CHUNK schedules, build the block→chunk
    index vector, pad to lane width — everything else now runs inside
    the jit'd program. The ratio is the offload win recorded into
    BENCH_e2e.json (acceptance: host pack off the hot path, prep
    near-zero)."""
    rng = np.random.default_rng(seed)
    bpc = (chunk_bytes + 15) // 16
    blocks = rng.integers(0, 256, (nchunks * bpc, 16), dtype=np.uint8)
    rk_list = [aes.expand_key(
        rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
        for _ in range(nchunks)]
    counts = np.full(nchunks, bpc, dtype=np.int64)

    def legacy():
        per_block = np.repeat(np.stack(rk_list), counts, axis=0)
        return (bitslice.pack_planes(blocks),
                bitslice.pack_round_keys(per_block))

    def prep():
        rks = np.stack(rk_list)
        idx = np.repeat(np.arange(nchunks, dtype=np.int32), counts)
        n = len(blocks)
        pad = -n % 32
        b = blocks if not pad else np.concatenate(
            [blocks, np.repeat(blocks[-1:], pad, axis=0)])
        i = idx if not pad else np.concatenate(
            [idx, np.full(pad, idx[-1], dtype=np.int32)])
        return rks, b, i

    legacy(), prep()
    leg_s = float(np.median([_timed(legacy) for _ in range(repeats)]))
    prep_s = float(np.median([_timed(prep) for _ in range(repeats)]))
    return {
        "chunks": nchunks,
        "chunk_bytes": chunk_bytes,
        "host_legacy_s": leg_s,
        "host_prep_s": prep_s,
        "host_offload_x": leg_s / max(prep_s, 1e-9),
    }


def _backend_names() -> list:
    return sorted(registered_backends()) + ["serial"]


def merge_bench_json(update: dict, section: str | None = None) -> None:
    """Read-merge-write BENCH_e2e.json (shared with e2e_read_latency so
    the two benches never clobber each other's keys). ``section=None``
    updates top-level keys; a section name nests per-entry updates
    under it."""
    data = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    if section is None:
        data.update(update)
    else:
        bucket = data.setdefault(section, {})
        for name, row in update.items():
            bucket.setdefault(name, {}).update(row)
    with open(BENCH_JSON, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)


def run() -> list:
    """Full measurement (benchmarks/run.py harness): the tile shape per
    backend plus the smoke-shape baselines the CI gate compares against,
    merged into BENCH_e2e.json."""
    rows = []
    update: dict = {}
    for name in _backend_names():
        full = measure_backend(name, *FULL_SHAPE)
        update[name] = dict(full)
        if name != "serial":
            update[name]["smoke"] = measure_ratios(name, *SMOKE_SHAPE)
        rows.append(dict(
            name=f"decode_kernels.{name}.keystream_gbps",
            value=full["keystream_gbps"],
            derived=f"{FULL_SHAPE[0]}x{FULL_SHAPE[1]}B chunks, "
                    f"best-of-3, byte-identical to serial oracle"))
        rows.append(dict(
            name=f"decode_kernels.{name}.verify_gbps",
            value=full["verify_gbps"],
            derived=f"batched SHA-256 verify, same batch"))
    fused = measure_fused(*FULL_SHAPE)
    if fused is not None:
        update.setdefault("bitsliced-fused", {})["fused"] = fused
        smoke_fused = measure_fused(*SMOKE_SHAPE)
        if smoke_fused is not None:
            update["bitsliced-fused"]["smoke_fused"] = smoke_fused
        rows.append(dict(
            name="decode_kernels.bitsliced-fused.fused_gbps",
            value=fused["fused_gbps"],
            derived="ONE pass: digests + plaintexts together"))
        rows.append(dict(
            name="decode_kernels.bitsliced-fused.fused_x_twopass",
            value=fused["fused_x_twopass"],
            derived="fused pass vs bitsliced verify-then-decrypt as two "
                    "launches, same batch same machine (target >= 1.5x)"))
    pack = measure_pack(*FULL_SHAPE)
    update["pack"] = pack
    rows.append(dict(
        name="decode_kernels.pack.host_legacy_s",
        value=pack["host_legacy_s"],
        derived="host bit-plane + per-block round-key pack the bitsliced "
                "path used to pay per tile (now on-device)"))
    rows.append(dict(
        name="decode_kernels.pack.host_prep_s",
        value=pack["host_prep_s"],
        derived="host work remaining on today's hot path (stack + index "
                "+ pad); ratio = pack.host_offload_x"))
    merge_bench_json(update, section="decode_kernels")
    return rows


def smoke() -> None:
    """CI gate: every registered backend must match the serial oracle
    byte-for-byte at the smoke shape, and hold ``REGRESSION_FRACTION``
    (half) of its RECORDED throughput ratio to the same-run serial
    oracle (machine-speed independent: the serial loop anchors both
    sides of the comparison). Non-zero exit on failure."""
    import sys

    baselines = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                baselines = json.load(f).get("decode_kernels", {})
        except (OSError, ValueError):
            baselines = {}
    failures = []
    report = []
    for name in sorted(registered_backends()):
        try:
            got = measure_ratios(name, *SMOKE_SHAPE)
        except AssertionError as e:
            failures.append(str(e))
            continue
        base = baselines.get(name, {}).get("smoke")
        note = ""
        if base and "keystream_x_serial" in base:
            for key, t_key, s_key in (
                    ("keystream_x_serial", "keystream_s",
                     "keystream_serial_s"),
                    ("verify_x_serial", "verify_s", "verify_serial_s")):
                if min(got[t_key], got.get(s_key, 0),
                       base.get(t_key, 0), base.get(s_key, 0)) \
                        < MIN_GATE_SECONDS:
                    continue            # below timer-jitter resolution
                if got[key] < base[key] * REGRESSION_FRACTION:
                    failures.append(
                        f"{name}: {key.split('_')[0]} regressed to "
                        f"{got[key]:.3f}x the serial oracle "
                        f"(< {REGRESSION_FRACTION:.0%} of the recorded "
                        f"{base[key]:.3f}x)")
        else:
            note = " (no recorded baseline; identity only)"
        report.append(f"  {name}: keystream {got['keystream_gbps']:.4f} "
                      f"GB/s ({got['keystream_x_serial']:.2f}x serial), "
                      f"verify {got['verify_gbps']:.4f} GB/s"
                      f"{note}")
    # the fused single-walk pass: same ratio-anchored gate, against its
    # recorded fused_x_serial baseline (identity asserted inside)
    try:
        got_f = measure_fused(*SMOKE_SHAPE)
    except AssertionError as e:
        got_f, _ = None, failures.append(str(e))
    if got_f is not None:
        base_f = baselines.get("bitsliced-fused", {}).get("smoke_fused")
        note = ""
        if base_f and "fused_x_serial" in base_f:
            if min(got_f["fused_s"], got_f["serial_s"], base_f["fused_s"],
                   base_f["serial_s"]) >= MIN_GATE_SECONDS and \
                    got_f["fused_x_serial"] < \
                    base_f["fused_x_serial"] * REGRESSION_FRACTION:
                failures.append(
                    f"bitsliced-fused: fused pass regressed to "
                    f"{got_f['fused_x_serial']:.3f}x the serial oracle "
                    f"(< {REGRESSION_FRACTION:.0%} of the recorded "
                    f"{base_f['fused_x_serial']:.3f}x)")
        else:
            note = " (no recorded baseline; identity only)"
        report.append(
            f"  bitsliced-fused[one-pass]: {got_f['fused_gbps']:.4f} GB/s "
            f"({got_f['fused_x_twopass']:.2f}x two-pass, "
            f"{got_f['fused_x_serial']:.2f}x serial){note}")
    if failures:
        print("DECODE KERNEL SMOKE REGRESSION:")
        for f in failures:
            print(f"  - {f}")
        sys.exit(1)
    print(f"DECODE KERNELS OK ({SMOKE_SHAPE[0]}x{SMOKE_SHAPE[1]}B, "
          f"all backends byte-identical to the serial oracle):")
    for line in report:
        print(line)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast identity + regression gate (tier-1)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        for row in run():
            print(f"{row['name']},{row['value']:.6g},\"{row['derived']}\"")
