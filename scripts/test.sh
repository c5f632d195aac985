#!/usr/bin/env bash
# Tier-1 verification, one command from a fresh clone, fully offline:
# sets PYTHONPATH=src and runs pytest, then the benchmark smoke that
# drives the streamed restore + the shared-service multi-tenant scenario
# end-to-end. The smoke FAILS (non-zero exit) on byte divergence from
# the serial/staged oracles, on missing cross-tenant dedup telemetry,
# or on a streamed-vs-serial perf regression — so `make verify` / CI
# stop on benchmark-smoke regressions instead of just printing them.
# `hypothesis` is optional — when absent, tests/conftest.py swaps in the
# vendored deterministic stub. Everything here runs on the CPU, with the
# Pallas kernels in interpret mode; `python chip_smoke.py` is the check
# on a TPU. A jax compile cache is used only where
# JAX_COMPILATION_CACHE_DIR is set, or where an entry point places it
# (core.decode.enable_persistent_compilation_cache).
#
#   scripts/test.sh              # whole suite (-x -q) + smoke gates
#   scripts/test.sh tests/test_cache.py -k lru   # any pytest args
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS=cpu
if [ "$#" -eq 0 ]; then
    python -m pytest -x -q tests
    if ! env PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
        python benchmarks/e2e_read_latency.py --smoke; then
        echo "FAIL: benchmark smoke regression (see SMOKE REGRESSION above)" >&2
        exit 1
    fi
    # decode-kernel gate: every registered backend byte-identical to the
    # serial oracle and holding at least half its recorded throughput
    # ratio vs the same-run serial oracle (see decode_kernels.py)
    if ! env PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
        python benchmarks/decode_kernels.py --smoke; then
        echo "FAIL: decode kernel smoke regression (see above)" >&2
        exit 1
    fi
    # fault-injection gate: a stripe node crashed/blackholed MID-streamed-
    # restore must not change restored bytes, and one crashed node must
    # not drop the L2 hit rate below the healthy-run ratio
    if ! env PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
        python benchmarks/fault_injection.py --smoke; then
        echo "FAIL: fault-injection smoke regression (see above)" >&2
        exit 1
    fi
    # cross-tier chaos gate: poisoned L1 + crashed peer + blackholed L2
    # node + flaky origin must restore byte-identical with zero
    # unrecovered failures; a full origin outage must trip the breaker,
    # shed cold starts with a retry-after, and recover to closed; and an
    # all-defaults-off run must move ZERO resilience counters (the
    # BENCH_e2e.json-baselines-unchanged fast-fail)
    if ! env PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
        python benchmarks/chaos_matrix.py --smoke; then
        echo "FAIL: chaos matrix smoke regression (see above)" >&2
        exit 1
    fi
    # cold-start-storm gate: a worker fleet storming one image through
    # the peer tier must stay byte-identical to the serial oracle (with
    # and without a peer crashed mid-transfer) and keep origin GETs
    # within 2x the unique chunk count (4x for the crashed-peer phase)
    if ! env PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
        python benchmarks/coldstart_storm.py --smoke; then
        echo "FAIL: cold-start storm smoke regression (see above)" >&2
        exit 1
    fi
    # publish-pipeline gate: batched write path byte-identical to the
    # serial create_image oracle and >= 2x its wall (full bench targets
    # 3x), checkpoint dedup falling with encrypt-skips, and a GC
    # generation roll under a frozen live restore honoring the pin/alarm
    # protocol
    if ! env PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
        python benchmarks/publish_pipeline.py --smoke; then
        echo "FAIL: publish pipeline smoke regression (see above)" >&2
        exit 1
    fi
    # dedup-statistics gate: the Fig-5 creation-time numbers stay in the
    # paper's ballpark (re-upload fraction, unique-chunk mean)
    if ! env PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
        python benchmarks/dedup_cdf.py --smoke; then
        echo "FAIL: dedup statistics smoke regression (see above)" >&2
        exit 1
    fi
    exit 0
fi
exec python -m pytest -x -q "$@"
