"""Chip smoke: publish -> cold start from origin -> serve, once, on one TPU.

Drives the system's main path through the entry points a user calls, at
the full width of ``smollm-360m`` (random weights from a fixed seed):

1. ``ImageService.publish`` images the parameter tree at 512 KiB chunks
   into a ``ChunkStore`` on local disk;
2. a fresh ``ImageService`` (cold L1) runs ``cold_start`` under the
   default policy — streamed read, ``auto`` decode backend — which places
   the restored weights in device memory;
3. ``ServeEngine`` serves 4 requests of 8 new tokens.

It checks that the restored tree is byte-identical to the published one,
that the greedy tokens equal those of the same ``decode_step`` run on the
original parameters, that every parameter leaf sits on the TPU, and that
every decode ran through compiled Pallas kernels (no interpreter, no host
backend, no XLA-jit route). Set-up — kernel and step compilation plus the
decode-tile autotune — is a first, untimed cold start through its own
fresh service; the cold start reported after it pays none of that.

Run from the checkout root, on a machine with a TPU::

    python chip_smoke.py

Anywhere else it exits non-zero before doing any work. The last line of
its standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core.decode import enable_persistent_compilation_cache  # noqa: E402

ARCH = "smollm-360m"
SEED = 0
TENANT_KEY = b"K" * 32
REQUESTS = 4
NEW_TOKENS = 8


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def run(cfg, workdir: str, *, log=print) -> dict:
    """Publish, cold-start and serve `cfg` (weights from ``SEED``) with a
    chunk store under `workdir`; raise ``SmokeFailure`` when the restore
    is not byte-identical or the tokens differ from the reference. Both
    cold starts run under the default policy. Returns what was measured
    and which decode routes the timed cold start took."""
    import jax

    from repro.core.gc import GenerationalGC
    from repro.core.service import ImageService, ServiceConfig
    from repro.core.store import ChunkStore
    from repro.core.telemetry import COUNTERS
    from repro.kernels import route_counts
    from repro.models import build_model
    from repro.serve.coldstart import cold_start
    from repro.serve.engine import Request, ServeEngine
    from repro.train.checkpoint import state_to_tree

    model = build_model(cfg)
    params = jax.block_until_ready(model.init(jax.random.key(SEED)))
    tree = state_to_tree(params)
    store = ChunkStore(os.path.join(workdir, "store"))
    config = ServiceConfig(root=GenerationalGC(store).active)

    publisher = ImageService(store, config)
    t0 = time.perf_counter()
    blob, pub = publisher.publish(tree, tenant="smoke", tenant_key=TENANT_KEY)
    publish_s = time.perf_counter() - t0
    publisher.close()
    log(f"image: {pub.bytes_total} bytes in {pub.total_chunks} chunks, "
        f"published in {publish_s:.3f}s")

    def fresh_cold_start():
        service = ImageService(store, config)      # cold L1
        try:
            return cold_start(model, blob, TENANT_KEY, service)
        finally:
            service.close()

    t0 = time.perf_counter()
    warm, _ = fresh_cold_start()
    setup_s = time.perf_counter() - t0
    del warm
    log(f"set-up (compile + autotune, one untimed cold start): "
        f"{setup_s:.3f}s")

    before = COUNTERS.snapshot()
    engine, stats = fresh_cold_start()
    after = COUNTERS.snapshot()
    routes = route_counts({k: v - before.get(k, 0) for k, v in after.items()
                           if v != before.get(k, 0)})
    log(f"cold start: {stats['load_seconds']:.3f}s (fetch busy "
        f"{stats['fetch_busy_s']:.3f}s, blocked on decode "
        f"{stats['fetch_blocked_s']:.3f}s, decode "
        f"{stats['decode_wall_s']:.3f}s)")
    log(f"decode backend: {stats['decode_backend']}; kernel routes: "
        f"{json.dumps(routes, sort_keys=True)}")

    leaves = jax.tree.leaves(engine.params)
    platforms = sorted({d.platform for leaf in leaves
                        if isinstance(leaf, jax.Array)
                        for d in leaf.devices()})
    on_device = sum(isinstance(leaf, jax.Array) for leaf in leaves)
    log(f"parameter leaves on device: {on_device}/{len(leaves)} "
        f"(platforms {platforms})")
    _check(on_device == len(leaves), "parameter leaves left on the host")

    restored = state_to_tree(engine.params)
    _check(restored.keys() == tree.keys(), "restored tree paths differ")
    for path, want in tree.items():
        got = restored[path]
        _check(got.dtype == want.dtype and got.shape == want.shape
               and got.tobytes() == want.tobytes(),
               f"restored leaf {path} is not byte-identical")
    log(f"restore byte-identical: {len(tree)} leaves, "
        f"{sum(a.nbytes for a in tree.values())} bytes")

    def serve(eng):
        reqs = [Request(i, prompt=[1 + i, 2, 3], max_new=NEW_TOKENS)
                for i in range(REQUESTS)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return reqs

    t0 = time.perf_counter()
    served = serve(engine)
    serve_s = time.perf_counter() - t0
    reference = serve(ServeEngine(model, params, max_batch=engine.B,
                                  max_len=engine.max_len))
    done = sum(r.done and len(r.out) == NEW_TOKENS for r in served)
    log(f"requests served: {done}/{REQUESTS} in {serve_s:.3f}s; tokens "
        f"{[r.out for r in served]}")
    _check(done == REQUESTS, "not every request was served")
    _check([r.out for r in served] == [r.out for r in reference],
           "greedy tokens differ from the original parameters'")
    log("tokens equal the reference decode_step on the original parameters")
    return {"image_bytes": pub.bytes_total, "chunks": pub.total_chunks,
            "publish_s": publish_s, "setup_s": setup_s,
            "coldstart_s": stats["load_seconds"],
            "decode_backend": stats["decode_backend"], "routes": routes,
            "leaf_platforms": platforms, "served": done}


def require_device_decode(report: dict) -> None:
    """Fail unless the timed cold start decoded on the chip: the fused
    backend, through compiled Pallas only — no interpreted launch, no
    XLA-jit route, no host backend."""
    routes = report["routes"]
    _check(report["decode_backend"] == "bitsliced-fused",
           f"decode backend {report['decode_backend']!r} is not the fused "
           f"device backend")
    _check(routes.get("fused", {}).get("pallas", 0) > 0,
           "no fused Pallas launch in the cold start")
    _check(all(set(r) == {"pallas"} for r in routes.values()),
           f"a decode left compiled Pallas: {routes}")
    _check(report["leaf_platforms"] == ["tpu"],
           f"parameters on {report['leaf_platforms']}, not the TPU")


def main() -> int:
    import jax

    from repro.configs import get_config

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; platform {dev.platform}; device_kind "
          f"{dev.device_kind}; devices {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    # nothing has compiled yet, so the cache placed here covers every step
    print(f"compile cache: {enable_persistent_compilation_cache()}",
          flush=True)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        report = run(get_config(ARCH), workdir,
                     log=lambda m: print(m, flush=True))
    require_device_decode(report)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
