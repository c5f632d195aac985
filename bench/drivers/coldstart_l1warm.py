"""Serial cold starts from a warm local cache, a closed loop of one client.

Set-up is ``coldstart``'s (the seed's tree, its image laid into the
on-disk origin, the warm-up), then reads every stored chunk of the image
once into one ``LocalCache`` of ``l1_bytes``, above the image's stored
bytes. Each unit is a cold start on a fresh ``ImageService`` handed that
cache as its L1, as a replica restarted on a worker whose local cache
already holds its image pays it: fetch never reaches origin, and decode,
assembly and placement do the work. ``check`` adds the unit's
``origin_fetches`` (limit 0) to ``coldstart``'s readings; ``verify``
restores the tampered chunk through a service without that cache, from
origin.

Traffic parameters: those of ``coldstart``, and ``l1_bytes``.
"""
from __future__ import annotations

import time

import jax

from bench.drivers import coldstart
from bench.harness import kernel_launches


def setup(ctx):
    from repro.core.cache.local import LocalCache
    from repro.core.service import ImageService

    state = coldstart.setup(ctx)
    l1 = LocalCache(int(state["traffic"]["l1_bytes"]), name="bench.l1warm")
    t0 = time.perf_counter()
    service = ImageService(state["store"], state["config"], l1=l1)
    try:
        handle = service.open(state["blob"], state["tenant_key"])
        handle.prefetch(list(range(handle.layout.num_chunks)))
    finally:
        service.close()
    ctx.log(f"{l1.lru.used} B of {len(l1.lru.data)} chunks read into L1 "
            f"in {time.perf_counter() - t0:.3f}s")
    state["l1"] = l1
    return state


def unit(ctx, state, i: int) -> dict:
    """One cold start from the warm L1 to its first token; its record."""
    from repro.core.service import ImageService
    from repro.core.telemetry import COUNTERS
    from repro.serve.coldstart import cold_start
    from repro.serve.engine import Request

    traffic = state["traffic"]
    before = COUNTERS.snapshot()
    t0 = time.perf_counter()
    service = ImageService(state["store"], state["config"], l1=state["l1"])
    try:
        with jax.profiler.TraceAnnotation("bench.coldstart"):
            engine, stats = cold_start(state["model"], state["blob"],
                                       state["tenant_key"], service)
        with jax.profiler.TraceAnnotation("bench.first_token"):
            req = Request(i, prompt=list(traffic["prompt"]),
                          max_new=int(traffic["new_tokens"]))
            engine.submit(req)
            engine.run_until_drained()
    finally:
        service.close()
    seconds = time.perf_counter() - t0
    return {"seconds": seconds,
            "load_seconds": stats["load_seconds"],
            "decode_wall_s": stats["decode_wall_s"],
            "image_bytes": state["image_bytes"],
            "origin_fetches": stats["origin_fetches"],
            "launches": kernel_launches(before, COUNTERS.snapshot()),
            "answered": req.done and len(req.out) == traffic["new_tokens"],
            "params": engine.params}


def check(ctx, state, rec) -> dict:
    got = coldstart.check(ctx, state, rec)
    got["origin_fetches"] = int(rec["origin_fetches"])
    return got


def verify(ctx, state, records) -> tuple:
    checks, failed = coldstart.verify(ctx, state, records)
    checks["origin_fetches"] = {
        "value": sum(r["readings"]["origin_fetches"] for r in records),
        "limit": 0}
    return checks, failed


def end_to_end(records) -> dict:
    return coldstart.end_to_end(records)
