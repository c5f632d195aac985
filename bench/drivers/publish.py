"""Checkpoint publishing from training, a closed loop of one job.

One training job publishes successive checkpoints of its whole parameter
tree into one store through one ``ImageService`` (every
``ServiceConfig`` default). Checkpoint ``k`` of a run is the seed's tree
number ``k``: every leaf, and so every chunk, has new bytes each time,
as in a full fine-tune, so nothing dedups and no encryption is skipped.
Set-up makes checkpoint 0 and publishes it once, untimed (compilation,
the encrypt path's first use). Each unit of the window makes the next
checkpoint on the device, copies it to the host, and publishes it; only
the ``publish`` call is timed. Right after each unit, outside the
window, its image is checked against an implementation of the format
that shares no code with the program (``bench/reference.py``).

Traffic parameters: ``tenant_key_hex``, ``salt_epoch`` (the program's
default epoch, 0) and ``chunk_bytes`` (the image's chunk size, which
the program's default has to give).
"""
from __future__ import annotations

import time

import jax

from bench import data, reference
from bench.harness import kernel_launches
from bench.model import build

SKIPPED = "publish.encrypt_skipped_chunks"


def setup(ctx):
    from repro.core.gc import GenerationalGC
    from repro.core.service import ImageService, ServiceConfig
    from repro.core.store import ChunkStore

    traffic = ctx.cell.traffic
    _, template = build(ctx.cell.config)
    store = ChunkStore(ctx.workdir / "store")
    config = ServiceConfig(root=GenerationalGC(store).active)
    state = {"make": data.tree_maker(template),
             "key": data.seed_key(ctx.seed), "store": store,
             "root": config.root,
             "service": ImageService(store, config),
             "tenant_key": bytes.fromhex(traffic["tenant_key_hex"]),
             "epoch": int(traffic["salt_epoch"])}
    t0 = time.perf_counter()
    warm = unit(ctx, state, -1)
    ctx.log(f"warm publish {warm['seconds']:.3f}s of "
            f"{warm['chunks']} chunks ({time.perf_counter() - t0:.3f}s "
            f"with the checkpoint)")
    return state


def unit(ctx, state, i: int) -> dict:
    """Make checkpoint ``i + 1`` and publish it; returns its record."""
    from repro.core.telemetry import COUNTERS

    step = i + 1
    with jax.profiler.TraceAnnotation("bench.checkpoint"):
        tree = data.host_tree(state["make"], state["key"], step)
    before = COUNTERS.snapshot()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.publish"):
        blob, stats = state["service"].publish(
            tree, tenant="bench", tenant_key=state["tenant_key"],
            salt_epoch=state["epoch"])
    seconds = time.perf_counter() - t0
    after = COUNTERS.snapshot()
    return {"seconds": seconds, "step": step,
            "blob": blob, "chunks": stats.total_chunks,
            "launches": kernel_launches(before, after),
            "encrypt_skipped": after.get(SKIPPED, 0) - before.get(SKIPPED, 0)}


def check(ctx, state, rec) -> dict:
    """The unit's image against the reference implementation, from the
    checkpoint made again from the seed."""
    tree = data.flat(data.host_tree(state["make"], state["key"],
                                    rec["step"]))
    store, root = state["store"], state["root"]
    return reference.publish_readings(
        rec["blob"], tree, tenant_key=state["tenant_key"], root=root,
        epoch=state["epoch"], chunk_size=int(ctx.cell.traffic["chunk_bytes"]),
        get_chunk=lambda name: store.get_chunk(root, name))


def verify(ctx, state, records) -> tuple:
    """The units' readings added up. Returns ({check: {value, limit}},
    failed units)."""
    state["service"].close()
    totals = {k: sum(r["readings"][k] for r in records)
              for k in ("chunks_wrong", "manifest_wrong")}
    failed = sum(any(r["readings"].values()) for r in records)
    skipped = sum(r["encrypt_skipped"] for r in records)
    ctx.log(f"encryptions skipped in the window: {skipped} (all-new "
            f"checkpoints: 0 expected)")
    return {k: {"value": v, "limit": 0} for k, v in totals.items()}, failed


def end_to_end(records) -> dict:
    return {"publish_s": sum(r["seconds"] for r in records) / len(records)}
