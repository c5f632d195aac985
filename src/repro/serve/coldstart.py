"""Replica cold-start via on-demand chunk loading — the paper's core
customer-visible metric, applied to model serving.

``cold_start`` admits the start through the shared ``ImageService``
(admission control lives in the service, §4.2: excess starts are
REJECTED with ``ColdStartRejected``, not queued), opens the image as a
tenant session, restores the weights through the shared cache tiers
under one ``ReadPolicy``, promotes any float64 leaves to float32 (the
serving dtype; see the test asserting this), places the tree in device
memory, and stands up a ``ServeEngine`` over it. A replica that holds
a share of the image (an expert-parallel rank: ``model.param_share()``)
passes it as ``share`` and restores only its slices (EP sparsity: the
demand-loading analogue of 'applications touch 6.4% of the image').

Each cold start is one ``request_scope``: its ``repro.coldstart`` span
holds ``.admit``, ``.open``, ``repro.restore`` (``repro.restore.share``
for a share) and ``.place``, and
every span of the start, on whichever thread, carries its ``request``.

The pre-redesign calling convention — a raw store plus the
l1/l2/limiter/fetch_limiter/batched/streamed/parallelism knob tuple —
still works as a deprecation path: it builds a private single-image
service per call. New code passes an ``ImageService`` and a
``ReadPolicy``.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from repro.core.blockdev import DEFAULT_PARALLELISM
from repro.core.service import ImageService, ReadPolicy, single_image_service
from repro.core.telemetry import COUNTERS, H2D_BYTES, request_scope, span
from repro.serve.engine import ServeEngine
from repro.train.checkpoint import tree_from_flat


def cold_start(model, manifest_blob: bytes, tenant_key: bytes, service, *,
               root=None, tenant=None, policy: ReadPolicy | None = None,
               max_batch=4, max_len=128, share: dict | None = None,
               # ---- deprecated store-calling-convention knobs (None
               # sentinels so misuse alongside a service is detectable) ----
               l1=None, l2=None, limiter=None, fetch_limiter=None,
               parallelism=None, batched=None, streamed=None,
               decoder=None) -> tuple:
    """Returns (engine, stats).

    `service` is the process-wide ``ImageService`` (shared L1/L2,
    admission + fetch limiters, decode pool); the restore runs through
    ``service.open(...)`` under `policy` (service default: streamed
    fetch→decode overlap). Admission control is the service's: when it
    is at ``max_coldstarts`` in-flight starts, ``ColdStartRejected``
    (a RuntimeError) is raised and ``serve.coldstart_rejected`` ticks.

    Restored weights are promoted float64 -> float32 (the serving
    dtype): images created from numpy-default-precision trees would
    otherwise double serve memory and halve matmul throughput. Other
    dtypes (float32/bf16-as-uint16/int8) pass through untouched. The
    tree is then placed on the default device, and ``load_seconds``
    ends once it is resident there (``block_until_ready``).

    `share` ({path: dim slices in the image's tensor | None}, as
    ``model.param_share()`` states it) restores only those slices,
    through ``ImageHandle.restore_share`` under the same policy and
    decoder; ``stats["share"]`` then holds its numbers.

    Deprecation path: passing a raw chunk store as `service` (with the
    old l1/l2/limiter/fetch_limiter/batched/streamed/parallelism/decoder
    keywords) builds a private single-image service per call — kept for
    the byte-identity oracles; `limiter` becomes the private service's
    admission limiter."""
    private_service = None
    if not isinstance(service, ImageService):
        service = single_image_service(service, l1=l1, l2=l2,
                                       fetch_limiter=fetch_limiter)
        service.admission = limiter
        # a per-call private service would leak its decoder pool and
        # session cache once the restore is done; close it on the way out
        private_service = service
        if policy is None:
            policy = ReadPolicy.from_legacy(
                batched=batched if batched is not None else True,
                streamed=streamed if streamed is not None else True,
                parallelism=parallelism if parallelism is not None
                else DEFAULT_PARALLELISM)
    elif any(k is not None for k in (l1, l2, limiter, fetch_limiter, decoder,
                                     parallelism, batched, streamed)):
        raise TypeError("cold_start(service=ImageService, ...) owns its "
                        "tiers and limiters and reads under a ReadPolicy; "
                        "the legacy l1/l2/limiter/fetch_limiter/decoder/"
                        "parallelism/batched/streamed keywords only apply "
                        "to the deprecated raw-store calling convention")
    try:
        return _cold_start_admitted(model, manifest_blob, tenant_key,
                                    service, root, tenant, policy,
                                    max_batch, max_len, decoder, share)
    finally:
        if private_service is not None:
            private_service.close()


def _cold_start_admitted(model, manifest_blob, tenant_key, service, root,
                         tenant, policy, max_batch, max_len, decoder,
                         share):
    with contextlib.ExitStack() as stack:
        stack.enter_context(request_scope())
        top = stack.enter_context(span("repro.coldstart"))
        with span("repro.coldstart.admit"):
            stack.enter_context(service.admission_slot())
        t0 = time.time()
        with span("repro.coldstart.open"):
            handle = service.open(manifest_blob, tenant_key, root=root,
                                  tenant=tenant, decoder=decoder)
        top.set_metadata(image_bytes=handle.layout.image_size,
                         tile_bytes=handle._resolve(policy)[1].max_batch_bytes)
        # origin traffic is attributed through the tenant's telemetry
        # scope, not the global counter — concurrent cold-starts of
        # OTHER tenants through the same service must not leak into
        # this replica's stats
        before_origin = handle.counters.get("read.origin_fetches")
        template = model.param_shapes()
        held = None
        if share is None:
            flat = handle.restore_tree(policy=policy)
        else:
            flat, held = handle.restore_share(share, policy=policy)
        with span("repro.restore.assemble"):
            # promoted on the host, so the device holds the serving dtype
            params = jax.tree.map(
                lambda p: p.astype(np.float32) if p.dtype == np.float64
                else p, tree_from_flat(template, flat))
        # place the tree in device memory once: the load clock stops when
        # the weights sit there, not on the host
        h2d = sum(p.nbytes for p in jax.tree.leaves(params))
        with span("repro.coldstart.place", h2d_bytes=h2d):
            params = jax.device_put(params)
            jax.block_until_ready(params)
        COUNTERS.add(H2D_BYTES, h2d)
        t_load = time.time() - t0
        engine = ServeEngine(model, params, max_batch=max_batch, max_len=max_len)
        # last_batch is the shared reader's most recent batch: exact for
        # this restore unless the SAME image is being restored by a
        # concurrent replica (whose batch may have landed later)
        lb = handle.reader.last_batch
        stats = {
            "load_seconds": t_load,
            "tenant": handle.tenant,
            "origin_fetches": handle.counters.get("read.origin_fetches")
            - before_origin,
            "image_bytes": handle.layout.image_size,
            # pipeline split: fetch at work, fetch blocked on the decode
            # queue, decode work and decode waiting on fetch; in streamed
            # mode overlap_s is the decode work done while fetch was busy
            "fetch_busy_s": lb.get("fetch_busy_s"),
            "fetch_blocked_s": lb.get("fetch_blocked_s"),
            "decode_starved_s": lb.get("decode_starved_s"),
            "decode_wall_s": lb.get("decode_wall_s"),
            "decode_backend": lb.get("decode_backend"),
            "streamed": lb.get("streamed"),
            "overlap_s": lb.get("overlap_s"),
            "overlap_fraction": lb.get("overlap_fraction"),
            "queue_hwm": lb.get("queue_hwm"),
            "eager_flushes": lb.get("eager_flushes"),
            "decode_tiles": lb.get("decode_tiles"),
            "tiles_overlapped": lb.get("tiles_overlapped"),
            "share": held,
        }
        return engine, stats

