"""``share_restore_roofline_pct``: a share's restore against its HBM
roofline. The least time the chip could take to restore the bytes a
replica holds is reading and writing them once at the published HBM
bandwidth: ``2 * held bytes / HBM bytes/s``. The share is that time over
the device busy time inside the ``bench.coldstart`` spans, whatever
implements the work (as ``restore_hbm_roofline_pct``, with the bytes
held in place of the image's)."""

from bench.peaks import peaks


def read(run):
    if run.trace is None:
        return None
    shares = [r["share"] for r in run.records if r.get("share")]
    spans = run.trace.spans_named("bench.coldstart")
    busy = run.trace.busy_ns(within=spans) if spans and shares else None
    if not busy:
        return None
    moved = sum(2 * s["held_bytes"] for s in shares)
    least_ns = moved / peaks(run.device_kind)["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / busy
