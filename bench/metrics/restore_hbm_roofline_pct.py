"""``restore_hbm_roofline_pct``: the restore's share of its HBM roofline.

The least time the chip could take to restore an image is reading and
writing its bytes once at the published HBM bandwidth:
``2 * image bytes / HBM bytes/s``. The share is that time over the device
busy time inside the ``bench.coldstart`` spans, whatever implements the
work. The VPU's integer peak is not published, so no compute bound is
taken."""

from bench.peaks import peaks


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("bench.coldstart")
    busy = run.trace.busy_ns(within=spans) if spans else None
    if not busy:
        return None
    moved = sum(2 * r["image_bytes"] for r in run.records
                if "load_seconds" in r)
    least_ns = moved / peaks(run.device_kind)["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / busy
