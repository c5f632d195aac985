"""``decode_wall_s``: mean per cold start of the restore's
``decode_wall_s`` stat (``core/decode.BatchDecoder``'s wall time)."""


def read(run):
    vals = [r["decode_wall_s"] for r in run.records
            if r.get("decode_wall_s") is not None]
    return sum(vals) / len(vals) if vals else None
