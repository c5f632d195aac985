"""``load_s``: mean of ``cold_start``'s ``load_seconds`` over the window's
cold starts (admission until the parameters are resident in HBM, on the
program's own host clock)."""


def read(run):
    vals = [r["load_seconds"] for r in run.records
            if r.get("load_seconds") is not None]
    return sum(vals) / len(vals) if vals else None
