"""``kernel_launches.coldstart``: kernel launches per cold start, the sum
of the ``kernel.route.<kernel>.<route>`` counter deltas over each cold
start. At 512 KiB chunks it reads the decode tile the run picked: one
launch per tile."""


def read(run):
    vals = [r["launches"] for r in run.records if "load_seconds" in r]
    return sum(vals) / len(vals) if vals else None
