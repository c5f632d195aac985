"""``kernel_launches.publish``: kernel launches per publish, the sum of the
``kernel.route.<kernel>.<route>`` counter deltas over each publish."""


def read(run):
    vals = [r["launches"] for r in run.records if "blob" in r]
    return sum(vals) / len(vals) if vals else None
