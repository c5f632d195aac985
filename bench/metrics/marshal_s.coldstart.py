"""``marshal_s.coldstart``: host marshalling per cold start in the kernel
adapters: the ``repro.kernel.pack`` spans (padding, key planes, before a
launch) and ``repro.kernel.split`` spans (results into per-chunk bytes,
after it) inside ``bench.coldstart``."""

from bench.program_spans import per_unit, summed


def read(run):
    return per_unit(run, "bench.coldstart",
                    summed("repro.kernel.pack", "repro.kernel.split"))
