"""``marshal_s.publish``: host marshalling per publish in the kernel
adapters: the ``repro.kernel.pack`` and ``repro.kernel.split`` spans
inside ``bench.publish``."""

from bench.program_spans import per_unit, summed


def read(run):
    return per_unit(run, "bench.publish",
                    summed("repro.kernel.pack", "repro.kernel.split"))
