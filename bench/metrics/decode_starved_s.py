"""``decode_starved_s``: time per cold start in which decode waited on
fetch: the ``repro.stream.get_wait`` spans (a get on the empty
fetch-to-decode queue) inside ``bench.coldstart``."""

from bench.program_spans import per_unit, summed


def read(run):
    return per_unit(run, "bench.coldstart", summed("repro.stream.get_wait"))
