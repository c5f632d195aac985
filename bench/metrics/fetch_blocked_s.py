"""``fetch_blocked_s``: time per cold start in which fetch waited on
decode: the ``repro.stream.put_wait`` spans (a put on the full
fetch-to-decode queue) inside ``bench.coldstart``."""

from bench.program_spans import per_unit, summed


def read(run):
    return per_unit(run, "bench.coldstart", summed("repro.stream.put_wait"))
