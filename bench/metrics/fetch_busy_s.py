"""``fetch_busy_s``: time per cold start in which the fetch stage
(``core/blockdev.TieredReader``) was at work: the union, over every
thread, of the ``repro.fetch.l1`` / ``.peer`` / ``.l2`` / ``.origin``
spans inside ``bench.coldstart``. Time the producer sat on a full
hand-off queue is not in it (``fetch_blocked_s``)."""

from bench.program_spans import covered, per_unit


def read(run):
    return per_unit(run, "bench.coldstart", covered("repro.fetch."))
