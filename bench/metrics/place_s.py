"""``place_s``: time per cold start spent placing the restored tree in
device memory (``jax.device_put`` until ``block_until_ready``): the
``repro.coldstart.place`` span inside ``bench.coldstart``."""

from bench.program_spans import per_unit, summed


def read(run):
    return per_unit(run, "bench.coldstart", summed("repro.coldstart.place"))
