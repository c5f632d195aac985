"""``idle_pct.coldstart``: the share of the traced window in which no
operation ran on the device, in the cold-start cells."""

from bench.trace import idle_pct


def read(run):
    return idle_pct(run.trace)
