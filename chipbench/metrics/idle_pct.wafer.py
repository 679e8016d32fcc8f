"""Share of the traced window in which no operation ran on the device (%),
in the wafer cells."""
from chipbench.metrics import reduce


def read(run):
    return reduce.idle_pct(run.lines)
