"""Device: share of the traced closed-loop window with no operation on
the chip (1 − union of device-operation intervals / window), in %."""
from bench.readings import idle_share_pct


def read(run):
    return idle_share_pct(run)
