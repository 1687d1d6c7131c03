"""Runner: share of the traced closed-loop window in which the device
idles while the host waits in a group's ``block_until_ready``: idle
gaps, each counted whole, whose innermost host event at the gap's
midpoint is ``ming:sync``, in %.  ``None`` where the ten listed gaps
leave it out."""
from bench.spans import idle_in_pct


def read(run):
    return idle_in_pct(run, "ming:sync")
