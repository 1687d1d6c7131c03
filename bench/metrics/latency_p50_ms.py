"""End to end: median over every request of the window, from its
scheduled arrival to its answer; a refused, failed or unanswered request
reads the whole wait."""
from bench.readings import latency_pct


def read(run):
    return latency_pct(run, 50)
