"""Runner: share of the batch rows executed in the serving window that
were bucket padding, the engine's ``run_rows_total{kind=padded}`` over
all its rows, in %."""
from bench.spans import counter_total


def read(run):
    snap = run.engine_metrics
    padded = counter_total(snap, "run_rows_total", kind="padded")
    rows = counter_total(snap, "run_rows_total")
    if padded is None or not rows:
        return None
    return 100.0 * padded / rows
