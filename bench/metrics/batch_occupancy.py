"""Server: mean requests per dispatched batch, the engine's
``serve_batch_occupancy`` sum over count."""
from bench.readings import histogram_mean


def read(run):
    return histogram_mean(run.engine_metrics, "serve_batch_occupancy")
