"""Server: mean wait from submit to the worker's dequeue, the engine's
``serve_stage_ms{stage=queue_wait}`` sum over count."""
from bench.readings import histogram_mean


def read(run):
    return histogram_mean(run.engine_metrics, "serve_stage_ms",
                          stage="queue_wait")
