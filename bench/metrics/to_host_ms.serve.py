"""Runner: mean time of a served batch's device-to-host copy at the
artifact boundary, the engine's ``run_to_host_ms`` sum over count."""
from bench.readings import histogram_mean


def read(run):
    return histogram_mean(run.engine_metrics, "run_to_host_ms")
