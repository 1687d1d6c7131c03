"""Runner: bytes of host (NumPy) arrays handed to the device per served
batch, inputs and constants: the engine's ``run_h2d_bytes_total`` over
``serve_batches_total``."""
from bench.spans import counter_total


def read(run):
    h2d = counter_total(run.engine_metrics, "run_h2d_bytes_total")
    batches = counter_total(run.engine_metrics, "serve_batches_total")
    if h2d is None or not batches:
        return None
    return h2d / batches
