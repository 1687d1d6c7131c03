"""Kernel: the streaming conv kernel's share of its roofline, in %.

The conv layers' FLOPs and bytes for every call of the traced window
(:func:`bench.model.conv_work`), over the device time of the Pallas
kernels in the trace; the roofline time is the larger of FLOPs over
the peak in the configuration's datapath and bytes over HBM bandwidth.
In this path every Pallas kernel is a conv."""
from bench import model
from bench.readings import itemsize


def read(run):
    red = run.reduced
    secs = red["kernel_s"].get("pallas", 0.0) if red else 0.0
    if secs <= 0 or not run.peaks:
        return None
    flops, nbytes = model.conv_work(run.config, itemsize(run),
                                    run.window["batch"])
    calls = run.window["calls"]
    bound = max(flops * calls / run.peaks[run.config["peak"]],
                nbytes * calls / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / secs
