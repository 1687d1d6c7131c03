"""Kernel: the depthwise streaming kernel's share of its roofline, in %.

The depthwise convs' FLOPs and bytes for every call of the traced
window (:func:`bench.dag.dwconv_work`: input and output activations
once per sample, weights and bias once per call), over the device time
of the operations the kernel's own name defines
(``kernel_s["dwconv"]``, ``bench/kernel_time.py``); the roofline time
is the larger of FLOPs over the peak in the configuration's datapath
and bytes over HBM bandwidth."""
from bench import dag
from bench.readings import itemsize


def read(run):
    red = run.reduced
    secs = red["kernel_s"].get("dwconv", 0.0) if red else 0.0
    if secs <= 0 or not run.peaks:
        return None
    flops, nbytes = dag.dwconv_work(run.config, itemsize(run),
                                    run.window["batch"])
    calls = run.window["calls"]
    bound = max(flops * calls / run.peaks[run.config["peak"]],
                nbytes * calls / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / secs
