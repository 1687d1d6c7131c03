"""Model step: model FLOPs per sample × samples/s of the traced window
over the chip's peak in the configuration's datapath, in %."""
from bench.readings import mfu_pct


def read(run):
    w = run.window
    if not w.get("completed") or not run.peaks:
        return None
    return mfu_pct(run, w["completed"] / w["elapsed_s"])
