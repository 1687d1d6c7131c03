"""End to end: samples completed in the window over the window's length,
host clock (closed loop: first call to the end of the last; open loop:
first arrival to the last answer)."""


def read(run):
    w = run.window
    if not w.get("completed") or not w.get("elapsed_s"):
        return None
    return w["completed"] / w["elapsed_s"]
