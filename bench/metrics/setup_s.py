"""End to end: process start to the first timed request: imports,
``compile_graph``, weights, warm-up of the cell's own shapes."""


def read(run):
    return run.setup.get("setup_s")
