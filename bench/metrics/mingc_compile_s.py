"""MING's own compile: host wall time of ``compile_graph`` in set-up."""


def read(run):
    return run.setup.get("mingc_compile_s")
