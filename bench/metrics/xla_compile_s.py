"""XLA and Mosaic compiles: JAX's backend-compile seconds in set-up
(persistent-cache hits and misses are on an earlier line)."""


def read(run):
    return run.setup.get("xla_compile_s")
