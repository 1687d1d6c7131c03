#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  ``<cell>`` is a ``workloads`` entry of
``BENCHMARK.json``.  The run sets up (imports, ``compile_graph``, the
seeded weights and inputs, warm-up of the cell's own shapes), measures
for ``--seconds``, then compares every answer of the window with the
plain reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
compared number beside its limit; the same numbers end standard error.

It exits 2 and prints no result when JAX's default device is not a TPU
or holds fewer chips than the cell asks for, and 1 when the checkout
lacks the program (``src/repro``).  JAX's persistent compilation cache
lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def metrics_of(run, root: str) -> dict:
    """The cell's end-to-end metrics, or with ``--trace 1`` the
    per-layer metrics whose readers find something to read; each read
    by its own ``bench/metrics/<metric>.py``.  An end-to-end metric that
    reads nothing is an error: every run of the cell reports it."""
    from bench import registry

    cell = run.cell
    out = {}
    for m in cell.per_layer if run.trace else cell.end_to_end:
        value = registry.reader(m["name"], root)(run)
        if value is None and not run.trace:
            raise ValueError(f"{cell.name}: end-to-end metric {m['name']} "
                             "read nothing")
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(run, dev: dict, root: str) -> dict:
    from bench import cells

    checks = cells.limits(run)
    device = dict(dev, memory_peak_bytes=run.memory_peak_bytes)
    line = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics_of(run, root),
        "device": device,
    }
    if run.trace:
        red = run.reduced
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["checks"] = checks
    return line


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: str = ROOT, t_start: float | None = None,
            require_tpu: bool = True, trace_out: str | None = None) -> dict:
    """Set up, measure and check one run; return its result line.
    ``require_tpu=False`` skips the look for a chip and its peaks (CPU
    tests); ``trace_out`` keeps the loaded trace of a traced run there."""
    from bench import cells, device, registry

    cell = registry.cell(workload, root)
    dev = device.check(cell.chips) if require_tpu else device.describe()
    run = cells.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                    t_start=T_START if t_start is None else t_start,
                    trace_out=trace_out)
    if trace and require_tpu:
        run.peaks = device.peaks(dev["kind"], root)
    clock = device.CompileClock().install()
    cells.drive(run, clock)
    s, w = run.setup, run.window
    _log(f"setup: {s['setup_s']:.3f} s, compile_graph {s['mingc_compile_s']:.3f}"
         f" s ({s['groups']} groups), XLA compile {s['xla_compile_s']:.3f} s,"
         f" persistent cache {s['cache_hits']} hits / {s['cache_misses']}"
         " misses")
    _log("window: " + json.dumps(w, default=float))
    line = result(run, dev, root)
    for name, c in line["checks"].items():
        _log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return line


def prepare(script: str) -> None:
    """Import path and compile cache for a process started as
    ``python3 bench/<script>.py``: the benchmark's modules import as
    ``bench.*`` (never bare), the program from ``src``, and JAX keeps
    its persistent cache in :data:`CACHE_DIR`."""
    here = os.path.dirname(os.path.abspath(script))
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def has_program() -> bool:
    if os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return True
    _log(f"no program under {os.path.join(ROOT, 'src')}: run the "
         "benchmark from a checkout of the repository")
    return False


def main(argv=None) -> int:
    args = parse(argv)
    if not has_program():
        return 1
    prepare(__file__)
    from bench import device

    try:
        line = execute(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except device.NoAccelerator as e:
        _log(f"no accelerator: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
