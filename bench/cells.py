"""One run of one cell: set-up, the measured window, the check.

The traffic mix's ``kind`` names its driver, ``bench/drivers/<kind>.py``
(found by :func:`bench.registry.driver`).  Every driver goes through the
program's front door: ``frontends.import_model`` on the configuration's
model card → ``repro.compile_graph`` → ``CompiledArtifact.run`` or
``ServeEngine``, with the weights as host NumPy arrays, the form an
importer hands them over in.  A driver fills the :class:`Run` record:
set-up times, what the window completed and how long it took, each
request's latency where requests have one, every answer of the window
compared with the plain reference, and, when the run traces, the
reduced device trace.  The metric readers under ``bench/metrics/`` take
their numbers from that record.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import tempfile
import time

import numpy as np

from bench import device, model, reference, trace_reduce

#: host tracing of a traced window: the benchmark's annotations and the
#: runtime's main events, no Python function events
HOST_TRACER_LEVEL = 1


@dataclasses.dataclass
class Run:
    """What one run measured."""

    cell: object                 # registry.Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float               # process start, on time.perf_counter
    setup: dict = dataclasses.field(default_factory=dict)
    # the window: ``completed`` samples over ``elapsed_s``, and what the
    # driver saw on the way
    window: dict = dataclasses.field(default_factory=dict)
    latencies_ms: np.ndarray | None = None  # one per request, open loop
    checks: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int | None = None
    engine_metrics: dict | None = None
    reduced: dict | None = None  # trace_reduce.reduce of the window
    peaks: dict | None = None    # bench/peaks.json row, traced runs
    trace_out: str | None = None  # where to keep the loaded trace
    open_loop: object = None     # generator.OpenLoopRun of the window

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def annotate(run: Run):
    if not run.trace:
        return None
    import jax

    return jax.profiler.TraceAnnotation


@contextlib.contextmanager
def _gc_pauses(run: Run):
    """Count the garbage collector's pauses in the window, and the
    longest: a pause stops every thread, the generator's too."""
    start, pauses = [0.0], []

    def note(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - start[0])

    gc.callbacks.append(note)
    try:
        yield
    finally:
        gc.callbacks.remove(note)
        run.window["gc_pauses"] = {"count": len(pauses),
                                   "max_ms": 1e3 * max(pauses, default=0.0)}


@contextlib.contextmanager
def window(run: Run, markers: dict | None = None):
    """The measured window, under the profiler when the run traces."""
    if not run.trace:
        with _gc_pauses(run):
            yield
        return
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = HOST_TRACER_LEVEL
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(log_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN), \
                    _gc_pauses(run):
                yield
        loaded = trace_reduce.load(trace_reduce.find_xplane(log_dir))
        run.reduced = trace_reduce.reduce(loaded, markers)
        if run.trace_out:
            trace_reduce.save(loaded, run.trace_out)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def compile_card(run: Run):
    """Import the card, compile it, make the weights."""
    from repro import CompileOptions, compile_graph, frontends

    imported = frontends.import_model(run.cell.config_path)
    t0 = time.perf_counter()
    art = compile_graph(imported.dfg,
                        CompileOptions(**run.config["compile_options"]))
    run.setup["mingc_compile_s"] = time.perf_counter() - t0
    run.setup["groups"] = len(art.design.groups)
    params = model.make_weights(run.config, run.config["weight_fill"], run.seed)
    return art, params


def end_setup(run: Run, clock: device.CompileClock) -> float:
    # set-up leaves a large heap (JAX, the program, the inputs): a full
    # collection over it stalls every thread for 100-150 ms, so it is
    # collected once here and frozen out of the window's collections
    gc.collect()
    gc.freeze()
    run.setup["setup_s"] = time.perf_counter() - run.t_start
    run.setup["xla_compile_s"] = clock.secs
    run.setup["cache_hits"] = clock.hits
    run.setup["cache_misses"] = clock.misses
    return clock.secs


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """The number compared for answers ``got`` against the reference's
    ``want``, one row per answer: the count of answers that differ at
    all for integer datapaths, the worst relative L2 error of an answer
    for float ones."""
    got = np.asarray(got).reshape(want.shape)
    if np.issubdtype(want.dtype, np.integer):
        return {"mismatched_answers": int(np.sum(np.any(got != want, axis=1)))}
    return {"max_rel_l2": float(reference.rel_l2_per_sample(got, want).max())}


#: limits that hold whatever the configuration: an answer that never
#: comes, or comes as an error, is never right
FIXED_LIMITS = {"unanswered": 0, "errored": 0}


def limits(run: Run) -> dict:
    """Each compared number beside its limit, in the order compared."""
    table = {**run.config["limits"], **FIXED_LIMITS}
    return {k: {"value": v, "limit": table[k]} for k, v in run.checks.items()}


def drive(run: Run, clock: device.CompileClock) -> Run:
    """Run the driver that the cell's traffic kind names."""
    from bench import registry

    registry.driver(run.traffic["kind"], run.cell.root)(run, clock)
    return run
