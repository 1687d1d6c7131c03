"""Closed loop on a graph card: back-to-back ``CompiledArtifact.run``
calls, each on the next of ``distinct_batches`` batches of ``batch``
host arrays, for the whole window; no queue in front of the program.

As ``closed_loop.py``, with the card read by ``bench/dag.py`` (weights
and biases) and the answers compared with ``bench/reference_dag.py``.
A traced window reports the Pallas kernels' time under ``pallas``
(``trace_reduce.PALLAS_MARKER``) and the depthwise kernel's under
``dwconv``, read by the kernel's own name
(``bench/kernel_time.py``)."""
from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

import numpy as np

from bench import cells, dag, device, kernel_time, model, reference_dag, \
    trace_reduce
from bench.traffic import generator


def compile_card(run: cells.Run):
    """Import the card, compile it, make its weights and biases."""
    from repro import CompileOptions, compile_graph, frontends

    imported = frontends.import_model(run.cell.config_path)
    t0 = time.perf_counter()
    art = compile_graph(imported.dfg,
                        CompileOptions(**run.config["compile_options"]))
    run.setup["mingc_compile_s"] = time.perf_counter() - t0
    run.setup["groups"] = len(art.design.groups)
    return art, dag.make_params(run.config, run.seed)


@contextlib.contextmanager
def window(run: cells.Run):
    """``cells.window``, with the depthwise kernel's time added to the
    reduced trace's ``kernel_s`` when the run traces."""
    if not run.trace:
        with cells.window(run):
            yield
        return
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = cells.HOST_TRACER_LEVEL
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(log_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN), \
                    cells._gc_pauses(run):
                yield
        loaded = trace_reduce.load(trace_reduce.find_xplane(log_dir))
        run.reduced = trace_reduce.reduce(
            loaded, {"pallas": trace_reduce.PALLAS_MARKER})
        run.reduced["kernel_s"]["dwconv"] = kernel_time.kernel_seconds(
            loaded, kernel_time.DWCONV_KERNEL)
        if run.trace_out:
            trace_reduce.save(loaded, run.trace_out)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def drive(run: cells.Run, clock: device.CompileClock) -> None:
    tr = run.traffic
    batch, k = tr["batch"], tr["distinct_batches"]
    art, params = compile_card(run)
    xs = model.make_inputs(run.config, run.config["input_fill"], k * batch,
                           run.seed)
    batches = xs.reshape((k, batch) + xs.shape[1:])
    for _ in range(tr["warmup_calls"]):
        art.run(batches[0], params)
    c0 = cells.end_setup(run, clock)
    with window(run):
        outs, elapsed = generator.closed_loop(
            lambda i: art.run(batches[i], params), k, run.seconds,
            annotate=cells.annotate(run))
    run.window.update(calls=len(outs), batch=batch,
                      completed=len(outs) * batch, elapsed_s=elapsed,
                      compile_s=clock.secs - c0)
    run.memory_peak_bytes = device.memory_peak_bytes()
    del art
    run.attempted = len(outs) * batch
    want = reference_dag.forward(run.config, params, xs)
    order = np.concatenate([np.arange(i * batch, (i + 1) * batch)
                            for i, _ in outs])
    run.checks.update(cells.compare(
        np.concatenate([np.asarray(y).reshape(batch, -1) for _, y in outs]),
        want[order]))
