"""Closed loop: back-to-back ``CompiledArtifact.run`` calls, each on the
next of ``distinct_batches`` batches of ``batch`` host arrays, for the
whole window; no queue in front of the program."""
from __future__ import annotations

import numpy as np

from bench import cells, device, model, reference, trace_reduce
from bench.traffic import generator


def drive(run: cells.Run, clock: device.CompileClock) -> None:
    tr = run.traffic
    batch, k = tr["batch"], tr["distinct_batches"]
    art, params = cells.compile_card(run)
    xs = model.make_inputs(run.config, run.config["input_fill"], k * batch,
                           run.seed)
    batches = xs.reshape((k, batch) + xs.shape[1:])
    for _ in range(tr["warmup_calls"]):
        art.run(batches[0], params)
    c0 = cells.end_setup(run, clock)
    with cells.window(run, {"pallas": trace_reduce.PALLAS_MARKER}):
        outs, elapsed = generator.closed_loop(
            lambda i: art.run(batches[i], params), k, run.seconds,
            annotate=cells.annotate(run))
    run.window.update(calls=len(outs), batch=batch,
                      completed=len(outs) * batch, elapsed_s=elapsed,
                      compile_s=clock.secs - c0)
    run.memory_peak_bytes = device.memory_peak_bytes()
    del art
    run.attempted = len(outs) * batch
    want = reference.forward(run.config, params, xs)
    order = np.concatenate([np.arange(i * batch, (i + 1) * batch)
                            for i, _ in outs])
    run.checks.update(cells.compare(
        np.concatenate([np.asarray(y).reshape(batch, -1) for _, y in outs]),
        want[order]))
