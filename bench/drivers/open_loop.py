"""Open loop: single requests into a ``ServeEngine`` at the arrivals the
mix's parameters give (:func:`bench.traffic.generator.arrival_offsets`),
never waiting on an answer inside the schedule; latency from each
request's scheduled arrival."""
from __future__ import annotations

import numpy as np

from bench import cells, device, model, reference
from bench.traffic import generator


def drive(run: cells.Run, clock: device.CompileClock) -> None:
    from repro.serve import ServeConfig, ServeEngine

    tr = run.traffic
    art, params = cells.compile_card(run)
    pool = model.make_inputs(run.config, run.config["input_fill"],
                             tr["distinct_inputs"], run.seed)
    server = ServeConfig(**tr["server"])
    # every batch size the engine can form: the runner pads and slices
    # each one with its own small programs
    for n in range(1, server.max_batch + 1):
        art.run(pool[:n], params)
    offsets = generator.arrival_offsets(tr, run.seconds, run.seed)
    pick = np.random.default_rng(run.seed).integers(0, len(pool),
                                                    len(offsets))
    c0 = cells.end_setup(run, clock)
    eng = ServeEngine(art, server, params=params).start()
    try:
        with cells.window(run):
            res = generator.open_loop(lambda i: eng.submit(pool[pick[i]]),
                                      offsets, drain_s=tr["drain_s"],
                                      annotate=cells.annotate(run))
    finally:
        eng.stop()
    run.engine_metrics = eng.metrics()
    run.open_loop = res
    run.memory_peak_bytes = device.memory_peak_bytes()
    del art, eng
    run.latencies_ms = res.latencies_ms()
    ok = np.flatnonzero(res.answered())
    unanswered = int(res.unanswered().sum())
    errored = int(res.errored().sum())
    last = float(res.done[ok].max() if len(ok) else res.t_close)
    run.attempted = len(offsets)
    run.failed = run.attempted - len(ok)
    run.window.update(
        requests=len(offsets), answered=len(ok),
        rejected=int(res.rejected.sum()), errored=errored,
        unanswered=unanswered, lateness_ms=generator.lateness_ms(res.late),
        compile_s=clock.secs - c0, completed=len(ok),
        elapsed_s=last - res.due[0], last_answer_s=last - res.due[0])
    if not len(ok):
        raise RuntimeError(f"none of {len(offsets)} requests was answered "
                           f"({errored} with an error)")
    want = reference.forward(run.config, params, pool)
    got = np.stack([np.asarray(res.results[i]).reshape(-1) for i in ok])
    run.checks.update(cells.compare(got, want[pick[ok]]))
    run.checks["unanswered"] = unanswered
    run.checks["errored"] = errored
