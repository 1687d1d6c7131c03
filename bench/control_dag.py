#!/usr/bin/env python3
"""Readings that set a graph cell's limit: the program's and the
control's.

    python3 bench/control_dag.py --workload <cell> --seeds 1,2,3 --seconds 30

``bench/control.py`` for cells whose card is a graph (traffic kind
``closed_loop_dag``): one process, on the chip.  For each seed it makes
one run of the cell as ``bench/run.py`` does and records the number
the run compared (the program's reading), its samples/s and set-up.
Then it puts the control in the program's place: the graph reference
with every conv, depthwise and dense operand rounded to the precision
below the one the configuration states, on the same inputs, compared
with the reference by the same number (the control's reading).  One
JSON line per seed, then a summary line with the largest program
reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)
    if not bench_run.has_program():
        return 1
    bench_run.prepare(__file__)
    from bench import cells, dag, device, model, reference_dag, registry

    cell = registry.cell(args.workload)
    dev = device.check(cell.chips)
    clock = device.CompileClock().install()
    conf, tr = cell.config, cell.traffic
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = cells.Run(cell=cell, seed=seed, seconds=args.seconds,
                        trace=False, t_start=time.perf_counter())
        cells.drive(run, clock)
        params = dag.make_params(conf, seed)
        xs = model.make_inputs(conf, conf["input_fill"],
                               tr["batch"] * tr["distinct_batches"], seed)
        control = cells.compare(
            reference_dag.forward(conf, params, xs, control=conf["control"]),
            reference_dag.forward(conf, params, xs))
        w = run.window
        row = {"seed": seed, "program": run.checks, "control": control,
               "attempted": run.attempted, "failed": run.failed,
               "samples_per_s": w["completed"] / w["elapsed_s"],
               "compile_s": w["compile_s"],
               "setup_s": run.setup["setup_s"],
               "memory_peak_bytes": run.memory_peak_bytes}
        rows.append(row)
        print(json.dumps(row, default=float), flush=True)
    (name,) = rows[0]["control"]
    summary = {"workload": args.workload, "device": dev, "number": name,
               "program_max": max(r["program"][name] for r in rows),
               "control_min": min(r["control"][name] for r in rows),
               "seeds": len(rows)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
