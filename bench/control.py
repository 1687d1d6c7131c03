#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 2

One process, on the chip.  For each seed it makes one run of the cell
as ``bench/run.py`` does, with a short window at the cell's own load,
and records the number the run compared (the program's reading).  Then
it puts the control in the program's place: the plain reference at the
precision below the one the configuration states, on the same inputs,
compared with the reference by the same number (the control's
reading).  One JSON line per seed, then a summary line with the
largest program reading and the smallest control reading.
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
    from bench import cells, device, model, reference, registry

    cell = registry.cell(args.workload)
    dev = device.check(cell.chips)
    clock = device.CompileClock().install()
    conf = cell.config
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = cells.Run(cell=cell, seed=seed, seconds=args.seconds,
                        trace=False, t_start=time.perf_counter())
        cells.drive(run, clock)
        params = model.make_weights(conf, conf["weight_fill"], seed)
        if cell.traffic["kind"] == "closed_loop":
            n = cell.traffic["batch"] * cell.traffic["distinct_batches"]
        else:
            n = cell.traffic["distinct_inputs"]
        xs = model.make_inputs(conf, conf["input_fill"], n, seed)
        control = cells.compare(
            reference.forward(conf, params, xs, control=conf["control"]),
            reference.forward(conf, params, xs))
        row = {"seed": seed, "program": run.checks, "control": control,
               "attempted": run.attempted, "failed": run.failed,
               "completed": run.window["completed"],
               "elapsed_s": run.window["elapsed_s"],
               "setup_s": run.setup["setup_s"]}
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
