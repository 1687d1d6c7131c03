#!/usr/bin/env python3
"""Record a short traced run of a cell and keep its loaded trace.

    python3 bench/record_trace.py --workload <cell> --seed <n> --seconds 0.3 \\
        --out bench/tests/data/<cell>.trace.json.gz

One process, on the chip.  The run is a ``--trace 1`` run of
``bench/run.py``; the trace as :func:`bench.trace_reduce.load` reads it
(device operations, host events, the window) is written as gzipped
JSON, which the CPU tests of the trace reduction read.  The result line
is printed as ``bench/run.py`` prints it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=0.3)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    if not bench_run.has_program():
        return 1
    bench_run.prepare(__file__)
    from bench import device

    try:
        line = bench_run.execute(args.workload, args.seed, args.seconds, True,
                                 trace_out=args.out)
    except device.NoAccelerator as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
