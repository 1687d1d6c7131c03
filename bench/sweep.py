#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the
system sustains; and look at what stalls its generator.

    python3 bench/sweep.py --workload <cell> --rates 5600,7000 \\
        --seeds 11,12,13 --seconds 30 [--witness] [--trace-out t.json.gz]

One process, on the chip.  Each rate and seed is one run of the cell as
``bench/run.py`` makes it, with the mix's rate replaced; one JSON line
per run: latency median and 95th percentile, answered requests per
second, refusals, errors, how late the generator ran and the mean
batch.  The cell then offers 4/5 of the knee, fixed in its traffic
file.

``--witness`` looks for whole-process stalls: a second process that
only sleeps 1 ms at a time records when it woke late, the cgroup's CPU
throttling counters are read before and after each run, and each
stall of the generator (a request submitted over 20 ms late) is matched
against the witness's.  A stall that the witness shares stops the whole
machine slice, not just this process.  ``--trace-out`` traces the runs
and, for the longest generator spans, lists the host events that
overlap them.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import run as bench_run  # noqa: E402

#: a stall is a wake-up or a submission this late, in s
STALL_S = 0.02

#: the witness: sleeps 1 ms at a time for argv[1] seconds and prints
#: ``start length`` of every wake-up later than argv[2] seconds, as it
#: happens (the clock is CLOCK_MONOTONIC, shared with the parent)
WITNESS = """
import sys, time
end = time.perf_counter() + float(sys.argv[1])
t = time.perf_counter()
while t < end:
    time.sleep(0.001)
    now = time.perf_counter()
    if now - t > float(sys.argv[2]):
        print(t, now - t, flush=True)
    t = now
"""

CPU_STAT = ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat",
            "/sys/fs/cgroup/cpu,cpuacct/cpu.stat")


def cpu_stat() -> dict:
    """The cgroup's CPU counters (throttled periods and time)."""
    for path in CPU_STAT:
        if os.path.exists(path):
            with open(path) as f:
                return {k: int(v) for k, v in
                        (line.split() for line in f if line.strip())}
    return {}


def cpu_limit() -> str | None:
    for path in ("/sys/fs/cgroup/cpu.max", "/sys/fs/cgroup/cpu/cpu.cfs_quota_us"):
        if os.path.exists(path):
            with open(path) as f:
                return f"{path}: {f.read().strip()}"
    return None


def threads() -> int | None:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("Threads:"):
                return int(line.split()[1])
    return None


def merge(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def generator_stalls(res) -> list[list[float]]:
    """``[start, end]`` of each stretch in which the generator submitted
    more than STALL_S late."""
    idx = np.flatnonzero(res.late > STALL_S)
    return merge([[res.due[i], res.due[i] + res.late[i]] for i in idx])


def shared(stalls, witness) -> int:
    """How many of ``stalls`` overlap a stall of the witness."""
    return sum(any(ws < e and ws + wl > s for ws, wl in witness)
               for s, e in stalls)


def host_look(path: str, top: int = 3) -> list:
    """For the longest generator spans of a saved trace, the host events
    that overlap them, longest overlap first."""
    from bench import trace_reduce

    host = trace_reduce.read_saved(path)["host"]
    spans = sorted((h for h in host if h[0] in ("bench:wait", "bench:submit")
                    and h[2] > STALL_S * 1e9), key=lambda h: -h[2])[:top]
    out = []
    for name, s, d in spans:
        over = []
        for n, hs, hd in host:
            if n == trace_reduce.WINDOW_SPAN or (n, hs, hd) == (name, s, d):
                continue
            o = min(s + d, hs + hd) - max(s, hs)
            if o > 0:
                over.append([n, o / 1e6, hd / 1e6])
        over.sort(key=lambda r: -r[1])
        out.append({"span": name, "ms": d / 1e6, "overlapping": over[:8]})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--seeds", default="1")
    p.add_argument("--witness", action="store_true")
    p.add_argument("--trace-out")
    args = p.parse_args(argv)
    if not bench_run.has_program():
        return 1
    bench_run.prepare(__file__)
    from bench import cells, device, readings, registry

    base = registry.cell(args.workload)
    device.check(base.chips)
    clock = device.CompileClock().install()
    if args.witness:
        print(json.dumps({"cpu_limit": cpu_limit(), "cpus": os.cpu_count(),
                          "affinity": len(os.sched_getaffinity(0))}),
              flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in (float(r) for r in args.rates.split(",")):
            cell = dataclasses.replace(base, traffic=dict(base.traffic,
                                                          rate_per_s=rate))
            run = cells.Run(cell=cell, seed=seed, seconds=args.seconds,
                            trace=bool(args.trace_out),
                            t_start=time.perf_counter(),
                            trace_out=args.trace_out)
            witness, seen, stat0 = None, [], cpu_stat()
            if args.witness:
                witness = subprocess.Popen(
                    [sys.executable, "-c", WITNESS,
                     "600", str(STALL_S)],
                    stdout=subprocess.PIPE, text=True)
            try:
                cells.drive(run, clock)
            finally:
                if witness is not None:
                    witness.terminate()
                    out, _ = witness.communicate()
                    seen = [[float(a) for a in line.split()]
                            for line in out.splitlines() if line.strip()]
            w, res = run.window, run.open_loop
            row = {
                "rate_per_s": rate, "seed": seed,
                "latency_p50_ms": readings.latency_pct(run, 50),
                "latency_p95_ms": readings.latency_pct(run, 95),
                "answered_per_s": w["answered"] / w["last_answer_s"],
                "rejected": w["rejected"], "unanswered": w["unanswered"],
                "errored": w["errored"],
                "lateness_ms": w["lateness_ms"], "gc_pauses": w["gc_pauses"],
                "batch_occupancy": readings.histogram_mean(
                    run.engine_metrics, "serve_batch_occupancy"),
                "compile_s": w["compile_s"], "checks": run.checks}
            if args.witness:
                stalls = generator_stalls(res)
                t0 = res.due[0]
                in_window = [x for x in seen
                             if t0 <= x[0] <= res.t_close]
                row.update(
                    witness_stalls=len(in_window),
                    witness_longest_ms=sorted(
                        (round(1e3 * x[1], 3) for x in in_window),
                        reverse=True)[:8],
                    shared_with_witness=shared(stalls, seen),
                    threads=threads(),
                    throttled={k: v - stat0.get(k, 0)
                               for k, v in cpu_stat().items()},
                    stalls=len(stalls),
                    longest_stalls_ms=sorted(
                        (round(1e3 * (e - s), 3) for s, e in stalls),
                        reverse=True)[:8],
                    stall_starts_s=[round(s - t0, 3) for s, _ in stalls][:20])
            print(json.dumps(row, default=float), flush=True)
            if args.trace_out:
                print(json.dumps({"seed": seed, "host_look":
                                  host_look(args.trace_out)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
