"""Benchmark orchestrator: ``PYTHONPATH=src python -m benchmarks.run``.

Sections:
  1. Paper tables (Table II, Fig. 3, Table IV) from the calibrated
     FPGA resource model — one harness per paper artifact.
  2. Pass-pipeline report: pre/post-fusion footprint + layer-group
     partitioning of deep_cascade at 32²/64²/224².
  3. Kernel micro-validation: every Pallas kernel vs its ref.py oracle
     (interpret mode) with wall-times (CPU emulation — correctness
     gates, not TPU performance).
  4. MING DSE micro-bench: ILP solve times + explored nodes (the paper's
     "lightweight DSE" claim).
  5. Roofline summary from dry-run artifacts (if present) + the three
     hillclimb cells.

``--smoke`` runs the model-only sections (1, 2, 4) as a fast CI sanity
gate — no Pallas interpret-mode execution, no roofline artifacts.  Both
modes additionally compile every suite graph through the unified driver
(``repro.core.compile_driver``) and write ``BENCH_smoke.json`` (cycles,
peak BRAM, group count, spill bytes per graph) so the perf trajectory is
tracked across PRs.

Writes everything it prints; exit code 0 iff all validations pass.
"""
from __future__ import annotations

import argparse
import sys
import time


def _section(title: str):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}", flush=True)


def paper_tables() -> bool:
    from benchmarks import paper_tables as pt

    _section("Paper tables (Table II / Fig. 3 / Table IV)")
    pt.run_all()
    return True


def passes_section() -> bool:
    from benchmarks import passes_report

    _section("Pass pipeline (fusion + layer-group partitioning)")
    passes_report.run_all()
    return True


def bench_smoke_json(path: str = "BENCH_smoke.json") -> bool:
    """Compile every suite graph through the public API — once per
    device preset (KV260, ZU3EG) — and write the perf-trajectory
    snapshot (cycles + BRAM per graph per target) that CI archives and
    diffs across runs (``scripts/smoke_diff.py``).  Rows come straight
    from ``CompiledArtifact.report()``.

    Every row additionally carries a ``provenance`` stamp (ISSUE 6):
    git sha, host, compile wall seconds, and per-pass wall times —
    measurements, not metrics, so ``smoke_diff`` excludes them from the
    regression gate (timing jitter must never trip the >10% gate)."""
    import json

    from benchmarks.paper_tables import compile_cached, sweep_suite
    from repro.core.compile_driver import TARGETS
    from repro.instrument import provenance

    _section(f"BENCH smoke snapshot → {path}")
    data = {}
    ok = True
    stamp = provenance()  # identity fields, resolved once per snapshot
    print("graph,target,total_cycles,max_group_cycles,max_bram,groups,"
          "spill_bytes,weight_streamed")
    for name, make in sweep_suite().items():
        data[name] = {}
        for tname, target in TARGETS.items():
            t0 = time.perf_counter()
            art = compile_cached(name, make, target)
            compile_s = time.perf_counter() - t0
            rep = art.report()
            pr = art.design.pass_result
            data[name][tname] = {
                "total_cycles": rep.total_cycles,
                "max_group_cycles": rep.max_group_cycles,
                "max_bram": rep.max_bram,
                "max_dsp": rep.max_dsp,
                "groups": len(rep.groups),
                "spill_bytes": rep.spill_bytes,
                "weight_streamed": art.design.weight_streamed,
                "feasible": rep.feasible,
                "provenance": dict(
                    stamp,
                    compile_s=round(compile_s, 4),
                    pass_ms={p.name: round(p.wall_ms, 3)
                             for p in pr.passes} if pr else {},
                ),
            }
            r = data[name][tname]
            print(f"{name},{tname},{r['total_cycles']},"
                  f"{r['max_group_cycles']},{r['max_bram']},{r['groups']},"
                  f"{r['spill_bytes']},{r['weight_streamed']}")
            if not r["feasible"]:
                print(f"# WARNING: {name} infeasible under {tname} budgets")
                ok = False
    # always write the snapshot — a regression run is exactly when the
    # trajectory artifact matters most (feasible:false rows included)
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    return ok


def kernel_validation() -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    _section("Kernel validation vs ref.py oracles (interpret mode)")
    ok = True
    print("kernel,case,us_per_call,max_abs_err,pass")

    def check(name, case, fn, oracle, atol):
        nonlocal ok
        t0 = time.perf_counter()
        out = jax.tree.map(np.asarray, fn())
        dt = (time.perf_counter() - t0) * 1e6
        exp = jax.tree.map(np.asarray, oracle())
        errs = [
            np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(exp))
        ]
        err = max(errs)
        good = err <= atol
        ok = ok and good
        print(f"{name},{case},{dt:.0f},{err:.2e},{good}")

    key = jax.random.key(0)
    ks = jax.random.split(key, 8)

    x8 = jax.random.randint(ks[0], (1, 16, 16, 8), -8, 8, jnp.int8)
    w8 = jax.random.randint(ks[1], (3, 3, 8, 16), -4, 4, jnp.int8)
    check("conv2d_stream", "int8_3x3",
          lambda: ops.conv2d_stream(x8, w8, fuse_relu=True),
          lambda: ref.conv2d(x8, w8, fuse_relu=True), 0)

    q = jax.random.normal(ks[2], (2, 8, 128, 64), jnp.float32)
    k = jax.random.normal(ks[3], (2, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[4], (2, 2, 128, 64), jnp.float32)
    check("flash_attention", "gqa_causal_128",
          lambda: ops.flash_attention(q, k, v, causal=True,
                                      block_q=64, block_k=64),
          lambda: ref.attention(q, k, v, causal=True), 5e-5)

    xm = jax.random.normal(ks[5], (64, 128), jnp.float32)
    wg = jax.random.normal(ks[6], (128, 256), jnp.float32) * 0.05
    wu = jax.random.normal(ks[7], (128, 256), jnp.float32) * 0.05
    wd = jax.random.normal(ks[0], (256, 128), jnp.float32) * 0.05
    check("fused_mlp", "gated_silu",
          lambda: ops.fused_mlp(xm, wg, wu, wd, block_m=32, block_f=64),
          lambda: ref.mlp(xm, wg, wu, wd), 1e-3)

    xs = jax.random.normal(ks[1], (2, 64, 4, 16), jnp.float32)
    dt_ = jax.nn.softplus(jax.random.normal(ks[2], (2, 64, 4)))
    a = -jnp.exp(jax.random.normal(ks[3], (4,)) * 0.3)
    bm = jax.random.normal(ks[4], (2, 64, 8)) * 0.5
    cm = jax.random.normal(ks[5], (2, 64, 8)) * 0.5
    check("mamba2_ssd", "chunk16",
          lambda: ops.mamba2_ssd(xs, dt_, a, bm, cm, chunk=16),
          lambda: ref.ssd(xs, dt_, a, bm, cm), 5e-3)
    return ok


def dse_bench() -> bool:
    from repro.core import cnn_graphs
    from repro.core.dse import solve_ilp
    from repro.core.streaming import plan_streams

    _section("DSE micro-bench (lightweight-ILP claim)")
    print("kernel,solve_ms,explored,objective_cycles,feasible")
    for name, make in cnn_graphs.PAPER_SUITE.items():
        plan = plan_streams(make())
        t0 = time.perf_counter()
        res = solve_ilp(plan)
        dt = (time.perf_counter() - t0) * 1e3
        print(f"{name},{dt:.1f},{res.explored},{res.objective_cycles},"
              f"{res.feasible}")
    return True


def roofline_summary() -> bool:
    import os

    from benchmarks import roofline

    for label, out in (("BASELINE (paper-faithful)", "runs/dryrun"),
                       ("OPTIMIZED (beyond-paper)", "runs/dryrun_opt")):
        _section(f"Roofline summary — {label} ({out})")
        if not os.path.isdir(out):
            print(f"# {out} not present — run the dry-run sweep first")
            continue
        roofline.table(out, mesh="single")
        print()
        roofline.table(out, mesh="multi")
    print()
    _section("Hillclimb cell selection (from baseline)")
    if os.path.isdir("runs/dryrun"):
        roofline.pick_hillclimb_cells("runs/dryrun")
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fast model-only sanity pass (CI gate)")
    args = ap.parse_args(argv)
    ok = True
    ok &= paper_tables()
    ok &= passes_section()
    if not (args.skip_kernels or args.smoke):
        ok &= kernel_validation()
    ok &= dse_bench()
    ok &= bench_smoke_json()
    if not args.smoke:
        ok &= roofline_summary()
    _section(f"RESULT: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    sys.exit(main())
