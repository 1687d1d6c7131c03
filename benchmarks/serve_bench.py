"""Serving load-test benchmark → ``BENCH_serve.json`` (ISSUE 7).

For each zoo model × target, compile through the serving
:class:`~repro.serve.ArtifactCache`, warm every batch bucket the
dynamic batcher can land on (steady-state serving never recompiles, so
neither does the measured trajectory), then drive the
:class:`~repro.serve.ServeEngine` open-loop at a sweep of offered QPS
levels and record p50/p99 latency + achieved throughput per level.

The snapshot additionally carries a ``_speedup`` section measuring the
tentpole claim *in the same run*: lenet5 at batch 32, vmapped device
dispatch (``batch_mode="vmap"``) vs the per-sample loop
(``batch_mode="loop"``) — the acceptance gate is ≥5×.

Every row carries a provenance stamp (ISSUE 6); ``scripts/smoke_diff.py
--mode serve`` diffs the rows fail-soft across runs (only a >10% p99 or
throughput regression hard-fails, provenance stripped).  Each
model×target cell additionally carries the engine's **metrics
snapshot** (ISSUE 10: lifecycle-stage histograms, rejection causes,
batch occupancy — the full :meth:`ServeEngine.metrics` document,
diff-exempt like provenance); ``--metrics-out`` also writes the last
cell's snapshot standalone for the CI artifact.

Usage::

  PYTHONPATH=src python -m benchmarks.serve_bench            # full sweep
  PYTHONPATH=src python -m benchmarks.serve_bench \
      --models lenet5 --targets kv260 --qps 200 --requests 60  # CI smoke
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.compile_driver import TARGETS, CompileOptions
from repro.frontends import zoo
from repro.instrument import provenance, validate_metrics_snapshot
from repro.kernels import ops
from repro.serve import ArtifactCache, ServeConfig, ServeEngine, run_load

#: the committed sweep: every zoo model on both targets, offered QPS
#: from comfortable to saturating (lenet5 vmapped capacity on one CPU
#: is a few thousand samples/s; the top level queues hard on purpose —
#: open-loop p99 under pressure is the number that matters).
DEFAULT_MODELS = ("lenet5", "tiny_vgg_32", "edge_residual_32")
DEFAULT_TARGETS = ("kv260", "zu3eg")
DEFAULT_QPS = (50.0, 200.0, 800.0)


def _warm_buckets(art, max_batch: int, seed: int) -> list[int]:
    """Execute one batched run per bucket ≤ ``max_batch`` so the serve
    sweep measures steady-state dispatch, not jit compiles."""
    src = art.design.source
    rng = np.random.default_rng(seed)
    x = {
        k: rng.integers(-4, 5, size=(max_batch,) + src.values[k].shape,
                        dtype=np.int32)
        for k in src.graph_inputs
    }
    warmed = []
    for b in ops.BATCH_BUCKETS:
        if b > max_batch:
            break
        art.run({k: v[:b] for k, v in x.items()})
        warmed.append(b)
    return warmed


def bench_speedup(cache: ArtifactCache, *, batch: int = 32,
                  reps: int = 3, seed: int = 0) -> dict:
    """The tentpole gate: lenet5@kv260 batch-``batch`` vmapped vs
    per-sample loop, min wall over ``reps`` after warming both paths."""
    options = CompileOptions(target=TARGETS["kv260"])
    art = cache.get_or_compile("lenet5", zoo.ZOO["lenet5"], options)
    src = art.design.source
    rng = np.random.default_rng(seed)
    x = {
        k: rng.integers(-4, 5, size=(batch,) + src.values[k].shape,
                        dtype=np.int32)
        for k in src.graph_inputs
    }
    y_loop = art.run(x, batch_mode="loop")
    y_vmap = art.run(x, batch_mode="vmap")
    exact = bool(np.array_equal(y_loop, y_vmap))
    loop_ms = min(
        _timed(lambda: art.run(x, batch_mode="loop")) for _ in range(reps)
    )
    vmap_ms = min(
        _timed(lambda: art.run(x, batch_mode="vmap")) for _ in range(reps)
    )
    return {
        "model": "lenet5",
        "target": "kv260",
        "batch": batch,
        "loop_ms": round(loop_ms, 3),
        "vmap_ms": round(vmap_ms, 3),
        "speedup": round(loop_ms / vmap_ms, 2) if vmap_ms else 0.0,
        "bit_exact": exact,
        "loop_throughput_sps": round(batch / loop_ms * 1e3, 1),
        "vmap_throughput_sps": round(batch / vmap_ms * 1e3, 1),
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return (time.perf_counter() - t0) * 1e3


def bench_serve_json(path: str = "BENCH_serve.json", *,
                     models=DEFAULT_MODELS, targets=DEFAULT_TARGETS,
                     qps_levels=DEFAULT_QPS, requests: int = 120,
                     max_batch: int = 32, latency_budget_ms: float = 5.0,
                     seed: int = 0, speedup: bool = True,
                     metrics_out: str | None = None) -> dict:
    cache = ArtifactCache(capacity=2 * len(models))
    stamp = provenance()
    data: dict = {}
    last_snapshot: dict | None = None
    print("model,target,offered_qps,achieved_qps,p50_ms,p99_ms,mean_batch")
    for model in models:
        if model not in zoo.ZOO:
            raise KeyError(f"unknown zoo model {model!r} — {sorted(zoo.ZOO)}")
        data[model] = {}
        for tname in targets:
            options = CompileOptions(target=TARGETS[tname])
            t0 = time.perf_counter()
            art = cache.get_or_compile(model, zoo.ZOO[model], options)
            compile_s = time.perf_counter() - t0
            warmed = _warm_buckets(art, max_batch, seed)
            cfg = ServeConfig(max_batch=max_batch,
                              latency_budget_ms=latency_budget_ms)
            rows = []
            with ServeEngine(art, cfg, seed=seed) as eng:
                for q in qps_levels:
                    rep = run_load(eng, offered_qps=q, requests=requests,
                                   seed=seed)
                    row = rep.row()
                    rows.append(row)
                    print(f"{model},{tname},{row['offered_qps']},"
                          f"{row['achieved_qps']},{row['p50_ms']},"
                          f"{row['p99_ms']},{row['mean_batch']}")
                snapshot = validate_metrics_snapshot(eng.metrics())
            last_snapshot = snapshot
            data[model][tname] = {
                "loads": rows,
                "max_batch": max_batch,
                "latency_budget_ms": latency_budget_ms,
                "warmed_buckets": warmed,
                "metrics": snapshot,
                "provenance": dict(stamp, compile_s=round(compile_s, 4)),
            }
    if speedup:
        sp = bench_speedup(cache, batch=max_batch, seed=seed)
        sp["provenance"] = dict(stamp)
        data["_speedup"] = sp
        print(f"# speedup lenet5@kv260 b{sp['batch']}: "
              f"loop {sp['loop_ms']}ms vmap {sp['vmap_ms']}ms "
              f"= {sp['speedup']}x (bit_exact={sp['bit_exact']})")
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")
    if metrics_out and last_snapshot is not None:
        with open(metrics_out, "w") as f:
            json.dump(last_snapshot, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {metrics_out}")
    return data


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--models", default=",".join(DEFAULT_MODELS))
    ap.add_argument("--targets", default=",".join(DEFAULT_TARGETS))
    ap.add_argument("--qps", default=",".join(str(q) for q in DEFAULT_QPS))
    ap.add_argument("--requests", type=int, default=120)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--latency-budget-ms", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-speedup", action="store_true",
                    help="skip the lenet5 vmap-vs-loop gate section")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="also write the last cell's metrics snapshot "
                         "standalone (the CI artifact)")
    ap.add_argument("--min-speedup", type=float, default=5.0,
                    help="hard-fail when the vmap-vs-loop speedup is "
                         "below this; 0 makes the speedup informational "
                         "(CI uses 0: wall-clock on shared runners is "
                         "noisy-neighbor flaky). Bit-exactness always "
                         "hard-fails.")
    args = ap.parse_args(argv)
    data = bench_serve_json(
        args.out,
        models=tuple(m for m in args.models.split(",") if m),
        targets=tuple(t for t in args.targets.split(",") if t),
        qps_levels=tuple(float(q) for q in args.qps.split(",") if q),
        requests=args.requests,
        max_batch=args.max_batch,
        latency_budget_ms=args.latency_budget_ms,
        seed=args.seed,
        speedup=not args.no_speedup,
        metrics_out=args.metrics_out,
    )
    sp = data.get("_speedup")
    if sp and not sp["bit_exact"]:
        # correctness is never a soft gate
        print("# FAIL: vmap run is not bit-exact against the loop")
        return 1
    if sp and sp["speedup"] < args.min_speedup:
        print(f"# FAIL: batched speedup gate "
              f"(speedup={sp['speedup']}x < {args.min_speedup}x)")
        return 1
    return 0


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    raise SystemExit(main())
