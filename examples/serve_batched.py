"""Batched serving example on the artifact engine (ISSUE 7).

Compile a zoo classifier through the serving artifact cache, stand up a
dynamic-batching :class:`repro.serve.ServeEngine` over it, push an
open-loop burst of requests, and show the observability contract: the
batch coalescing, p50/p99 latency, and the serve and runtime spans
landing in the same Chrome trace as the artifact cache's events.

Run:  PYTHONPATH=src python examples/serve_batched.py
"""
import numpy as np

from repro.core.compile_driver import CompileOptions
from repro.frontends import zoo
from repro.instrument import Tracer, use_tracer, validate_chrome_trace
from repro.serve import ArtifactCache, ServeConfig, ServeEngine, run_load


def main() -> None:
    tracer = Tracer()
    with use_tracer(tracer):
        # artifact LRU keyed (model, CompileOptions.cache_key()) — the
        # second lookup is a hit, no second balanced-DP solve
        cache = ArtifactCache(capacity=4)
        options = CompileOptions(target="kv260")
        art = cache.get_or_compile("lenet5", zoo.ZOO["lenet5"], options)
        assert cache.get_or_compile("lenet5", zoo.ZOO["lenet5"],
                                    options) is art
        print(f"artifact cache: {cache.stats}")

        src = art.source
        name = src.graph_inputs[0]
        rng = np.random.default_rng(0)

        cfg = ServeConfig(max_batch=16, latency_budget_ms=5.0)
        with ServeEngine(art, cfg) as engine:
            # single blocking request (warms the bucket-1 executable)
            x = rng.integers(-4, 5, src.values[name].shape, dtype=np.int32)
            y = engine(x)
            print(f"single request → logits {y.shape}")

            # a concurrent burst coalesces into vmapped batches
            futs = [
                engine.submit(
                    rng.integers(-4, 5, src.values[name].shape,
                                 dtype=np.int32)
                )
                for _ in range(32)
            ]
            outs = [f.result() for f in futs]
            print(f"burst of 32 → {engine.stats['batches']} batches "
                  f"(max batch seen {engine.stats['max_batch_seen']})")
            assert all(o.shape == outs[0].shape for o in outs)

            # open-loop load level: offered vs achieved QPS, p50/p99
            rep = run_load(engine, offered_qps=200, requests=100, seed=1)
            print(f"offered {rep.offered_qps:.0f} qps → achieved "
                  f"{rep.achieved_qps:.0f} qps, p50 {rep.p50_ms:.1f} ms, "
                  f"p99 {rep.p99_ms:.1f} ms, mean batch {rep.mean_batch:.1f}")

    # one trace, one tracer: the artifact cache's events, the serve
    # worker's ming:serve.* spans and the runner's ming:* spans together
    obj = tracer.to_chrome()
    validate_chrome_trace(obj)
    serve_events = sorted({
        e["name"] for e in obj["traceEvents"]
        if e["name"].startswith(("ming:", "artifact"))
    })
    print(f"chrome trace OK: {len(obj['traceEvents'])} events, "
          f"serve and runtime events {serve_events}")


if __name__ == "__main__":
    main()
