"""Serving runtime (ISSUE 7): cache_key, ArtifactCache, ServeEngine,
load generator, and the single-tracer observability contract.
"""
import queue
import time

import numpy as np
import pytest

from repro import api
from repro.core import cnn_graphs
from repro.core.compile_driver import CompileOptions, KV260, ZU3EG
from repro.frontends import zoo
from repro.instrument import Tracer, use_tracer, validate_chrome_trace
from repro.serve import (
    ArtifactCache,
    LoadReport,
    ServeConfig,
    ServeEngine,
    run_load,
)


class TestCacheKey:
    def test_stable_across_instances(self):
        assert (CompileOptions(target="kv260").cache_key()
                == CompileOptions(target="kv260").cache_key())
        assert CompileOptions().cache_key() == CompileOptions().cache_key()

    def test_distinct_per_target_and_options(self):
        keys = {
            CompileOptions(target="kv260").cache_key(),
            CompileOptions(target="zu3eg").cache_key(),
            CompileOptions(strategy="greedy").cache_key(),
            CompileOptions(max_unroll=8).cache_key(),
            CompileOptions(weight_streaming="off").cache_key(),
            CompileOptions(passes=("dce",)).cache_key(),
        }
        assert len(keys) == 6

    def test_trace_does_not_change_identity(self):
        """Instrumentation never changes what gets compiled — a traced
        and an untraced compile must share a cache entry."""
        assert (CompileOptions(trace=True).cache_key()
                == CompileOptions().cache_key())

    def test_key_is_short_hashable_digest(self):
        k = CompileOptions().cache_key()
        assert isinstance(k, str) and len(k) == 16
        hash(k)


class TestArtifactCache:
    def _make(self, c_out):
        return lambda: cnn_graphs.conv_relu(8, c_out=c_out)

    def test_hit_returns_same_artifact(self):
        cache = ArtifactCache(capacity=4)
        a1 = cache.get_or_compile("m", self._make(4), CompileOptions())
        a2 = cache.get_or_compile("m", self._make(4), CompileOptions())
        assert a1 is a2
        assert cache.stats == {"hits": 1, "misses": 1, "evictions": 0}

    def test_distinct_options_distinct_entries(self):
        cache = ArtifactCache(capacity=4)
        a = cache.get_or_compile("m", self._make(4),
                                 CompileOptions(target="kv260"))
        b = cache.get_or_compile("m", self._make(4),
                                 CompileOptions(target="zu3eg"))
        assert a is not b and len(cache) == 2

    def test_lru_eviction_bounded(self):
        cache = ArtifactCache(capacity=2)
        for name in ("a", "b", "c"):
            cache.get_or_compile(name, self._make(4), CompileOptions())
        assert len(cache) == 2
        assert cache.stats["evictions"] == 1
        # "a" was evicted; "c" (and "b") still resident
        assert cache.get("a", CompileOptions()) is None
        assert cache.get("c", CompileOptions()) is not None

    def test_lru_refresh_on_hit(self):
        cache = ArtifactCache(capacity=2)
        cache.get_or_compile("a", self._make(4), CompileOptions())
        cache.get_or_compile("b", self._make(5), CompileOptions())
        cache.get_or_compile("a", self._make(4), CompileOptions())  # hot
        cache.get_or_compile("c", self._make(6), CompileOptions())
        assert cache.get("a", CompileOptions()) is not None
        assert cache.get("b", CompileOptions()) is None

    def test_capacity_validated(self):
        with pytest.raises(ValueError, match="capacity"):
            ArtifactCache(capacity=0)


@pytest.fixture(scope="module")
def lenet_art():
    return api.compile_graph(zoo.lenet5())


def _sample_inputs(src, n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {k: rng.integers(-4, 5, size=src.values[k].shape, dtype=np.int32)
         for k in src.graph_inputs}
        for _ in range(n)
    ]


class TestServeEngine:
    def test_results_match_direct_run(self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 5, seed=1)
        with ServeEngine(lenet_art, ServeConfig(max_batch=4)) as eng:
            futs = [eng.submit(s) for s in samples]
            got = [f.result(timeout=60) for f in futs]
        name = lenet_art.source.graph_inputs[0]
        stacked = np.stack([s[name] for s in samples])
        want = lenet_art.run({name: stacked})
        for i in range(5):
            np.testing.assert_array_equal(got[i], want[i])

    def test_batches_respect_max_batch(self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 6, seed=2)
        with ServeEngine(lenet_art,
                         ServeConfig(max_batch=2,
                                     latency_budget_ms=50.0)) as eng:
            futs = [eng.submit(s) for s in samples]
            for f in futs:
                f.result(timeout=60)
        assert eng.stats["max_batch_seen"] <= 2
        assert eng.stats["requests"] == 6
        assert eng.stats["batches"] >= 3

    def test_dynamic_batching_coalesces(self, lenet_art):
        """A generous budget coalesces queued singles into one batch."""
        samples = _sample_inputs(lenet_art.source, 4, seed=3)
        with ServeEngine(lenet_art,
                         ServeConfig(max_batch=8,
                                     latency_budget_ms=500.0)) as eng:
            futs = [eng.submit(s) for s in samples]
            for f in futs:
                f.result(timeout=60)
        assert eng.stats["batches"] < 4

    def test_bare_array_single_input(self, lenet_art):
        x = _sample_inputs(lenet_art.source, 1, seed=4)[0]
        name = lenet_art.source.graph_inputs[0]
        with ServeEngine(lenet_art) as eng:
            got = eng(x[name])
        np.testing.assert_array_equal(got,
                                      lenet_art.run({name: x[name][None]})[0])

    def test_malformed_requests_rejected_at_admission(self, lenet_art):
        """Bad requests fail their *own* caller at submit(), before
        they can poison the innocent requests they would have
        co-batched with at np.stack time."""
        src = lenet_art.source
        name = src.graph_inputs[0]
        good = _sample_inputs(src, 1, seed=5)[0]
        with ServeEngine(lenet_art) as eng:
            with pytest.raises(ValueError, match="per-sample shape"):
                eng.submit(np.zeros((3, 3), np.int32))  # wrong shape
            with pytest.raises(ValueError, match="missing"):
                eng.submit({})  # dict missing the graph input
            with pytest.raises(ValueError, match="unknown"):
                eng.submit(dict(good, bogus=good[name]))
            with pytest.raises(ValueError, match="per-sample shape"):
                eng.submit({name: good[name][None]})  # stray batch dim
            # engine keeps serving well-formed requests
            eng(good)
        assert eng.stats["requests"] == 1

    def test_execute_errors_propagate_to_future(self, lenet_art):
        """A failure *inside* the batch execute still resolves every
        future with the exception — no hung callers."""
        x = _sample_inputs(lenet_art.source, 1, seed=5)[0]

        def boom(*a, **k):
            raise RuntimeError("kaboom")

        with ServeEngine(lenet_art) as eng:
            lenet_art.run = boom  # instance shadow over the method
            try:
                fut = eng.submit(x)
                with pytest.raises(RuntimeError, match="kaboom"):
                    fut.result(timeout=60)
            finally:
                del lenet_art.run
            # engine keeps serving after a poisoned batch
            eng(x)

    def test_stop_drains_queued_requests(self, lenet_art):
        """Requests stuck in the queue behind the stop signal fail
        loudly with RuntimeError instead of blocking their callers on
        fut.result() forever."""
        import threading
        from concurrent.futures import Future

        from repro.serve import engine as engine_mod

        x = _sample_inputs(lenet_art.source, 1, seed=11)[0]
        started, gate = threading.Event(), threading.Event()
        real_run = type(lenet_art).run

        def slow_run(*a, **k):
            started.set()
            assert gate.wait(timeout=30)
            return real_run(lenet_art, *a, **k)

        lenet_art.run = slow_run  # instance shadow over the method
        try:
            eng = ServeEngine(lenet_art,
                              ServeConfig(latency_budget_ms=0.0)).start()
            fut = eng.submit(x)
            assert started.wait(timeout=30)  # worker busy in _execute
            # jam a request behind a stop signal — the shape admission
            # racing shutdown would take
            eng._queue.put(engine_mod._STOP)
            orphan = engine_mod._Request(
                -1, {k: np.asarray(v) for k, v in x.items()},
                Future(), time.perf_counter())
            eng._queue.put(orphan)
            gate.set()
            eng.stop()
        finally:
            del lenet_art.run
        fut.result(timeout=60)  # the in-flight batch still completed
        with pytest.raises(RuntimeError, match="engine stopped"):
            orphan.future.result(timeout=60)
        assert eng.stats["rejected"] == 1
        with pytest.raises(RuntimeError, match="not started"):
            eng.submit(x)  # a stopped engine rejects new work

    def test_submit_requires_start(self, lenet_art):
        eng = ServeEngine(lenet_art)
        with pytest.raises(RuntimeError, match="not started"):
            eng.submit(np.zeros((1,), np.int32))

    def test_queue_depth_rejects(self, lenet_art):
        eng = ServeEngine(lenet_art, ServeConfig(queue_depth=1))
        # fill the queue without a worker draining it
        eng._worker = object()  # type: ignore[assignment]
        x = _sample_inputs(lenet_art.source, 1, seed=6)[0]
        eng._params_resolved = {}
        eng.submit(x)
        with pytest.raises(queue.Full):
            eng.submit(x)
        assert eng.stats["rejected"] == 1

    def test_config_validation(self):
        with pytest.raises(ValueError, match="max_batch"):
            ServeConfig(max_batch=0)
        with pytest.raises(ValueError, match="latency_budget_ms"):
            ServeConfig(latency_budget_ms=-1)
        with pytest.raises(ValueError, match="queue_depth"):
            ServeConfig(queue_depth=0)


class TestServeTracing:
    """Acceptance: serve counters land in the PR 6 Chrome trace — the
    same tracer, not a second telemetry path."""

    def test_serve_counters_in_chrome_trace(self, lenet_art):
        tracer = Tracer()
        samples = _sample_inputs(lenet_art.source, 4, seed=7)
        with use_tracer(tracer):
            cache = ArtifactCache(capacity=2)
            cache.put("lenet5", CompileOptions(), lenet_art)
            art = cache.get_or_compile("lenet5", zoo.lenet5,
                                       CompileOptions())
            assert art is lenet_art
            with ServeEngine(art, ServeConfig(max_batch=4)) as eng:
                futs = [eng.submit(s) for s in samples]
                for f in futs:
                    f.result(timeout=60)
        obj = tracer.to_chrome()
        validate_chrome_trace(obj)
        names = {e["name"] for e in obj["traceEvents"]}
        assert {"ming:serve.form", "ming:serve.stack", "ming:run",
                "ming:serve.respond", "artifact_cache"} <= names
        assert not {"serve_batch", "serve_latency_ms", "serve_qps"} & names
        # counter args are numeric (validate_chrome_trace-compatible)
        for ev in obj["traceEvents"]:
            if ev["ph"] == "C":
                assert all(isinstance(v, (int, float))
                           for v in ev["args"].values())

    def test_worker_thread_sees_artifact_tracer(self):
        """No ambient tracer: the worker installs the artifact's
        compile-time tracer across the thread boundary."""
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4),
                                api.CompileOptions(trace=True))
        x = _sample_inputs(art.source, 2, seed=8)
        with ServeEngine(art, ServeConfig(max_batch=2)) as eng:
            futs = [eng.submit(s) for s in x]
            for f in futs:
                f.result(timeout=60)
        names = {e["name"] for e in art.tracer.events}
        assert "ming:serve.stack" in names and "ming:dispatch" in names


class TestLoadGenerator:
    def test_report_shape_and_totals(self, lenet_art):
        with ServeEngine(lenet_art, ServeConfig(max_batch=8)) as eng:
            rep = run_load(eng, offered_qps=500, requests=20, seed=9)
        assert isinstance(rep, LoadReport)
        assert rep.requests == 20
        assert rep.achieved_qps > 0
        assert 0 < rep.p50_ms <= rep.p99_ms
        assert rep.mean_batch >= 1
        row = rep.row()
        assert set(row) == {"offered_qps", "achieved_qps", "requests",
                            "duration_s", "p50_ms", "p99_ms", "mean_ms",
                            "mean_batch", "batches", "rejected"}

    def test_validates_arguments(self, lenet_art):
        with ServeEngine(lenet_art) as eng:
            with pytest.raises(ValueError, match="offered_qps"):
                run_load(eng, offered_qps=0, requests=1)
            with pytest.raises(ValueError, match="requests"):
                run_load(eng, offered_qps=1, requests=0)


class TestServeDiff:
    """scripts/smoke_diff.py --mode serve: fail-soft row diffs, hard
    fail only on >threshold p99/throughput regressions, provenance
    stripped."""

    @staticmethod
    def _sd():
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "smoke_diff_serve",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "smoke_diff.py"))
        sd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sd)
        return sd

    @staticmethod
    def _snap(p99=10.0, qps=200.0, sha="aaa"):
        return {
            "lenet5": {"kv260": {
                "loads": [{"offered_qps": 200.0, "achieved_qps": qps,
                           "p50_ms": 7.0, "p99_ms": p99, "mean_ms": 7.5,
                           "mean_batch": 2.0, "requests": 60,
                           "duration_s": 0.3, "batches": 30, "rejected": 0,
                           "provenance": {"git_sha": sha}}],
                "provenance": {"git_sha": sha},
            }},
            "_speedup": {"speedup": 10.0, "provenance": {"git_sha": sha}},
        }

    def test_provenance_only_change_is_soft(self):
        sd = self._sd()
        lines = []
        assert sd.diff_serve(self._snap(sha="aaa"), self._snap(sha="bbb"),
                             0.10, emit=lines.append) == 0
        assert lines == [
            "model,target,offered_qps,metric,previous,current,delta_pct"
        ]

    def test_small_drift_is_soft(self):
        sd = self._sd()
        assert sd.diff_serve(self._snap(p99=10.0), self._snap(p99=10.5),
                             0.10, emit=lambda *_: None) == 0

    def test_p99_and_throughput_regressions_hard_fail(self):
        sd = self._sd()
        assert sd.diff_serve(self._snap(p99=10.0), self._snap(p99=12.0),
                             0.10, emit=lambda *_: None) == 1
        assert sd.diff_serve(self._snap(qps=200.0), self._snap(qps=150.0),
                             0.10, emit=lambda *_: None) == 1
        # improvements never fail
        assert sd.diff_serve(self._snap(p99=12.0, qps=150.0),
                             self._snap(p99=10.0, qps=200.0),
                             0.10, emit=lambda *_: None) == 0
