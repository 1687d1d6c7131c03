"""Metrics registry, request-lifecycle observability, and the
modeled-vs-measured profiler (ISSUE 10).

The two contracts under test, in the tracer's image:

* **disabled path is free and invisible** — with ``NULL_REGISTRY`` (the
  ambient default) every instrument is a shared no-op and instrumented
  code produces byte-identical output;
* **enabled path is consistent** — snapshots are schema-valid,
  histogram buckets are cumulative ``le`` semantics exactly, counters
  are thread-safe under contention, and the serve engine's lifecycle
  series add up.
"""
import json
import queue
import threading
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro import api
from repro.frontends import zoo
from repro.instrument import (
    NULL_REGISTRY,
    MetricsRegistry,
    profile_artifact,
    use_metrics,
    validate_metrics_snapshot,
)
from repro.instrument import metrics as metrics_mod
from repro.instrument.metrics import LATENCY_BUCKETS_MS, quantile
from repro.serve import ServeConfig, ServeEngine, run_load
from repro.serve.loadgen import _percentile


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


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------


class TestCounter:
    def test_inc_value_total(self):
        r = MetricsRegistry()
        c = r.counter("reqs", "requests", labels=("cause",))
        c.inc(cause="a")
        c.inc(2.5, cause="b")
        assert c.value(cause="a") == 1
        assert c.value(cause="b") == 2.5
        assert c.value(cause="never") == 0
        assert c.total() == 3.5

    def test_counters_only_go_up(self):
        c = MetricsRegistry().counter("n")
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)

    def test_label_names_enforced(self):
        c = MetricsRegistry().counter("n", labels=("cause",))
        with pytest.raises(ValueError, match="label"):
            c.inc()  # missing the declared label
        with pytest.raises(ValueError, match="label"):
            c.inc(cause="x", extra="y")

    def test_redeclare_get_or_create(self):
        r = MetricsRegistry()
        assert r.counter("n", labels=("a",)) is r.counter("n", labels=("a",))
        with pytest.raises(ValueError, match="already declared"):
            r.counter("n", labels=("b",))  # different labels
        with pytest.raises(ValueError, match="already declared"):
            r.gauge("n")  # different kind

    def test_redeclare_histogram_buckets(self):
        r = MetricsRegistry()
        h = r.histogram("lat")
        assert r.histogram("lat") is h  # the default ladder, same object
        assert r.histogram("lat", buckets=list(LATENCY_BUCKETS_MS)) is h
        with pytest.raises(ValueError, match="already declared"):
            r.histogram("lat", buckets=(1.0, 10.0))  # different bounds
        with pytest.raises(ValueError, match="already declared"):
            r.counter("lat")  # different kind


class TestGauge:
    def test_set_inc_dec(self):
        g = MetricsRegistry().gauge("depth")
        g.inc()
        g.inc(3)
        g.dec()
        assert g.value() == 3
        g.set(-7.5)
        assert g.value() == -7.5


class TestHistogram:
    def test_sum_count_min_max(self):
        h = MetricsRegistry().histogram("lat", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 500.0):
            h.observe(v)
        row = h._export_child(h._children[()])
        assert row["count"] == 3
        assert row["sum"] == pytest.approx(505.5)
        assert row["min"] == 0.5 and row["max"] == 500.0

    def test_bucket_bounds_validated(self):
        r = MetricsRegistry()
        with pytest.raises(ValueError, match="at least one"):
            r.histogram("a", buckets=())
        with pytest.raises(ValueError, match="strictly increase"):
            r.histogram("b", buckets=(1.0, 1.0))
        with pytest.raises(ValueError, match="finite"):
            r.histogram("c", buckets=(1.0, float("inf")))

    def test_default_buckets_are_the_latency_ladder(self):
        h = MetricsRegistry().histogram("lat")
        assert h.buckets == LATENCY_BUCKETS_MS
        assert all(b2 == 2 * b1 for b1, b2 in
                   zip(LATENCY_BUCKETS_MS, LATENCY_BUCKETS_MS[1:]))

    def test_boundary_value_lands_in_its_bucket(self):
        """``le`` semantics: an observation exactly at a bound counts in
        that bound's bucket, not the next one."""
        bounds = (1.0, 2.0, 4.0)
        r = MetricsRegistry()
        h = r.histogram("lat", buckets=bounds)
        for b in bounds:
            h.observe(b)
        row = r.snapshot()["histograms"]["lat"]["values"][0]
        cum = {b["le"]: b["count"] for b in row["buckets"]}
        assert cum[1.0] == 1 and cum[2.0] == 2 and cum[4.0] == 3
        assert cum["+Inf"] == 3

    @settings(max_examples=40)
    @given(st.lists(st.integers(min_value=-(10 ** 4), max_value=10 ** 7),
                    min_size=0, max_size=50))
    def test_bucket_counts_match_direct_computation(self, raw):
        """Property sweep: for arbitrary observations the exported
        cumulative counts equal a direct ``v <= bound`` count, the +Inf
        bucket equals the total, and counts never decrease."""
        values = [v / 97.0 for v in raw]  # cover sub-bucket fractions
        r = MetricsRegistry()
        h = r.histogram("lat", buckets=LATENCY_BUCKETS_MS)
        for v in values:
            h.observe(v)
        snap = validate_metrics_snapshot(r.snapshot())
        rows = snap["histograms"]["lat"]["values"]
        if not values:
            assert rows == []
            return
        buckets = rows[0]["buckets"]
        for b in buckets[:-1]:
            assert b["count"] == sum(1 for v in values if v <= b["le"])
        assert buckets[-1]["le"] == "+Inf"
        assert buckets[-1]["count"] == len(values)
        counts = [b["count"] for b in buckets]
        assert counts == sorted(counts)
        assert rows[0]["sum"] == pytest.approx(sum(values), abs=1e-4)

    def test_quantile_estimator(self):
        r = MetricsRegistry()
        h = r.histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
        for v in (0.5, 1.5, 3.0, 6.0):
            h.observe(v)
        row = r.snapshot()["histograms"]["lat"]["values"][0]
        assert 0 < quantile(row, 50) <= 4.0
        assert quantile(row, 100) <= 8.0
        assert quantile({"count": 0, "buckets": []}, 50) == 0.0
        with pytest.raises(ValueError, match="quantile"):
            quantile(row, 101)


class TestThreadSafety:
    def test_concurrent_updates_are_exact(self):
        r = MetricsRegistry()
        c = r.counter("n", labels=("worker",))
        h = r.histogram("lat", buckets=(1.0, 10.0, 100.0))
        g = r.gauge("depth")
        N, K = 8, 500

        def work(w):
            for i in range(K):
                c.inc(worker=str(w % 2))
                h.observe(float(i % 7))
                g.inc()
                g.dec()

        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.total() == N * K
        snap = validate_metrics_snapshot(r.snapshot())
        row = snap["histograms"]["lat"]["values"][0]
        assert row["count"] == N * K
        assert row["buckets"][-1]["count"] == N * K
        assert g.value() == 0


# ---------------------------------------------------------------------------
# snapshot schema + exposition
# ---------------------------------------------------------------------------


class TestSnapshot:
    def _registry(self):
        r = MetricsRegistry()
        r.counter("reqs", "requests", labels=("cause",)).inc(cause="full")
        r.gauge("depth", "queue depth").set(3)
        r.histogram("lat", "latency", buckets=(1.0, 10.0)).observe(0.4)
        return r

    def test_snapshot_is_json_and_valid(self):
        snap = self._registry().snapshot()
        validate_metrics_snapshot(json.loads(json.dumps(snap)))
        assert snap["version"] == 1
        assert set(snap) == {"version", "counters", "gauges", "histograms"}

    def test_validator_rejects_tampering(self):
        snap = self._registry().snapshot()
        bad = json.loads(json.dumps(snap))
        bad["histograms"]["lat"]["values"][0]["buckets"][-1]["le"] = 10.0
        with pytest.raises(ValueError, match="\\+Inf"):
            validate_metrics_snapshot(bad)
        bad = json.loads(json.dumps(snap))
        bad["histograms"]["lat"]["values"][0]["buckets"][0]["count"] = 99
        with pytest.raises(ValueError, match="cumulative|count"):
            validate_metrics_snapshot(bad)
        bad = json.loads(json.dumps(snap))
        bad["counters"]["reqs"]["values"][0]["labels"] = {"other": "x"}
        with pytest.raises(ValueError, match="labels"):
            validate_metrics_snapshot(bad)
        with pytest.raises(ValueError, match="version"):
            validate_metrics_snapshot({"version": 2})
        with pytest.raises(ValueError, match="dict"):
            validate_metrics_snapshot([])

    def test_prometheus_exposition(self):
        text = self._registry().to_prometheus()
        assert "# TYPE reqs counter" in text
        assert 'reqs{cause="full"} 1.0' in text
        assert "# TYPE depth gauge" in text
        assert "# TYPE lat histogram" in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_sum 0.4" in text
        assert "lat_count 1" in text
        assert text.endswith("\n")

    def test_prometheus_escapes_label_values(self):
        r = MetricsRegistry()
        r.counter("n", labels=("msg",)).inc(msg='he said "hi"\n')
        assert r'\"hi\"' in r.to_prometheus()


class TestNullRegistry:
    def test_disabled_and_noop(self):
        assert NULL_REGISTRY.enabled is False
        c = NULL_REGISTRY.counter("n", labels=("x",))
        c.inc()          # no label check, no state, no error
        c.inc(5, x="y")
        assert c.value() == 0.0
        NULL_REGISTRY.gauge("g").set(9)
        NULL_REGISTRY.histogram("h").observe(1.0)
        snap = NULL_REGISTRY.snapshot()
        validate_metrics_snapshot(snap)
        assert snap["counters"] == {}
        assert NULL_REGISTRY.to_prometheus() == ""

    def test_ambient_default_and_scope(self):
        assert metrics_mod.current() is NULL_REGISTRY
        r = MetricsRegistry()
        with use_metrics(r):
            assert metrics_mod.current() is r
            with use_metrics(None):  # no-op scope
                assert metrics_mod.current() is r
            with use_metrics(r):     # already installed: no-op
                assert metrics_mod.current() is r
        assert metrics_mod.current() is NULL_REGISTRY


# ---------------------------------------------------------------------------
# loadgen: _percentile edge cases + saturation handling
# ---------------------------------------------------------------------------


class TestPercentile:
    """Satellite: nearest-rank edge cases for the loadgen estimator."""

    def test_empty(self):
        assert _percentile([], 50) == 0.0

    def test_single_sample_all_quantiles(self):
        for q in (0, 1, 50, 99, 100):
            assert _percentile([7.5], q) == 7.5

    def test_q0_and_q100_hit_the_ends(self):
        xs = [1.0, 2.0, 3.0, 4.0, 5.0]
        assert _percentile(xs, 0) == 1.0
        assert _percentile(xs, 100) == 5.0

    def test_ties(self):
        xs = [3.0, 3.0, 3.0, 3.0]
        for q in (0, 25, 50, 75, 100):
            assert _percentile(xs, q) == 3.0

    def test_nearest_rank_rounding(self):
        xs = [10.0, 20.0]
        assert _percentile(xs, 49) == 10.0   # rounds to index 0
        assert _percentile(xs, 51) == 20.0   # rounds to index 1
        # exactly .5 hits Python's round-half-to-even: index 0
        assert _percentile(xs, 50) == 10.0

    def test_never_out_of_range(self):
        xs = sorted([5.0, 1.0, 9.0])
        for q in range(0, 101, 7):
            assert _percentile(xs, q) in xs


class _SaturatingEngine:
    """Deterministic stand-in: rejects every other submit with
    ``queue.Full`` (what a saturated admission queue does), resolves
    accepted futures immediately."""

    def __init__(self):
        self._stats = {"requests": 0, "batches": 0, "rejected": 0,
                       "max_batch_seen": 1}
        self.artifact = SimpleNamespace(
            source=SimpleNamespace(graph_inputs=["x"], values={}))
        self._n = 0

    @property
    def stats(self):  # point-in-time copy, the engine's contract
        return dict(self._stats)

    def submit(self, inputs):
        self._n += 1
        if self._n % 2 == 0:
            self._stats["rejected"] += 1
            raise queue.Full("admission queue full")
        fut = Future()
        fut.set_result(np.zeros(1))
        self._stats["requests"] += 1
        self._stats["batches"] += 1
        return fut


class TestLoadgenSaturation:
    """Satellite: ``run_load`` must survive admission rejection, count
    it, and keep rejected arrivals out of the latency distribution."""

    def test_queue_full_is_counted_not_raised(self):
        eng = _SaturatingEngine()
        rep = run_load(eng, offered_qps=50000, requests=10,
                       inputs=[{"x": np.zeros(1)}])
        assert rep.rejected == 5
        assert rep.requests == 5          # served only
        assert rep.batches == 5
        assert rep.mean_batch == 1.0
        assert rep.p99_ms >= 0            # computed over served only

    def test_all_rejected_yields_empty_distribution(self):
        eng = _SaturatingEngine()
        eng.submit = lambda inputs: (_ for _ in ()).throw(
            queue.Full("full"))
        rep = run_load(eng, offered_qps=50000, requests=4,
                       inputs=[{"x": np.zeros(1)}])
        assert rep.requests == 0
        assert rep.rejected == 4
        assert rep.p50_ms == 0.0 and rep.mean_ms == 0.0


# ---------------------------------------------------------------------------
# serve engine lifecycle metrics
# ---------------------------------------------------------------------------


class TestEngineMetrics:
    def test_lifecycle_series_add_up(self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 6, seed=4)
        with ServeEngine(lenet_art, ServeConfig(max_batch=4)) as eng:
            futs = [eng.submit(s) for s in samples]
            for f in futs:
                f.result()
            snap = validate_metrics_snapshot(eng.metrics())
        served = snap["counters"]["serve_requests_total"]["values"][0]
        assert served["value"] == 6
        batches = snap["counters"]["serve_batches_total"]["values"][0]
        assert 2 <= batches["value"] <= 6  # max_batch=4 forces >= 2
        stages = {row["labels"]["stage"]: row["count"]
                  for row in snap["histograms"]["serve_stage_ms"]["values"]}
        assert set(stages) == {"queue_wait", "batch_form", "execute",
                               "respond"}
        assert stages["queue_wait"] == 6          # one per request
        assert stages["execute"] == batches["value"]   # one per batch
        occ = snap["histograms"]["serve_batch_occupancy"]["values"][0]
        assert occ["count"] == batches["value"]
        assert occ["sum"] == 6                    # occupancies sum to reqs
        lat = snap["histograms"]["serve_request_latency_ms"]["values"][0]
        assert lat["count"] == 6
        # nothing left in flight after the context exits
        depth = snap["gauges"]["serve_queue_depth"]["values"][0]
        assert depth["value"] == 0
        inflight = snap["gauges"]["serve_inflight_batches"]["values"][0]
        assert inflight["value"] == 0

    def test_invalid_request_counted_by_cause(self, lenet_art):
        with ServeEngine(lenet_art) as eng:
            with pytest.raises(ValueError):
                eng.submit({"nope": np.zeros((1, 8, 8))})
            snap = eng.metrics()
        rej = {row["labels"]["cause"]: row["value"]
               for row in snap["counters"]["serve_rejected_total"]["values"]}
        assert rej == {"invalid": 1}

    def test_request_ids_and_flight_recorder(self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 5, seed=5)
        cfg = ServeConfig(max_batch=2, flight_records=2)
        with ServeEngine(lenet_art, cfg) as eng:
            for s in samples:
                eng.submit(s).result()
            recs = eng.flight_records()
        assert len(recs) == 2  # ring bounded by config
        ids = [i for r in recs for i in r["request_ids"]]
        assert ids == sorted(ids)  # monotone request ids
        for r in recs:
            assert r["outcome"] == "ok"
            assert r["n"] == len(r["request_ids"])
            for k in ("queue_wait_ms", "batch_form_ms", "execute_ms",
                      "respond_ms"):
                assert r[k] >= 0

    def test_flight_recorder_records_failures(self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 1, seed=6)
        with ServeEngine(lenet_art) as eng:
            eng.artifact = _Exploding(lenet_art)
            fut = eng.submit(samples[0])
            with pytest.raises(RuntimeError, match="boom"):
                fut.result()
            recs = eng.flight_records()
            snap = eng.metrics()
            eng.artifact = lenet_art
        assert recs and recs[-1]["outcome"] == "error:RuntimeError"
        rej = {row["labels"]["cause"]: row["value"]
               for row in snap["counters"]["serve_rejected_total"]["values"]}
        assert rej.get("execute_error") == 1

    def test_flight_recorder_disabled_by_config(self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 2, seed=7)
        with ServeEngine(lenet_art,
                         ServeConfig(flight_records=0)) as eng:
            for s in samples:
                eng.submit(s).result()
            assert eng.flight_records() == []

    def test_stats_property_is_a_safe_copy(self, lenet_art):
        """Satellite: ``stats`` is a point-in-time snapshot — mutating
        the returned dict never corrupts the engine's accounting."""
        samples = _sample_inputs(lenet_art.source, 2, seed=8)
        with ServeEngine(lenet_art) as eng:
            for s in samples:
                eng.submit(s).result()
            seen = eng.stats
            seen["requests"] = -999
            assert eng.stats["requests"] == 2
        assert eng.stats["requests"] == 2

    def test_null_registry_engine(self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 2, seed=9)
        with ServeEngine(lenet_art, registry=NULL_REGISTRY) as eng:
            outs = [eng.submit(s).result() for s in samples]
            snap = validate_metrics_snapshot(eng.metrics())
        assert snap["counters"] == {}
        assert len(outs) == 2
        assert eng.stats["requests"] == 2  # legacy counters still work

    def test_shared_registry_aggregates_engines(self, lenet_art):
        shared = MetricsRegistry()
        samples = _sample_inputs(lenet_art.source, 2, seed=10)
        for _ in range(2):
            with ServeEngine(lenet_art, registry=shared) as eng:
                for s in samples:
                    eng.submit(s).result()
        snap = shared.snapshot()
        assert (snap["counters"]["serve_requests_total"]["values"][0]
                ["value"]) == 4


class _Exploding:
    """Artifact proxy whose run() always raises."""

    def __init__(self, art):
        self.source = art.source
        self.tracer = art.tracer

    def run(self, *a, **k):
        raise RuntimeError("boom")


# ---------------------------------------------------------------------------
# byte-identity with metrics disabled (acceptance criterion)
# ---------------------------------------------------------------------------


class TestByteIdentity:
    def test_run_outputs_identical_with_and_without_registry(self,
                                                             lenet_art):
        x = _sample_inputs(lenet_art.source, 1, seed=11)[0]
        name = lenet_art.source.graph_inputs[0]
        y_plain = lenet_art.run({name: x[name]}, seed=0)
        with use_metrics(MetricsRegistry()) as reg:
            y_metered = lenet_art.run({name: x[name]}, seed=0)
            assert reg.snapshot()["histograms"]  # it did record
        assert np.asarray(y_plain).tobytes() == \
            np.asarray(y_metered).tobytes()

    def test_serve_outputs_identical_with_and_without_registry(
            self, lenet_art):
        samples = _sample_inputs(lenet_art.source, 3, seed=12)
        with ServeEngine(lenet_art, registry=NULL_REGISTRY) as eng:
            null_out = [eng.submit(s).result() for s in samples]
        with ServeEngine(lenet_art) as eng:
            live_out = [eng.submit(s).result() for s in samples]
        for a, b in zip(null_out, live_out):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    def test_ambient_registry_records_run_series(self, lenet_art):
        x = _sample_inputs(lenet_art.source, 1, seed=13)[0]
        name = lenet_art.source.graph_inputs[0]
        reg = MetricsRegistry()
        with use_metrics(reg):
            lenet_art.run({name: x[name]}, seed=0)
        snap = validate_metrics_snapshot(reg.snapshot())
        walls = snap["histograms"]["run_group_wall_ms"]["values"]
        assert walls and all(row["count"] >= 1 for row in walls)

    def test_report_telemetry_gains_metrics_section(self, lenet_art):
        x = _sample_inputs(lenet_art.source, 1, seed=14)[0]
        name = lenet_art.source.graph_inputs[0]
        reg = MetricsRegistry()
        with use_metrics(reg):
            lenet_art.run({name: x[name]}, seed=0)
            rep = lenet_art.report()
        assert rep.telemetry is not None
        validate_metrics_snapshot(rep.telemetry["metrics"])
        assert "metrics:" in str(rep)
        # without an ambient registry the section is absent
        rep_plain = lenet_art.report()
        assert "metrics" not in (rep_plain.telemetry or {})


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------


class TestProfiler:
    def test_profile_lenet5(self, lenet_art):
        rep = profile_artifact(lenet_art, reps=1, warmup=0)
        assert rep.model == "lenet5"
        assert rep.groups and rep.layers
        for g in rep.groups:
            assert g["modeled_cycles"] > 0
            assert g["measured_ms"] > 0
            assert g["implied_clock_mhz"] > 0
            assert g["ratio"] == pytest.approx(
                g["measured_ms"] / g["modeled_ms"], rel=1e-3)
            assert g["roofline_util"] is None or 0 <= g["roofline_util"] <= 1
        # layer attribution partitions each group's measured wall
        for g in rep.groups:
            attributed = sum(n["attributed_ms"] for n in rep.layers
                             if n["group"] == g["group"])
            assert attributed == pytest.approx(g["measured_ms"], abs=0.05)
        doc = json.loads(json.dumps(rep.to_json()))
        assert doc["version"] == 1 and doc["groups"]
        table = rep.format_table()
        assert "modeled_cyc" in table and rep.groups[0]["group"] in table

    def test_profile_all_zoo_models_both_targets(self):
        """Acceptance: a per-group table (and JSON) for every zoo model
        on both device presets."""
        for model, make in sorted(zoo.ZOO.items()):
            for target in ("kv260", "zu3eg"):
                art = api.compile_graph(make(), target=target)
                rep = profile_artifact(art, reps=1, warmup=0)
                assert rep.target == target
                assert rep.groups, f"{model}@{target}: no group rows"
                assert rep.layers, f"{model}@{target}: no layer rows"
                json.dumps(rep.to_json())
                assert model in rep.format_table()

    def test_drift_flagging_is_median_relative(self, lenet_art):
        rep = profile_artifact(lenet_art, reps=1, warmup=0,
                               threshold=1000.0)
        # an absurd threshold flags nothing
        assert rep.flagged == []
        assert all(not g["drift"] for g in rep.groups)

    def test_argument_validation(self, lenet_art):
        with pytest.raises(ValueError, match="reps"):
            profile_artifact(lenet_art, reps=0)
        with pytest.raises(ValueError, match="threshold"):
            profile_artifact(lenet_art, threshold=1.0)
        with pytest.raises(ValueError, match="clock"):
            profile_artifact(lenet_art, clock_mhz=0)

    def test_edge_roofline_helper(self):
        from benchmarks.roofline import edge_ideal_cycles

        # compute-bound: 1248 DSPs at 0.5 DSP/mult = 2496 MACs/cycle
        assert edge_ideal_cycles(249600, 0, d_total=1248) == 100
        # memory-bound: 16 B/cycle
        assert edge_ideal_cycles(0, 1600, d_total=1248) == 100
        # max of the two
        assert edge_ideal_cycles(249600, 160000, d_total=1248) == 10000
        with pytest.raises(ValueError, match="d_total"):
            edge_ideal_cycles(1, 1, d_total=0)


# ---------------------------------------------------------------------------
# smoke_diff blindness to the metrics fields (satellite)
# ---------------------------------------------------------------------------


class TestSmokeDiffMetricsBlind:
    @staticmethod
    def _sd():
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "smoke_diff_metrics",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "smoke_diff.py"))
        sd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sd)
        return sd

    def test_compile_mode_ignores_metrics(self):
        sd = self._sd()
        assert "metrics" in sd.IGNORED_KEYS

        def snap(n):
            return {"lenet5": {"kv260": {
                "total_cycles": 100, "max_group_cycles": 100,
                "max_bram": 1, "groups": 1, "spill_bytes": 0,
                "metrics": {"version": 1, "counters": {"c": n}},
            }}}

        lines = []
        assert sd.diff(snap(1), snap(2), 0.10, emit=lines.append) == 0
        assert lines == ["graph,target,metric,previous,current,delta_pct"]

    def test_serve_mode_ignores_cell_metrics(self):
        sd = self._sd()

        def snap(n):
            return {"lenet5": {"kv260": {
                "loads": [{"offered_qps": 100.0, "achieved_qps": 50.0,
                           "p50_ms": 5.0, "p99_ms": 9.0, "mean_ms": 6.0,
                           "mean_batch": 2.0, "batches": 10,
                           "rejected": 0}],
                "metrics": {"version": 1, "counters": {"c": n}},
            }}}

        lines = []
        assert sd.diff_serve(snap(1), snap(2), 0.10,
                             emit=lines.append) == 0
        assert lines == [
            "model,target,offered_qps,metric,previous,current,delta_pct"
        ]
