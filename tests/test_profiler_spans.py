"""Runtime and serve spans on the profiler's clock, and the runner's
host-to-device and padding counters.

Spans of the ``runtime`` and ``serve`` categories open a
``jax.profiler.TraceAnnotation`` whether or not a tracer is installed,
so a profiler session sees them in its ``/host:CPU`` plane with their
arguments as event stats; here a session runs on the CPU and the trace
is read back with ``jax.profiler.ProfileData``."""
import glob
import os

import jax
import numpy as np
import pytest

from repro import api
from repro.core import cnn_graphs
from repro.core.compile_driver import Target
from repro.frontends import zoo
from repro.instrument import (MetricsRegistry, NULL_TRACER, tracer as
                              tracer_mod, use_metrics)
from repro.kernels import ops
from repro.serve import ServeConfig, ServeEngine


def _int8(shape, rng):
    return rng.integers(-4, 5, size=shape, dtype=np.int8)


def _params(src, seed=0):
    rng = np.random.default_rng(seed)
    return {k: _int8(v.shape, rng) for k, v in src.values.items()
            if v.is_constant}


def _host_events(log_dir):
    """``(name, start, end, stats)`` of every event of the trace's
    ``/host:CPU`` plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for line in plane.lines for e in line.events]


def _named(events, name):
    return [e for e in events if e[0] == name]


@pytest.fixture(scope="module")
def two_groups():
    art = api.compile_graph(cnn_graphs.cascade_conv(16, c_mid=8),
                            api.CompileOptions(target=Target(
                                name="tiny", d_total=64, b_total=2)))
    assert len(art.design.groups) == 2
    return art


def test_artifact_run_spans_in_profiler_trace(two_groups, tmp_path):
    art = two_groups
    src = art.source
    rng = np.random.default_rng(1)
    x = {k: _int8((3,) + src.values[k].shape, rng) for k in src.graph_inputs}
    params = _params(src)
    art.run(x, params)  # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        art.run(x, params)
    ev = _host_events(str(tmp_path))
    (run,) = _named(ev, "ming:run")
    assert run[3] == {"graph": src.name, "batch": 3}

    def inside(e):
        return run[1] <= e[1] and e[2] <= run[2]

    groups = [g.name for g in art.design.groups]
    for name in ("ming:dispatch", "ming:sync"):
        spans = sorted(_named(ev, name), key=lambda e: e[1])
        assert [e[3]["group"] for e in spans] == groups
        assert all(inside(e) for e in spans)
    (inputs,) = _named(ev, "ming:inputs")
    (to_host,) = _named(ev, "ming:to_host")
    assert inside(inputs) and inside(to_host)
    first_dispatch = min(e[1] for e in _named(ev, "ming:dispatch"))
    last_sync = max(e[2] for e in _named(ev, "ming:sync"))
    assert inputs[2] <= first_dispatch and last_sync <= to_host[1]
    # dispatch and sync are siblings: neither holds the other
    for d, s in zip(sorted(_named(ev, "ming:dispatch"), key=lambda e: e[1]),
                    sorted(_named(ev, "ming:sync"), key=lambda e: e[1])):
        assert d[2] <= s[1]


def test_uninstrumented_runner_dispatches_without_sync(two_groups, tmp_path):
    """With no stats, tracer or registry the runner never blocks: a
    dispatch span per group, no sync span."""
    art = two_groups
    src = art.source
    rng = np.random.default_rng(2)
    env = {**_params(src),
           **{k: _int8((2,) + src.values[k].shape, rng)
              for k in src.graph_inputs}}
    ops.run_compiled_batched(art.design, env, 2)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(ops.run_compiled_batched(art.design, env, 2))
    ev = _host_events(str(tmp_path))
    assert len(_named(ev, "ming:dispatch")) == 2
    assert len(_named(ev, "ming:inputs")) == 1
    assert not _named(ev, "ming:sync")


def test_null_tracer_runtime_span_is_the_annotation():
    """No tracer installed: a runtime span is one profiler annotation
    whose entry hands out the shared discard sink; compile-time spans
    stay the shared no-op."""
    span = NULL_TRACER.span("ming:dispatch", cat="runtime",
                            args={"group": "g0"})
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span as sargs:
        sargs.update(ignored=1)
        assert sargs is tracer_mod._DISCARD and not sargs
    assert NULL_TRACER.span("pass:x", cat="passes") is tracer_mod._NULL_SPAN


def test_runner_counts_host_bytes_and_rows_once(two_groups):
    art = two_groups
    src = art.source
    rng = np.random.default_rng(3)
    x = {k: _int8((5,) + src.values[k].shape, rng) for k in src.graph_inputs}
    params = _params(src)
    reg = MetricsRegistry()
    with use_metrics(reg):
        art.run(x, params)
    h2d = reg.counter("run_h2d_bytes_total", labels=("kind",))
    rows = reg.counter("run_rows_total", labels=("kind",))
    assert h2d.value(kind="inputs") == sum(v.nbytes for v in x.values())
    assert h2d.value(kind="constants") == sum(v.nbytes
                                              for v in params.values())
    assert rows.value(kind="useful") == 5 and rows.value(kind="padded") == 3
    to_host = reg.snapshot()["histograms"]["run_to_host_ms"]["values"]
    assert [r["count"] for r in to_host] == [1]


def test_serve_engine_spans_and_runner_series(tmp_path):
    art = api.compile_graph(zoo.lenet5())
    src = art.source
    params = _params(src, seed=4)
    rng = np.random.default_rng(5)
    samples = [_int8(src.values[src.graph_inputs[0]].shape, rng)
               for _ in range(3)]
    art.run(np.stack(samples), params)  # compiled outside the trace
    cfg = ServeConfig(max_batch=4, latency_budget_ms=500)
    with jax.profiler.trace(str(tmp_path)):
        with ServeEngine(art, cfg, params=params) as eng:
            futs = [eng.submit(s) for s in samples]
            for f in futs:
                f.result(timeout=60)
    ev = _host_events(str(tmp_path))
    for name in ("ming:serve.form", "ming:serve.stack",
                 "ming:serve.respond", "ming:run"):
        assert len(_named(ev, name)) == 1, name
    assert eng.stats["batches"] == 1
    snap = eng.metrics()
    h2d = {r["labels"]["kind"]: r["value"]
           for r in snap["counters"]["run_h2d_bytes_total"]["values"]}
    assert h2d == {"inputs": sum(s.nbytes for s in samples),
                   "constants": sum(v.nbytes for v in params.values())}
    rows = {r["labels"]["kind"]: r["value"]
            for r in snap["counters"]["run_rows_total"]["values"]}
    assert rows == {"useful": 3, "padded": 4 - 3}
    (to_host,) = snap["histograms"]["run_to_host_ms"]["values"]
    assert to_host["count"] == 1
