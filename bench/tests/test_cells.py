"""Whole runs of tiny cells on the CPU, with the look for a chip
skipped: a cell added as files and entries only runs and comes out
correct, and the same run with the timed path broken underneath comes
out not correct."""
import time

import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.tests import tiny

SEED = 2**40 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark with two tiny cells added as files."""
    root = tiny.copy_benchmark(str(tmp_path_factory.mktemp("checkout")))
    tiny.add_cell(root, "tinyf.offline",
                  tiny.tiny_card("tinyf", tiny.FLOAT_CARD_EXTRA), tiny.OFFLINE)
    tiny.add_cell(root, "tinyi.serve",
                  tiny.tiny_card("tinyi", tiny.INT_CARD_EXTRA), tiny.SERVE)
    # a new mix (bursty arrivals) judged on a metric its kind did not
    # report before (completed samples/s), as data files and entries
    tiny.add_cell(root, "tinyi.bursty",
                  tiny.tiny_card("tinyb", tiny.INT_CARD_EXTRA),
                  dict(tiny.SERVE, rate_per_s=200,
                       bursts={"factor": 5, "burst_s": 0.1, "period_s": 0.5}),
                  widen=("samples_per_s", "latency_p50_ms"))
    return root


def _run(root, cell, trace=False):
    return bench_run.execute(cell, SEED, 0.5, trace, root=root,
                             require_tpu=False, t_start=time.perf_counter())


@pytest.mark.parametrize("cell,e2e", [
    ("tinyf.offline", {"samples_per_s", "setup_s"}),
    ("tinyi.serve", {"latency_p50_ms", "setup_s"}),
    ("tinyi.bursty", {"samples_per_s", "latency_p50_ms", "setup_s"}),
])
def test_added_cell_runs_correct(root, cell, e2e):
    line = _run(root, cell)
    assert line["correct"] is True
    assert set(line["metrics"]) == e2e
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])


def _alter_one(out):
    """An answer altered where it is produced: the first row's output."""
    return {k: v.at[0].add(jnp.asarray(1000, v.dtype)) for k, v in out.items()}


def _drop_half(out):
    """Half of the batch left out: its rows come back as zeros."""
    def half(v):
        keep = (jnp.arange(v.shape[0]) < (v.shape[0] + 1) // 2)
        return v * keep.reshape((-1,) + (1,) * (v.ndim - 1)).astype(v.dtype)
    return {k: half(v) for k, v in out.items()}


def _swap_rows(out):
    """Answers handed to the wrong requests: the batch reversed."""
    return {k: v[::-1] if v.shape[0] > 1 else v + 1 for k, v in out.items()}


@pytest.mark.parametrize("cell,fault", [
    ("tinyf.offline", _alter_one),
    ("tinyf.offline", _drop_half),
    ("tinyi.serve", _alter_one),
    ("tinyi.serve", _swap_rows),
], ids=["offline-altered", "offline-half-left-out", "serve-altered",
        "serve-swapped"])
def test_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    from repro.kernels import ops

    real = ops.run_compiled_batched

    def broken(design, env, batch, **kw):
        return fault(real(design, env, batch, **kw))

    monkeypatch.setattr(ops, "run_compiled_batched", broken)
    line = _run(root, cell)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_unanswered_request_is_not_correct(root, monkeypatch):
    """A request whose answer never comes fails the run."""
    from repro.serve import ServeEngine

    real = ServeEngine.submit
    count = {"n": 0}

    def lossy(self, x):
        count["n"] += 1
        fut = real(self, x)
        # past the set-up's warm-up burst, every seventh caller never
        # hears back
        if count["n"] > 2 * tiny.SERVE["server"]["max_batch"] and \
                count["n"] % 7 == 3:
            from concurrent.futures import Future
            return Future()
        return fut

    monkeypatch.setattr(ServeEngine, "submit", lossy)
    line = _run(root, "tinyi.serve")
    assert line["correct"] is False
    assert line["checks"]["unanswered"]["value"] > 0
    assert line["failed"] > 0


def test_errored_requests_are_not_correct(root, monkeypatch):
    """A request the server answers with an exception fails the run:
    here the runner raises on every other batch of the window."""
    from repro.kernels import ops

    real = ops.run_compiled_batched
    calls = {"n": 0}
    warmup = tiny.SERVE["server"]["max_batch"]

    def flaky(design, env, batch, **kw):
        calls["n"] += 1
        if calls["n"] > warmup and calls["n"] % 2 == 0:
            raise RuntimeError("runner fault")
        return real(design, env, batch, **kw)

    monkeypatch.setattr(ops, "run_compiled_batched", flaky)
    line = _run(root, "tinyi.serve")
    assert line["correct"] is False
    assert line["checks"]["errored"]["value"] > 0
    assert line["checks"]["errored"]["limit"] == 0
    assert line["failed"] >= line["checks"]["errored"]["value"]


def test_traced_serve_run_reads_server_counters(root):
    """With --trace 1 the per-layer readers report; on the CPU the trace
    holds no TPU plane, so the device's idle share is left out rather
    than read as 0."""
    line = _run(root, "tinyi.serve", trace=True)
    assert line["correct"] is True
    got = line["metrics"]
    for name in ("queue_wait_ms", "execute_ms", "batch_occupancy",
                 "mingc_compile_s", "xla_compile_s"):
        assert name in got, name
    assert 1 <= got["batch_occupancy"]["value"] <= 4
    assert "device_idle_share.serve" not in got
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
