"""The readers of the program's spans and counters: on made-up reduced
traces and registry snapshots (what each reads, its 0.0 case where a
reduction lists every gap and none of its span, ``None`` where there is
nothing to read or its share is unknown), on a tiny serve cell run
traced on the CPU, and on the two recorded chip traces of
``vgg16.offline_b32``, before and after the runner had spans."""
import os
import time
import types

import pytest

from bench import model, registry, trace_reduce
from bench import run as bench_run
from bench.tests import tiny


def _reader(name):
    return registry.reader(name)


def _offline(idle_gaps, window_s=10.0, busy_s=4.0):
    return types.SimpleNamespace(
        reduced={"window_s": window_s, "busy_s": busy_s,
                 "idle_share": 1 - busy_s / window_s,
                 "idle_gaps": idle_gaps},
        engine_metrics=None)


GAPS = [["ming:sync", 3.0], ["bench:art.run", 0.5], ["ming:dispatch", 0.8],
        ["Transpose", 0.6], ["ming:run", 0.2], ["idle host", 0.1],
        ["ming:inputs", 0.3]]


def test_idle_in_sync():
    run = _offline(GAPS)
    assert _reader("idle_in_sync.offline")(run) == pytest.approx(30.0)


def test_idle_in_sync_is_unknown_when_the_listed_gaps_leave_it_out():
    """Ten gaps listed and none is ``ming:sync``: whatever it holds lies
    below the tenth, so the reader says nothing rather than 0.0."""
    full = [[f"Event{i}", 1.0 - i / 20] for i in range(10)]
    run = _offline(full, window_s=20.0, busy_s=4.0)
    assert _reader("idle_in_sync.offline")(run) is None
    run = _offline(full[:9], window_s=20.0, busy_s=4.0)
    assert _reader("idle_in_sync.offline")(run) == 0.0


def test_idle_unattributed_counts_vague_gaps_and_the_unlisted_rest():
    run = _offline(GAPS)
    # idle 6.0 s, listed 5.5 s: 0.5 unlisted + bench 0.5 + run 0.2 +
    # idle host 0.1 = 1.3 s of 10
    assert _reader("idle_unattributed.offline")(run) == pytest.approx(13.0)


def test_offline_readers_on_a_trace_without_program_spans():
    """The benchmark's span alone, as a program without ``ming:*`` spans
    leaves it: nothing in sync, all of it unattributed."""
    run = _offline([["bench:art.run", 4.1], ["Transpose", 1.2]])
    assert _reader("idle_in_sync.offline")(run) == 0.0
    # 4.1 + the 0.7 s outside the listed gaps
    assert _reader("idle_unattributed.offline")(run) == pytest.approx(48.0)


def _counter(rows):
    return {"help": "", "labels": sorted({k for r in rows
                                          for k in r["labels"]}),
            "values": rows}


def _serve(counters=None, histograms=None):
    snap = {"version": 1, "counters": counters or {}, "gauges": {},
            "histograms": histograms or {}}
    return types.SimpleNamespace(reduced=None, engine_metrics=snap)


SERVE_COUNTERS = {
    "serve_batches_total": _counter([{"labels": {}, "value": 10}]),
    "run_h2d_bytes_total": _counter([
        {"labels": {"kind": "inputs"}, "value": 10 * 31 * 1024},
        {"labels": {"kind": "constants"}, "value": 10 * 61470}]),
    "run_rows_total": _counter([
        {"labels": {"kind": "useful"}, "value": 318},
        {"labels": {"kind": "padded"}, "value": 2}]),
}


def test_h2d_bytes_per_batch():
    run = _serve(SERVE_COUNTERS)
    assert _reader("h2d_bytes_per_batch.serve")(run) == 61470 + 31 * 1024


def test_padded_rows_share():
    run = _serve(SERVE_COUNTERS)
    assert _reader("padded_rows_share.serve")(run) == pytest.approx(
        100 * 2 / 320)


def test_padded_rows_share_reads_zero_with_full_buckets():
    counters = dict(SERVE_COUNTERS, run_rows_total=_counter([
        {"labels": {"kind": "useful"}, "value": 320},
        {"labels": {"kind": "padded"}, "value": 0}]))
    assert _reader("padded_rows_share.serve")(_serve(counters)) == 0.0


def test_to_host_ms():
    hist = {"run_to_host_ms": {"help": "", "labels": [], "values": [
        {"labels": {}, "count": 4, "sum": 1.0, "min": 0.1, "max": 0.4,
         "buckets": []}]}}
    assert _reader("to_host_ms.serve")(_serve(histograms=hist)) == 0.25


@pytest.mark.parametrize("name", ["h2d_bytes_per_batch.serve",
                                  "to_host_ms.serve",
                                  "padded_rows_share.serve"])
def test_serve_readers_without_the_runner_series(name):
    """An engine snapshot from a program that counts none of it."""
    only_serve = {"serve_batches_total": SERVE_COUNTERS["serve_batches_total"]}
    assert _reader(name)(_serve(only_serve)) is None


def test_new_metrics_are_listed_for_their_cells():
    spec = {m["name"]: m for m in registry.benchmark()["per_layer"]}
    assert "idle_in_dispatch.offline" not in spec
    for name in ("idle_in_sync.offline", "idle_unattributed.offline"):
        assert spec[name]["workloads"] == ["vgg16.offline_b32"]
        assert spec[name]["moves"] == "samples_per_s"
    for name in ("h2d_bytes_per_batch.serve", "to_host_ms.serve",
                 "padded_rows_share.serve"):
        assert spec[name]["workloads"] == ["lenet5.serve_poisson"]
        assert spec[name]["moves"] == "latency_p50_ms"


def test_offline_readers_without_device_operations():
    """A CPU trace holds no device plane: nothing to attribute."""
    run = _offline([], busy_s=0.0)
    for name in ("idle_in_sync.offline", "idle_unattributed.offline"):
        assert _reader(name)(run) is None


NEW_SERVE = ("h2d_bytes_per_batch.serve", "to_host_ms.serve",
             "padded_rows_share.serve")


def test_traced_tiny_serve_cell_reads_the_runner_counters(tmp_path):
    """A tiny serve cell that lists the new metrics, run traced on the
    CPU: every one reads a number from the engine's snapshot."""
    root = tiny.copy_benchmark(str(tmp_path))
    card = tiny.tiny_card("tinys", tiny.INT_CARD_EXTRA)
    tiny.add_cell(root, "tinys.serve", card, tiny.SERVE,
                  widen=tiny.WIDEN["open_loop"] + NEW_SERVE)
    line = bench_run.execute("tinys.serve", 2**40 + 29, 0.5, True, root=root,
                             require_tpu=False, t_start=time.perf_counter())
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    occupancy = got["batch_occupancy"]
    # the tiny card's int8 weights plus 12x12x2 int8 bytes per request
    weights = sum(w.nbytes for w in model.make_weights(
        card, card["weight_fill"], 1).values())
    assert got["h2d_bytes_per_batch.serve"] == pytest.approx(
        weights + 288 * occupancy)
    assert got["to_host_ms.serve"] > 0
    assert 0 <= got["padded_rows_share.serve"] < 100


OFFLINE_READERS = ("idle_in_sync.offline", "idle_unattributed.offline")


def _recorded(name):
    trace = trace_reduce.read_saved(
        os.path.join(os.path.dirname(__file__), "data", name))
    red = trace_reduce.reduce(trace, {"pallas": trace_reduce.PALLAS_MARKER})
    run = types.SimpleNamespace(reduced=red, engine_metrics=None)
    return trace, red, {n: _reader(n)(run) for n in OFFLINE_READERS}


def test_recorded_trace_without_program_spans_reads_the_before_value():
    """The chip trace recorded before the runner had spans: no gap in
    sync, and the idle time under the benchmark's own ``bench:art.run``
    span, and outside the listed gaps, unattributed."""
    _, red, got = _recorded("vgg16_offline_b32.trace.json.gz")
    # ten gaps listed, none of them in sync: its share is unknown
    assert len(red["idle_gaps"]) == 10
    assert got["idle_in_sync.offline"] is None
    gaps = dict(red["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    want = gaps["bench:art.run"] + idle - sum(gaps.values())
    assert got["idle_unattributed.offline"] == pytest.approx(
        100 * want / red["window_s"])
    assert 40 < got["idle_unattributed.offline"] < 50


def test_recorded_trace_with_program_spans_names_sync_and_dispatch():
    """The chip trace recorded with the runner's spans (three calls of
    batch 32): the idle time of a call falls under ``ming:sync``, the
    runtime's upload events and ``ming:dispatch``, and little stays
    unattributed."""
    trace, red, got = _recorded("vgg16_offline_b32.spans.trace.json.gz")
    names = [h[0] for h in trace["host"]]
    for span in ("ming:run", "ming:inputs", "ming:to_host"):
        assert names.count(span) == 3, span
    # 13 groups a call, each dispatched and synced
    assert names.count("ming:dispatch") == names.count("ming:sync") == 39
    gaps = dict(red["idle_gaps"])
    assert {"ming:sync", "ming:dispatch"} <= set(gaps)
    assert red["idle_gaps"][0][0] == "ming:sync"
    assert not any(name.startswith("bench:") for name in gaps)
    idle_pct = 100 * red["idle_share"]
    assert got["idle_in_sync.offline"] == pytest.approx(
        100 * gaps["ming:sync"] / red["window_s"])
    assert {"Transpose", "XlaLinearize"} <= set(gaps)
    assert got["idle_unattributed.offline"] < 10
    assert sum(got.values()) <= idle_pct
