"""The traffic generator: the seeded Poisson schedule, the open loop's
timing and lateness report, the closed loop, and the mix files."""
import glob
import json
import os
import queue
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench.registry import ROOT
from bench.traffic import generator


def test_poisson_schedule_is_seeded():
    a = generator.poisson_offsets(500.0, 2.0, 2**40 + 5)
    b = generator.poisson_offsets(500.0, 2.0, 2**40 + 5)
    c = generator.poisson_offsets(500.0, 2.0, 2**40 + 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_poisson_schedule_rate_and_work():
    """Every seed offers the same number of requests over the same
    span, with exponential gaps of mean 1/rate; only the order moves."""
    rate, secs = 800.0, 5.0
    a = generator.poisson_offsets(rate, secs, 1)
    b = generator.poisson_offsets(rate, secs, 2)
    assert len(a) == len(b) == 4000
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)
    ga, gb = np.diff(a), np.diff(b)
    assert abs(ga.mean() - 1 / rate) < 0.02 / rate
    assert abs(a[-1] - b[-1]) < 0.02 * secs  # the same work, reordered
    # exponential: the coefficient of variation of the gaps is about 1
    assert 0.9 < ga.std() / ga.mean() < 1.1
    # Kolmogorov-Smirnov distance to Exp(rate) is small
    x = np.sort(ga)
    ks = np.max(np.abs(np.arange(1, len(x) + 1) / len(x)
                       - (1 - np.exp(-rate * x))))
    assert ks < 0.02


def test_arrivals_without_bursts_are_the_poisson_stream():
    mix = {"rate_per_s": 300.0}
    assert np.array_equal(generator.arrival_offsets(mix, 2.0, 9),
                          generator.poisson_offsets(300.0, 2.0, 9))


def test_bursts_keep_the_work_and_gather_it():
    """10x bursts of 0.2 s every 2 s: the same requests in the same
    order, all of them inside the bursts, the mean rate kept."""
    rate, secs = 1000.0, 10.0
    mix = {"rate_per_s": rate,
           "bursts": {"factor": 10, "burst_s": 0.2, "period_s": 2.0}}
    flat = generator.poisson_offsets(rate, secs, 4)
    got = generator.arrival_offsets(mix, secs, 4)
    assert len(got) == len(flat) and np.all(np.diff(got) >= 0)
    assert np.all(np.mod(got, 2.0) < 0.2 + 1e-9)
    assert abs(got[-1] - flat[-1]) < 2.0
    # in a burst the rate is ten times the mean
    first = got[got < 0.2]
    assert abs(len(first) / 0.2 - 10 * rate) < 0.1 * 10 * rate


def test_partial_bursts_keep_a_slower_stream_between():
    mix = {"rate_per_s": 1000.0,
           "bursts": {"factor": 4, "burst_s": 0.1, "period_s": 1.0}}
    got = generator.arrival_offsets(mix, 5.0, 4)
    inside = np.mod(got, 1.0) < 0.1
    assert 0.35 < inside.mean() < 0.45   # 4 x 0.1 of each second's work
    assert np.all(np.diff(got) >= 0)


def test_bursts_that_break_the_mean_are_refused():
    with pytest.raises(ValueError, match="mean rate"):
        generator.burst_offsets(np.arange(5.0), 20, 0.2, 2.0)


def _instant(i):
    f = Future()
    f.set_result(i)
    return f


def test_open_loop_times_from_schedule_and_reports_lateness():
    offsets = np.arange(20) * 0.002
    res = generator.open_loop(_instant, offsets, drain_s=1.0)
    assert [r for r in res.results] == list(range(20))
    lat = res.latencies_ms()
    assert np.all(lat >= 0) and np.all(lat < 50)
    assert np.all(res.late >= 0)
    rep = generator.lateness_ms(res.late)
    assert set(rep) == {"p50", "p95", "max"}
    assert rep["p50"] <= rep["p95"] <= rep["max"]


def test_open_loop_counts_refused_and_unanswered_as_missing():
    pending = []

    def submit(i):
        if i == 1:
            raise queue.Full()
        f = Future()
        if i == 2:
            pending.append(f)  # never answered
        else:
            f.set_result(i)
        return f

    res = generator.open_loop(submit, np.array([0.0, 0.001, 0.002, 0.003]),
                              drain_s=0.05)
    assert res.rejected.tolist() == [False, True, False, False]
    assert np.isnan(res.done[2])
    assert res.unanswered().tolist() == [False, False, True, False]
    assert res.answered().tolist() == [True, False, False, True]
    lat = res.latencies_ms()
    # the refused and the unanswered read the whole wait: worse than any
    assert lat[1] > lat[0] and lat[2] > lat[3]
    assert lat[1] >= 50 and lat[2] >= 50


def test_open_loop_counts_errors_apart():
    """A request answered with an exception is neither answered nor
    unanswered: it is errored."""
    def submit(i):
        f = Future()
        if i == 1:
            f.set_exception(RuntimeError("runner fault"))
        else:
            f.set_result(i)
        return f

    res = generator.open_loop(submit, np.array([0.0, 0.001, 0.002]),
                              drain_s=0.05)
    assert res.errored().tolist() == [False, True, False]
    assert res.answered().tolist() == [True, False, True]
    assert not res.unanswered().any()


def test_open_loop_latency_counts_a_stall():
    """A stall delays later requests; their latency counts from when
    they were due, not from when they were sent."""
    def submit(i):
        if i == 0:
            time.sleep(0.05)
        return _instant(i)

    res = generator.open_loop(submit, np.array([0.0, 0.01]), drain_s=1.0)
    assert res.late[1] > 0.03
    assert res.latencies_ms()[1] > 30


def test_closed_loop_runs_the_window_and_cycles():
    calls = []
    outs, elapsed = generator.closed_loop(
        lambda i: calls.append(i) or i, 3, 0.05)
    assert elapsed >= 0.05
    assert [i for i, _ in outs] == [k % 3 for k in range(len(outs))]
    assert calls == [i for i, _ in outs]


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(ROOT, "bench", "traffic", "*.json"))),
    ids=os.path.basename)
def test_mix_files_name_a_driver(path):
    with open(path) as f:
        mix = json.load(f)
    from bench import registry

    assert callable(registry.driver(mix["kind"]))
    if mix["kind"] == "open_loop":
        assert mix["rate_per_s"] > 0 and mix["server"]["max_batch"] >= 1
        assert len(generator.arrival_offsets(mix, 1.0, 1)) > 0
    else:
        assert mix["batch"] >= 1 and mix["distinct_batches"] >= 1
