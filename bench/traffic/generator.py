"""The one traffic generator: it reads a mix's parameters and drives
the system under test.

* Closed loop: back-to-back calls, each on the next of a fixed set of
  batches, until the window has passed (:func:`closed_loop`).
* Open loop: single requests at arrivals drawn from the mix's
  parameters (:func:`arrival_offsets`: a Poisson stream of
  ``rate_per_s``, optionally gathered into ``bursts``), submitted on
  schedule (:func:`open_loop`).  The core is the program's
  ``serve.loadgen.run_load``, copied: open loop, latency timed from the
  scheduled arrival, an admission refusal recorded and the schedule
  kept.  Unlike that core, the gaps are exponential, and how late the
  generator ran is reported.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import queue
import threading
import time

import numpy as np


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Arrival times (s from the window's start) of a Poisson stream of
    ``rate`` requests/s over ``seconds``.  Every seed gets the same
    gaps, the ``n = rate * seconds`` quantiles of the exponential
    distribution, in its own order: the seed changes when requests
    bunch up, not how much work the window holds."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def burst_offsets(offsets: np.ndarray, factor: float, burst_s: float,
                  period_s: float) -> np.ndarray:
    """Gather a stream of mean rate ``r`` into bursts: in each
    ``period_s`` the first ``burst_s`` run at ``factor × r`` and the rest
    at the rate that keeps the mean at ``r`` (none where ``factor ×
    burst_s == period_s``).  A time change of ``offsets``: the number of
    requests and their order stay as they are."""
    if not (factor >= 1 and 0 < burst_s < period_s
            and factor * burst_s <= period_s):
        raise ValueError(f"bursts of {factor}x for {burst_s} s every "
                         f"{period_s} s do not keep the mean rate")
    k, r = np.divmod(np.asarray(offsets, float), period_s)
    peak = factor * burst_s          # the stream's time a burst holds
    rest = period_s - burst_s
    slow = (period_s - peak) / rest  # the rate outside, relative
    t = np.where(r < peak, r / factor,
                 burst_s + (r - peak) / (slow if slow > 0 else 1.0))
    return k * period_s + t


def arrival_offsets(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """The arrival times of an open-loop mix: ``rate_per_s`` Poisson
    over ``seconds`` (:func:`poisson_offsets`), gathered into
    ``bursts`` (``factor``, ``burst_s``, ``period_s``) where the mix
    has them (:func:`burst_offsets`)."""
    offsets = poisson_offsets(mix["rate_per_s"], seconds, seed)
    b = mix.get("bursts")
    if b:
        offsets = burst_offsets(offsets, b["factor"], b["burst_s"],
                                b["period_s"])
    return offsets


@dataclasses.dataclass
class OpenLoopRun:
    """What one open-loop window did; times on ``time.perf_counter``."""

    due: np.ndarray          # scheduled arrival of each request
    late: np.ndarray         # how late the generator submitted it
    done: np.ndarray         # completion time, NaN if none by the deadline
    results: list            # answer, or the exception it raised
    rejected: np.ndarray     # admission refused it (queue full)
    t_close: float           # the window's scheduled end
    deadline: float          # how long answers were waited for

    def answered(self) -> np.ndarray:
        """Requests answered by the deadline, and not with an error."""
        return ~np.isnan(self.done) & np.array(
            [not isinstance(r, BaseException) for r in self.results])

    def errored(self) -> np.ndarray:
        """Requests answered by the deadline with an error."""
        return ~np.isnan(self.done) & np.array(
            [isinstance(r, BaseException) for r in self.results])

    def unanswered(self) -> np.ndarray:
        """Admitted requests with no answer by the deadline."""
        return np.isnan(self.done) & ~self.rejected

    def latencies_ms(self) -> np.ndarray:
        """Scheduled arrival to answer.  A request rejected, failed or
        never answered misses every limit: it reads the time from its
        arrival to the end of the wait for answers."""
        end = np.where(self.answered(), self.done, self.deadline)
        return (end - self.due) * 1e3


def lateness_ms(late_s: np.ndarray) -> dict:
    """Median, 95th percentile and worst of how late the generator
    submitted, in ms."""
    ms = np.asarray(late_s) * 1e3
    return {"p50": float(np.percentile(ms, 50)),
            "p95": float(np.percentile(ms, 95)), "max": float(ms.max())}


def open_loop(submit, offsets: np.ndarray, *, drain_s: float = 60.0,
              annotate=None, lead_s: float = 0.01) -> OpenLoopRun:
    """Call ``submit(i)`` (returns a Future, raises ``queue.Full`` when
    admission refuses) at ``offsets`` from now + ``lead_s``; never wait
    on an answer inside the schedule.  After the last arrival, wait up
    to ``drain_s`` for the answers still out.

    The bookkeeping keeps no Future and no per-request Python object
    the garbage collector tracks (times in arrays, answers as arrays),
    so that the generator adds no collector pauses of its own."""
    span = annotate or (lambda name: contextlib.nullcontext())
    n = len(offsets)
    done = np.full(n, np.nan)
    results: list = [None] * n
    rejected = np.zeros(n, bool)
    late = np.zeros(n)
    left = threading.Semaphore(0)

    def stamp(i, fut):
        t = time.perf_counter()
        exc = fut.exception()
        results[i] = exc if exc is not None else fut.result()
        done[i] = t
        left.release()

    t0 = time.perf_counter() + lead_s
    due = t0 + np.asarray(offsets, float)
    admitted = 0
    for i in range(n):
        delay = due[i] - time.perf_counter()
        if delay > 0:
            with span("bench:wait"):
                time.sleep(delay)
        late[i] = time.perf_counter() - due[i]
        try:
            with span("bench:submit"):
                fut = submit(i)
        except queue.Full:
            rejected[i] = True
            results[i] = queue.Full()
            continue
        fut.add_done_callback(functools.partial(stamp, i))
        admitted += 1
    t_close = float(due[-1])
    deadline = max(time.perf_counter(), t_close) + drain_s
    with span("bench:drain"):
        for _ in range(admitted):
            if not left.acquire(timeout=max(deadline - time.perf_counter(),
                                            0.0)):
                break
    # answers that come after the deadline are late and count as none
    return OpenLoopRun(due, late, done.copy(), list(results), rejected,
                       t_close, deadline)


def closed_loop(call, n_distinct: int, seconds: float, *, annotate=None):
    """Call ``call(i)`` back to back, ``i`` cycling over ``n_distinct``
    batches, until ``seconds`` have passed; the last call runs to its
    end.  Returns ``([(i, answer), ...], elapsed_s)``."""
    span = annotate or (lambda name: contextlib.nullcontext())
    outs = []
    t0 = time.perf_counter()
    while True:
        i = len(outs) % n_distinct
        with span("bench:art.run"):
            outs.append((i, call(i)))
        if time.perf_counter() - t0 >= seconds:
            break
    return outs, time.perf_counter() - t0
