"""The reduction from a profiler trace to device numbers, on made-up
intervals and on a short trace of ``vgg16.offline_b32`` recorded on a
TPU v5e (``bench/record_trace.py``, three calls of batch 32)."""
import os

import numpy as np
import pytest

from bench import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "vgg16_offline_b32.trace.json.gz")
PALLAS = {"pallas": tr.PALLAS_MARKER}


def test_union_clip_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 4), (6, 9), (10, 12)], 2, 10) == [(2, 4), (6, 9)]
    assert tr.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_op_kind():
    assert tr.op_kind("%f.1 = f32[4,8]{1,0:T(8,128)} fusion(f32[4]{0} %x)") \
        == "fusion f32[4,8]{1,0:T(8,128)}"
    tup = ("%fusion.61 = (s8[2]{0:T(8,128)(4,1)S(1)}, s32[2]{0}) "
           "fusion(s8[2]{0} %a), kind=kLoop")
    assert tr.op_kind(tup) == "fusion (s8[2]{0:T(8,128)(4,1)S(1)}, s32[2]{0})"
    assert tr.op_kind("copy-start.3") == "copy-start.3"


def test_host_activity_picks_innermost():
    host = [("outer", 0, 100), ("inner", 10, 20), ("later", 50, 10)]
    assert tr.host_activity(host, [5, 15, 40, 55, 200]) == [
        "outer", "inner", "outer", "later", "idle host"]


def _synthetic():
    ms = 1_000_000
    hlo = ('%k = f32[8]{0} custom-call(f32[8]{0} %a), '
           'custom_call_target="tpu_custom_call"')
    return {
        "window": [0, 100 * ms],
        # two overlapping ops, one kernel, one op partly outside
        "devices": [[("%f = f32[4]{0} fusion(f32[4]{0} %x)", 10 * ms, 20 * ms),
                     (hlo, 20 * ms, 20 * ms),
                     ("%c = f32[4]{0} copy(f32[4]{0} %y)", 90 * ms, 30 * ms)]],
        "host": [("bench:window", 0, 100 * ms),
                 ("bench:art.run", 0, 60 * ms),
                 ("bench:wait", 60 * ms, 40 * ms)],
    }


def test_reduce_synthetic():
    red = tr.reduce(_synthetic(), PALLAS)
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [10, 40] and [90, 100] ms
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["idle_share"] == pytest.approx(0.6)
    assert red["kernel_s"]["pallas"] == pytest.approx(0.020)
    ops = dict(red["device_ops"])
    assert ops["tpu_custom_call f32[8]{0}"] == pytest.approx(0.020)
    assert ops["fusion f32[4]{0}"] == pytest.approx(0.020)
    assert ops["copy f32[4]{0}"] == pytest.approx(0.010)
    # each gap goes to what the host did at its middle: [0, 10] ms to
    # art.run, [40, 90] ms to wait
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"bench:art.run": 0.010, "bench:wait": 0.050})


def test_reduce_averages_over_devices():
    t = _synthetic()
    t["devices"].append([])  # a second chip that did nothing
    red = tr.reduce(t, PALLAS)
    assert red["busy_s"] == pytest.approx(0.020)
    assert red["idle_share"] == pytest.approx(0.8)


@pytest.fixture(scope="module")
def recorded():
    return tr.read_saved(RECORDED)


def _busy_by_timeline(trace):
    """Busy time by marking a 1 us timeline: an independent union."""
    lo, hi = trace["window"]
    n = int((hi - lo) // 1000) + 1
    mark = np.zeros(n, bool)
    for _, s, d in trace["devices"][0]:
        a = int(max(s, lo) - lo) // 1000
        b = int(min(s + d, hi) - lo) // 1000
        if b > a:
            mark[a:b] = True
    return mark.sum() * 1e-6


def test_recorded_trace_busy_and_idle(recorded):
    red = tr.reduce(recorded, PALLAS)
    assert red["window_s"] == pytest.approx(0.431155847, rel=1e-6)
    assert red["busy_s"] == pytest.approx(_busy_by_timeline(recorded),
                                          rel=1e-2)
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])
    assert 0.5 < red["idle_share"] < 0.8


def test_recorded_trace_kernel_time(recorded):
    red = tr.reduce(recorded, PALLAS)
    kernels = [(n, s, d) for n, s, d in recorded["devices"][0]
               if tr.PALLAS_MARKER in n]
    # 13 convs, the 512-channel ones in weight tiles, in each of 3 calls
    assert len(kernels) % 3 == 0 and len(kernels) >= 39
    lo, hi = recorded["window"]
    want = sum(min(s + d, hi) - max(s, lo) for _, s, d in kernels
               if s + d > lo and s < hi) / 1e9
    assert red["kernel_s"]["pallas"] == pytest.approx(want)
    assert 0 < red["kernel_s"]["pallas"] < red["busy_s"]
    assert red["device_ops"][0][0].startswith("tpu_custom_call ")


def test_recorded_trace_gap_attribution(recorded):
    red = tr.reduce(recorded, PALLAS)
    gaps = dict(red["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    assert sum(gaps.values()) <= idle * (1 + 1e-9)
    assert len(red["idle_gaps"]) <= 10 and len(red["device_ops"]) <= 10
    # the benchmark's own span around art.run holds the most idle time
    assert red["idle_gaps"][0][0] == "bench:art.run"
    assert tr.WINDOW_SPAN not in gaps
