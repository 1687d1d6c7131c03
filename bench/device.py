"""The chip: which one this run holds, its peaks, its memory, and the
compiles JAX reports."""
from __future__ import annotations

import json
import os

from bench.registry import ROOT

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def describe() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX holds."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def check(chips: int) -> dict:
    """:func:`describe` of the TPU JAX holds; raises
    :class:`NoAccelerator` for any other platform or too few chips."""
    dev = describe()
    if dev["platform"] != "tpu":
        raise NoAccelerator(f"JAX's default device is {dev['platform']!r} "
                            f"({dev['kind']}); the benchmark runs on a "
                            "TPU only")
    if dev["count"] < chips:
        raise NoAccelerator(f"{dev['count']} TPU chip(s); the cell asks "
                            f"for {chips}")
    return dev


def peaks(kind: str, root: str = ROOT) -> dict:
    """The peaks-table row of ``device_kind`` ``kind``; a device that is
    not in ``bench/peaks.json`` is an error."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table)})")
    return table[kind]


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest local device, where the backend
    reports it."""
    import jax

    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.local_devices()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


class CompileClock:
    """Sums JAX's backend-compile seconds and counts persistent-cache
    hits and misses (copied from the program's ``chip_smoke.py``)."""

    def __init__(self) -> None:
        self.secs = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.secs += duration

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HITS:
            self.hits += 1
        elif event == CACHE_MISSES:
            self.misses += 1
