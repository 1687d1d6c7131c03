"""Helpers the metric readers under ``bench/metrics/`` share."""
from __future__ import annotations

import numpy as np

from bench import model


def histogram_mean(snapshot: dict | None, family: str, **labels):
    """Exact mean (sum / count) of a histogram series in a metrics
    registry snapshot, or ``None`` where it holds no observation."""
    if not snapshot:
        return None
    fam = snapshot.get("histograms", {}).get(family)
    if fam is None:
        return None
    rows = [r for r in fam["values"]
            if all(r["labels"].get(k) == v for k, v in labels.items())]
    count = sum(r["count"] for r in rows)
    return sum(r["sum"] for r in rows) / count if count else None


def latency_pct(run, q: float):
    """The ``q``-th percentile of the window's request latencies, in ms."""
    lat = run.latencies_ms
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, q))


def idle_share_pct(run):
    """100 × the traced window's device idle share."""
    red = run.reduced
    if not red or not red["window_s"] or red["busy_s"] <= 0:
        return None
    return 100.0 * red["idle_share"]


def itemsize(run) -> int:
    return np.dtype(run.config["dtype"]).itemsize


def mfu_pct(run, samples_per_s: float):
    """100 × model FLOPs per sample × samples/s over the chip's peak in
    the configuration's datapath."""
    flops = model.model_flops_per_sample(run.config)
    return 100.0 * flops * samples_per_s / run.peaks[run.config["peak"]]
