"""Tiny cells for CPU tests, added the way a later change adds a cell:
a copy of the benchmark with new files and ``BENCHMARK.json`` entries,
and no file of the copy edited."""
from __future__ import annotations

import json
import os
import shutil

from bench.registry import ROOT

FLOAT_CARD_EXTRA = {
    "source": "test model",
    "reduced": [],
    "dtype": "float32",
    "control": "fp8",
    "peak": "bf16_flops_per_s",
    "weight_fill": "he_normal",
    "input_fill": "standard_normal",
    "compile_options": {"target": "kv260"},
    "limits": {"max_rel_l2": 0.02},
}
INT_CARD_EXTRA = dict(FLOAT_CARD_EXTRA, dtype="int8", control="int4",
                      peak="int8_ops_per_s", weight_fill="int8_uniform",
                      input_fill="int8_uniform",
                      limits={"mismatched_answers": 0})

OFFLINE = {"kind": "closed_loop", "batch": 4, "distinct_batches": 2,
           "warmup_calls": 1}
SERVE = {"kind": "open_loop", "rate_per_s": 100,
         "server": {"max_batch": 4, "latency_budget_ms": 2.0},
         "distinct_inputs": 16, "drain_s": 5}


def tiny_card(name: str, extra: dict) -> dict:
    """conv-relu-pool twice, then two dense layers, at 12x12x2."""
    from repro.api import Conv2D, Dense, Flatten, MaxPool, ReLU, Sequential
    from repro.frontends import export_card

    net = Sequential([Conv2D(4), ReLU(), MaxPool(2), Conv2D(8), ReLU(),
                      MaxPool(2), Flatten(), Dense(16), ReLU(), Dense(6)],
                     input_shape=(1, 12, 12, 2), name=name).build()
    return dict(export_card(net), **extra)


def add_cell(root: str, name: str, card: dict, traffic: dict,
             widen=None) -> None:
    """Add one cell to the benchmark under ``root`` as files and
    entries: its configuration file, its traffic mix file, its
    ``configs`` and ``workloads`` entries, and its name in the
    ``workloads`` lists of the metrics in ``widen`` (by default those
    its kind of traffic reports, :data:`WIDEN`)."""
    widen = WIDEN[traffic["kind"]] if widen is None else widen
    cfg, mix = card["name"], f"{name.replace('.', '_')}_mix"
    with open(os.path.join(root, "bench", "configs", cfg + ".json"), "w") as f:
        json.dump(card, f)
    with open(os.path.join(root, "bench", "traffic", mix + ".json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": cfg, "source": "test model",
                            "file": f"bench/configs/{cfg}.json",
                            "reduced": [], "why": "CPU test"})
    spec["workloads"].append({"name": name, "config": cfg, "traffic": mix,
                              "chips": 1, "why": "CPU test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and m["name"] in widen:
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(spec, f)


#: the metrics a tiny cell of each traffic kind reports beside the ones
#: every cell reports
WIDEN = {
    "closed_loop": ("samples_per_s",),
    "open_loop": ("latency_p50_ms", "queue_wait_ms",
                          "batch_occupancy", "execute_ms"),
}


def copy_benchmark(dst: str) -> str:
    """``BENCHMARK.json`` and ``bench/`` (without its tests) under
    ``dst``, as a checkout holds them."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return dst
