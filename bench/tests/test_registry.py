"""``BENCHMARK.json`` and the files it names: everything is found by
name, and the file keeps to the benchmark's contract."""
import json
import os
import re

import pytest

from bench import registry
from bench.registry import ROOT

SPEC = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_one_line_texts():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
                assert "\t" not in e[key]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_configs_are_files_under_paths_with_their_reductions():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        card = registry._json(os.path.join(ROOT, c["file"]))
        assert card["reduced"] == c["reduced"]
        assert card["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])


def test_end_to_end_bounds():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_files_by_name(name):
    cell = registry.cell(name)
    assert cell.chips in (1, 4)
    assert os.path.exists(cell.config_path)
    assert cell.traffic["kind"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
    for m in cell.per_layer + cell.end_to_end:
        assert callable(registry.reader(m["name"]))
    assert callable(registry.driver(cell.traffic["kind"]))


def test_every_listed_workload_exists():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        for w in m.get("workloads", []):
            assert w in CELLS


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        registry.cell("nope.nothing")


def test_unknown_kind_or_metric_is_an_error():
    with pytest.raises(KeyError, match="bench/drivers/teleport.py"):
        registry.driver("teleport")
    with pytest.raises(KeyError, match="bench/metrics/nope.py"):
        registry.reader("nope")


def test_reader_returns_none_when_nothing_to_read():
    """A reader that finds nothing returns None, never 0."""
    class Empty:
        reduced = None
        engine_metrics = None
        latencies_ms = None
        setup: dict = {}
        window: dict = {}
    for m in SPEC["per_layer"] + SPEC["end_to_end"]:
        assert registry.reader(m["name"])(Empty()) is None, m["name"]


def test_peaks_table_keyed_by_device_kind():
    from bench import device

    row = device.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        device.peaks("TPU v9 imaginary")


def test_no_accelerator_exits_nonzero_without_result(capsys, monkeypatch):
    """On the CPU, a run exits non-zero and prints no result line."""
    from bench import run as bench_run

    monkeypatch.setattr(bench_run, "prepare", lambda script: None)
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_checkout_without_program_exits_nonzero(tmp_path, capsys, monkeypatch):
    from bench import run as bench_run

    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_benchmark_json_is_plain_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert json.load(f) == SPEC
