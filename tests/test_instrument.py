"""Instrumentation layer (ISSUE 6): tracer span model, Chrome trace
export/validation, null-tracer no-op guarantees (byte-identical
schedules + emitted HLS with tracing off), DP search statistics,
runtime counters, Report telemetry, and the ``--trace`` CLI path.
"""
import json
import os
import pickle

import pytest

from repro import instrument
from repro.core import cnn_graphs
from repro.core.compile_driver import CompileOptions, compile_design
from repro.core.emit_hls import emit_design
from repro.instrument import (
    NULL_TRACER,
    Tracer,
    diff_snapshots,
    provenance,
    snapshot_dfg,
    use_tracer,
    validate_chrome_trace,
)


class TestTracer:
    def test_spans_nest_and_export_chrome_complete_events(self):
        t = Tracer()
        with t.span("outer", cat="compile", args={"k": 1}):
            with t.span("inner", cat="passes") as sargs:
                sargs["extra"] = "v"
        obj = t.to_chrome()
        ev = {e["name"]: e for e in obj["traceEvents"]}
        assert ev["outer"]["ph"] == "X" and ev["inner"]["ph"] == "X"
        assert ev["outer"]["args"] == {"k": 1}
        assert ev["inner"]["args"] == {"extra": "v"}
        # inner is temporally contained in outer (ts/dur in microseconds)
        o, i = ev["outer"], ev["inner"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
        validate_chrome_trace(obj)

    def test_span_args_mutable_mid_span(self):
        t = Tracer()
        with t.span("s") as sargs:
            sargs.update({"found": 3})
        (e,) = t.to_chrome()["traceEvents"]
        assert e["args"]["found"] == 3

    def test_instant_and_counter_events(self):
        t = Tracer()
        t.instant("mark", cat="partition", args={"reason": "BRAM"})
        t.counter("dma_bytes", {"write": 128, "read": 64})
        ev = t.to_chrome()["traceEvents"]
        phases = sorted(e["ph"] for e in ev)
        assert phases == ["C", "i"]
        validate_chrome_trace(t.to_chrome())

    def test_write_stamps_provenance(self, tmp_path):
        t = Tracer()
        with t.span("s"):
            pass
        p = tmp_path / "trace.json"
        t.write(str(p), provenance={"graph": "g"})
        obj = json.loads(p.read_text())
        validate_chrome_trace(obj)
        assert obj["otherData"]["provenance"]["graph"] == "g"
        assert obj["displayTimeUnit"] == "ms"

    def test_null_tracer_records_nothing_and_discards_args(self):
        assert not NULL_TRACER.enabled
        with NULL_TRACER.span("s", args={"a": 1}) as sargs:
            sargs["b"] = 2       # discarded, not an error
            sargs.update(c=3)
        NULL_TRACER.instant("i")
        NULL_TRACER.counter("c", {"v": 1.0})
        assert NULL_TRACER.to_chrome()["traceEvents"] == []

    def test_contextvar_threading(self):
        assert instrument.current() is NULL_TRACER
        assert not instrument.tracing_active()
        t = Tracer()
        with use_tracer(t):
            assert instrument.current() is t
            assert instrument.tracing_active()
            with instrument.span("ambient"):
                pass
        assert instrument.current() is NULL_TRACER
        assert [e["name"] for e in t.to_chrome()["traceEvents"]] == \
            ["ambient"]

    def test_use_tracer_none_is_noop_scope(self):
        with use_tracer(None):
            assert instrument.current() is NULL_TRACER
            # module-level helpers stay safe no-ops
            with instrument.span("x") as sargs:
                sargs["k"] = 1
            instrument.instant("y")


class TestValidator:
    def _base(self, **kw):
        e = {"name": "n", "ph": "X", "ts": 1.0, "dur": 2.0,
             "pid": 1, "tid": 1, "cat": "c", "args": {}}
        e.update(kw)
        return {"traceEvents": [e]}

    def test_accepts_well_formed(self):
        validate_chrome_trace(self._base())

    @pytest.mark.parametrize("bad", [
        {"ph": "Z"},                    # unknown phase
        {"ts": -1.0},                   # negative timestamp
        {"dur": -5.0},                  # negative duration
        {"pid": "zero"},                # non-int pid
        {"args": "notadict"},           # non-dict args
        {"name": 42},                   # non-string name
    ])
    def test_rejects_malformed_events(self, bad):
        with pytest.raises(ValueError):
            validate_chrome_trace(self._base(**bad))

    def test_rejects_non_list_traceevents(self):
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": {}})

    def test_counter_args_must_be_numeric(self):
        with pytest.raises(ValueError):
            validate_chrome_trace(
                self._base(ph="C", args={"v": "high"}))


class TestSnapshots:
    def test_diff_detects_structural_change(self):
        a = cnn_graphs.conv_relu(8, c_out=4)
        before = snapshot_dfg(a)
        opts = CompileOptions()
        fused = opts.run_pipeline(a).dfg
        d = diff_snapshots(before, snapshot_dfg(fused))
        assert not instrument.diff_is_empty(d)
        assert d["nodes_removed"] or d["nodes_changed"]

    def test_identical_graphs_diff_empty(self):
        s = snapshot_dfg(cnn_graphs.conv_relu(8, c_out=4))
        assert instrument.diff_is_empty(diff_snapshots(s, s))


class TestNoOpByteIdentity:
    """The acceptance contract: tracing off == tracing never existed."""

    def test_schedule_and_hls_bit_identical_traced_vs_untraced(self):
        dfg = cnn_graphs.deep_cascade(64)
        plain = compile_design(dfg, options=CompileOptions())
        traced = compile_design(cnn_graphs.deep_cascade(64),
                                options=CompileOptions(trace=True))
        assert plain.schedule() == traced.schedule()
        assert emit_design(plain) == emit_design(traced)
        assert plain.tracer is None
        assert traced.tracer is not None and traced.tracer.enabled

    def test_untraced_compile_leaves_no_ambient_tracer(self):
        compile_design(cnn_graphs.conv_relu(8, c_out=4),
                       options=CompileOptions())
        assert instrument.current() is NULL_TRACER

    def test_tracer_never_pickled(self):
        d = compile_design(cnn_graphs.conv_relu(8, c_out=4),
                           options=CompileOptions(trace=True))
        assert d.tracer is not None
        d2 = pickle.loads(pickle.dumps(d))
        assert d2.tracer is None
        assert d2.schedule() == d.schedule()


class TestCompileTrace:
    @pytest.fixture(scope="class")
    def traced_224(self):
        """Acceptance graph: deep_cascade_224 compiled with tracing on."""
        return compile_design(cnn_graphs.deep_cascade(224),
                              options=CompileOptions(trace=True))

    def test_pass_spans_present_with_wall_times(self, traced_224):
        ev = traced_224.tracer.to_chrome()["traceEvents"]
        passes = [e for e in ev if e["name"].startswith("pass:")]
        assert passes, "no pass spans recorded"
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in passes)
        # PassStats carries wall_ms regardless of tracing
        assert all(p.wall_ms >= 0
                   for p in traced_224.pass_result.passes)

    def test_dp_stats_event_with_rejected_cut_reasons(self, traced_224):
        ev = traced_224.tracer.to_chrome()["traceEvents"]
        dp = [e for e in ev if e["name"].startswith("dp_stats:")]
        assert len(dp) == 1, "expected exactly one DP statistics event"
        stats = dp[0]["args"]
        assert stats["dp_states"] > 0
        assert stats["ilp_solves"] > 0
        # 224² cascade cannot fit whole-graph: cuts were rejected
        assert stats["rejected_cuts"], "no rejected cuts recorded"
        reasons = {c["reason"] for c in stats["rejected_cuts"]}
        assert reasons <= {"BRAM", "DSP", "BRAM+DSP", "infeasible"}
        assert stats["rejected_by_reason"]
        assert sum(stats["rejected_by_reason"].values()) == \
            len(stats["rejected_cuts"])
        # the kept frontier mirrors the final grouping
        assert len(stats["frontier"]) == len(traced_224.groups)

    def test_dp_stats_attached_even_untraced(self):
        d = compile_design(cnn_graphs.deep_cascade(64),
                           options=CompileOptions())
        assert d.dp_stats is not None
        assert d.dp_stats["dp_states"] >= 0

    def test_whole_trace_validates(self, traced_224):
        validate_chrome_trace(traced_224.tracer.to_chrome())

    def test_ir_after_instants_carry_diffs(self, traced_224):
        ev = traced_224.tracer.to_chrome()["traceEvents"]
        ir = [e for e in ev if e["name"].startswith("ir_after:")]
        assert ir, "no ir_after instants"
        assert all("diff" in e["args"] for e in ir)

    def test_emit_spans_recorded_under_artifact_scope(self, traced_224,
                                                      tmp_path):
        from repro.api import CompiledArtifact

        CompiledArtifact(traced_224).emit_hls(str(tmp_path))
        ev = traced_224.tracer.to_chrome()["traceEvents"]
        emits = [e for e in ev if e["name"].startswith("emit:")]
        assert emits, "no emit spans"
        assert any(e["name"].endswith(".cpp") for e in emits)

    def test_trace_option_validation(self):
        with pytest.raises(ValueError):
            CompileOptions(trace="")
        with pytest.raises(ValueError):
            CompileOptions(trace=3.14)
        assert CompileOptions(trace="/tmp/t.json").trace_path == \
            "/tmp/t.json"
        assert CompileOptions(trace=True).trace_path is None


class TestRuntimeCounters:
    @pytest.fixture(scope="class")
    def ran(self):
        from repro import api

        art = api.compile_graph(cnn_graphs.deep_cascade(64),
                                api.CompileOptions(trace=True))
        out = art.run(interpret=True)
        return art, out

    def test_last_run_stats_per_group(self, ran):
        art, _ = ran
        st = art.last_run_stats
        assert st is not None and st["samples"] == 1
        assert st["wall_ms"] > 0
        names = {g.name for g in art.design.groups}
        assert {row["group"] for row in st["groups"]} == names
        for row in st["groups"]:
            assert row["wall_ms"] >= 0
            assert row["jit_cache"] in ("hit", "miss", "unjitted")

    def test_runtime_spans_and_jit_cache_events(self, ran):
        art, _ = ran
        ev = art.tracer.to_chrome()["traceEvents"]
        runs = [e for e in ev if e["name"] == "ming:run"]
        assert [e["args"]["graph"] for e in runs] == \
            [art.design.source.name], "no runtime spans"
        names = [g.name for g in art.design.groups]
        for span in ("ming:dispatch", "ming:sync"):
            group_spans = [e for e in ev if e["name"] == span]
            assert [e["args"]["group"] for e in group_spans] == names
            assert all(e["cat"] == "runtime" for e in group_spans)
        assert any(e["name"] == "jit_cache" for e in ev)

    def test_exec_cache_stats_surface_in_run_stats(self, ran):
        art, _ = ran
        from repro.kernels import ops

        st = art.last_run_stats
        assert set(st["exec_cache"]) == {"hits", "misses"}
        total = st["exec_cache_total"]
        assert total["hits"] <= ops.exec_cache_stats["hits"]
        assert total["misses"] <= ops.exec_cache_stats["misses"]

    def test_write_trace(self, ran, tmp_path):
        art, _ = ran
        p = tmp_path / "t.json"
        art.write_trace(str(p))
        obj = validate_chrome_trace(json.loads(p.read_text()))
        prov = obj["otherData"]["provenance"]
        assert prov["graph"] == art.design.source.name
        assert "git_sha" in prov and "host" in prov

    def test_write_trace_without_tracer_raises(self):
        from repro import api

        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4),
                                api.CompileOptions())
        with pytest.raises(ValueError, match="trace"):
            art.write_trace("/tmp/never.json")


class TestReportTelemetry:
    def test_report_shows_dma_transitions_for_partitioned(self):
        from repro import api

        art = api.compile_graph(cnn_graphs.deep_cascade(224),
                                api.CompileOptions())
        rep = art.report()
        assert len(rep.groups) > 1
        assert len(rep.transitions) == len(rep.groups) - 1
        s = str(rep)
        assert "-- dma" in s and "overlapped" in s
        for tr in rep.transitions:
            assert tr.cycles >= 0

    def test_single_group_report_has_no_transitions(self):
        from repro import api

        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4),
                                api.CompileOptions())
        rep = art.report()
        assert rep.transitions == ()
        assert "-- dma" not in str(rep)

    def test_telemetry_present_but_excluded_from_equality(self):
        from repro import api

        a1 = api.compile_graph(cnn_graphs.deep_cascade(64),
                               api.CompileOptions())
        a2 = api.compile_graph(cnn_graphs.deep_cascade(64),
                               api.CompileOptions())
        r1, r2 = a1.report(), a2.report()
        assert r1.telemetry and r1.telemetry["passes"]
        assert r1 == r2   # wall-time jitter must not break equality
        assert "telemetry" in str(r1)


class TestProvenance:
    def test_fields(self):
        p = provenance(extra={"k": "v"})
        for key in ("git_sha", "host", "platform", "python", "time_unix"):
            assert key in p
        assert p["k"] == "v"

    def test_names_the_jax_device(self):
        import jax

        devices = jax.devices()
        assert provenance()["device"] == {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }

    def test_no_device_without_jax(self, subproc):
        r = subproc(
            "import sys\n"
            "from repro.instrument import provenance\n"
            "assert provenance()['device'] is None\n"
            "assert 'jax' not in sys.modules\n"
            "print('OK')\n"
        )
        assert r.returncode == 0, r.stderr[-1500:]
        assert "OK" in r.stdout

    def test_stamping_starts_no_backend(self, subproc):
        """A compile-only process has imported JAX but run nothing on
        a device: its stamp names no device and starts no backend."""
        r = subproc(
            "import jax\n"
            "from jax._src import xla_bridge\n"
            "from repro.instrument import provenance\n"
            "assert provenance()['device'] is None\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "jax.numpy.zeros(1).block_until_ready()\n"
            "assert provenance()['device']['count'] >= 1\n"
            "print('OK')\n"
        )
        assert r.returncode == 0, r.stderr[-1500:]
        assert "OK" in r.stdout

    def test_env_override(self, monkeypatch):
        import importlib

        # the package re-exports the provenance *function* under the
        # submodule's name, so resolve the module via importlib
        pm = importlib.import_module("repro.instrument.provenance")
        monkeypatch.setenv("REPRO_GIT_SHA", "deadbeef")
        monkeypatch.setattr(pm, "_GIT_SHA", None)  # drop process cache
        assert provenance()["git_sha"] == "deadbeef"
        monkeypatch.setattr(pm, "_GIT_SHA", None)


class TestSmokeDiffIgnoresProvenance:
    def test_provenance_only_change_is_not_a_delta(self, capsys):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "smoke_diff",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "smoke_diff.py"))
        sd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sd)
        row = {"total_cycles": 100, "max_group_cycles": 60, "max_bram": 10,
               "groups": 2, "spill_bytes": 0,
               "provenance": {"git_sha": "aaa", "compile_s": 1.0}}
        prev = {"g": {"kv260": dict(row)}}
        cur = {"g": {"kv260": dict(row,
                                   provenance={"git_sha": "bbb",
                                               "compile_s": 9.9})}}
        lines = []
        assert sd.diff(prev, cur, 0.10, emit=lines.append) == 0
        assert lines == ["graph,target,metric,previous,current,delta_pct"]

    def test_metric_regression_still_caught(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "smoke_diff2",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "smoke_diff.py"))
        sd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sd)
        prev = {"g": {"kv260": {"total_cycles": 100}}}
        cur = {"g": {"kv260": {"total_cycles": 150}}}
        lines = []
        assert sd.diff(prev, cur, 0.10, emit=lines.append) == 1


class TestCLITrace:
    def test_compile_trace_flag_writes_valid_trace(self, tmp_path, capsys):
        from repro.__main__ import main as cli_main

        p = tmp_path / "trace.json"
        rc = cli_main(["compile", "conv_relu_32", "--trace", str(p),
                       "--quiet"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "trace written" in out
        obj = validate_chrome_trace(json.loads(p.read_text()))
        names = [e["name"] for e in obj["traceEvents"]]
        assert any(n.startswith("pass:") for n in names)
        assert any(n.startswith("partition:") for n in names)
        assert "provenance" in obj["otherData"]
