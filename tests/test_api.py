"""Public API (ISSUE 4): CompileOptions validation, the
CompiledArtifact session handle, the retired ``compile`` alias
(ISSUE 5), batched runs, and the ``python -m repro`` CLI.
"""
import os

import numpy as np
import pytest

from repro import api
from repro.__main__ import main as cli_main
from repro.core import cnn_graphs
from repro.core.compile_driver import (
    KV260,
    ZU3EG,
    CompileOptions,
    Target,
    compile_design,
)
from repro.passes import PartitionError, interp


class TestCompileOptions:
    def test_preset_name_resolves_to_target(self):
        o = CompileOptions(target="zu3eg")
        assert o.target is ZU3EG
        assert CompileOptions().target is KV260

    def test_custom_target_passes_through(self):
        tiny = Target(name="tiny", d_total=64, b_total=32)
        assert CompileOptions(target=tiny).target is tiny

    @pytest.mark.parametrize("bad,match", [
        (dict(target="nope"), "unknown target preset"),
        (dict(target=42), "Target or preset name"),
        (dict(strategy="zigzag"), "unknown partition strategy"),
        (dict(weight_streaming="sometimes"), "weight_streaming"),
        (dict(max_unroll=0), "max_unroll"),
        (dict(passes=("dce", "zap")), "unknown pass name"),
    ])
    def test_validation_happens_at_construction(self, bad, match):
        with pytest.raises(ValueError, match=match):
            CompileOptions(**bad)

    def test_max_unroll_defers_to_target(self):
        assert CompileOptions().resolved_max_unroll == KV260.max_unroll
        assert CompileOptions(max_unroll=8).resolved_max_unroll == 8

    def test_frozen(self):
        o = CompileOptions()
        with pytest.raises(Exception):
            o.strategy = "greedy"

    def test_pass_selection_runs_exactly_those_passes(self):
        o = CompileOptions(passes=("canonicalize", "dce"))
        res = o.run_pipeline(cnn_graphs.conv_relu(8, c_out=4))
        assert [p.name for p in res.passes] == ["canonicalize", "dce"]
        # no fusion selected: both nodes survive
        assert len(res.dfg.nodes) == 2

    def test_empty_passes_skip_pipeline(self):
        d = compile_design(cnn_graphs.conv_relu(8, c_out=4),
                           options=CompileOptions(passes=()))
        assert d.pass_result is None
        assert len(d.source.nodes) == 2

    def test_options_and_legacy_kwargs_are_exclusive(self):
        with pytest.raises(ValueError, match="not both"):
            compile_design(cnn_graphs.conv_relu(8, c_out=4),
                           KV260, options=CompileOptions())

    def test_weight_streaming_off_rejects_fat_conv(self):
        with pytest.raises(PartitionError, match="weight_streaming"):
            compile_design(cnn_graphs.fat_conv(),
                           options=CompileOptions(weight_streaming="off"))

    def test_options_recorded_on_design(self):
        o = CompileOptions(strategy="greedy")
        d = compile_design(cnn_graphs.conv_relu(8, c_out=4), options=o)
        assert d.options is o

    def test_partitioner_and_ilp_reject_mixed_options_and_kwargs(self):
        """No silent override anywhere in the stack: options and loose
        kwargs are mutually exclusive at every layer."""
        from repro.core.dse import solve_ilp
        from repro.core.streaming import plan_streams
        from repro.passes import partition_layer_groups

        dfg = cnn_graphs.conv_relu(8, c_out=4)
        with pytest.raises(ValueError, match="not both"):
            partition_layer_groups(dfg, options=CompileOptions(), b_total=50)
        with pytest.raises(ValueError, match="not both"):
            solve_ilp(plan_streams(dfg), options=CompileOptions(), d_total=50)
        # options alone still works end to end at both layers
        d = partition_layer_groups(dfg, options=CompileOptions())
        assert d.feasible
        assert solve_ilp(plan_streams(dfg), options=CompileOptions()).feasible


class TestRetiredCompileAlias:
    """ISSUE 5 satellite: the deprecating ``compile`` alias is gone —
    a clear AttributeError points at ``compile_design``."""

    def test_attribute_access_raises_with_pointer(self):
        from repro.core import compile_driver

        with pytest.raises(AttributeError, match="compile_design"):
            compile_driver.compile  # noqa: B018

    def test_from_import_fails_too(self):
        with pytest.raises(ImportError, match="compile"):
            from repro.core.compile_driver import compile  # noqa: F401

    def test_other_attributes_error_normally(self):
        from repro.core import compile_driver

        with pytest.raises(AttributeError, match="no_such_thing"):
            compile_driver.no_such_thing  # noqa: B018


class TestCompiledArtifact:
    def test_compile_graph_accepts_builders_and_dfgs(self):
        net = api.Sequential([api.Conv2D(4), api.ReLU()],
                             input_shape=(1, 8, 8, 3), name="t")
        a1 = api.compile_graph(net)
        a2 = api.compile_graph(net.build())
        assert a1.report() == a2.report()
        with pytest.raises(TypeError, match="DFG or a builder"):
            api.compile_graph(42)

    def test_kwarg_sugar(self):
        a = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4),
                              target="zu3eg")
        assert a.target_name == "zu3eg"
        with pytest.raises(ValueError, match="not both"):
            api.compile_graph(cnn_graphs.conv_relu(8, c_out=4),
                              api.CompileOptions(), target="zu3eg")

    @pytest.mark.parametrize("target", [KV260, ZU3EG], ids=["kv260", "zu3eg"])
    @pytest.mark.parametrize("make", [
        lambda: cnn_graphs.conv_relu(8, c_out=4),
        lambda: cnn_graphs.residual_block(8, c=4),
        lambda: cnn_graphs.conv_avgpool(8, c_out=4),
        lambda: cnn_graphs.feed_forward(batch=16, d_in=8, d_hidden=16),
    ], ids=["conv_relu", "residual", "conv_avgpool", "feed_forward"])
    def test_run_bit_exact_with_interp_on_both_targets(self, make, target):
        """Acceptance: builder graph → CompileOptions → artifact.run is
        bit-exact with the DFG interpreter on every device preset."""
        dfg = make()
        art = api.compile_graph(dfg, api.CompileOptions(target=target))
        env = interp.random_env(art.design.source, seed=3)
        want = interp.graph_outputs(art.design.source, env)
        inputs = {k: env[k] for k in art.design.source.graph_inputs}
        got = art.run(inputs, params=env, interpret=True, seed=3)
        outs = got if isinstance(got, dict) else {
            art.design.source.graph_outputs[0]: got
        }
        for k, arr in want.items():
            np.testing.assert_array_equal(np.asarray(arr),
                                          np.asarray(outs[k]))

    def test_run_accepts_bare_array_for_single_input(self):
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        env = interp.random_env(art.design.source, seed=5)
        got_map = art.run({"x": env["x"]}, params=env, interpret=True)
        got_bare = art.run(env["x"], params=env, interpret=True)
        np.testing.assert_array_equal(np.asarray(got_map),
                                      np.asarray(got_bare))

    def test_run_rejects_unknown_bindings(self):
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        with pytest.raises(KeyError, match="not a constant"):
            art.run(params={"nonsense": 1}, interpret=True)
        with pytest.raises(KeyError, match="not a graph input"):
            art.run({"nonsense": 1}, interpret=True)

    def test_run_rejects_non_constant_param(self):
        """A param naming a surviving intermediate would be silently
        recomputed over — reject it instead."""
        art = api.compile_graph(cnn_graphs.cascade_conv(8, c_mid=4),
                                api.CompileOptions(passes=()))
        inter = art.design.source.nodes[0].output
        with pytest.raises(KeyError, match="not a constant"):
            art.run(params={inter: 1}, interpret=True)

    def test_run_rejects_partially_bound_inputs(self):
        g = api.Graph("two_in")
        a = g.input((1, 4, 4, 2), name="a")
        b = g.input((1, 4, 4, 2), name="b")
        g.output(g.add(a, b))
        art = api.compile_graph(g.build())
        env = interp.random_env(art.design.source, seed=1)
        with pytest.raises(ValueError, match="missing graph input"):
            art.run({"a": env["a"]}, interpret=True)
        # all inputs bound, or none (smoke run), both work
        art.run({"a": env["a"], "b": env["b"]}, interpret=True)
        art.run(interpret=True)

    def test_batched_run_stacks_per_sample_outputs(self):
        """ISSUE 5 satellite: one extra leading dim on every input =>
        per-sample execution, outputs stacked along a new batch axis."""
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        src = art.design.source
        env = interp.random_env(src, seed=11)
        xs = np.stack([
            np.asarray(env["x"]),
            np.asarray(env["x"]) + 1,
            np.asarray(env["x"]) - 2,
        ])
        got = art.run({"x": xs}, params=env, interpret=True)
        assert got.shape[0] == 3
        for i in range(3):
            want = art.run({"x": xs[i]}, params=env, interpret=True)
            np.testing.assert_array_equal(np.asarray(got[i]),
                                          np.asarray(want))

    def test_batched_run_multi_input_consistency(self):
        g = api.Graph("two_in")
        a = g.input((1, 4, 4, 2), name="a")
        b = g.input((1, 4, 4, 2), name="b")
        g.output(g.add(a, b))
        art = api.compile_graph(g.build())
        rng = np.random.default_rng(0)
        xa = rng.integers(-4, 5, (2, 1, 4, 4, 2)).astype(np.int32)
        xb = rng.integers(-4, 5, (2, 1, 4, 4, 2)).astype(np.int32)
        got = art.run({"a": xa, "b": xb}, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), xa + xb)
        # mixed batched/unbatched inputs fail loudly
        with pytest.raises(ValueError, match="leading batch extent"):
            art.run({"a": xa, "b": xb[0]}, interpret=True)
        # wrong ranks fail loudly
        with pytest.raises(ValueError, match="expected"):
            art.run({"a": xa[None], "b": xb[None]}, interpret=True)
        # batch extent 0 is a clear error, not a numpy stack crash
        with pytest.raises(ValueError, match="batch extent 0"):
            art.run({"a": xa[:0], "b": xb[:0]}, interpret=True)

    def test_batched_run_on_zoo_classifier(self):
        """Imported classifiers validate on small input batches."""
        from repro.frontends import zoo

        art = api.compile_graph(zoo.lenet5())
        src = art.design.source
        env = interp.random_env(src, seed=2)
        xs = np.random.default_rng(3).integers(
            -4, 5, (2,) + src.values["x"].shape
        ).astype(np.int32)
        got = art.run(xs, params=env, interpret=True)
        assert got.shape == (2, 1, 10)
        for i in range(2):
            np.testing.assert_array_equal(
                np.asarray(art.run(xs[i], params=env, interpret=True)),
                np.asarray(got[i]),
            )

    def test_report_table(self):
        art = api.compile_graph(cnn_graphs.deep_cascade(32))
        rep = art.report()
        assert rep.graph == "deep_cascade_32" and rep.target == "kv260"
        assert rep.total_cycles == art.design.total_cycles
        assert rep.max_bram == art.design.max_bram
        assert len(rep.groups) == len(art.design.groups)
        text = str(rep)
        assert "deep_cascade_32 @ kv260" in text
        assert "group,nodes,cycles" in text

    def test_report_shows_streamed_weights(self):
        rep = api.compile_graph(cnn_graphs.fat_conv()).report()
        assert any(g.weight_streamed for g in rep.groups)
        assert "conv0/" in str(rep)

    def test_emit_hls_writes_kernels_and_host_schedule(self, tmp_path):
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        paths = art.emit_hls(str(tmp_path / "out"))
        names = sorted(os.path.basename(p) for p in paths)
        assert names == ["conv_relu_8_g0.cpp", "host_schedule.cpp"]
        for p in paths:
            assert os.path.getsize(p) > 0

    def test_save_load_roundtrip(self, tmp_path):
        art = api.compile_graph(cnn_graphs.conv_avgpool(8, c_out=4))
        path = art.save(str(tmp_path / "cache" / "a.artifact"))
        loaded = api.CompiledArtifact.load(path)
        assert loaded.report() == art.report()
        env = interp.random_env(art.design.source, seed=7)
        inputs = {k: env[k] for k in art.design.source.graph_inputs}
        a = art.run(inputs, params=env, interpret=True)
        b = loaded.run(inputs, params=env, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_load_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.artifact"
        import pickle

        p.write_bytes(pickle.dumps({"not": "an artifact"}))
        with pytest.raises(ValueError, match="not a CompiledArtifact"):
            api.CompiledArtifact.load(str(p))

    def test_suite_registry_covers_paper_suite(self):
        s = api.suite()
        assert set(cnn_graphs.PAPER_SUITE) <= set(s)
        for extra in ("conv_pool_32", "conv_avgpool_32", "fat_conv_16",
                      "fat_cascade_16"):
            assert extra in s
        # and the model zoo rides along (ISSUE 5)
        from repro.frontends import zoo

        assert set(zoo.ZOO) <= set(s)

    def test_every_small_suite_graph_compiles_on_both_targets(self):
        """Acceptance (model level): every suite graph is expressible
        via the builder and compiles under CompileOptions on both
        presets.  224² variants are covered by the benchmark smoke
        (BENCH_smoke.json) — too slow to re-solve here."""
        small = [n for n in api.suite() if "224" not in n]
        for name in small:
            dfg = api.suite()[name]()
            for tname in ("kv260", "zu3eg"):
                art = api.compile_graph(
                    dfg, api.CompileOptions(target=tname)
                )
                assert art.feasible, (name, tname)


class TestTopLevelExports:
    def test_lazy_package_surface(self):
        import repro

        assert repro.CompileOptions is CompileOptions
        assert repro.Sequential is api.Sequential
        assert callable(repro.compile_graph)
        assert "compile_graph" in dir(repro)
        with pytest.raises(AttributeError):
            repro.no_such_symbol


class TestCli:
    def test_list(self, capsys):
        assert cli_main(["list"]) == 0
        out = capsys.readouterr().out
        assert "conv_relu_32" in out and "kv260" in out

    def test_compile_report_and_emit(self, tmp_path, capsys):
        rc = cli_main([
            "compile", "conv_relu_32", "--target", "zu3eg",
            "--emit", str(tmp_path / "hls"),
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "conv_relu_32 @ zu3eg" in out
        assert (tmp_path / "hls" / "host_schedule.cpp").exists()

    def test_unknown_graph_fails_with_hint(self, capsys):
        assert cli_main(["compile", "resnet152"]) == 2
        assert "python -m repro list" in capsys.readouterr().err

    def test_compile_model_card_file_and_run(self, capsys):
        """ISSUE 5 acceptance: `python -m repro compile examples/
        lenet5.json --run` compiles and executes the imported model."""
        card = os.path.join(os.path.dirname(__file__), "..", "examples",
                            "lenet5.json")
        assert cli_main(["compile", card, "--run", "--quiet"]) == 0
        out = capsys.readouterr().out
        assert "ran OK" in out

    def test_zoo_lists_and_exports_cards(self, tmp_path, capsys):
        assert cli_main(["zoo", "--export", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "lenet5" in out
        for name in ("lenet5", "tiny_vgg_32", "edge_residual_32"):
            assert (tmp_path / f"{name}.json").exists()

    def test_compile_unknown_extension_fails_cleanly(self, tmp_path, capsys):
        bad = tmp_path / "model.txt"
        bad.write_text("nope")
        assert cli_main(["compile", str(bad)]) == 2
        assert "unknown model extension" in capsys.readouterr().err

    def test_suite_name_wins_over_cwd_entry(self, tmp_path, monkeypatch,
                                            capsys):
        """A stray file/dir named like a suite graph must not shadow
        the registry (regression: os.path.exists checked first)."""
        (tmp_path / "conv_relu_32").mkdir()
        monkeypatch.chdir(tmp_path)
        assert cli_main(["compile", "conv_relu_32", "--quiet"]) == 0

    def test_compile_directory_path_exits_two(self, tmp_path, capsys):
        """IsADirectoryError (and friends) are bad arguments (exit 2),
        never raw tracebacks."""
        d = tmp_path / "model.json"
        d.mkdir()
        assert cli_main(["compile", str(d)]) == 2
        assert "error:" in capsys.readouterr().err

    def test_bad_option_fails_cleanly(self, capsys):
        assert cli_main(["compile", "conv_relu_32", "--target", "vu9p"]) == 2
        assert "unknown target preset" in capsys.readouterr().err

    def test_infeasible_design_exits_one_not_two(self, capsys):
        """PartitionError on a valid command line is exit 1 (infeasible
        design), reserving 2 for bad arguments."""
        rc = cli_main(["compile", "fat_conv_16", "--weight-streaming",
                       "off", "--quiet"])
        assert rc == 1
        assert "infeasible" in capsys.readouterr().err

    def test_cli_keeps_compile_cache_where_env_says(self, tmp_path, subproc):
        """The entry point caches compiled executables in
        ``JAX_COMPILATION_CACHE_DIR`` when it is set, and nowhere else."""
        from repro import compile_cache

        def repo_entries():
            if not os.path.isdir(compile_cache.REPO_CACHE_DIR):
                return set()
            return set(os.listdir(compile_cache.REPO_CACHE_DIR))

        before = repo_entries()
        cache = tmp_path / "jax_cache"
        r = subproc(
            "import runpy, sys\n"
            "sys.argv = ['repro', 'compile', 'conv_relu_32', '--run',"
            " '--quiet']\n"
            "runpy.run_module('repro', run_name='__main__')\n",
            env={"JAX_COMPILATION_CACHE_DIR": str(cache)},
        )
        assert r.returncode == 0, r.stderr[-1500:]
        assert "ran OK" in r.stdout
        assert any(cache.iterdir())
        assert repo_entries() == before
