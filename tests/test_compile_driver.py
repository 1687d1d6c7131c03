"""Unified compile driver (ISSUE 2): schedule IR, cycle-balanced
partitioning, partial weight streaming, and single-schedule consumers."""
import numpy as np
import pytest

from repro.core import cnn_graphs
from repro.core.compile_driver import (
    KV260,
    TARGETS,
    ZU3EG,
    CompiledDesign,
    GroupSchedule,
    Target,
    compile_design,
)
from repro.core.dse import solve_ilp
from repro.core.emit_hls import emit_design
from repro.core.resource_model import (
    DRAM_BYTES_PER_CYCLE,
    KV260_BRAM18K,
    KV260_DSP,
    transition_cycles,
)
from repro.core.streaming import plan_streams
from repro.passes import (
    PartitionError,
    partition_layer_groups,
    run_default_pipeline,
)
from repro.passes import interp


@pytest.fixture()
def deep224_design(deep224_partition):
    """deep_cascade(224), balanced partition (session-shared IR — the
    same CompiledDesign compile() builds)."""
    return deep224_partition


@pytest.fixture(scope="module")
def deep224_greedy(deep224_fused):
    return partition_layer_groups(deep224_fused, strategy="greedy")


class TestCompiledDesign:
    """The one object every backend consumes."""

    def test_single_group_when_graph_fits(self):
        d = compile_design(cnn_graphs.conv_relu(32))
        assert isinstance(d, CompiledDesign)
        assert d.whole_graph_feasible and not d.partitioned
        assert len(d.groups) == 1 and isinstance(d.groups[0], GroupSchedule)
        assert d.target == KV260
        assert d.original is not None and d.pass_result is not None
        # pass pipeline ran: conv+relu fused into one node
        assert len(d.source.nodes) == 1

    def test_partition_returns_same_ir(self, deep224_design):
        """partition_layer_groups and compile() build the same IR — no
        second plan-derivation path left."""
        d = deep224_design
        assert isinstance(d, CompiledDesign)
        assert all(isinstance(g, GroupSchedule) for g in d.groups)
        assert d.partitioned and d.feasible
        assert d.max_bram <= d.b_total and d.max_dsp <= d.d_total

    def test_schedule_rows_carry_weight_streaming(self):
        d = compile_design(cnn_graphs.fat_conv())
        rows = d.schedule()
        assert any(r["weight_streamed"] for r in rows)

    def test_custom_target(self):
        tiny = Target(name="tiny", d_total=64, b_total=32)
        d = compile_design(cnn_graphs.conv_relu(8, c_out=4), tiny)
        assert d.d_total == 64 and d.b_total == 32
        assert d.feasible


class TestCycleAccounting:
    """Satellite: spill-buffer sizing and host-schedule cycle property."""

    @pytest.mark.parametrize("n,c_mid,b_total", [
        (8, 4, 2), (8, 4, KV260_BRAM18K),
        (16, 8, 2), (16, 8, 4), (16, 8, 8), (16, 4, 3),
        (32, 8, 16), (32, 16, 8),
    ])
    def test_total_cycles_identity(self, n, c_mid, b_total):
        """Property (swept over graph sizes × budgets): sum(group cycles)
        + overlapped boundary DMA == total_cycles, with each boundary
        recomputed independently from the adjacent groups' spill lists —
        and never above the PR 2 serial round-trip baseline."""
        fused = run_default_pipeline(cnn_graphs.cascade_conv(n, c_mid=c_mid)).dfg
        try:
            pp = partition_layer_groups(fused, b_total=b_total)
        except PartitionError:
            pytest.skip("unsplittable under this budget")
        for s in pp.spills():
            assert s.bits == fused.values[s.value].total_bits
            assert s.bytes == -(-s.bits // 8)
        expected_spill = 0
        for left, right in zip(pp.groups, pp.groups[1:]):
            w = sum(-(-fused.values[v].total_bits // 8) for v in left.spill_out)
            r = sum(-(-fused.values[v].total_bits // 8) for v in right.spill_in)
            expected_spill += transition_cycles(w, r)
        assert pp.spill_cycles == expected_spill
        assert pp.total_cycles == sum(g.cycles for g in pp.groups) + expected_spill
        # the overlapped model must never price a cut above PR 2's
        # serial write-then-read charge
        assert pp.spill_cycles <= pp.serial_spill_cycles

    def test_deep224_accounting(self, deep224_design):
        d = deep224_design
        assert d.total_cycles == sum(g.cycles for g in d.groups) + d.spill_cycles
        assert d.spill_cycles > 0
        assert d.max_group_cycles == max(g.cycles for g in d.groups)


class TestBalancedPartitioning:
    """Tentpole: DP min-max beats the greedy prefix cut on cycles."""

    def test_deep224_fits_and_improves_on_greedy(
        self, deep224_design, deep224_greedy
    ):
        bal, greedy = deep224_design, deep224_greedy
        assert bal.feasible and bal.max_bram <= KV260_BRAM18K
        assert bal.max_dsp <= KV260_DSP
        # regression: the balanced cut's slowest group is strictly faster
        assert bal.max_group_cycles < greedy.max_group_cycles
        # and not at the price of a slower end-to-end schedule
        assert bal.total_cycles <= greedy.total_cycles

    def test_balanced_never_worse_than_greedy_forced_cuts(self):
        """On a tiny forced partition the DP is at least as good."""
        fused = run_default_pipeline(cnn_graphs.cascade_conv(16, c_mid=8)).dfg
        bal = partition_layer_groups(fused, b_total=2)
        greedy = partition_layer_groups(fused, b_total=2, strategy="greedy")
        assert bal.max_group_cycles <= greedy.max_group_cycles

    def test_groups_cover_graph_in_topo_order(self, deep224_design):
        d = deep224_design
        covered = [n for g in d.groups for n in g.node_names]
        assert sorted(covered) == sorted(n.name for n in d.source.nodes)
        # every spill-out is a later group's spill-in
        outs = {v for g in d.groups for v in g.spill_out}
        ins = {v for g in d.groups for v in g.spill_in}
        assert outs == ins and outs

    def test_groupwise_semantics_preserved(self, deep224_design):
        """Interpreter-chained groups == whole graph, on a small clone
        of the same cut structure."""
        fused = run_default_pipeline(cnn_graphs.cascade_conv(16, c_mid=8)).dfg
        pp = partition_layer_groups(fused, b_total=2)
        assert pp.partitioned
        env = interp.random_env(fused, seed=11)
        whole = interp.graph_outputs(fused, env)
        chained = dict(env)
        for g in pp.groups:
            chained.update(interp.execute_dfg(g.dfg, chained))
        for k, v in whole.items():
            np.testing.assert_array_equal(np.asarray(v), np.asarray(chained[k]))


class TestWeightStreaming:
    """Tentpole: weight-dominated convs compile via DRAM-tiled weights."""

    def test_fat_conv_infeasible_without_streaming(self):
        fused = run_default_pipeline(cnn_graphs.fat_conv()).dfg
        whole = solve_ilp(plan_streams(fused))
        assert not whole.feasible

    def test_fat_conv_compiles_via_streaming(self):
        d = compile_design(cnn_graphs.fat_conv())
        assert d.feasible
        assert d.weight_streamed, "expected a weight-streamed node"
        (node, tiles), = d.weight_streamed.items()
        assert tiles > 1
        assert d.max_bram <= KV260_BRAM18K and d.max_dsp <= KV260_DSP

    def test_streaming_charges_dram_cycles(self):
        """The streamed design must be slower than a hypothetical
        resident-weight plan of the same unroll — the DRAM round trip is
        in the ledger, not hidden."""
        d = compile_design(cnn_graphs.fat_conv())
        g = d.groups[0]
        w_bits = sum(
            v.total_bits for v in g.dfg.values.values() if v.is_constant
        )
        dram_cycles = -(-2 * (w_bits // 8) // DRAM_BYTES_PER_CYCLE)
        assert g.cycles > dram_cycles  # round trip included in the total

    def test_solver_prefers_resident_weights_when_they_fit(self):
        """weight_streaming=True must not change designs that fit: the
        streamed variants are strictly slower, so the ILP ignores them."""
        plan = plan_streams(
            run_default_pipeline(cnn_graphs.conv_relu(32)).dfg
        )
        base = solve_ilp(plan)
        ws = solve_ilp(plan, weight_streaming=True)
        assert base.feasible and ws.feasible
        assert not ws.weight_tiles
        assert ws.objective_cycles == base.objective_cycles


class TestEmitConsumesDesign:
    def test_emit_design_weight_streamed_golden(self, golden_check):
        d = compile_design(cnn_graphs.fat_conv())
        files = emit_design(d)
        golden_check("fat_conv_16_g0.cpp", files["fat_conv_16_g0.cpp"])

    def test_double_buffered_kernel_structure(self):
        d = compile_design(cnn_graphs.fat_conv())
        files = emit_design(d)
        cpp = files["fat_conv_16_g0.cpp"]
        tiles = d.weight_streamed["conv0"]
        assert f"WT: for (int wt = 0; wt < {tiles}; ++wt)" in cpp
        assert "load_tile(wtile[0], dram_w0, 0);" in cpp     # preload
        assert (  # guarded prefetch — never reads past the last tile
            f"if (wt + 1 < {tiles}) load_tile(wtile[(wt + 1) & 1]" in cpp
        )
        assert "wtile[2][" in cpp
        assert "const elem_t *dram_w0" in cpp
        assert cpp.count("{") == cpp.count("}")
        host = files["host_schedule.cpp"]
        assert "wstream_w0" in host and "weights streamed" in host

    def test_single_group_design_emits(self):
        d = compile_design(cnn_graphs.conv_relu(32))
        files = emit_design(d)
        assert set(files) == {f"{d.groups[0].name}.cpp", "host_schedule.cpp"}
        assert "#pragma HLS DATAFLOW" in files[f"{d.groups[0].name}.cpp"]


class TestOverlappedSpills:
    """ISSUE 3 tentpole: spill writes of group k overlap group k+1's
    fill — max(spill, fill) + burst tail, not a serial round trip."""

    def test_deep224_beats_serial_spill_baseline(self, deep224_design):
        """The acceptance regression: modeled total cycles strictly
        below the PR 2 serial-spill baseline on deep_cascade_224."""
        d = deep224_design
        assert d.partitioned and d.spill_cycles > 0
        serial_total = sum(g.cycles for g in d.groups) + d.serial_spill_cycles
        assert d.spill_cycles < d.serial_spill_cycles
        assert d.total_cycles < serial_total

    def test_boundary_traffic_matches_spill_lists(self, deep224_design):
        d = deep224_design
        traffic = d.boundary_traffic()
        assert len(traffic) == len(d.groups) - 1
        for (w, r), left, right in zip(traffic, d.groups, d.groups[1:]):
            assert w == sum(
                -(-d.source.values[v].total_bits // 8) for v in left.spill_out
            )
            assert r == sum(
                -(-d.source.values[v].total_bits // 8) for v in right.spill_in
            )

    def test_transition_never_above_serial(self):
        """max(w, r) + capped tail degenerates to the serial sum for
        sub-burst transfers and beats it for long ones."""
        from repro.core.resource_model import DRAM_BURST_BYTES

        for w, r in [(0, 0), (0, 4096), (128, 128), (128, 4096),
                     (4096, 4096), (1 << 20, 1 << 20), (1 << 20, 64)]:
            serial = -(-w // DRAM_BYTES_PER_CYCLE) + -(-r // DRAM_BYTES_PER_CYCLE)
            assert transition_cycles(w, r) <= serial
        big = 1 << 20
        assert transition_cycles(big, big) < (
            -(-2 * big // DRAM_BYTES_PER_CYCLE)
        )
        assert transition_cycles(big, 0) == -(-big // DRAM_BYTES_PER_CYCLE)

    def test_host_schedule_issues_overlapped_transfers(self, deep224_design):
        files = emit_design(deep224_design)
        host = files["host_schedule.cpp"]
        assert "dma_write_async(" in host and "dma_read_async(" in host
        assert "dma_join();" in host
        assert host.count("// transition ") == len(deep224_design.groups) - 1


class TestCostAwareStreaming:
    """ISSUE 3 tentpole: weight streaming is a first-class DP choice —
    any slice may stream; the single-node rescue path is gone."""

    def test_fat_cascade_streams_every_conv(self):
        """Every layer's weights exceed the budget alone, so no resident
        cut exists: the DP must schedule streamed groups end to end."""
        d = compile_design(cnn_graphs.fat_cascade())
        assert d.feasible
        assert set(d.weight_streamed) == {"conv0", "conv1"}
        assert all(t > 1 for t in d.weight_streamed.values())
        assert d.max_bram <= KV260_BRAM18K and d.max_dsp <= KV260_DSP

    def test_multi_node_slices_can_stream(self):
        """The capability the PR 2 rescue lacked: a multi-node slice
        that is over budget resident gets a feasible weight-streamed
        plan, so the DP prices it against cutting instead of being
        forced to cut."""
        from repro.passes.partition import _GroupPlanner

        fused = run_default_pipeline(cnn_graphs.fat_cascade()).dfg
        planner = _GroupPlanner(
            fused, d_total=KV260_DSP, b_total=KV260_BRAM18K,
            model=None, max_unroll=4096,
        )
        # the probe reaches the whole graph only via streamed weights
        assert planner.max_feasible_end(0) == len(planner.order)
        merged = planner.group(0, 2)
        assert merged.dse.feasible and merged.dse.weight_tiles
        assert not planner.resident_feasible(0, 2)
        # the DP rejected the merged slice on modeled cycles, not by fiat
        d = compile_design(cnn_graphs.fat_cascade())
        assert d.max_group_cycles <= merged.cycles

    @pytest.mark.parametrize("strategy", ["balanced", "greedy"])
    def test_fat_graphs_compile_under_both_strategies(self, strategy):
        for make in (cnn_graphs.fat_conv, cnn_graphs.fat_cascade):
            d = compile_design(make(), strategy=strategy)
            assert d.feasible and d.weight_streamed


class TestMultiTarget:
    def test_targets_registry(self):
        assert set(TARGETS) >= {"kv260", "zu3eg"}
        assert TARGETS["kv260"] is KV260 and TARGETS["zu3eg"] is ZU3EG
        assert ZU3EG.b_total > KV260.b_total  # BRAM-richer
        assert ZU3EG.d_total < KV260.d_total  # DSP-poorer

    def test_zu3eg_flips_fat_conv_to_resident(self):
        """The same graph maps differently per part: streamed weight
        tiles on the BRAM-poor KV260, resident on the ZU3EG."""
        kv = compile_design(cnn_graphs.fat_conv())
        zu = compile_design(cnn_graphs.fat_conv(), ZU3EG)
        assert kv.weight_streamed and not zu.weight_streamed
        assert zu.whole_graph_feasible and zu.max_bram <= ZU3EG.b_total

    def test_zu3eg_fits_deep224_whole_but_slower(
        self, deep224_fused, deep224_partition
    ):
        zu = partition_layer_groups(
            deep224_fused, d_total=ZU3EG.d_total, b_total=ZU3EG.b_total
        )
        assert zu.whole_graph_feasible and len(zu.groups) == 1
        assert zu.max_dsp <= ZU3EG.d_total
        # no spills on the BRAM-richer part — but far fewer DSPs, so the
        # partitioned KV260 schedule is still the faster one
        assert zu.spill_cycles == 0
        assert zu.total_cycles > deep224_partition.total_cycles


class TestExecutableCache:
    """Satellite: lower_group caches jitted executables per group
    signature — repeated run_compiled calls stop re-jitting."""

    def test_lower_group_caches_jitted_executables(self, monkeypatch):
        from repro.kernels import ops

        d = compile_design(cnn_graphs.cascade_conv(8, c_mid=4))
        env = interp.random_env(d.source, seed=2)
        calls = {"n": 0}
        orig = ops._build_group_fn

        def probe(group, interpret, jit, batch=None):
            calls["n"] += 1
            return orig(group, interpret, jit, batch=batch)

        monkeypatch.setattr(ops, "_build_group_fn", probe)
        ops._EXEC_CACHE.clear()
        before_hits = ops.exec_cache_stats["hits"]
        first = ops.run_compiled(d, env, interpret=True)
        n_first = calls["n"]
        assert n_first == len(d.groups)  # one build per group
        second = ops.run_compiled(d, env, interpret=True)
        assert calls["n"] == n_first  # cache hit: no re-build, no re-jit
        assert ops.exec_cache_stats["hits"] == before_hits + len(d.groups)
        for k in first:
            np.testing.assert_array_equal(
                np.asarray(first[k]), np.asarray(second[k])
            )

    def test_recompiled_design_reuses_executables(self, monkeypatch):
        """Two separate compile() runs of the same graph share one
        executable (signature-keyed, not object-keyed)."""
        from repro.kernels import ops

        env = interp.random_env(compile_design(
            cnn_graphs.conv_relu(8, c_out=4)).source, seed=4)
        calls = {"n": 0}
        orig = ops._build_group_fn

        def probe(group, interpret, jit, batch=None):
            calls["n"] += 1
            return orig(group, interpret, jit, batch=batch)

        monkeypatch.setattr(ops, "_build_group_fn", probe)
        ops._EXEC_CACHE.clear()
        ops.run_compiled(compile_design(cnn_graphs.conv_relu(8, c_out=4)),
                         env, interpret=True)
        n_first = calls["n"]
        ops.run_compiled(compile_design(cnn_graphs.conv_relu(8, c_out=4)),
                         env, interpret=True)
        assert calls["n"] == n_first

    def test_cached_executable_is_what_ran(self):
        """``cached_executable`` hands back the entry a run cached, for
        that batch only, and it lowers to the StableHLO of what ran."""
        from repro.kernels import ops

        d = compile_design(cnn_graphs.conv_relu(8, c_out=4))
        env = interp.random_env(d.source, seed=6)
        g = d.groups[0]
        ops._EXEC_CACHE.clear()
        assert ops.cached_executable(g, interpret=True) is None
        ops.run_compiled(d, env, interpret=True)
        fn = ops.cached_executable(g, interpret=True)
        assert fn is ops.lower_group(g, interpret=True)
        assert ops.cached_executable(g, interpret=True, batch=4) is None
        # the run's int32 operands: the output type of the group
        assert "tensor<1x8x8x4xi32>" in fn.lower(env).as_text()


_SMALL_GRAPHS = pytest.mark.parametrize(
    "make, convs",
    [
        (lambda: cnn_graphs.conv_relu(8, c_out=4), True),
        (lambda: cnn_graphs.cascade_conv(8, c_mid=4), True),
        (lambda: cnn_graphs.conv_pool(8, c_out=4), True),
        (lambda: cnn_graphs.residual_block(8, c=4), True),
        (cnn_graphs.feed_forward, False),
    ],
    ids=["conv_relu", "cascade", "conv_pool", "residual", "feed_forward"],
)
_FAT_GRAPHS = pytest.mark.parametrize(
    "make", [cnn_graphs.fat_conv, cnn_graphs.fat_cascade],
    ids=["fat_conv", "fat_cascade"],
)


class TestPallasConsumesDesign:
    """kernels/ops.run_compiled executes the identical schedule.

    Operands are ``interp.random_env``'s small integers in int8 or
    float32.  int8 reaches the streaming kernel for every conv fed
    int8 (a conv fed int32 accumulators lowers through
    ``conv2d_same_mm``); float32 reaches it for every conv, and its
    sums of small integers are exact in any order."""

    @_SMALL_GRAPHS
    def test_run_compiled_matches_interp(self, make, convs):
        calls = _assert_run_matches_interp(compile_design(make()), 7, "int8")
        assert (calls["stream"] > 0) == convs

    @_SMALL_GRAPHS
    def test_run_compiled_matches_interp_f32(self, make, convs):
        calls = _assert_run_matches_interp(compile_design(make()), 7,
                                           "float32")
        assert (calls["stream"] > 0) == convs and calls["mm"] == 0

    def test_partitioned_design_chains_groups(self):
        fused = run_default_pipeline(cnn_graphs.cascade_conv(16, c_mid=8)).dfg
        pp = partition_layer_groups(fused, b_total=2)
        assert pp.partitioned and pp.source is fused
        calls = _assert_run_matches_interp(pp, 3, "int8")
        assert calls["stream"] > 0

    @pytest.mark.parametrize("strategy", ["balanced", "greedy"])
    @_FAT_GRAPHS
    def test_streamed_groups_match_interp(self, make, strategy):
        """Satellite: run_compiled's weight-tiled lowering (the TPU dual
        of the emitter's wtile loop) is bit-exact with the reference
        interpreter for streamed-weight groups, both strategies."""
        d = compile_design(make(), strategy=strategy)
        assert d.weight_streamed, "expected a weight-streamed schedule"
        calls = _assert_run_matches_interp(d, 5, "int8")
        assert calls["stream"] >= max(d.weight_streamed.values())

    @pytest.mark.parametrize("strategy", ["balanced", "greedy"])
    @_FAT_GRAPHS
    def test_streamed_groups_match_interp_f32(self, make, strategy):
        """Every streamed conv, not only the first, runs the kernel
        tile by tile."""
        d = compile_design(make(), strategy=strategy)
        calls = _assert_run_matches_interp(d, 5, "float32")
        assert calls["stream"] == sum(d.weight_streamed.values())
        assert calls["mm"] == 0


def _assert_run_matches_interp(design, seed, dtype):
    """``run_compiled`` (interpret mode) == the interpreter, bit for
    bit, on ``random_env(seed)`` cast to ``dtype`` → how often the
    lowering called each conv kernel (``stream``: the Pallas streaming
    kernel, ``mm``: ``conv2d_same_mm``) while tracing."""
    from repro.kernels import ops

    env = {k: v.astype(dtype)
           for k, v in interp.random_env(design.source, seed=seed).items()}
    want = interp.graph_outputs(design.source, env)
    calls = {"stream": 0, "mm": 0}

    def counting(kind, fn):
        def probe(*args, **kwargs):
            calls[kind] += 1
            return fn(*args, **kwargs)
        return probe

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "conv2d_stream", counting("stream", ops.conv2d_stream))
        mp.setattr(ops, "conv2d_same_mm", counting("mm", ops.conv2d_same_mm))
        ops._EXEC_CACHE.clear()  # trace afresh so the probes see the calls
        got = ops.run_compiled(design, env, interpret=True)
    assert set(want) == set(got)
    for k in want:
        np.testing.assert_array_equal(np.asarray(want[k]), np.asarray(got[k]))
    return calls
