"""Batched device execution (ISSUE 7): vmapped group executables.

The acceptance contract: ``CompiledArtifact.run`` with the default
``batch_mode="vmap"`` is bit-exact against the per-sample loop
(``batch_mode="loop"``) on every zoo model on both targets, ragged
batches pad to buckets without leaking padding rows, each group
compiles at most once per batch bucket, and the exec cache is a real
LRU (bounded, evictions counted).
"""
import numpy as np
import pytest

from repro import api
from repro.core import cnn_graphs
from repro.core.compile_driver import KV260, ZU3EG
from repro.frontends import zoo
from repro.kernels import ops


def _batched_inputs(src, batch, seed=0):
    rng = np.random.default_rng(seed)
    return {
        k: rng.integers(-4, 5, size=(batch,) + src.values[k].shape,
                        dtype=np.int8)
        for k in src.graph_inputs
    }


def _int8_params(src, seed=0):
    rng = np.random.default_rng(seed + 1000)
    return {
        k: rng.integers(-4, 5, size=v.shape, dtype=np.int8)
        for k, v in src.values.items() if v.is_constant
    }


def _assert_vmap_equals_loop(art, batch, seed=0, *, streams=True):
    """int8 inputs and weights: the loop's per-sample executables take
    the streaming Pallas kernel for every conv fed int8 (``streams``
    asserts it got there — convs fed the int32 accumulators of an
    earlier one lower through ``conv2d_same_mm``), while the batched
    executables lower every integer conv through ``conv2d_same_mm``,
    so the comparison crosses the two lowerings."""
    x = _batched_inputs(art.source, batch, seed)
    params = _int8_params(art.source, seed)
    calls = []
    real = ops.conv2d_stream

    def probe(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "conv2d_stream", probe)
        ops._EXEC_CACHE.clear()  # trace afresh so the probe sees the calls
        want = art.run(x, params, batch_mode="loop")
    got = art.run(x, params, batch_mode="vmap")
    assert bool(calls) == streams, calls
    if isinstance(want, dict):
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
    else:
        np.testing.assert_array_equal(got, want)
    assert art.last_run_stats["batch_mode"] == "vmap"
    assert art.last_run_stats["samples"] == batch


class TestBatchBuckets:
    def test_bucket_rounds_up(self):
        assert ops.batch_bucket(1) == 1
        assert ops.batch_bucket(3) == 4
        assert ops.batch_bucket(8) == 8
        assert ops.batch_bucket(17) == 32
        assert ops.batch_bucket(64) == 64

    def test_bucket_rejects_out_of_range(self):
        with pytest.raises(ValueError, match=">= 1"):
            ops.batch_bucket(0)
        with pytest.raises(ValueError, match="top bucket"):
            ops.batch_bucket(65)

    def test_chunks_cover_batch_exactly(self):
        chunks = list(ops._batch_chunks(70))
        assert chunks == [(0, 64, 64), (64, 6, 8)]
        assert list(ops._batch_chunks(5)) == [(0, 5, 8)]
        for batch in (1, 31, 64, 65, 200):
            spans = list(ops._batch_chunks(batch))
            assert sum(n for _, n, _ in spans) == batch
            assert all(n <= b for _, n, b in spans)


class TestVmapBitExact:
    @pytest.mark.parametrize("target", [KV260, ZU3EG], ids=["kv260", "zu3eg"])
    @pytest.mark.parametrize("model", sorted(zoo.ZOO))
    def test_zoo_models_both_targets(self, model, target):
        """The acceptance criterion, verbatim: every zoo model, both
        targets, batched run bit-exact vs the per-sample loop."""
        art = api.compile_graph(zoo.ZOO[model](),
                                api.CompileOptions(target=target))
        _assert_vmap_equals_loop(art, batch=3, seed=7)

    @pytest.mark.parametrize("make, streams", [
        (lambda: cnn_graphs.conv_relu(8, c_out=4), True),
        (lambda: cnn_graphs.residual_block(8, c=4), True),
        (lambda: cnn_graphs.feed_forward(batch=16, d_in=8, d_hidden=16),
         False),
    ], ids=["conv_relu", "residual", "feed_forward"])
    def test_builder_graphs(self, make, streams):
        _assert_vmap_equals_loop(api.compile_graph(make()), batch=4,
                                 streams=streams)

    def test_random_builder_graphs_property(self):
        """Property-style sweep: random little Sequential stacks (seeded
        layer choices) must agree between the two batch modes."""
        rng = np.random.default_rng(42)
        for trial in range(3):
            layers = [api.Conv2D(int(rng.integers(2, 5)), kernel=3)]
            if rng.integers(2):
                layers.append(api.ReLU())
            if rng.integers(2):
                layers.append(api.MaxPool(2))
            layers += [api.Flatten(), api.Dense(int(rng.integers(3, 8)))]
            net = api.Sequential(
                layers, input_shape=(1, 8, 8, 2), name=f"rand{trial}"
            )
            target = (KV260, ZU3EG)[trial % 2]
            art = api.compile_graph(net, api.CompileOptions(target=target))
            _assert_vmap_equals_loop(art, batch=int(rng.integers(2, 6)),
                                     seed=trial)

    def test_multi_input_graph(self):
        g = api.Graph("two_in")
        a = g.input((1, 4, 4, 2), name="a")
        b = g.input((1, 4, 4, 2), name="b")
        g.output(g.add(a, b))
        art = api.compile_graph(g.build())
        _assert_vmap_equals_loop(art, batch=5, streams=False)

    def test_bad_mode_rejected(self):
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        with pytest.raises(ValueError, match="batch_mode"):
            art.run(_batched_inputs(art.source, 2), batch_mode="turbo")


class TestStridedConvProperty:
    """ISSUE 8 satellite: the generalized stride-s / VALID streaming
    path against ``jax.lax.conv_general_dilated``, random geometry,
    both targets, and vmap == loop on every config."""

    @staticmethod
    def _same_pads(n, k, s):
        out = -(-n // s)
        total = max(0, s * (out - 1) + k - n)
        return total // 2, total - total // 2

    def test_random_strided_valid_convs(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(1234)
        for trial in range(8):
            k = int(rng.integers(1, 6))
            s = int(rng.integers(1, 4))
            h = int(rng.integers(max(k, 6), 15))
            c_in = int(rng.integers(1, 4))
            c_out = int(rng.integers(2, 6))
            padding = "SAME" if trial % 2 == 0 else "VALID"
            target = (KV260, ZU3EG)[trial % 2]

            g = api.Graph(f"pconv{trial}")
            x_ref = g.input((1, h, h, c_in), name="x")
            g.output(g.conv2d(x_ref, c_out, kernel=k, stride=s,
                              padding=padding, weight="w"))
            art = api.compile_graph(g.build(),
                                    api.CompileOptions(target=target))
            assert art.feasible

            w = rng.integers(-4, 5, (k, k, c_in, c_out)).astype(np.int8)
            # int8 operands: the per-sample run takes the streaming
            # kernel (wider integers lower through conv2d_same_mm)
            x = rng.integers(-4, 5, (1, h, h, c_in)).astype(np.int8)
            got = np.asarray(
                art.run({"x": x}, params={"w": w}, interpret=True)
            )
            pads = ((0, 0), (0, 0)) if padding == "VALID" else (
                self._same_pads(h, k, s), self._same_pads(h, k, s))
            want = jax.lax.conv_general_dilated(
                jnp.asarray(x, jnp.int32),
                jnp.asarray(w, jnp.int32),
                window_strides=(s, s),
                padding=pads,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            np.testing.assert_array_equal(
                got, np.asarray(want),
                err_msg=f"trial {trial}: k={k} s={s} h={h} "
                        f"c={c_in}->{c_out} {padding} @ {target.name}",
            )
            _assert_vmap_equals_loop(art, batch=3, seed=trial)


class TestIntegerAccumulators:
    """The fast batched integer-conv lowering (``conv2d_same_mm``) must
    return the same int32 accumulators as the streaming Pallas kernel:
    int8/int16 inputs previously accumulated (and wrapped) in the input
    dtype, silently changing batched-run results on sub-int32 models."""

    @pytest.mark.parametrize(
        "dtype", [np.int8, np.uint8, np.int16, np.int32],
        ids=["int8", "uint8", "int16", "int32"])
    def test_mm_matches_stream_dtype_and_values(self, dtype):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        lo, hi = (0, 6) if dtype == np.uint8 else (-6, 6)
        x = rng.integers(lo, hi, size=(2, 8, 8, 5)).astype(dtype)
        w = rng.integers(lo, hi, size=(3, 3, 5, 4)).astype(dtype)
        a = ops.conv2d_stream(jnp.asarray(x), jnp.asarray(w),
                              interpret=True)
        b = ops.conv2d_same_mm(jnp.asarray(x), jnp.asarray(w))
        assert a.dtype == jnp.int32 and b.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_int8_accumulation_exceeds_input_width(self):
        import jax.numpy as jnp

        # 3*3*16 taps of ~100*100 products: the accumulator is far
        # outside int8 (and int16) range, so wrapping would show
        rng = np.random.default_rng(1)
        x = rng.integers(50, 101, size=(1, 6, 6, 16)).astype(np.int8)
        w = rng.integers(50, 101, size=(3, 3, 16, 2)).astype(np.int8)
        a = ops.conv2d_stream(jnp.asarray(x), jnp.asarray(w),
                              interpret=True)
        b = ops.conv2d_same_mm(jnp.asarray(x), jnp.asarray(w))
        assert b.dtype == jnp.int32
        assert int(np.max(np.asarray(b))) > np.iinfo(np.int16).max
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_wide_int_per_sample_conv_takes_xla(self):
        """int32 operands take ``conv2d_same_mm`` on the per-sample path
        too: with ``interpret=False`` on the CPU no Pallas kernel may be
        reached (it cannot lower there), and the result is the
        interpreter's."""
        from repro.passes import interp

        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        env = interp.random_env(art.source, seed=3)
        (x_name,) = art.source.graph_inputs
        params = {k: v for k, v in env.items() if k != x_name}
        got = art.run(env[x_name], params, interpret=False)
        want = interp.graph_outputs(art.source, env)
        (out_name,) = art.source.graph_outputs
        assert got.dtype == np.int32
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want[out_name]))

    def test_vmap_loop_bit_exact_int8_end_to_end(self):
        """An all-int8 batched run (inputs *and* weights — the PTQ
        regime the importer admits) must match the per-sample loop in
        dtype and bits through the artifact surface."""
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        src = art.source
        rng = np.random.default_rng(2)
        x = {
            k: rng.integers(-4, 5,
                            size=(5,) + src.values[k].shape).astype(np.int8)
            for k in src.graph_inputs
        }
        params = {
            n: rng.integers(-4, 5, size=v.shape).astype(np.int8)
            for n, v in src.values.items() if v.is_constant
        }
        want = art.run(x, params, batch_mode="loop")
        got = art.run(x, params, batch_mode="vmap")
        assert want.dtype == got.dtype == np.int32
        np.testing.assert_array_equal(got, want)


class TestRaggedBatches:
    """Padding to a bucket must never leak into outputs."""

    @pytest.mark.parametrize("batch", [3, 5, 17])
    def test_ragged_equals_loop(self, batch):
        art = api.compile_graph(zoo.lenet5())
        _assert_vmap_equals_loop(art, batch=batch)

    def test_prefix_consistency_across_buckets(self):
        """Samples keep their identity whatever bucket the batch pads
        to: row i of a ragged batch equals row i of the full batch."""
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        x = _batched_inputs(art.source, 8, seed=3)
        full = art.run(x)
        for n in (1, 3, 5):
            got = art.run({k: v[:n] for k, v in x.items()})
            np.testing.assert_array_equal(got, full[:n])

    def test_chunked_batch_over_top_bucket(self):
        """A batch above the top bucket splits into chunks and
        concatenates — still exact, still one stacked output."""
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        x = _batched_inputs(art.source, 70, seed=5)
        got = art.run(x)
        assert got.shape[0] == 70
        want = art.run({k: v[:8] for k, v in x.items()})
        np.testing.assert_array_equal(got[:8], want)
        assert art.last_run_stats is not None


class TestCompileCounts:
    """≤1 compile per group per batch bucket (acceptance probe)."""

    def test_recompiles_bounded_by_buckets(self, monkeypatch):
        art = api.compile_graph(zoo.lenet5())
        n_groups = len(art.design.groups)
        builds = []
        real_build = ops._build_group_fn

        def probe(group, interpret, jit, batch=None):
            builds.append((group.name, batch))
            return real_build(group, interpret, jit, batch=batch)

        monkeypatch.setattr(ops, "_build_group_fn", probe)
        ops._EXEC_CACHE.clear()
        x = _batched_inputs(art.source, 8, seed=1)
        for batch in (3, 4, 2, 8, 3):  # buckets {4, 2, 8}
            art.run({k: v[:batch] for k, v in x.items()})
        batched_builds = [b for b in builds if b[1] is not None]
        assert len(batched_builds) == len(set(batched_builds))
        assert len(batched_builds) <= 3 * n_groups
        # same buckets again: zero new builds
        before = len(builds)
        for batch in (3, 4, 2, 8):
            art.run({k: v[:batch] for k, v in x.items()})
        assert len(builds) == before

    def test_exec_cache_delta_reports_hits(self):
        art = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        x = _batched_inputs(art.source, 4, seed=2)
        art.run(x)
        art.run(x)
        delta = art.last_run_stats["exec_cache"]
        assert delta["misses"] == 0 and delta["hits"] >= 1


class TestExecCacheLRU:
    """Satellite: the exec cache is bounded with counted evictions."""

    def test_eviction_at_cap(self, monkeypatch):
        monkeypatch.setattr(ops, "_EXEC_CACHE_CAP", 2)
        ops._EXEC_CACHE.clear()
        ev0 = ops.exec_cache_stats["evictions"]
        arts = [
            api.compile_graph(cnn_graphs.conv_relu(8, c_out=c))
            for c in (2, 3, 4)
        ]
        for art in arts:
            art.run(interpret=True)
        assert len(ops._EXEC_CACHE) <= 2
        assert ops.exec_cache_stats["evictions"] > ev0

    def test_lru_order_keeps_hot_entry(self, monkeypatch):
        monkeypatch.setattr(ops, "_EXEC_CACHE_CAP", 2)
        ops._EXEC_CACHE.clear()
        a = api.compile_graph(cnn_graphs.conv_relu(8, c_out=2))
        b = api.compile_graph(cnn_graphs.conv_relu(8, c_out=3))
        c = api.compile_graph(cnn_graphs.conv_relu(8, c_out=4))
        a.run(interpret=True)
        b.run(interpret=True)
        a.run(interpret=True)  # refresh a: b is now LRU
        h0 = ops.exec_cache_stats["hits"]
        c.run(interpret=True)  # evicts b, not a
        a.run(interpret=True)
        assert ops.exec_cache_stats["hits"] > h0
