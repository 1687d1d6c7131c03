"""ONNX importer (ISSUE 5): the vendored wire decoder against real
bytes, the checked-in LeNet-5 golden fixture end to end (import →
compile → emit → run, bit-exact with an independent NumPy NCHW oracle
on both device presets), and the unsupported-feature error paths.
"""
import os

import numpy as np
import pytest

import _onnx_fixture as fx
from repro.frontends import OnnxImportError, import_model, load_onnx
from repro.frontends.onnx_reader import decode_wire

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "lenet5.onnx")
GOLDEN2 = os.path.join(os.path.dirname(__file__), "golden",
                       "resnet_tiny.onnx")


class TestWireDecoder:
    def test_decodes_fixture_structure(self):
        og = decode_wire(fx.lenet5_model_bytes())
        assert og.name == "lenet5"
        assert [n.op_type for n in og.nodes] == [
            "Conv", "Relu", "MaxPool", "Conv", "Relu", "MaxPool",
            "Flatten", "Gemm", "Relu", "Gemm", "Relu", "Gemm",
        ]
        assert og.inputs == [("input", (1, 1, 32, 32))]
        assert og.outputs == ["logits"]
        w = fx.lenet5_weights(0)
        assert set(og.initializers) == set(w)
        for k in w:
            np.testing.assert_array_equal(og.initializers[k], w[k])
            assert og.initializers[k].dtype == w[k].dtype

    def test_attributes_decode(self):
        og = decode_wire(fx.lenet5_model_bytes())
        conv = og.nodes[0]
        assert conv.attrs["kernel_shape"] == [5, 5]
        assert conv.attrs["pads"] == [2, 2, 2, 2]
        gemm = og.nodes[7]
        assert gemm.attrs["transB"] == 1
        assert gemm.attrs["alpha"] == pytest.approx(1.0)

    def test_symbolic_output_dims_are_ignored(self):
        """Graph *outputs* only need names — a symbolic output shape
        (shape-inferred dynamic dim) must not fail the wire decoder
        when the onnx-package path would accept it."""
        g = fx.graph(
            "symout",
            [fx.node("Relu", ["x"], ["y"], "r")],
            [],
            [fx.value_info("x", (1, 8))],
            [fx.value_info("y", (), symbolic="N")],
        )
        m = load_onnx(fx.model(g))
        assert m.dfg.graph_outputs  # imported fine

    def test_symbolic_dims_rejected(self):
        g = fx.graph(
            "sym",
            [fx.node("Relu", ["x"], ["y"], "r")],
            [],
            [fx.value_info("x", (), symbolic="batch")],
            [fx.value_info("y", (1,))],
        )
        with pytest.raises(OnnxImportError, match="symbolic"):
            load_onnx(fx.model(g))

    def test_garbage_bytes_rejected(self):
        with pytest.raises(OnnxImportError):
            load_onnx(b"\xff\xff\xff\xff not a protobuf")


class TestLeNetGolden:
    """The checked-in fixture: regenerate with
    ``python tests/_onnx_fixture.py``."""

    def test_golden_bytes_are_the_seeded_fixture(self):
        with open(GOLDEN, "rb") as f:
            assert f.read() == fx.lenet5_model_bytes(seed=0)

    def test_import_shape_and_params(self):
        m = load_onnx(GOLDEN)
        assert m.name == "lenet5"
        assert m.source == "onnx"
        assert m.missing_params() == []
        # OIHW -> HWIO weight relayout happened
        assert m.params["conv1_w"].shape == (5, 5, 1, 6)
        assert m.params["fc1_w"].shape == (1024, 120)
        # the imported graph keeps the ONNX NCHW contract at the boundary
        assert m.dfg.values[m.dfg.graph_inputs[0]].shape == (1, 1, 32, 32)
        assert m.dfg.values[m.dfg.graph_outputs[0]].shape == (1, 10)

    @pytest.mark.parametrize("target", ["kv260", "zu3eg"])
    def test_bit_exact_against_numpy_oracle(self, target):
        """Acceptance: imported model compiles (layout pass active) and
        runs bit-exact with an executor-independent NumPy oracle."""
        from repro import api

        m = load_onnx(GOLDEN)
        art = api.compile_graph(m.dfg, api.CompileOptions(target=target))
        assert art.feasible
        x = np.random.default_rng(7).integers(
            -4, 5, (1, 1, 32, 32)
        ).astype(np.int32)
        got = np.asarray(
            art.run({m.dfg.graph_inputs[0]: x}, params=m.params,
                    interpret=True)
        )
        want = fx.lenet5_numpy(x.astype(np.int64), fx.lenet5_weights(0))
        np.testing.assert_array_equal(got.astype(np.int64), want)

    def test_run_matches_dfg_interpreter(self):
        from repro import api
        from repro.passes import interp

        m = load_onnx(GOLDEN)
        art = api.compile_graph(m.dfg)
        env = dict(m.params)
        x = np.random.default_rng(3).integers(
            -4, 5, (1, 1, 32, 32)
        ).astype(np.int32)
        env[m.dfg.graph_inputs[0]] = x
        want = interp.graph_outputs(
            m.dfg, {k: np.asarray(v) for k, v in env.items()}
        )
        got = art.run({m.dfg.graph_inputs[0]: x}, params=m.params,
                      interpret=True)
        np.testing.assert_array_equal(
            np.asarray(want[m.dfg.graph_outputs[0]]), np.asarray(got)
        )

    def test_layout_pass_leaves_single_boundary_transpose(self):
        from repro import api
        from repro.core.analysis import reorder_spec

        m = load_onnx(GOLDEN)
        art = api.compile_graph(m.dfg)
        specs = [reorder_spec(n) for n in art.design.source.nodes]
        transposes = [s for s in specs if s and s[0] == "transpose"]
        flattens = [s for s in specs if s and s[0] == "flatten"]
        assert len(transposes) == 1  # the NCHW graph-input bridge
        assert len(flattens) == 1
        # the flatten absorbed the NHWC->NCHW head transpose: its
        # linearization order is channels-major over the NHWC tensor
        assert flattens[0][1] == (3, 1, 2)

    def test_emit_hls_end_to_end(self, tmp_path):
        from repro import api

        m = load_onnx(GOLDEN)
        art = api.compile_graph(m.dfg)
        paths = art.emit_hls(str(tmp_path))
        names = {os.path.basename(p) for p in paths}
        assert "host_schedule.cpp" in names
        assert any(n.startswith("lenet5_g") for n in names)

    def test_cli_compile_onnx_runs(self, capsys):
        from repro.__main__ import main as cli_main

        assert cli_main(["compile", GOLDEN, "--run", "--quiet"]) == 0
        assert "ran OK" in capsys.readouterr().out

    def test_batched_validation_on_imported_classifier(self):
        """ISSUE 5 satellite meets the tentpole: a small input batch
        through the imported classifier, one oracle check per sample."""
        from repro import api

        m = load_onnx(GOLDEN)
        art = api.compile_graph(m.dfg)
        rng = np.random.default_rng(11)
        xs = rng.integers(-4, 5, (3, 1, 1, 32, 32)).astype(np.int32)
        got = np.asarray(
            art.run({m.dfg.graph_inputs[0]: xs}, params=m.params,
                    interpret=True)
        )
        assert got.shape == (3, 1, 10)
        w = fx.lenet5_weights(0)
        for i in range(3):
            np.testing.assert_array_equal(
                got[i].astype(np.int64),
                fx.lenet5_numpy(xs[i].astype(np.int64), w),
            )


class TestUnsupportedFeatures:
    def _conv_model(self, **overrides):
        """A one-conv model with attribute overrides for error paths."""
        attrs = {
            "kernel_shape": fx.attr_ints("kernel_shape", [3, 3]),
            "strides": fx.attr_ints("strides", [1, 1]),
            "pads": fx.attr_ints("pads", [1, 1, 1, 1]),
        }
        attrs.update(overrides)
        w = np.zeros((4, 2, 3, 3), np.int8)
        g = fx.graph(
            "one_conv",
            [fx.node("Conv", ["x", "w"], ["y"], "conv",
                     tuple(a for a in attrs.values() if a is not None))],
            [fx.tensor("w", w)],
            [fx.value_info("x", (1, 2, 8, 8))],
            [fx.value_info("y", (1, 4, 8, 8))],
        )
        return fx.model(g)

    def test_unsupported_op_named(self):
        g = fx.graph(
            "soft",
            [fx.node("Softmax", ["x"], ["y"], "sm")],
            [],
            [fx.value_info("x", (1, 8))],
            [fx.value_info("y", (1, 8))],
        )
        with pytest.raises(OnnxImportError, match="Softmax"):
            load_onnx(fx.model(g))

    def test_grouped_conv_rejected(self):
        data = self._conv_model(group=fx.attr_int("group", 2))
        with pytest.raises(OnnxImportError, match="group"):
            load_onnx(data)

    def test_dilated_conv_rejected(self):
        data = self._conv_model(
            dilations=fx.attr_ints("dilations", [2, 2]))
        with pytest.raises(OnnxImportError, match="dilation"):
            load_onnx(data)

    def test_pool_missing_kernel_shape_named(self):
        """ISSUE 8 satellite: a pool node with no kernel_shape used to
        surface as a misleading non-square-[] error — it must name the
        missing attribute and the node."""
        g = fx.graph(
            "nop",
            [fx.node("MaxPool", ["x"], ["y"], "pool_k")],
            [],
            [fx.value_info("x", (1, 2, 4, 4))],
            [fx.value_info("y", (1, 2, 2, 2))],
        )
        with pytest.raises(OnnxImportError,
                           match=r"pool_k.*kernel_shape"):
            load_onnx(fx.model(g))

    def test_flatten_axis_2_rejected(self):
        g = fx.graph(
            "flat2",
            [fx.node("Flatten", ["x"], ["y"], "f",
                     (fx.attr_int("axis", 2),))],
            [],
            [fx.value_info("x", (1, 2, 4, 4))],
            [fx.value_info("y", (2, 16))],
        )
        with pytest.raises(OnnxImportError, match="axis=1"):
            load_onnx(fx.model(g))

    def test_non_initializer_weight_rejected(self):
        w = np.zeros((4, 2, 3, 3), np.int8)
        g = fx.graph(
            "dyn_w",
            [fx.node("Conv", ["x", "wdyn"], ["y"], "conv",
                     (fx.attr_ints("pads", [1, 1, 1, 1]),))],
            [fx.tensor("unused", w)],
            [fx.value_info("x", (1, 2, 8, 8)),
             fx.value_info("wdyn", (4, 2, 3, 3))],
            [fx.value_info("y", (1, 4, 8, 8))],
        )
        with pytest.raises(OnnxImportError, match="initializer"):
            load_onnx(fx.model(g))


def _conv_nchw(x, wgt, stride=1, pads=((0, 0), (0, 0))):
    """Independent NCHW conv oracle, int64 accumulation."""
    from numpy.lib.stride_tricks import sliding_window_view

    k = wgt.shape[2]
    (pt, pb), (pl, pr) = pads
    xp = np.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    win = sliding_window_view(xp, (k, k), axis=(2, 3))
    win = win[:, :, ::stride, ::stride]
    return np.einsum("nchwij,ocij->nohw", win.astype(np.int64),
                     wgt.astype(np.int64))


class TestConvPaddingMatrix:
    """The tentpole import rules: (auto_pad, pads, kernel, stride) →
    SAME / VALID / named rejection, each accepted cell bit-exact
    against the NCHW oracle."""

    def _model(self, w, h_in, attrs):
        g = fx.graph(
            "pm",
            [fx.node("Conv", ["x", "w"], ["y"], "conv", tuple(attrs))],
            [fx.tensor("w", w)],
            [fx.value_info("x", (1, int(w.shape[1]), h_in, h_in))],
            [fx.value_info("y", (1,))],
        )
        return fx.model(g)

    def _run(self, data, h_in, c_in, seed=3):
        from repro import api

        m = load_onnx(data)
        art = api.compile_graph(m.dfg)
        x = np.random.default_rng(seed).integers(
            -4, 5, (1, c_in, h_in, h_in)
        ).astype(np.int32)
        got = np.asarray(
            art.run({m.dfg.graph_inputs[0]: x}, params=m.params,
                    interpret=True)
        )
        return x.astype(np.int64), got.astype(np.int64)

    def test_strided_conv_imports(self):
        """Flip of ISSUE 5's rejection: stride-2 with explicit
        SAME_UPPER-frame pads now streams."""
        w = np.random.default_rng(0).integers(
            -4, 5, (4, 2, 3, 3)).astype(np.int8)
        data = self._model(w, 8, [fx.attr_ints("kernel_shape", [3, 3]),
                                  fx.attr_ints("strides", [2, 2]),
                                  fx.attr_ints("pads", [0, 0, 1, 1])])
        x, got = self._run(data, 8, 2)
        want = _conv_nchw(x, w, stride=2, pads=((0, 1), (0, 1)))
        np.testing.assert_array_equal(got, want)

    def test_valid_conv_imports(self):
        """Flip of ISSUE 5's rejection: zero pads = VALID now streams
        (8×8, k3 → 6×6)."""
        w = np.random.default_rng(1).integers(
            -4, 5, (4, 2, 3, 3)).astype(np.int8)
        data = self._model(w, 8, [fx.attr_ints("pads", [0, 0, 0, 0])])
        x, got = self._run(data, 8, 2)
        assert got.shape == (1, 4, 6, 6)
        np.testing.assert_array_equal(got, _conv_nchw(x, w))

    def test_even_kernel_same_upper_end_heavy(self):
        """Satellite 1, the wrong-answer repro: an even-kernel
        SAME_UPPER conv pads end-heavy.  The begin-heavy (mirrored)
        placement the old early-return would have silently produced is
        a *different* array — assert both that the mis-placement is
        observable and that the import matches the correct one."""
        w = np.random.default_rng(2).integers(
            -4, 5, (4, 2, 4, 4)).astype(np.int8)
        data = self._model(w, 8, [fx.attr_string("auto_pad", "SAME_UPPER")])
        x, got = self._run(data, 8, 2)
        want = _conv_nchw(x, w, pads=((1, 2), (1, 2)))      # end-heavy
        wrong = _conv_nchw(x, w, pads=((2, 1), (2, 1)))     # begin-heavy
        assert not np.array_equal(want, wrong)
        np.testing.assert_array_equal(got, want)

    def test_even_kernel_same_lower_rejected(self):
        """Satellite 1: SAME_LOWER's begin-heavy split cannot ride the
        end-heavy streaming frame when the total pad is odd — named
        rejection, not a mirrored window."""
        w = np.zeros((4, 2, 4, 4), np.int8)
        data = self._model(w, 8, [fx.attr_string("auto_pad", "SAME_LOWER")])
        with pytest.raises(OnnxImportError, match="SAME_LOWER"):
            load_onnx(data)

    def test_same_lower_odd_kernel_imports(self):
        """SAME_LOWER with a symmetric split (odd kernel, stride 1) is
        identical to SAME_UPPER — accepted."""
        w = np.random.default_rng(4).integers(
            -4, 5, (4, 2, 3, 3)).astype(np.int8)
        data = self._model(w, 8, [fx.attr_string("auto_pad", "SAME_LOWER")])
        x, got = self._run(data, 8, 2)
        np.testing.assert_array_equal(
            got, _conv_nchw(x, w, pads=((1, 1), (1, 1))))

    def test_arbitrary_pads_rejected(self):
        """Symmetric [1,1,1,1] on an even kernel is neither VALID nor
        the SAME_UPPER frame [1,1,2,2] — named rejection."""
        w = np.zeros((4, 2, 4, 4), np.int8)
        data = self._model(w, 8, [fx.attr_ints("pads", [1, 1, 1, 1])])
        with pytest.raises(OnnxImportError, match="neither zero"):
            load_onnx(data)

    def test_auto_pad_with_explicit_pads_rejected(self):
        w = np.zeros((4, 2, 3, 3), np.int8)
        data = self._model(w, 8, [fx.attr_string("auto_pad", "SAME_UPPER"),
                                  fx.attr_ints("pads", [1, 1, 1, 1])])
        with pytest.raises(OnnxImportError, match="forbids"):
            load_onnx(data)

    def test_strided_valid_even_kernel_imports(self):
        """k2 s2 VALID — the classic learned-downsample shape."""
        w = np.random.default_rng(5).integers(
            -4, 5, (4, 2, 2, 2)).astype(np.int8)
        data = self._model(w, 8, [fx.attr_string("auto_pad", "VALID"),
                                  fx.attr_ints("strides", [2, 2])])
        x, got = self._run(data, 8, 2)
        assert got.shape == (1, 4, 4, 4)
        np.testing.assert_array_equal(got, _conv_nchw(x, w, stride=2))


class TestGemmAttributeMatrix:
    """Satellite 3: every (alpha, beta, transA, transB, bias-arity)
    cell of the Gemm attribute matrix pinned against the ONNX spec —
    Y = alpha·A'·B' + beta·C."""

    W = np.arange(-10, 14, dtype=np.int8).reshape(6, 4)   # (units, d_in)
    C = np.arange(1, 7, dtype=np.int32)                   # (units,)

    def _model(self, attrs, with_c=True, w=None):
        w = self.W if w is None else w
        ins = ["x", "w"] + (["c"] if with_c else [])
        inits = [fx.tensor("w", w)]
        if with_c:
            inits.append(fx.tensor("c", self.C))
        g = fx.graph(
            "gm",
            [fx.node("Gemm", ins, ["y"], "gemm", tuple(attrs))],
            inits,
            [fx.value_info("x", (1, 4))],
            [fx.value_info("y", (1, 6))],
        )
        return fx.model(g)

    def _run(self, data):
        from repro import api

        m = load_onnx(data)
        art = api.compile_graph(m.dfg)
        x = np.arange(2, 6, dtype=np.int32).reshape(1, 4)
        got = np.asarray(art.run(x, params=m.params, interpret=True))
        return x.astype(np.int64), got.astype(np.int64)

    def test_defaults_transb_bias(self):
        """alpha=1 beta=1 transB=1 with C: the torchvision export
        shape."""
        x, got = self._run(self._model((fx.attr_int("transB", 1),)))
        np.testing.assert_array_equal(
            got, x @ self.W.T.astype(np.int64) + self.C)

    def test_transb_0(self):
        """transB=0: B is already (d_in, units)."""
        w = np.ascontiguousarray(self.W.T)                # (4, 6)
        x, got = self._run(self._model((), with_c=False, w=w))
        np.testing.assert_array_equal(got, x @ w.astype(np.int64))

    def test_beta_0_drops_bias(self):
        """beta=0 with C present: the spec says the bias term vanishes."""
        x, got = self._run(self._model(
            (fx.attr_int("transB", 1), fx.attr_float("beta", 0.0))))
        np.testing.assert_array_equal(got, x @ self.W.T.astype(np.int64))

    def test_beta_nonunit_without_c_accepted(self):
        """beta=2 but no C input: beta multiplies nothing — accepted."""
        x, got = self._run(self._model(
            (fx.attr_int("transB", 1), fx.attr_float("beta", 2.0)),
            with_c=False))
        np.testing.assert_array_equal(got, x @ self.W.T.astype(np.int64))

    def test_beta_nonunit_with_c_rejected(self):
        data = self._model(
            (fx.attr_int("transB", 1), fx.attr_float("beta", 0.5)))
        with pytest.raises(OnnxImportError, match="beta"):
            load_onnx(data)

    def test_alpha_nonunit_rejected(self):
        data = self._model(
            (fx.attr_int("transB", 1), fx.attr_float("alpha", 2.0)))
        with pytest.raises(OnnxImportError, match="alpha"):
            load_onnx(data)

    def test_trans_a_rejected(self):
        data = self._model(
            (fx.attr_int("transB", 1), fx.attr_int("transA", 1)))
        with pytest.raises(OnnxImportError, match="transA"):
            load_onnx(data)

    def test_c_wrong_arity_rejected(self):
        """C must be the (units,) per-unit bias — a (d_in,)-sized C is
        rejected by name, not silently broadcast."""
        w = np.ascontiguousarray(self.W.T)                # units = 6
        ins = ["x", "w", "c"]
        g = fx.graph(
            "gm",
            [fx.node("Gemm", ins, ["y"], "gemm", ())],
            [fx.tensor("w", w),
             fx.tensor("c", np.arange(4, dtype=np.int32))],
            [fx.value_info("x", (1, 4))],
            [fx.value_info("y", (1, 6))],
        )
        with pytest.raises(OnnxImportError, match="elements"):
            load_onnx(fx.model(g))


class TestBatchNormFold:
    """BN folding error paths — fold *correctness* is pinned by the
    resnet_tiny golden (BN applied unfolded in the oracle)."""

    def _bn_stats(self, c, var=1.0):
        return [fx.tensor("s", np.full(c, 2.0, np.float32)),
                fx.tensor("B", np.zeros(c, np.float32)),
                fx.tensor("m", np.zeros(c, np.float32)),
                fx.tensor("v", np.full(c, var, np.float32))]

    def test_bn_not_after_conv_rejected(self):
        g = fx.graph(
            "bn_solo",
            [fx.node("Relu", ["x"], ["h"], "r"),
             fx.node("BatchNormalization", ["h", "s", "B", "m", "v"],
                     ["y"], "bn", (fx.attr_float("epsilon", 0.0),))],
            self._bn_stats(2),
            [fx.value_info("x", (1, 2, 4, 4))],
            [fx.value_info("y", (1, 2, 4, 4))],
        )
        with pytest.raises(OnnxImportError, match="not a Conv output"):
            load_onnx(fx.model(g))

    def test_bn_on_shared_conv_output_rejected(self):
        w = np.ones((2, 2, 3, 3), np.int8)
        g = fx.graph(
            "bn_shared",
            [fx.node("Conv", ["x", "w"], ["h"], "conv",
                     (fx.attr_string("auto_pad", "SAME_UPPER"),)),
             fx.node("BatchNormalization", ["h", "s", "B", "m", "v"],
                     ["y"], "bn", (fx.attr_float("epsilon", 0.0),))],
            [fx.tensor("w", w)] + self._bn_stats(2),
            [fx.value_info("x", (1, 2, 4, 4))],
            [fx.value_info("h", (1, 2, 4, 4)),
             fx.value_info("y", (1, 2, 4, 4))],
        )
        with pytest.raises(OnnxImportError, match="other consumers"):
            load_onnx(fx.model(g))

    def test_bn_fractional_fold_on_int_weights_rejected(self):
        """var=4, scale=2 → s=1 is exact; var=16, scale=2 → s=0.5 is
        not representable in int8 weights — named rejection instead of
        silent rounding."""
        w = np.ones((2, 2, 3, 3), np.int8)
        g = fx.graph(
            "bn_frac",
            [fx.node("Conv", ["x", "w"], ["h"], "conv",
                     (fx.attr_string("auto_pad", "SAME_UPPER"),)),
             fx.node("BatchNormalization", ["h", "s", "B", "m", "v"],
                     ["y"], "bn", (fx.attr_float("epsilon", 0.0),))],
            [fx.tensor("w", w)] + self._bn_stats(2, var=16.0),
            [fx.value_info("x", (1, 2, 4, 4))],
            [fx.value_info("y", (1, 2, 4, 4))],
        )
        with pytest.raises(OnnxImportError, match="requantization"):
            load_onnx(fx.model(g))


class TestResnetTinyGolden:
    """The ISSUE 8 golden: stride-2 downsamples under three padding
    spellings, BN folds, a GlobalAveragePool head.  Regenerate with
    ``python tests/_onnx_fixture.py``."""

    def test_golden_bytes_are_the_seeded_fixture(self):
        with open(GOLDEN2, "rb") as f:
            assert f.read() == fx.resnet_tiny_model_bytes(seed=0)

    def test_bn_nodes_fold_away(self):
        m = load_onnx(GOLDEN2)
        assert m.missing_params() == []
        # 3 convs survive; BN left no standalone nodes behind
        payloads = [op.name for op in m.dfg.nodes]
        assert not any("bn" in p for p in payloads)

    @pytest.mark.parametrize("target", ["kv260", "zu3eg"])
    def test_bit_exact_against_numpy_oracle(self, target):
        """Acceptance: the strided ResNet-style export compiles end to
        end and matches the independent un-folded NumPy oracle."""
        from repro import api

        m = load_onnx(GOLDEN2)
        art = api.compile_graph(m.dfg, api.CompileOptions(target=target))
        assert art.feasible
        x = np.random.default_rng(17).integers(
            -4, 5, (1, 3, 16, 16)
        ).astype(np.int32)
        got = np.asarray(
            art.run({m.dfg.graph_inputs[0]: x}, params=m.params,
                    interpret=True)
        )
        want = fx.resnet_tiny_numpy(x.astype(np.int64),
                                    fx.resnet_tiny_weights(0))
        np.testing.assert_array_equal(got.astype(np.int64), want)

    def test_global_average_pool_floor_division(self):
        """GAP rides the AVG epilogue's DIV exit: floor division for
        integers, including negative sums."""
        g = fx.graph(
            "gap",
            [fx.node("GlobalAveragePool", ["x"], ["y"], "gap")],
            [],
            [fx.value_info("x", (1, 2, 4, 4))],
            [fx.value_info("y", (1, 2, 1, 1))],
        )
        m = load_onnx(fx.model(g))
        from repro import api

        art = api.compile_graph(m.dfg)
        x = (np.arange(32, dtype=np.int32) - 19).reshape(1, 2, 4, 4)
        got = np.asarray(art.run(x, interpret=True))
        want = x.astype(np.int64).sum(axis=(2, 3), keepdims=True) // 16
        np.testing.assert_array_equal(got.astype(np.int64), want)


class TestBiasFootprint:
    def test_broadcast_bias_reduces_modeled_bram(self):
        """Acceptance: a rank-1 (C,) bias epilogue operand costs C
        resident elements; the old full-tensor materialization charged
        H·W·C — the modeled BRAM must drop.  On the DSP-poor ZU3EG the
        unroll (and hence the array partitioning) is small, so the
        full-tensor constant lands squarely in RAM18K blocks."""
        from repro import api
        from repro.api.builder import Graph

        def build(full):
            g = Graph("bias_full" if full else "bias_bcast")
            x = g.input((1, 64, 64, 8))
            h = g.conv2d(x, 32, kernel=3)
            if full:
                k = g.constant((1, 64, 64, 32), name="b")
            else:
                k = g.constant((32,), name="b")
            g.output(g.add(h, k))
            return g.build()

        opts = api.CompileOptions(target="zu3eg")
        art_full = api.compile_graph(build(True), opts)
        art_bcast = api.compile_graph(build(False), opts)
        # both fuse the bias into the conv epilogue; the plans differ
        # only in the resident constant footprint
        plan_bits = lambda a: next(  # noqa: E731
            iter(a.design.groups[0].plan.nodes.values())
        ).const_buffer_bits
        assert plan_bits(art_bcast) < plan_bits(art_full)
        assert art_bcast.report().max_bram < art_full.report().max_bram


class TestSmallModels:
    def test_gemm_bias_and_add_paths(self):
        """Gemm with transB + bias, then Add with an initializer: both
        constant-binding paths, checked against numpy."""
        rng = np.random.default_rng(5)
        w = rng.integers(-3, 4, (6, 8)).astype(np.int8)     # (units, d_in)
        b = rng.integers(-3, 4, (6,)).astype(np.int32)
        k = rng.integers(-3, 4, (1, 6)).astype(np.int32)
        g = fx.graph(
            "mlp",
            [
                fx.node("Gemm", ["x", "w", "b"], ["h"], "gemm",
                        (fx.attr_int("transB", 1),)),
                fx.node("Add", ["h", "k"], ["y"], "bias2"),
            ],
            [fx.tensor("w", w), fx.tensor("b", b), fx.tensor("k", k)],
            [fx.value_info("x", (1, 8))],
            [fx.value_info("y", (1, 6))],
        )
        m = load_onnx(fx.model(g))
        from repro import api

        art = api.compile_graph(m.dfg)
        x = rng.integers(-3, 4, (1, 8)).astype(np.int32)
        got = np.asarray(art.run(x, params=m.params, interpret=True))
        want = x.astype(np.int64) @ w.T.astype(np.int64) + b + k
        np.testing.assert_array_equal(got.astype(np.int64), want)

    def test_avgpool_model(self):
        g = fx.graph(
            "ap",
            [fx.node("AveragePool", ["x"], ["y"], "pool",
                     (fx.attr_ints("kernel_shape", [2, 2]),
                      fx.attr_ints("strides", [2, 2])))],
            [],
            [fx.value_info("x", (1, 2, 4, 4))],
            [fx.value_info("y", (1, 2, 2, 2))],
        )
        m = load_onnx(fx.model(g))
        from repro import api

        art = api.compile_graph(m.dfg)
        x = np.arange(32, dtype=np.int32).reshape(1, 2, 4, 4)
        got = np.asarray(art.run(x, interpret=True))
        want = x.reshape(1, 2, 2, 2, 2, 2).sum(axis=(3, 5)) // 4
        np.testing.assert_array_equal(got, want)

    def test_import_model_dispatches_onnx(self):
        m = import_model(GOLDEN)
        assert m.source == "onnx"


class TestDepthwiseAndClip:
    """A MobileNetV2-style export: ``Conv`` with ``group`` equal to the
    channels becomes the depthwise node, ``Clip(0, 6)`` ReLU6."""

    def test_imports_depthwise_nodes_and_relu6(self):
        from repro.core.analysis import is_depthwise
        from repro.core.ir import PayloadKind

        m = load_onnx(fx.mobilenet_tiny_model_bytes(0))
        assert m.missing_params() == []
        dws = [op for op in m.dfg.nodes if is_depthwise(op)]
        assert [op.dim_sizes for op in dws] == [(1, 8, 8, 16, 3, 3),
                                               (1, 4, 4, 8, 3, 3)]
        assert sum(op.payload == PayloadKind.RELU6
                   for op in m.dfg.nodes) == 3
        w = fx.mobilenet_tiny_weights(0)["d1_w"]
        np.testing.assert_array_equal(
            m.params[dws[0].inputs[1]][:, :, 5], w[5, 0])

    @pytest.mark.parametrize("seed", [0, 1])
    def test_matches_numpy_oracle(self, seed):
        """Float32 through both depthwise strides, the residual add and
        the head, against the float64 NumPy oracle: 1e-5 relative, the
        float32 rounding of sums of a few hundred terms."""
        from repro import api

        data = fx.mobilenet_tiny_model_bytes(seed)
        m = load_onnx(data)
        art = api.compile_graph(m.dfg)
        x = np.random.default_rng(seed + 5).standard_normal(
            (1, 8, 8, 8)).astype(np.float32)
        got = np.asarray(art.run({m.dfg.graph_inputs[0]: x}, params=m.params,
                                 interpret=True), np.float64)
        want = fx.mobilenet_tiny_numpy(x, fx.mobilenet_tiny_weights(seed))
        np.testing.assert_allclose(got.reshape(want.shape), want,
                                   rtol=1e-5, atol=1e-5)

    def test_other_group_counts_still_fail(self):
        w = np.ones((4, 2, 3, 3), np.int8)   # group 2 over 4 channels
        g = fx.graph(
            "g2",
            [fx.node("Conv", ["x", "w"], ["y"], "conv_g2",
                     (fx.attr_ints("kernel_shape", [3, 3]),
                      fx.attr_int("group", 2),
                      fx.attr_ints("pads", [1, 1, 1, 1])))],
            [fx.tensor("w", w)],
            [fx.value_info("x", (1, 4, 8, 8))],
            [fx.value_info("y", (1, 4, 8, 8))],
        )
        with pytest.raises(OnnxImportError, match="conv_g2.*group=2"):
            load_onnx(fx.model(g))

    @pytest.mark.parametrize("lo,hi", [(0.0, 5.0), (-1.0, 6.0)])
    def test_other_clip_bounds_fail(self, lo, hi):
        g = fx.graph(
            "clip",
            [fx.node("Clip", ["x", "lo", "hi"], ["y"], "clip_x")],
            [fx.tensor("lo", np.full((), lo, np.float32)),
             fx.tensor("hi", np.full((), hi, np.float32))],
            [fx.value_info("x", (1, 8), fx.FLOAT)],
            [fx.value_info("y", (1, 8), fx.FLOAT)],
        )
        with pytest.raises(OnnxImportError, match="clip_x"):
            load_onnx(fx.model(g))

    def test_clip_bounds_as_attributes(self):
        """Opset < 11 spells the bounds as attributes."""
        from repro.core.ir import PayloadKind

        g = fx.graph(
            "clip",
            [fx.node("Clip", ["x"], ["y"], "clip_x",
                     (fx.attr_float("min", 0.0), fx.attr_float("max", 6.0)))],
            [],
            [fx.value_info("x", (1, 8), fx.FLOAT)],
            [fx.value_info("y", (1, 8), fx.FLOAT)],
        )
        m = load_onnx(fx.model(g, opset=10))
        assert [op.payload for op in m.dfg.nodes] == [PayloadKind.RELU6]
