"""Model-card format (ISSUE 5): schema validation, weight embedding,
and the load-bearing contract — ``import_card(export_card(g))`` is
node-for-node identical to ``g`` for any builder graph (reusing the
PR 4 equality pins via dataclass equality).
"""
import json

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro.api.builder import (
    Activation,
    AvgPool,
    Conv2D,
    Dense,
    DepthwiseConv2D,
    Flatten,
    Graph,
    MaxPool,
    ReLU,
    Residual,
    Sequential,
)
from repro.core import cnn_graphs
from repro.core.ir import PayloadKind
from repro.frontends import (
    ModelCardError,
    ZOO,
    export_card,
    import_card,
    import_model,
)
from repro.frontends.modelcard import FORMAT, SCHEMA_VERSION
from test_frontend import assert_dfg_equal


def roundtrip(dfg):
    """export → JSON text → import (the on-disk path, not just dicts)."""
    card = json.loads(json.dumps(export_card(dfg)))
    return import_card(card)


class TestRoundTrip:
    @pytest.mark.parametrize("name", sorted(cnn_graphs.PAPER_SUITE))
    def test_paper_suite_round_trips(self, name):
        dfg = cnn_graphs.PAPER_SUITE[name]()
        assert_dfg_equal(roundtrip(dfg).dfg, dfg)

    @pytest.mark.parametrize("name", sorted(ZOO))
    def test_zoo_round_trips(self, name):
        dfg = ZOO[name]()
        assert_dfg_equal(roundtrip(dfg).dfg, dfg)

    def test_showcases_round_trip(self):
        for make in (cnn_graphs.conv_pool, cnn_graphs.conv_avgpool,
                     cnn_graphs.fat_conv):
            dfg = make()
            assert_dfg_equal(roundtrip(dfg).dfg, dfg)

    def test_reorder_ops_round_trip(self):
        g = Graph("r")
        x = g.input((1, 2, 6, 6))
        h = g.transpose(x, (0, 2, 3, 1))
        h = g.conv2d(h, 4)
        h = g.transpose(h, (0, 3, 1, 2))
        h = g.flatten(h)
        g.output(g.dense(h, 5))
        dfg = g.build()
        assert_dfg_equal(roundtrip(dfg).dfg, dfg)

    def test_non_default_flatten_order_round_trips(self):
        g = Graph("r")
        x = g.input((1, 4, 6, 2))
        g.output(g.flatten(x, order=(3, 1, 2)))
        dfg = g.build()
        assert_dfg_equal(roundtrip(dfg).dfg, dfg)

    def test_bare_constant_add_round_trips(self):
        g = Graph("bias")
        x = g.input((1, 8))
        k = g.constant((1, 8), name="bias0")
        g.output(g.add(x, k))
        dfg = g.build()
        assert_dfg_equal(roundtrip(dfg).dfg, dfg)


class TestRoundTripProperty:
    @given(st.integers(4, 16), st.integers(1, 6), st.integers(1, 3),
           st.integers(0, 2), st.integers(0, 3))
    @settings(max_examples=20, deadline=None)
    def test_random_builder_graphs_round_trip(self, n, c, layers, head, dw):
        """Random conv cascades with optional depthwise/ReLU6 layers
        (stride 1 or 2, SAME or VALID) and pool/residual/dense heads —
        every one must survive export → import node-for-node."""
        specs = []
        for _ in range(layers):
            specs += [Conv2D(c), ReLU()]
            if dw:
                specs += [DepthwiseConv2D(3, 1, padding="VALID" if dw == 3
                                          else "SAME"),
                          Activation(PayloadKind.RELU6)]
        if dw == 2:
            specs += [DepthwiseConv2D(3, 2)]
            n_out = -(-n // 2)
        else:
            n_out = n - 2 * layers if dw == 3 else n
        if n_out < 1:
            return
        if head == 1:
            specs += [MaxPool(2) if n_out % 2 == 0 else ReLU()]
        elif head == 2:
            specs += [Residual([Conv2D(c), ReLU(), Conv2D(c)]),
                      Flatten(), Dense(4)]
        net = Sequential(specs, input_shape=(1, n, n, c), name="rand")
        dfg = net.build()
        assert_dfg_equal(roundtrip(dfg).dfg, dfg)


class TestWeights:
    def test_params_embed_and_decode(self):
        dfg = ZOO["lenet5"]()
        rng = np.random.default_rng(0)
        params = {
            name: rng.integers(-4, 5, v.shape).astype(np.int8)
            for name, v in dfg.values.items() if v.is_constant
        }
        m = import_card(export_card(dfg, params=params))
        assert m.missing_params() == []
        for k, v in params.items():
            np.testing.assert_array_equal(m.params[k], v)

    def test_partial_params_are_reported_missing(self):
        dfg = cnn_graphs.conv_relu(8, c_out=4)
        (wname,) = [n for n, v in dfg.values.items() if v.is_constant]
        m = import_card(export_card(dfg))
        assert m.missing_params() == [wname]

    def test_param_shape_mismatch_rejected(self):
        dfg = cnn_graphs.conv_relu(8, c_out=4)
        with pytest.raises(ModelCardError, match="shape"):
            export_card(dfg, params={"w0": np.zeros((2, 2), np.int8)})

    def test_param_unknown_name_rejected(self):
        dfg = cnn_graphs.conv_relu(8, c_out=4)
        with pytest.raises(ModelCardError, match="not a constant"):
            export_card(dfg, params={"nope": np.zeros((1,), np.int8)})

    def test_imported_weights_flow_into_run(self):
        from repro import api

        dfg = cnn_graphs.conv_relu(8, c_out=4)
        rng = np.random.default_rng(1)
        params = {"w0": rng.integers(-3, 4, (3, 3, 3, 4)).astype(np.int8)}
        m = import_card(export_card(dfg, params=params))
        art = api.compile_graph(m.dfg)
        x = rng.integers(-3, 4, (1, 8, 8, 3)).astype(np.int32)
        got = np.asarray(art.run(x, params=m.params, interpret=True))
        from repro.kernels import ref

        want = np.maximum(
            np.asarray(ref.conv2d(x, params["w0"].astype(np.int32))), 0
        )
        np.testing.assert_array_equal(got, want)


class TestValidation:
    def test_format_and_version_checked(self):
        card = export_card(cnn_graphs.conv_relu(8, c_out=4))
        bad = dict(card, format="something-else")
        with pytest.raises(ModelCardError, match="not a ming-modelcard"):
            import_card(bad)
        bad = dict(card, version=99)
        with pytest.raises(ModelCardError, match="version"):
            import_card(bad)

    def test_unknown_op_rejected(self):
        card = export_card(cnn_graphs.conv_relu(8, c_out=4))
        bad = dict(card, layers=card["layers"] + [{"op": "softmax"}])
        with pytest.raises(ModelCardError, match="unknown op"):
            import_card(bad)

    def test_dangling_reference_rejected(self):
        card = export_card(cnn_graphs.conv_relu(8, c_out=4))
        bad = json.loads(json.dumps(card))
        bad["layers"][0]["input"] = "ghost"
        with pytest.raises(ModelCardError, match="ghost"):
            import_card(bad)

    def test_missing_sections_rejected(self):
        for drop in ("inputs", "layers", "outputs", "name"):
            card = export_card(cnn_graphs.conv_relu(8, c_out=4))
            del card[drop]
            with pytest.raises(ModelCardError):
                import_card(card)

    def test_invalid_json_text_rejected(self):
        with pytest.raises(ModelCardError, match="JSON"):
            import_card("{not json")

    def test_missing_file_is_file_not_found(self, capsys):
        """A typo'd path must surface as file-not-found, not as
        'invalid JSON' (the inline-document fallback only engages for
        strings that look like JSON)."""
        with pytest.raises(FileNotFoundError):
            import_card("examples/lent5.json")
        from repro.__main__ import main as cli_main

        assert cli_main(["compile", "examples/lent5.json"]) == 2
        assert "No such file" in capsys.readouterr().err

    def test_fused_graphs_not_exportable(self):
        from repro.passes import run_default_pipeline

        fused = run_default_pipeline(cnn_graphs.conv_relu(8, c_out=4)).dfg
        with pytest.raises(ModelCardError, match="pre-pass"):
            export_card(fused)


class TestFilesAndDispatch:
    def test_card_file_import_and_dispatch(self, tmp_path):
        from repro.frontends import zoo

        path = tmp_path / "lenet5.json"
        path.write_text(zoo.card_json("lenet5"))
        m = import_model(str(path))
        assert_dfg_equal(m.dfg, zoo.lenet5())

    def test_examples_lenet5_card_matches_zoo(self):
        import os

        from repro.frontends import zoo

        path = os.path.join(os.path.dirname(__file__), "..", "examples",
                            "lenet5.json")
        m = import_card(path)
        assert_dfg_equal(m.dfg, zoo.lenet5())

    def test_unknown_extension_rejected(self):
        with pytest.raises(ValueError, match="unknown model extension"):
            import_model("model.yaml")

    def test_card_constants(self):
        card = export_card(ZOO["lenet5"]())
        assert card["format"] == FORMAT
        assert card["version"] == SCHEMA_VERSION
