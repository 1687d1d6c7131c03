"""The depthwise conv through every layer of the compiler: its maps, the
streaming plan, the resource model, the HLS it emits, the fusion of its
bias and ReLU6, the VPU kernel in interpret mode, and a small
MobileNetV2 run end to end.  The numbers are checked against the
benchmark's plain reference (``bench/reference_dag.py``), which
imports nothing of the program."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import dag, reference, reference_dag
from repro import CompileOptions, compile_graph
from repro.api import Graph
from repro.core.analysis import (
    KernelClass,
    channelwise_dims,
    classify_kernel,
    conv_spatial_pads,
    is_depthwise,
)
from repro.core.emit_hls import emit_node
from repro.core.ir import PayloadKind, make_depthwise_conv2d_op
from repro.core.resource_model import FpgaResourceModel
from repro.core.streaming import plan_streams
from repro.frontends import export_card
from repro.frontends.zoo import mobilenet_v2
from repro.instrument.metrics import MetricsRegistry, use_metrics
from repro.kernels import ops
from repro.passes import run_default_pipeline

#: float32 sums in another order than XLA's conv: a few ulps of the
#: largest partial sum, far below any wrong tap or channel
KERNEL_RTOL = 1e-5


def _dw_graph(h, w, c, stride, padding, bias, relu6):
    g = Graph("dw")
    y = g.depthwise_conv2d(g.input((1, h, w, c)), 3, stride, padding=padding)
    if bias:
        y = g.add(y, g.constant((c,)))
    if relu6:
        y = g.activation(y, PayloadKind.RELU6, "relu6")
    g.output(y)
    return g.build()


class TestMaps:
    def test_maps_and_classification(self):
        op = make_depthwise_conv2d_op("dw", "x", "w", "y", n=1, h_out=7,
                                      w_out=7, c=96, kh=3, kw=3, stride=2)
        assert op.n_dims == 6 and op.payload == PayloadKind.MAC
        assert [repr(m) for m in op.indexing_maps] == [
            "(d0, d1, d2, d3, d4, d5) -> (d0, 2*d1 + d4, 2*d2 + d5, d3)",
            "(d0, d1, d2, d3, d4, d5) -> (d4, d5, d3)",
            "(d0, d1, d2, d3, d4, d5) -> (d0, d1, d2, d3)",
        ]
        info = classify_kernel(op)
        assert info.kernel_class == KernelClass.SLIDING_WINDOW
        assert (info.stride, info.dilation) == (2, 1)
        assert channelwise_dims(op) == (3,) and is_depthwise(op)
        # SAME at stride 2 on 14: needs 15 rows, pads (0, 1)
        assert conv_spatial_pads(op, (1, 14, 14, 96))[1:3] == ((0, 1), (0, 1))

    def test_full_conv_and_pool_are_not_depthwise(self):
        g = Graph("c")
        x = g.input((1, 8, 8, 4))
        g.output(g.max_pool(g.conv2d(x, 4), 2))
        for op in g.build().nodes:
            assert channelwise_dims(op) == () and not is_depthwise(op)


class TestCompilerCharges:
    """A depthwise node does K·K MACs per channel per output point: its
    weights are K·K·C, its window K·K per channel lane — never the
    K·K·C per output point of a full conv."""

    def _plan(self):
        dfg = _dw_graph(16, 16, 32, 1, "SAME", False, False)
        return dfg, plan_streams(dfg).nodes["dwconv0"]

    def test_streaming_plan(self):
        dfg, p = self._plan()
        bits = p.op.elem_bits
        assert p.line_buffer_bits == 2 * 18 * 32 * bits   # (K-1) x W x C
        assert p.window_buffer_bits == 9 * 32 * bits
        assert p.const_buffer_bits == 9 * 32 * bits
        assert p.weight_tile_dims == ()   # the channel also indexes the stream
        assert math.prod(p.loops.trip_counts) == 16 * 16 * 32 * 9

    def test_resource_model(self):
        dfg, p = self._plan()
        assert p.op.macs() == 16 * 16 * 32 * 9
        model = FpgaResourceModel()
        # one int8 multiply (half a DSP) per unrolled lane
        assert model.node_dsp(p, 32) == 16 + 4
        assert model.node_cycles(p, 9, 1) == 16 * 16 * 32 + 4

    def test_emitted_hls(self):
        dfg, p = self._plan()
        text = emit_node(p, 1, 1, values=dfg.values)
        assert "elem_t win[9];" in text and "elem_t wgt[9];" in text
        assert "line_buf[2][18 * 32]" in text
        assert "9 (K·K) MACs per channel lane" in text
        trips = [int(t) for t in re.findall(r"< (\d+); \+\+i", text)]
        assert math.prod(trips) == 16 * 16 * 32 * 9


def test_bias_and_relu6_fold_into_both_conv_kinds():
    g = Graph("fold")
    x = g.input((1, 8, 8, 4))
    h = g.add(g.conv2d(x, 8, 1), g.constant((8,)))
    h = g.activation(h, PayloadKind.RELU6, "relu6")
    h = g.add(g.depthwise_conv2d(h, 3), g.constant((8,)))
    g.output(g.activation(h, PayloadKind.RELU6, "relu6"))
    dfg = run_default_pipeline(g.build()).dfg
    assert [op.name for op in dfg.nodes] == ["conv0", "dwconv0"]
    for op in dfg.nodes:
        assert [(e.kind, e.operand is not None) for e in op.epilogue] == [
            (PayloadKind.ADD, True), (PayloadKind.RELU6, False)]


@pytest.mark.parametrize("c", [32, 96, 144])
@pytest.mark.parametrize("h,w,stride,padding", [
    (9, 9, 1, "SAME"), (8, 10, 2, "SAME"), (9, 8, 2, "VALID"),
    (6, 7, 1, "VALID")])
@pytest.mark.parametrize("bias,relu6", [(False, False), (True, True)])
def test_kernel_matches_reference(c, h, w, stride, padding, bias, relu6):
    """The VPU kernel in interpret mode, two rows of output a grid step
    (so the line buffer carries rows), against the benchmark's
    reference on the same layer as a card."""
    dfg = _dw_graph(h, w, c, stride, padding, bias, relu6)
    card = dict(export_card(dfg), weight_fill="he_normal",
                bias_fill="normal_0.1")
    params = dag.make_params(card, 7 + c + h)
    xs = np.asarray(jax.random.normal(jax.random.key(c * h + w),
                                      (2, 1, h, w, c), jnp.float32))
    want = reference_dag.forward(card, params, xs)
    x = xs[:, 0]
    wt = params[next(iter(dag.weight_shapes(card)))]
    b = params[next(iter(dag.bias_shapes(card)))] if bias else None
    got = ops.dwconv2d_stream(x, wt, b, stride=stride, padding=padding,
                              epilogue="relu6" if relu6 else None,
                              interpret=True, rows_per_block=2 * stride)
    np.testing.assert_allclose(np.asarray(got).reshape(2, -1), want,
                               rtol=KERNEL_RTOL, atol=KERNEL_RTOL)


def test_conv_stream_bias_and_relu6_in_kernel():
    x = jax.random.normal(jax.random.key(0), (2, 10, 10, 16), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (1, 1, 16, 24), jnp.float32)
    b = jax.random.normal(jax.random.key(2), (24,), jnp.float32) * 3
    got = ops.conv2d_stream(x, w, b, epilogue="relu6", interpret=True)
    from repro.kernels import ref

    want = ref.unary("relu6", ref.conv2d(x, w) + b)
    np.testing.assert_allclose(got, want, rtol=KERNEL_RTOL, atol=KERNEL_RTOL)
    assert float(jnp.max(got)) == 6.0


@pytest.fixture(scope="module")
def small_mobilenet():
    dfg = mobilenet_v2(32, 0.35, 10)
    card = dict(export_card(dfg), weight_fill="he_normal",
                bias_fill="normal_0.1")
    art = compile_graph(dfg, CompileOptions(target="kv260",
                                            strategy="greedy"))
    return card, art


def test_small_mobilenet_v2_end_to_end(small_mobilenet):
    """Every block kind (no expansion, expansion, stride 2, residual
    add) and the head, batched, against the reference: relative L2 of
    each answer within 1e-5 (float32 accumulation in another order,
    through 52 convs), and every conv on its kernel."""
    card, art = small_mobilenet
    params = dag.make_params(card, 2**33 + 5)
    xs = np.asarray(jax.random.normal(jax.random.key(3), (3, 1, 32, 32, 3),
                                      jnp.float32))
    reg = MetricsRegistry()
    with use_metrics(reg):
        got = np.asarray(art.run(xs, params))
    want = reference_dag.forward(card, params, xs)
    assert reference.rel_l2_per_sample(got.reshape(3, -1), want).max() < 1e-5
    counts = {r["labels"]["kernel"]: r["value"] for r in
              reg.snapshot()["counters"]["lower_conv_total"]["values"]}
    assert counts == {"depthwise": 17, "stream": 35}


def test_mobilenet_v2_224_lowers_every_conv_to_a_kernel():
    """One lowering of the benchmark design's groups at bucket 64:
    17 depthwise and 35 stream-kernel convs, none through XLA."""
    from repro import frontends

    m = frontends.import_model("bench/configs/mobilenetv2.json")
    art = compile_graph(m.dfg, CompileOptions(target="kv260",
                                              strategy="greedy"))
    assert len(art.design.groups) == 6
    reg = MetricsRegistry()
    with use_metrics(reg):
        for g in art.design.groups:
            d = g.dfg
            env = {v: jax.ShapeDtypeStruct((64,) + d.values[v].shape,
                                           jnp.float32)
                   for v in d.graph_inputs}
            env.update({n: jax.ShapeDtypeStruct(v.shape, jnp.float32)
                        for n, v in d.values.items() if v.is_constant})
            fn = ops.lower_group(g, interpret=True, jit=False, batch=64)
            jax.eval_shape(fn, env)
    counts = {r["labels"]["kernel"]: r["value"] for r in
              reg.snapshot()["counters"]["lower_conv_total"]["values"]}
    assert counts == {"depthwise": 17, "stream": 35}


def test_integer_graph_per_sample_is_bit_exact():
    """The per-sample path on int32 operands: conv → depthwise
    (stride 2) → bias → ReLU6, against the DFG interpreter, exactly
    (int32 accumulation on both sides).  The full conv takes
    ``conv2d_same_mm`` at that width; the depthwise conv keeps its
    kernel."""
    from repro.passes import interp

    g = Graph("dwint")
    h = g.relu(g.conv2d(g.input((1, 9, 9, 3)), 8, 3))
    h = g.add(g.depthwise_conv2d(h, 3, 2), g.constant((8,)))
    g.output(g.activation(h, PayloadKind.RELU6, "relu6"))
    dfg = g.build()
    env = interp.random_env(dfg, seed=4)
    art = compile_graph(dfg)
    x = env.pop(dfg.graph_inputs[0])
    reg = MetricsRegistry()
    with use_metrics(reg):
        got = art.run(np.asarray(x), {k: np.asarray(v) for k, v in env.items()})
    env[dfg.graph_inputs[0]] = x
    want = interp.graph_outputs(dfg, env)[dfg.graph_outputs[0]]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0 < int(np.asarray(want).max()) <= 6
    counts = {r["labels"]["kernel"]: r["value"] for r in
              reg.snapshot()["counters"]["lower_conv_total"]["values"]}
    assert counts == {"depthwise": 1, "same_mm": 1}
