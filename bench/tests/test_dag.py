"""The graph reader (``bench/dag.py``), its reference
(``bench/reference_dag.py``), the depthwise kernel's time and roofline
share, and a tiny MobileNetV2 cell run whole on the CPU."""
import json
import os
import time

import numpy as np
import pytest

from bench import dag, kernel_time, model, reference, reference_dag, registry
from bench import run as bench_run
from bench.registry import ROOT
from bench.tests import tiny

MNV2 = registry._json(os.path.join(ROOT, "bench", "configs",
                                   "mobilenetv2.json"))
SEED = 2**40 + 23


def test_card_is_the_zoo_graph():
    """The card is generated from ``zoo.mobilenet_v2``, not written by
    hand."""
    from repro.frontends import export_card
    from repro.frontends.zoo import mobilenet_v2

    card = export_card(mobilenet_v2(224, 1.0))
    for key in ("inputs", "layers", "outputs"):
        assert MNV2[key] == card[key], key


def test_mobilenetv2_counts():
    """Sandler et al., Table 2: 300 M multiply-adds and 3.4 M
    parameters (BN folded: one bias per conv channel)."""
    lays = dag.layers(MNV2)
    assert sum(lay.macs for lay in lays) == 300_774_272
    assert dag.param_count(MNV2) == MNV2["expect"]["weights"] == 3_487_816
    kinds = [lay.op for lay in lays]
    assert kinds.count("depthwise_conv2d") == 17
    assert kinds.count("conv2d") == 35
    assert sum(1 for lay in lays if lay.op == "add"
               and lay.inputs[1] not in dag.bias_shapes(MNV2)) == 10
    assert lays[-1].out_shape == (1000,)
    per = dag.conv_bytes_per_sample(MNV2, 4)
    flops, nbytes = dag.dwconv_work(MNV2, 4, 1)
    biases = sum(lay.out_shape[-1] for lay in lays
                 if lay.op == "depthwise_conv2d") * 4
    assert nbytes == per["depthwise_conv2d"] + biases
    # the depthwise convs are about 39% of the conv bytes, 7% of the MACs
    assert 0.38 < nbytes / (per["conv2d"] + nbytes) < 0.40
    assert 0.06 < flops / dag.model_flops_per_sample(MNV2) < 0.08


def test_dwconv_work_per_call_by_hand():
    """One depthwise conv with its bias, 8x8x4, stride 2, at batch 3."""
    from repro.api import Graph
    from repro.frontends import export_card

    g = Graph("dw")
    h = g.depthwise_conv2d(g.input((1, 8, 8, 4)), 3, 2)
    g.output(g.add(h, g.constant((4,))))
    card = export_card(g.build())
    flops, nbytes = dag.dwconv_work(card, 4, 3)
    assert flops == 2 * 4 * 4 * 4 * 9 * 3
    assert nbytes == ((8 * 8 * 4 + 4 * 4 * 4) * 3 + 9 * 4 + 4) * 4


def test_reference_dag_equals_chain_reference():
    """On a float chain card of the ``vgg16`` card's layer kinds the
    graph reference computes what ``bench/reference.py`` does."""
    card = tiny.tiny_card("tinyc", tiny.FLOAT_CARD_EXTRA)
    assert {rec["op"] for rec in card["layers"]} == set(model.SUPPORTED)
    params = model.make_weights(card, card["weight_fill"], SEED)
    xs = model.make_inputs(card, card["input_fill"], 6, SEED)
    want = reference.forward_float(card, params, xs)
    got = reference_dag.forward(card, params, xs, block=4)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    ctl = reference_dag.forward(card, params, xs, control="fp8")
    np.testing.assert_allclose(
        ctl, reference.forward_float(card, params, xs,
                                     operand=reference.fp8_operands),
        rtol=1e-6, atol=1e-6)
    assert reference.rel_l2_per_sample(ctl, want).min() > 0


def test_reference_dag_depthwise_by_hand():
    """A depthwise conv, bias, ReLU6 and a residual add, against NumPy
    written out."""
    from repro.api import Graph
    from repro.core.ir import PayloadKind
    from repro.frontends import export_card

    g = Graph("dwres")
    x = g.input((1, 5, 5, 3))
    h = g.add(g.depthwise_conv2d(x, 3), g.constant((3,)))
    h = g.add(g.activation(h, PayloadKind.RELU6, "relu6"), x)
    g.output(g.flatten(h))
    card = dict(export_card(g.build()), weight_fill="he_normal",
                bias_fill="normal_0.1")
    params = dag.make_params(card, SEED)
    (w_name,) = dag.weight_shapes(card)
    (b_name,) = dag.bias_shapes(card)
    xs = np.random.default_rng(0).standard_normal((2, 1, 5, 5, 3)).astype(
        np.float32)
    got = reference_dag.forward(card, params, xs)
    xp = np.pad(xs[:, 0].astype(np.float64), ((0, 0), (1, 1), (1, 1), (0, 0)))
    w = params[w_name].astype(np.float64)
    conv = sum(xp[:, i:i + 5, j:j + 5] * w[i, j]
               for i in range(3) for j in range(3))
    want = np.clip(conv + params[b_name], 0, 6) + xs[:, 0]
    np.testing.assert_allclose(got, want.reshape(2, -1), rtol=1e-5,
                               atol=1e-5)


def test_seeded_params_repeat_and_differ():
    card = _tiny_mnv2_card()
    a, b = dag.make_params(card, SEED), dag.make_params(card, SEED)
    c = dag.make_params(card, SEED + 1)
    assert set(a) == set(dag.weight_shapes(card)) | set(dag.bias_shapes(card))
    name = next(iter(dag.bias_shapes(card)))
    np.testing.assert_array_equal(a[name], b[name])
    assert not np.array_equal(a[name], c[name])
    assert 0.07 < float(np.concatenate(
        [a[n] for n in dag.bias_shapes(card)]).std()) < 0.13


def _op(name, opcode, start, dur, operands=""):
    return [f"%{name} = f32[64,1,58,56,144] {opcode}({operands})", start, dur]


def test_kernel_seconds_reads_the_kernel_own_name():
    """The kernel's time is that of the ops it names itself; an op that
    reads its output names it as an operand and is not counted."""
    k = kernel_time.DWCONV_KERNEL
    trace = {"window": [1000, 11000], "host": [], "devices": [[
        _op(f"vmap_{k}_.1", "custom-call", 2000, 3000, "f32[] %pad.1"),
        _op("fusion.7", "fusion", 5000, 1000, f"f32[] %vmap_{k}_.1"),
        _op(f"{k}.2", "custom-call", 10500, 2000),   # cut at the window
        _op("vmap_ming_conv2d_stream_.4", "custom-call", 6000, 1000),
    ]]}
    assert kernel_time.kernel_seconds(trace, k) == pytest.approx(3.5e-6)
    assert kernel_time.kernel_seconds(trace, "ming_conv2d_stream") == \
        pytest.approx(1e-6)
    assert k not in "ming_conv2d_stream" and \
        "ming_conv2d_stream" not in k


class _Run:
    config = MNV2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    window = {"batch": 64, "calls": 10}

    def __init__(self, dw_s):
        self.reduced = {"kernel_s": {"pallas": 1.0, "dwconv": dw_s}}


def test_dwconv_roofline_reads_a_synthetic_trace():
    read = registry.reader("dwconv_roofline.mnv2")
    flops, nbytes = dag.dwconv_work(MNV2, 4, 64)
    # memory bound: the bytes set the roofline time
    assert nbytes / 819e9 > flops / 197e12
    bound = 10 * nbytes / 819e9
    assert read(_Run(2 * bound)) == pytest.approx(50.0)
    assert read(_Run(0.0)) is None
    run = _Run(1.0)
    run.reduced = {"kernel_s": {"pallas": 1.0}}
    assert read(run) is None


def _tiny_mnv2_card() -> dict:
    from repro.frontends import export_card
    from repro.frontends.zoo import mobilenet_v2

    extra = {k: v for k, v in MNV2.items()
             if k not in ("format", "version", "name", "inputs", "layers",
                          "outputs")}
    card = dict(export_card(mobilenet_v2(32, 0.35, 10)), **extra)
    card.update(source="test model", expect={},
                compile_options={"target": "kv260", "strategy": "greedy"})
    return card


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.copy_benchmark(str(tmp_path_factory.mktemp("checkout")))
    tiny.add_cell(root, "tinymnv2.offline", _tiny_mnv2_card(),
                  {"kind": "closed_loop_dag", "batch": 2,
                   "distinct_batches": 2, "warmup_calls": 1},
                  widen=("samples_per_s",))
    return root


def test_tiny_mobilenet_cell_runs_correct(root):
    """A MobileNetV2 at 32x32, width 0.35, added as files and entries,
    runs through the front door and comes out correct against the
    graph reference, every conv kind of the card on the way."""
    line = bench_run.execute("tinymnv2.offline", SEED, 0.5, False,
                             root=root, require_tpu=False,
                             t_start=time.perf_counter())
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["max_rel_l2"]["value"] < 1e-5
    path = os.path.join(root, "bench", "configs",
                        "mobilenetv2_32_0.35_10.json")
    with open(path) as f:
        card = json.load(f)
    assert {"depthwise_conv2d", "activation", "add", "avg_pool"} <= {
        rec["op"] for rec in card["layers"]}
