"""The benchmark's FLOP and byte functions against hand counts, and the
two model cards against what MING compiles them into."""
import os

import numpy as np
import pytest

from bench import model, reference
from bench.registry import ROOT, _json

VGG16 = _json(os.path.join(ROOT, "bench", "configs", "vgg16.json"))
LENET5 = _json(os.path.join(ROOT, "bench", "configs", "lenet5.json"))

#: VGG-16 configuration D: (spatial size, in channels, out channels) of
#: its 13 conv3x3 layers, then its three dense layers
VGG16_CONVS = [(224, 3, 64), (224, 64, 64), (112, 64, 128), (112, 128, 128),
               (56, 128, 256), (56, 256, 256), (56, 256, 256),
               (28, 256, 512), (28, 512, 512), (28, 512, 512),
               (14, 512, 512), (14, 512, 512), (14, 512, 512)]
VGG16_DENSE = [(25088, 4096), (4096, 4096), (4096, 1000)]


def test_vgg16_macs_by_hand():
    conv = sum(h * h * 9 * cin * cout for h, cin, cout in VGG16_CONVS)
    dense = sum(a * b for a, b in VGG16_DENSE)
    assert conv == 15_346_630_656
    assert dense == 123_633_664
    assert model.model_flops_per_sample(VGG16) == 2 * (conv + dense)
    assert 2 * (conv + dense) == 30_940_528_640  # about 15.5 G MACs


def test_vgg16_conv_work_per_call():
    flops, nbytes = model.conv_work(VGG16, itemsize=4, batch=32)
    assert flops == 2 * 15_346_630_656 * 32
    weights = sum(9 * cin * cout for _, cin, cout in VGG16_CONVS)
    acts = sum(h * h * (cin + cout) for h, cin, cout in VGG16_CONVS)
    assert nbytes == 4 * (weights + 32 * acts)


def test_lenet5_counts_by_hand():
    macs = (28 * 28 * 6 * 25 * 1 + 10 * 10 * 16 * 25 * 6
            + 400 * 120 + 120 * 84 + 84 * 10)
    assert macs == 416_520
    assert model.model_flops_per_sample(LENET5) == 2 * macs
    flops, nbytes = model.conv_work(LENET5, itemsize=1, batch=1)
    assert flops == 2 * (28 * 28 * 6 * 25 + 10 * 10 * 16 * 25 * 6)
    assert nbytes == (32 * 32 + 28 * 28 * 6 + 150) + (14 * 14 * 6 + 1600 + 2400)


@pytest.mark.parametrize("card", [VGG16, LENET5], ids=["vgg16", "lenet5"])
def test_weight_count_matches_expectation(card):
    assert model.weight_count(card) == card["expect"]["weights"]
    assert model.layers(card)[-1].out_shape == (
        1000 if card is VGG16 else 10,)


@pytest.mark.parametrize("card", [VGG16, LENET5], ids=["vgg16", "lenet5"])
def test_card_compiles_to_expected_groups(card):
    """The card, read through the program's front door, compiles into
    the group count measured for it, over the same weights."""
    from repro import CompileOptions, compile_graph, frontends

    imported = frontends.import_model(
        os.path.join(ROOT, "bench", "configs", card["name"] + ".json"))
    art = compile_graph(imported.dfg, CompileOptions(**card["compile_options"]))
    assert len(art.design.groups) == card["expect"]["groups"]
    consts = {n: v.shape for n, v in art.source.values.items() if v.is_constant}
    assert consts == model.weight_shapes(card)


def test_seeded_fills_repeat_and_differ():
    a = model.make_inputs(LENET5, "int8_uniform", 4, 2**40 + 1)
    b = model.make_inputs(LENET5, "int8_uniform", 4, 2**40 + 1)
    c = model.make_inputs(LENET5, "int8_uniform", 4, 2**40 + 2)
    assert a.dtype == np.int8 and a.shape == (4, 1, 32, 32, 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    w = model.make_weights(LENET5, "int8_uniform", 7)
    assert {k: v.shape for k, v in w.items()} == model.weight_shapes(LENET5)
    assert not np.array_equal(w["w0"].ravel()[:16], a.ravel()[:16])


def test_he_normal_scale():
    w = model.make_weights(LENET5, "he_normal", 3)["w2"]  # 400 -> 120
    assert w.dtype == np.float32
    assert abs(float(w.std()) - np.sqrt(2 / 400)) < 0.005


def test_int_reference_wraps_like_int32():
    """Accumulators wrap mod 2**32 as the int8 datapath states."""
    lay = [x for x in model.layers(LENET5) if x.name == "linear0"][0]
    x = np.full((1, 400), 2**30, np.int32)
    w = np.ones((400, 120), np.int8)
    got = reference._wrap32(x.astype(np.float64) @ w.astype(np.float64))
    want = (x.astype(np.int64) @ w.astype(np.int64)).astype(np.int32)
    assert lay.in_shape == (400,) and np.array_equal(got, want)
