"""The bundled model zoo: real CNN topologies through the importer path.

Three classifier-shaped models exercise everything the paper suite's
synthetic kernels do not — conv→dense transitions (flatten), deep
pool pyramids, and residual trunks feeding a head:

* ``lenet5``          — the classic 5-layer LeNet (SAME-padding
                        variant: this stack's convs are 'same', so the
                        32→28→14→10→5 VALID cascade becomes
                        32→32→16→16→8), conv/pool ×2 → flatten →
                        three dense layers;
* ``tiny_vgg_32``     — a VGG-style double-conv pyramid at 32²,
                        (conv·conv·pool)×2 → flatten → dense head;
* ``edge_residual_32``— two residual blocks with an avg-pool and a
                        dense head — the skip-connection model an edge
                        deployment actually ships;
* ``resnet_mini_16``  — a ResNet-18-flavoured strided trunk: stride-1
                        stem, two stride-2 SAME downsample convs each
                        followed by an identity residual block, then a
                        global average pool and the dense head — the
                        strided streaming conv path end to end.

:func:`mobilenet_v2` builds MobileNetV2 (depthwise convs, ReLU6, folded
biases, inverted residuals) at any size and width; it stays out of the
registry below, which the smoke suite compiles whole.

Every entry is a plain builder graph (so the whole pass pipeline,
partitioner, and both backends apply unchanged), is registered in the
benchmark suite (``repro.api.suite()`` → per-target BENCH_smoke rows),
and round-trips through the model-card format —
``python -m repro zoo --export DIR`` writes the cards
(``examples/lenet5.json`` is exactly ``card("lenet5")``).
"""
from __future__ import annotations

import json

from repro.api.builder import (
    AvgPool,
    Conv2D,
    Dense,
    Flatten,
    Graph,
    MaxPool,
    ReLU,
    Residual,
    Sequential,
)
from repro.core.ir import DFG, PayloadKind

from .modelcard import export_card


def lenet5(n_size: int = 32, c_in: int = 1, classes: int = 10) -> DFG:
    """LeNet-5 (SAME-padding variant): C6@5×5 → pool → C16@5×5 → pool →
    flatten → 120 → 84 → ``classes``."""
    return Sequential(
        [
            Conv2D(6, kernel=5), ReLU(), MaxPool(2),
            Conv2D(16, kernel=5), ReLU(), MaxPool(2),
            Flatten(),
            Dense(120), ReLU(),
            Dense(84), ReLU(),
            Dense(classes),
        ],
        input_shape=(1, n_size, n_size, c_in),
        name="lenet5",
    ).build()


def tiny_vgg(n_size: int = 32, c_in: int = 3, classes: int = 10) -> DFG:
    """A VGG-flavoured double-conv pyramid: 16·16/pool → 32·32/pool →
    flatten → 64 → ``classes``."""
    return Sequential(
        [
            Conv2D(16), ReLU(), Conv2D(16), ReLU(), MaxPool(2),
            Conv2D(32), ReLU(), Conv2D(32), ReLU(), MaxPool(2),
            Flatten(),
            Dense(64), ReLU(),
            Dense(classes),
        ],
        input_shape=(1, n_size, n_size, c_in),
        name=f"tiny_vgg_{n_size}",
    ).build()


def edge_residual(n_size: int = 32, c: int = 16, classes: int = 10) -> DFG:
    """Residual edge model: stem conv → two residual blocks → avg-pool →
    flatten → dense head (the diamond FIFO sizing meets the classifier
    head)."""
    block = lambda: Residual([Conv2D(c), ReLU(), Conv2D(c)])  # noqa: E731
    return Sequential(
        [
            Conv2D(c), ReLU(),
            block(), ReLU(),
            block(), ReLU(),
            AvgPool(2),
            Flatten(),
            Dense(classes),
        ],
        input_shape=(1, n_size, n_size, 3),
        name=f"edge_residual_{n_size}",
    ).build()


def resnet_mini(n_size: int = 16, c: int = 8, classes: int = 10) -> DFG:
    """ResNet-18-flavoured strided trunk: stem → (stride-2 downsample
    conv → identity residual block) ×2 → global average pool → dense
    head.  Each downsample halves the map and doubles the channels; the
    global pool is an AvgPool whose window is the whole remaining map
    (the DIV exit path, floor division in the integer regime)."""
    block = lambda ch: Residual([Conv2D(ch), ReLU(), Conv2D(ch)])  # noqa: E731
    return Sequential(
        [
            Conv2D(c), ReLU(),
            Conv2D(2 * c, stride=2), ReLU(),
            block(2 * c), ReLU(),
            Conv2D(4 * c, stride=2), ReLU(),
            block(4 * c), ReLU(),
            AvgPool(n_size // 4),
            Flatten(),
            Dense(classes),
        ],
        input_shape=(1, n_size, n_size, 3),
        name=f"resnet_mini_{n_size}",
    ).build()


#: MobileNetV2's inverted-residual stages (Sandler et al. 2018, Table 2):
#: expansion t, output channels c, repeats n, first stride s
MOBILENET_V2_STAGES = (
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
    (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1),
)


def _divisible(v: float, by: int = 8) -> int:
    """Channels scaled by a width multiplier, rounded to a multiple of
    ``by`` and never more than 10% below ``v`` (the reference
    implementations' rule)."""
    out = max(by, int(v + by / 2) // by * by)
    return out + by if out < 0.9 * v else out


def mobilenet_v2(n_size: int = 224, width_mult: float = 1.0,
                 classes: int = 1000) -> DFG:
    """MobileNetV2 in inference form, BatchNorm folded: each conv is
    followed by a per-channel bias add, then ReLU6 except after the
    linear bottleneck projections.  A 3×3 stride-2 stem, the 17
    inverted-residual blocks of :data:`MOBILENET_V2_STAGES` (1×1
    expansion, 3×3 depthwise conv, 1×1 projection; a residual add where
    the stride is 1 and the channels match), a 1×1 conv to 1280, a
    global average pool and the dense classifier.  Padding is SAME.
    Channels scale with ``width_mult`` (the 1280 only above 1.0).
    Not in :data:`ZOO`: at 224² it is a benchmark configuration
    (``bench/configs/mobilenetv2.json`` is its card), not a smoke
    model."""
    relu6 = PayloadKind.RELU6
    default = (n_size, width_mult, classes) == (224, 1.0, 1000)
    g = Graph("mobilenetv2" if default
              else f"mobilenetv2_{n_size}_{width_mult:g}_{classes}")

    def biased(h):
        return g.add(h, g.constant((h.shape[-1],)))

    def conv(h, filters, kernel=1, stride=1, act=True):
        h = biased(g.conv2d(h, filters, kernel, stride))
        return g.activation(h, relu6, "relu6") if act else h

    h = conv(g.input((1, n_size, n_size, 3)), _divisible(32 * width_mult),
             kernel=3, stride=2)
    for t, c, n, s in MOBILENET_V2_STAGES:
        out_c = _divisible(c * width_mult)
        for i in range(n):
            stride = s if i == 0 else 1
            skip = h
            if t != 1:
                h = conv(h, skip.shape[-1] * t)
            h = biased(g.depthwise_conv2d(h, 3, stride))
            h = g.activation(h, relu6, "relu6")
            h = conv(h, out_c, act=False)
            if stride == 1 and skip.shape[-1] == out_c:
                h = g.add(h, skip)
    h = conv(h, _divisible(1280 * max(1.0, width_mult)))
    h = g.flatten(g.avg_pool(h, h.shape[1]))
    g.output(biased(g.dense(h, classes)))
    return g.build()


#: the registry the CLI (`python -m repro zoo`), the benchmark suite,
#: and the tests iterate — names match each graph's DFG name
ZOO: dict[str, object] = {
    "lenet5": lenet5,
    "tiny_vgg_32": tiny_vgg,
    "edge_residual_32": edge_residual,
    "resnet_mini_16": resnet_mini,
}


def card(name: str) -> dict:
    """The model card for a zoo entry (weightless — the run path's
    deterministic random init stands in for training)."""
    if name not in ZOO:
        raise KeyError(f"unknown zoo model {name!r} — one of {sorted(ZOO)}")
    return export_card(ZOO[name]())


def card_json(name: str) -> str:
    return json.dumps(card(name), indent=2) + "\n"
