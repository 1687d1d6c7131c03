"""What the benchmark reads from a model card that is a graph.

``bench/model.py`` reads cards that are chains.  This module reads any
card whose layers name their inputs, so a layer may read a value made
long before it (a residual add):

* ``conv2d`` (HWIO weight) and ``depthwise_conv2d`` (``(k, k, C)``
  weight, one filter per channel), SAME or VALID, stride;
* ``relu``, and ``activation`` of kind ``relu6`` (a clamp to [0, 6]);
* ``max_pool`` and ``avg_pool`` (VALID windows);
* ``flatten`` (over H, W, C in the card's order) and ``dense``
  (``(d_in, units)`` weight);
* ``add`` of two values of one shape, or of a rank-1 ``constant`` with
  one value per channel (a bias);
* ``constant`` (a bias: a card's only bare constants).

Every float card of the benchmark (``vgg16``, ``mobilenetv2``) reads
here.  Shapes carry no per-sample axis.  From the card alone it gives
each layer's shapes and multiply-accumulates, the FLOPs and bytes of
the depthwise convs (the roofline of their kernel), the parameter
shapes, and the seeded weights and biases, made on the device in one
jitted call and handed over as host arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import model

SUPPORTED = ("conv2d", "depthwise_conv2d", "relu", "activation",
             "max_pool", "avg_pool", "flatten", "dense", "add", "constant")
#: the activation kinds a card may name
ACTIVATIONS = ("relu6",)
#: fills of a card's bare constants (biases): normal, standard deviation
BIAS_FILLS = {"normal_0.1": 0.1}


@dataclasses.dataclass(frozen=True)
class Layer:
    """One card layer with its per-sample shapes (no leading axis)."""

    op: str
    name: str
    inputs: tuple            # names of the values it reads
    out: str
    in_shape: tuple          # shape of the first input
    out_shape: tuple
    weight: str | None = None
    weight_shape: tuple | None = None
    kernel: int = 1
    stride: int = 1
    padding: str = "SAME"
    kind: str | None = None  # activation kind
    order: tuple = (1, 2, 3)  # flatten: axes in linearization order

    @property
    def macs(self) -> int:
        """Multiply-accumulates per sample (0 for layers without any)."""
        if self.op == "conv2d":
            h, w, cout = self.out_shape
            return h * w * cout * self.kernel ** 2 * self.in_shape[-1]
        if self.op == "depthwise_conv2d":
            h, w, c = self.out_shape
            return h * w * c * self.kernel ** 2
        if self.op == "dense":
            return self.in_shape[0] * self.out_shape[0]
        return 0


def _window_out(x: tuple, k: int, s: int, pad: str) -> tuple:
    h, w = x[:2]
    if pad == "VALID":
        return (h - k) // s + 1, (w - k) // s + 1
    return -(-h // s), -(-w // s)


def layers(card: dict) -> list[Layer]:
    """The card's layers in order, shapes inferred; one input, ops in
    :data:`SUPPORTED`."""
    (inp,) = card["inputs"]
    shape = {inp["name"]: tuple(inp["shape"][1:])}
    out = []
    for rec in card["layers"]:
        op = rec["op"]
        if op not in SUPPORTED:
            raise NotImplementedError(
                f"{card['name']}: layer {rec.get('name')!r} is a {op!r}; the "
                f"benchmark's graph reader covers {SUPPORTED}")
        if op == "constant":
            shape[rec["name"]] = tuple(rec["shape"])
            out.append(Layer(op, rec["name"], (), rec["name"], (),
                             tuple(rec["shape"])))
            continue
        names = (rec["a"], rec["b"]) if op == "add" else (rec["input"],)
        x = shape[names[0]]
        kw = {}
        if op in ("conv2d", "depthwise_conv2d"):
            k, s = rec.get("kernel", 3), rec.get("stride", 1)
            pad = rec.get("padding", "SAME")
            c = x[-1]
            if op == "conv2d":
                y = _window_out(x, k, s, pad) + (rec["filters"],)
                wshape = (k, k, c, rec["filters"])
            else:
                y = _window_out(x, k, s, pad) + (c,)
                wshape = (k, k, c)
            kw = dict(weight=rec["weight"], weight_shape=wshape,
                      kernel=k, stride=s, padding=pad)
        elif op in ("max_pool", "avg_pool"):
            k = rec.get("window", 2)
            s = rec.get("stride") or k
            y = _window_out(x, k, s, "VALID") + (x[-1],)
            kw = dict(kernel=k, stride=s, padding="VALID")
        elif op == "flatten":
            y = (int(np.prod(x)),)
            kw = dict(order=tuple(rec.get("order") or range(1, len(x) + 1)))
        elif op == "dense":
            y = (rec["units"],)
            kw = dict(weight=rec["weight"], weight_shape=(x[0], rec["units"]))
        elif op == "activation":
            if rec.get("kind") not in ACTIVATIONS:
                raise NotImplementedError(
                    f"{card['name']}: activation {rec.get('kind')!r}; the "
                    f"graph reader covers {ACTIVATIONS}")
            y, kw = x, dict(kind=rec["kind"])
        elif op == "add":
            b = shape[names[1]]
            if b != x and b != (x[-1],):
                raise ValueError(f"{card['name']}: add {rec['name']!r} of "
                                 f"{x} and {b}")
            y = x
        else:  # relu
            y = x
        shape[rec["out"]] = y
        out.append(Layer(op, rec["name"], names, rec["out"], x, y, **kw))
    return out


def weight_shapes(card: dict) -> dict[str, tuple]:
    """The weights of the card's conv, depthwise and dense layers."""
    return {lay.weight: lay.weight_shape for lay in layers(card)
            if lay.weight is not None}


def bias_shapes(card: dict) -> dict[str, tuple]:
    """The card's bare constants: its biases."""
    return {lay.name: lay.out_shape for lay in layers(card)
            if lay.op == "constant"}


def param_count(card: dict) -> int:
    """Weights and biases, all of them."""
    shapes = {**weight_shapes(card), **bias_shapes(card)}
    return sum(int(np.prod(s)) for s in shapes.values())


def model_flops_per_sample(card: dict) -> int:
    """2 × the multiply-accumulates of every conv and dense layer."""
    return 2 * sum(lay.macs for lay in layers(card))


def _biases_of(lays: list[Layer]) -> dict[str, Layer]:
    """The bias constant added to each value, by the value's name."""
    consts = {lay.name: lay for lay in lays if lay.op == "constant"}
    return {lay.inputs[0]: consts[lay.inputs[1]] for lay in lays
            if lay.op == "add" and lay.inputs[1] in consts}


def dwconv_work(card: dict, itemsize: int, batch: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the depthwise convs for one call at ``batch``:
    input and output activations once per sample, the weights and the
    bias that the conv's output takes once per call."""
    lays = layers(card)
    bias = _biases_of(lays)
    flops = nbytes = 0
    for lay in lays:
        if lay.op != "depthwise_conv2d":
            continue
        flops += 2 * lay.macs * batch
        act = np.prod(lay.in_shape) + np.prod(lay.out_shape)
        const = np.prod(lay.weight_shape)
        if lay.out in bias:
            const += np.prod(bias[lay.out].out_shape)
        nbytes += int(act * batch + const) * itemsize
    return flops, nbytes


def conv_bytes_per_sample(card: dict, itemsize: int) -> dict[str, int]:
    """Bytes a sample moves through each conv kind, with its weights
    (``conv2d``: 3×3 and 1×1 alike; ``depthwise_conv2d``)."""
    out = {"conv2d": 0, "depthwise_conv2d": 0}
    for lay in layers(card):
        if lay.op in out:
            act = np.prod(lay.in_shape) + np.prod(lay.out_shape)
            out[lay.op] += int(act + np.prod(lay.weight_shape)) * itemsize
    return out


def make_params(card: dict, seed: int) -> dict[str, np.ndarray]:
    """Every weight (``weight_fill``, He-normal over the fan-in: every
    axis but the last) and every bias (``bias_fill``, a key of
    :data:`BIAS_FILLS`) of the card, drawn on the device in one jitted
    call from ``seed``, returned as host arrays."""
    import jax
    import jax.numpy as jnp

    weights = sorted(weight_shapes(card).items())
    biases = sorted(bias_shapes(card).items())
    std = BIAS_FILLS[card["bias_fill"]]

    @jax.jit
    def gen(key):
        kw, kb = jax.random.split(key)
        out = {n: model._fill(k, s, card["weight_fill"]) for k, (n, s) in
               zip(jax.random.split(kw, len(weights)), weights)}
        out.update({n: jax.random.normal(k, s, jnp.float32) * np.float32(std)
                    for k, (n, s) in
                    zip(jax.random.split(kb, max(len(biases), 1)), biases)})
        return out

    out = gen(model.seed_key(seed, 0))
    return {n: np.asarray(v) for n, v in out.items()}
