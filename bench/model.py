"""What the benchmark reads from a configuration's model card.

A model card (``bench/configs/<config>.json``) lists layers in order:
``conv2d`` (HWIO weight, SAME or VALID, stride), ``relu``,
``max_pool`` (VALID window), ``flatten`` (over H, W, C in the card's order) and
``dense`` (``(d_in, units)`` weight).  The card's shapes carry a
leading per-sample axis of 1; batches add one more axis in front.

From the card alone this module gives each layer's shapes and
multiply-accumulates (the FLOP and byte functions of the benchmark),
the weight shapes, and the seeded weights and inputs, which are made on
the device in one jitted call each and handed over as host arrays, the
way an importer returns them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SUPPORTED = ("conv2d", "relu", "max_pool", "flatten", "dense")


@dataclasses.dataclass(frozen=True)
class Layer:
    """One card layer with its per-sample shapes (no leading axis)."""

    op: str
    name: str
    in_shape: tuple
    out_shape: tuple
    weight: str | None = None
    weight_shape: tuple | None = None
    kernel: int = 1
    stride: int = 1
    padding: str = "SAME"
    order: tuple = (1, 2, 3)     # flatten: axes in linearization order

    @property
    def macs(self) -> int:
        """Multiply-accumulates per sample (0 for layers without any)."""
        if self.op == "conv2d":
            h, w, cout = self.out_shape
            return h * w * cout * self.kernel * self.kernel * self.in_shape[-1]
        if self.op == "dense":
            return self.in_shape[0] * self.out_shape[0]
        return 0


def layers(card: dict) -> list[Layer]:
    """The card's layers in order, shapes inferred; single input and
    output, ops in :data:`SUPPORTED`."""
    (inp,) = card["inputs"]
    shape = {inp["name"]: tuple(inp["shape"][1:])}
    out = []
    for rec in card["layers"]:
        op = rec["op"]
        if op not in SUPPORTED:
            raise NotImplementedError(
                f"{card['name']}: layer {rec.get('name')!r} is a {op!r}; the "
                f"benchmark's reference covers {SUPPORTED}")
        x = shape[rec["input"]]
        kw = {}
        if op == "conv2d":
            k, s = rec.get("kernel", 3), rec.get("stride", 1)
            pad = rec.get("padding", "SAME")
            h, w, cin = x
            if pad == "VALID":
                ho, wo = (h - k) // s + 1, (w - k) // s + 1
            else:
                ho, wo = -(-h // s), -(-w // s)
            y = (ho, wo, rec["filters"])
            kw = dict(weight=rec["weight"], weight_shape=(k, k, cin,
                                                          rec["filters"]),
                      kernel=k, stride=s, padding=pad)
        elif op == "max_pool":
            k = rec.get("window", 2)
            s = rec.get("stride") or k
            h, w, c = x
            y = ((h - k) // s + 1, (w - k) // s + 1, c)
            kw = dict(kernel=k, stride=s, padding="VALID")
        elif op == "flatten":
            y = (int(np.prod(x)),)
            kw = dict(order=tuple(rec.get("order") or range(1, len(x) + 1)))
        elif op == "dense":
            y = (rec["units"],)
            kw = dict(weight=rec["weight"], weight_shape=(x[0], rec["units"]))
        else:  # relu
            y = x
        shape[rec["out"]] = y
        out.append(Layer(op, rec["name"], x, y, **kw))
    return out


def input_shape(card: dict) -> tuple:
    """Per-request input shape, leading axis of 1 included."""
    (inp,) = card["inputs"]
    return tuple(inp["shape"])


def weight_shapes(card: dict) -> dict[str, tuple]:
    return {lay.weight: lay.weight_shape for lay in layers(card)
            if lay.weight is not None}


def weight_count(card: dict) -> int:
    return sum(int(np.prod(s)) for s in weight_shapes(card).values())


def model_flops_per_sample(card: dict) -> int:
    """2 × the multiply-accumulates of every conv and dense layer."""
    return 2 * sum(lay.macs for lay in layers(card))


def conv_work(card: dict, itemsize: int, batch: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the conv layers for one call at ``batch``: the
    weights are read once per call, activations once per sample."""
    flops = nbytes = 0
    for lay in layers(card):
        if lay.op != "conv2d":
            continue
        flops += 2 * lay.macs * batch
        act = np.prod(lay.in_shape) + np.prod(lay.out_shape)
        nbytes += int(act * batch + np.prod(lay.weight_shape)) * itemsize
    return flops, nbytes


def seed_key(seed: int, stream: int):
    """A threefry key from any whole number ``seed`` (wider than 32
    bits too) and a stream number, so weights and inputs differ."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed) & (2**64 - 1), stream]
                                   ).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words),
                                    impl="threefry2x32")


def _fill(key, shape, fill: str):
    import jax
    import jax.numpy as jnp

    if fill == "he_normal":  # fan-in: all axes but the last
        fan_in = int(np.prod(shape[:-1]))
        return (jax.random.normal(key, shape, jnp.float32)
                * np.float32(np.sqrt(2.0 / fan_in)))
    if fill == "standard_normal":
        return jax.random.normal(key, shape, jnp.float32)
    if fill == "int8_uniform":
        return jax.random.randint(key, shape, -128, 128,
                                  jnp.int32).astype(jnp.int8)
    raise ValueError(f"unknown fill {fill!r}")


def make_weights(card: dict, fill: str, seed: int) -> dict[str, np.ndarray]:
    """Every weight of the card, drawn on the device in one jitted call
    from ``seed``, returned as host arrays."""
    import jax

    shapes = sorted(weight_shapes(card).items())

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(shapes))
        return {n: _fill(k, s, fill) for k, (n, s) in zip(keys, shapes)}

    out = gen(seed_key(seed, 0))
    return {n: np.asarray(v) for n, v in out.items()}


def make_inputs(card: dict, fill: str, n: int, seed: int) -> np.ndarray:
    """``n`` requests of the card's input shape, drawn on the device
    from ``seed``, as one host array ``(n,) + input_shape``."""
    import jax

    shape = (n,) + input_shape(card)
    gen = jax.jit(lambda key: _fill(key, shape, fill))
    return np.asarray(gen(seed_key(seed, 1)))
