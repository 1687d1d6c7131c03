"""The plain reference of a graph card: its forward pass, written out.

It imports nothing of the program and takes nothing the program made:
only the card (read by ``bench/dag.py``), the benchmark's seeded
weights and biases, and the inputs.  ``jax.numpy`` in float32, every
conv and matmul at ``Precision.HIGHEST``, run on the device a block of
samples at a time.  The depthwise conv is ``lax.conv_general_dilated``
with ``feature_group_count`` equal to the channel count.

The control (``control=``, a key of ``bench.reference.CONTROLS``)
rounds every conv, depthwise and dense operand, inputs and weights
alike, to the precision below the one the configuration states; biases
and the values added to them stay as they are.
"""
from __future__ import annotations

import functools

import numpy as np

from bench import dag, reference


def _forward(card, operand, params, x):
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    op = operand or (lambda v: v)
    (inp,) = card["inputs"]
    env = {inp["name"]: x}
    for lay in dag.layers(card):
        if lay.op == "constant":
            env[lay.out] = params[lay.name]
            continue
        v = env[lay.inputs[0]]
        if lay.op in ("conv2d", "depthwise_conv2d"):
            w = op(params[lay.weight])
            groups = 1
            if lay.op == "depthwise_conv2d":
                groups = w.shape[-1]
                w = w.reshape(w.shape[:2] + (1, groups))
            v = lax.conv_general_dilated(
                op(v), w, (lay.stride, lay.stride), lay.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups, precision=hi)
        elif lay.op == "dense":
            v = jnp.dot(op(v), op(params[lay.weight]), precision=hi)
        elif lay.op == "relu":
            v = jnp.maximum(v, 0)
        elif lay.op == "activation":  # relu6
            v = jnp.clip(v, 0, 6)
        elif lay.op == "add":
            v = v + env[lay.inputs[1]]
        elif lay.op in ("max_pool", "avg_pool"):
            k, s = lay.kernel, lay.stride
            if lay.op == "max_pool":
                v = lax.reduce_window(v, -jnp.inf, lax.max, (1, k, k, 1),
                                      (1, s, s, 1), "VALID")
            else:
                v = lax.reduce_window(v, 0.0, lax.add, (1, k, k, 1),
                                      (1, s, s, 1), "VALID") / (k * k)
        else:  # flatten
            v = v.transpose((0,) + lay.order).reshape(v.shape[0], -1)
        env[lay.out] = v
    (out,) = card["outputs"]
    return env[out]


def forward(card: dict, params: dict, xs: np.ndarray, *,
            control: str | None = None, block: int = 32) -> np.ndarray:
    """Logits ``(n, classes)`` for requests ``xs`` of shape
    ``(n,) + input_shape``, ``block`` samples per device call; with
    ``control`` the control's."""
    import jax
    import jax.numpy as jnp

    operand = reference.CONTROLS[control] if control else None
    fn = jax.jit(functools.partial(_forward, card, operand))
    dev = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    out = []
    for i in range(0, len(xs), block):
        xb = xs[i:i + block]
        xb = jnp.asarray(xb.reshape((len(xb),) + xb.shape[2:]), jnp.float32)
        out.append(np.asarray(fn(dev, xb)))
    return np.concatenate(out).reshape(len(xs), -1)
