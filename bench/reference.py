"""The plain reference: a model card's forward pass, written out.

It imports nothing of the program and takes nothing the program made:
only the card, the benchmark's seeded weights and the inputs.

* Float cards: ``jax.numpy`` in float32 with every conv and matmul at
  ``Precision.HIGHEST``, run on the device a block of samples at a time.
* Integer cards: NumPy.  Every conv and dense layer accumulates in
  int32 with wraparound, as the int8 datapath states: the exact sum is
  formed in float64 (every partial sum of these layers stays below
  2**53, so it is exact) and wrapped to int32.  ReLU and max pool then
  act on the wrapped int32 values.

The control (``operand=``) rounds every conv and dense operand, inputs
and weights alike, to the precision below the one the configuration
states (:data:`CONTROLS`).
"""
from __future__ import annotations

import functools

import numpy as np

from bench import model


def fp8_operands(x):
    """float8_e4m3fn with one scale per tensor (its largest magnitude
    maps to 448), back to float32: what an fp8 matmul path feeds the
    MXU."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def int4_operands(x: np.ndarray) -> np.ndarray:
    """int8 operands cut to their top 4 bits (int4 at scale 16); wider
    integers (int32 activations) pass unchanged."""
    if x.dtype.itemsize != 1:
        return x
    return (np.clip(np.round(x / 16.0), -8, 7) * 16).astype(np.int16)


#: the control of each stated precision: the next precision below it
CONTROLS = {"fp8": fp8_operands, "int4": int4_operands}


# -- float path ----------------------------------------------------------------


def _float_forward(card, operand, params, x):
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    op = operand or (lambda v: v)
    for lay in model.layers(card):
        if lay.op == "conv2d":
            x = lax.conv_general_dilated(
                op(x), op(params[lay.weight]), (lay.stride, lay.stride),
                lay.padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=hi)
        elif lay.op == "dense":
            x = jnp.dot(op(x), op(params[lay.weight]), precision=hi)
        elif lay.op == "relu":
            x = jnp.maximum(x, 0)
        elif lay.op == "max_pool":
            k, s = lay.kernel, lay.stride
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                                  (1, s, s, 1), "VALID")
        else:  # flatten
            x = x.transpose((0,) + lay.order).reshape(x.shape[0], -1)
    return x


def forward_float(card: dict, params: dict, xs: np.ndarray, *,
                  operand=None, block: int = 32) -> np.ndarray:
    """Logits ``(n, classes)`` for requests ``xs`` of shape
    ``(n,) + input_shape``, ``block`` samples per device call."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(_float_forward, card, operand))
    dev = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    out = []
    for i in range(0, len(xs), block):
        xb = xs[i:i + block]
        xb = jnp.asarray(xb.reshape((len(xb),) + xb.shape[2:]), jnp.float32)
        out.append(np.asarray(fn(dev, xb)))
    return np.concatenate(out).reshape(len(xs), -1)


# -- integer path --------------------------------------------------------------


def _wrap32(exact: np.ndarray) -> np.ndarray:
    return np.rint(exact).astype(np.int64).astype(np.int32)


def _conv_int(x, w, lay):
    n, h, wd, c = x.shape
    k, s = lay.kernel, lay.stride
    ho, wo = lay.out_shape[:2]
    if lay.padding == "SAME":
        ph = max((ho - 1) * s + k - h, 0)
        pw = max((wo - 1) * s + k - wd, 0)
        x = np.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                       (pw // 2, pw - pw // 2), (0, 0)))
    cols = np.empty((n, ho, wo, k, k, c), np.float64)
    for dy in range(k):
        for dx in range(k):
            cols[:, :, :, dy, dx] = x[:, dy:dy + (ho - 1) * s + 1:s,
                                      dx:dx + (wo - 1) * s + 1:s]
    flat = cols.reshape(n * ho * wo, k * k * c)
    out = flat @ w.reshape(k * k * c, -1).astype(np.float64)
    return _wrap32(out.reshape(n, ho, wo, -1))


def _int_forward(card, params, x, operand):
    op = operand or (lambda v: v)
    for lay in model.layers(card):
        if lay.op == "conv2d":
            x = _conv_int(op(x), op(params[lay.weight]), lay)
        elif lay.op == "dense":
            w = op(params[lay.weight]).astype(np.float64)
            x = _wrap32(op(x).astype(np.float64) @ w)
        elif lay.op == "relu":
            x = np.maximum(x, 0)
        elif lay.op == "max_pool":
            k, s = lay.kernel, lay.stride
            ho, wo = lay.out_shape[:2]
            x = np.max(np.stack([
                x[:, dy:dy + (ho - 1) * s + 1:s, dx:dx + (wo - 1) * s + 1:s]
                for dy in range(k) for dx in range(k)]), axis=0)
        else:
            x = x.transpose((0,) + lay.order).reshape(x.shape[0], -1)
    return x


def forward_int(card: dict, params: dict, xs: np.ndarray, *,
                operand=None, block: int = 512) -> np.ndarray:
    """int32 logits ``(n, classes)`` for integer requests ``xs``."""
    out = [_int_forward(card, params,
                        xs[i:i + block].reshape((-1,) + xs.shape[2:]),
                        operand)
           for i in range(0, len(xs), block)]
    return np.concatenate(out).reshape(len(xs), -1)


def forward(card: dict, params: dict, xs: np.ndarray, *,
            control: str | None = None) -> np.ndarray:
    """The reference's logits for ``xs``; with ``control`` (a key of
    :data:`CONTROLS`) the control's."""
    operand = CONTROLS[control] if control else None
    if np.issubdtype(xs.dtype, np.integer):
        return forward_int(card, params, xs, operand=operand)
    return forward_float(card, params, xs, operand=operand)


def rel_l2_per_sample(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """‖got − want‖ / ‖want‖ of each row, in float64; NaN reads +inf."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64)
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    return np.where(np.isnan(err), np.inf, err)
