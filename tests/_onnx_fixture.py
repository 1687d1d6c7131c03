"""Minimal protobuf *encoder* for ONNX test fixtures.

The mirror image of the vendored decoder in
``repro.frontends.onnx_reader``: enough of the ModelProto wire format to
synthesize small CNN checkpoints in-memory, so the reader's no-``onnx``
path is exercised against real bytes (and so ``tests/golden/lenet5.onnx``
can be regenerated deterministically — run this module as a script).

Encoder and decoder are developed against the same field tables but
share no code, which is the point: a decoder bug cannot cancel out in
the round trip.
"""
from __future__ import annotations

import struct

import numpy as np

# TensorProto.DataType
FLOAT, UINT8, INT8, INT32, INT64 = 1, 2, 3, 6, 7

_NP_CODES = {
    np.dtype(np.float32): FLOAT,
    np.dtype(np.uint8): UINT8,
    np.dtype(np.int8): INT8,
    np.dtype(np.int32): INT32,
    np.dtype(np.int64): INT64,
}

# AttributeProto.AttributeType
_AT_FLOAT, _AT_INT, _AT_STRING, _AT_INTS = 1, 2, 3, 7


def _varint(v: int) -> bytes:
    if v < 0:
        v += 1 << 64
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(fno: int, wt: int) -> bytes:
    return _varint((fno << 3) | wt)


def _int_field(fno: int, v: int) -> bytes:
    return _tag(fno, 0) + _varint(v)


def _bytes_field(fno: int, payload: bytes) -> bytes:
    return _tag(fno, 2) + _varint(len(payload)) + payload


def _str_field(fno: int, s: str) -> bytes:
    return _bytes_field(fno, s.encode())


def _float_field(fno: int, f: float) -> bytes:
    return _tag(fno, 5) + struct.pack("<f", f)


def tensor(name: str, arr: np.ndarray) -> bytes:
    """TensorProto with raw_data."""
    a = np.ascontiguousarray(arr)
    code = _NP_CODES[a.dtype]
    out = b"".join(_int_field(1, int(d)) for d in a.shape)
    out += _int_field(2, code)
    out += _str_field(8, name)
    out += _bytes_field(9, a.tobytes())
    return out


def value_info(name: str, shape, elem_type: int = INT8,
               symbolic: str | None = None) -> bytes:
    dims = b""
    for d in shape:
        dims += _bytes_field(1, _int_field(1, int(d)))
    if symbolic is not None:
        dims += _bytes_field(1, _str_field(2, symbolic))
    shape_msg = _bytes_field(2, dims)
    tensor_type = _bytes_field(1, _int_field(1, elem_type) + shape_msg)
    return _str_field(1, name) + _bytes_field(2, tensor_type)


def attr_int(name: str, v: int) -> bytes:
    return (_str_field(1, name) + _int_field(3, v)
            + _int_field(20, _AT_INT))


def attr_ints(name: str, vals) -> bytes:
    out = _str_field(1, name)
    for v in vals:
        out += _int_field(8, int(v))
    return out + _int_field(20, _AT_INTS)


def attr_float(name: str, f: float) -> bytes:
    return (_str_field(1, name) + _float_field(2, f)
            + _int_field(20, _AT_FLOAT))


def attr_string(name: str, s: str) -> bytes:
    return (_str_field(1, name) + _bytes_field(4, s.encode())
            + _int_field(20, _AT_STRING))


def node(op_type: str, inputs, outputs, name: str = "",
         attrs=()) -> bytes:
    out = b"".join(_str_field(1, i) for i in inputs)
    out += b"".join(_str_field(2, o) for o in outputs)
    out += _str_field(3, name)
    out += _str_field(4, op_type)
    out += b"".join(_bytes_field(5, a) for a in attrs)
    return out


def graph(name: str, nodes, initializers, inputs, outputs) -> bytes:
    out = b"".join(_bytes_field(1, n) for n in nodes)
    out += _str_field(2, name)
    out += b"".join(_bytes_field(5, t) for t in initializers)
    out += b"".join(_bytes_field(11, vi) for vi in inputs)
    out += b"".join(_bytes_field(12, vi) for vi in outputs)
    return out


def model(graph_bytes: bytes, ir_version: int = 8,
          opset: int = 13) -> bytes:
    opset_import = _str_field(1, "") + _int_field(2, opset)
    return (
        _int_field(1, ir_version)
        + _str_field(2, "ming-repro-fixture")
        + _bytes_field(7, graph_bytes)
        + _bytes_field(8, opset_import)
    )


# ---------------------------------------------------------------------------
# The LeNet-5 fixture (int8 weights, int32 biases — integer-exact)
# ---------------------------------------------------------------------------


def lenet5_weights(seed: int = 0) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)

    def w8(*shape):
        return rng.integers(-4, 5, shape).astype(np.int8)

    def b32(n):
        return rng.integers(-8, 9, (n,)).astype(np.int32)

    return {
        "conv1_w": w8(6, 1, 5, 5), "conv1_b": b32(6),
        "conv2_w": w8(16, 6, 5, 5), "conv2_b": b32(16),
        "fc1_w": w8(120, 1024), "fc1_b": b32(120),
        "fc2_w": w8(84, 120), "fc2_b": b32(84),
        "fc3_w": w8(10, 84), "fc3_b": b32(10),
    }


def lenet5_model_bytes(seed: int = 0) -> bytes:
    """LeNet-5 (SAME-padding variant) as NCHW ONNX bytes: the golden
    fixture ``tests/golden/lenet5.onnx`` is exactly this with seed 0."""
    w = lenet5_weights(seed)
    conv_attrs = lambda k: (attr_ints("kernel_shape", [k, k]),  # noqa: E731
                            attr_ints("strides", [1, 1]),
                            attr_ints("pads", [(k - 1) // 2] * 4))
    pool_attrs = (attr_ints("kernel_shape", [2, 2]),
                  attr_ints("strides", [2, 2]))
    gemm_attrs = (attr_int("transB", 1), attr_float("alpha", 1.0),
                  attr_float("beta", 1.0))
    nodes = [
        node("Conv", ["input", "conv1_w", "conv1_b"], ["c1"], "conv1",
             conv_attrs(5)),
        node("Relu", ["c1"], ["r1"], "relu1"),
        node("MaxPool", ["r1"], ["p1"], "pool1", pool_attrs),
        node("Conv", ["p1", "conv2_w", "conv2_b"], ["c2"], "conv2",
             conv_attrs(5)),
        node("Relu", ["c2"], ["r2"], "relu2"),
        node("MaxPool", ["r2"], ["p2"], "pool2", pool_attrs),
        node("Flatten", ["p2"], ["flat"], "flatten", (attr_int("axis", 1),)),
        node("Gemm", ["flat", "fc1_w", "fc1_b"], ["f1"], "fc1", gemm_attrs),
        node("Relu", ["f1"], ["fr1"], "relu3"),
        node("Gemm", ["fr1", "fc2_w", "fc2_b"], ["f2"], "fc2", gemm_attrs),
        node("Relu", ["f2"], ["fr2"], "relu4"),
        node("Gemm", ["fr2", "fc3_w", "fc3_b"], ["logits"], "fc3",
             gemm_attrs),
    ]
    g = graph(
        "lenet5",
        nodes,
        [tensor(k, v) for k, v in w.items()],
        [value_info("input", (1, 1, 32, 32), INT8)],
        [value_info("logits", (1, 10), INT32)],
    )
    return model(g)


# ---------------------------------------------------------------------------
# NumPy NCHW oracle (independent of the repo's executors)
# ---------------------------------------------------------------------------


def lenet5_numpy(x: np.ndarray, w: dict[str, np.ndarray]) -> np.ndarray:
    """Reference forward pass on NCHW int inputs, int64 accumulation."""
    from numpy.lib.stride_tricks import sliding_window_view

    def conv(x, wgt, b):  # x (1,C,H,W), wgt (O,C,k,k)
        k = wgt.shape[2]
        p = (k - 1) // 2
        xp = np.pad(x, ((0, 0), (0, 0), (p, p), (p, p)))
        win = sliding_window_view(xp, (k, k), axis=(2, 3))
        out = np.einsum("nchwij,ocij->nohw", win.astype(np.int64),
                        wgt.astype(np.int64))
        return out + b[None, :, None, None]

    def pool(x):
        n, c, h, wdt = x.shape
        return x.reshape(n, c, h // 2, 2, wdt // 2, 2).max(axis=(3, 5))

    relu = lambda v: np.maximum(v, 0)  # noqa: E731
    h = relu(conv(x, w["conv1_w"], w["conv1_b"]))
    h = pool(h)
    h = relu(conv(h, w["conv2_w"], w["conv2_b"]))
    h = pool(h)
    h = h.reshape(1, -1)
    h = relu(h @ w["fc1_w"].T.astype(np.int64) + w["fc1_b"])
    h = relu(h @ w["fc2_w"].T.astype(np.int64) + w["fc2_b"])
    return h @ w["fc3_w"].T.astype(np.int64) + w["fc3_b"]


# ---------------------------------------------------------------------------
# The strided ResNet-style fixture (ISSUE 8): stride-2 downsample convs
# under three padding spellings (auto_pad SAME_UPPER, explicit
# SAME-frame pads, auto_pad VALID with an even kernel), inference-mode
# BatchNormalization after the first two convs, and a
# GlobalAveragePool head.  BN statistics are float32 but integral with
# var=1 and epsilon=0, so the importer's conv fold is integer-exact.
# ---------------------------------------------------------------------------


def same4(n: int, k: int, s: int) -> tuple[int, int]:
    """End-heavy (begin, end) SAME_UPPER split for one spatial axis."""
    out = -(-n // s)
    total = max(0, s * (out - 1) + k - n)
    return total // 2, total - total // 2


def resnet_tiny_weights(seed: int = 0) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)

    def w8(*shape):
        return rng.integers(-4, 5, shape).astype(np.int8)

    def b32(n):
        return rng.integers(-8, 9, (n,)).astype(np.int32)

    def bn(prefix, c):
        return {
            f"{prefix}_scale": rng.integers(1, 3, (c,)).astype(np.float32),
            f"{prefix}_B": rng.integers(-8, 9, (c,)).astype(np.float32),
            f"{prefix}_mean": rng.integers(-8, 9, (c,)).astype(np.float32),
            f"{prefix}_var": np.ones(c, np.float32),
        }

    out = {
        "c1_w": w8(8, 3, 3, 3), "c1_b": b32(8),
        "c2_w": w8(16, 8, 3, 3),
        "c3_w": w8(16, 16, 2, 2), "c3_b": b32(16),
        "fc_w": w8(10, 16), "fc_b": b32(10),
    }
    out.update(bn("bn1", 8))
    out.update(bn("bn2", 16))
    return out


def resnet_tiny_model_bytes(seed: int = 0) -> bytes:
    """The strided golden fixture ``tests/golden/resnet_tiny.onnx`` is
    exactly this with seed 0.  Topology (NCHW):

        input (1,3,16,16)
          Conv k3 s2 auto_pad=SAME_UPPER (+bias) → BN → Relu   (1,8,8,8)
          Conv k3 s2 explicit pads [0,0,1,1]     → BN → Relu   (1,16,4,4)
          Conv k2 s2 auto_pad=VALID (+bias)           → Relu   (1,16,2,2)
          GlobalAveragePool                                    (1,16,1,1)
          Flatten → Gemm(transB) (+bias)                       (1,10)
    """
    w = resnet_tiny_weights(seed)
    bn_attrs = (attr_float("epsilon", 0.0),)
    bn_ins = lambda p: [f"{p}_scale", f"{p}_B", f"{p}_mean",  # noqa: E731
                        f"{p}_var"]
    nodes = [
        node("Conv", ["input", "c1_w", "c1_b"], ["c1"], "conv1",
             (attr_ints("kernel_shape", [3, 3]),
              attr_ints("strides", [2, 2]),
              attr_string("auto_pad", "SAME_UPPER"))),
        node("BatchNormalization", ["c1"] + bn_ins("bn1"), ["n1"], "bn1",
             bn_attrs),
        node("Relu", ["n1"], ["r1"], "relu1"),
        node("Conv", ["r1", "c2_w"], ["c2"], "conv2",
             (attr_ints("kernel_shape", [3, 3]),
              attr_ints("strides", [2, 2]),
              attr_ints("pads", [0, 0, 1, 1]))),
        node("BatchNormalization", ["c2"] + bn_ins("bn2"), ["n2"], "bn2",
             bn_attrs),
        node("Relu", ["n2"], ["r2"], "relu2"),
        node("Conv", ["r2", "c3_w", "c3_b"], ["c3"], "conv3",
             (attr_ints("kernel_shape", [2, 2]),
              attr_ints("strides", [2, 2]),
              attr_string("auto_pad", "VALID"))),
        node("Relu", ["c3"], ["r3"], "relu3"),
        node("GlobalAveragePool", ["r3"], ["gap"], "gap"),
        node("Flatten", ["gap"], ["flat"], "flatten", (attr_int("axis", 1),)),
        node("Gemm", ["flat", "fc_w", "fc_b"], ["logits"], "fc",
             (attr_int("transB", 1), attr_float("alpha", 1.0),
              attr_float("beta", 1.0))),
    ]
    g = graph(
        "resnet_tiny",
        nodes,
        [tensor(k, v) for k, v in w.items()],
        [value_info("input", (1, 3, 16, 16), INT8)],
        [value_info("logits", (1, 10), INT32)],
    )
    return model(g)


def resnet_tiny_numpy(x: np.ndarray, w: dict[str, np.ndarray]) -> np.ndarray:
    """Reference forward pass on NCHW int inputs, int64 accumulation.
    BN is applied directly (not folded) — an independent check of the
    importer's fold.  GlobalAveragePool floor-divides like the DIV exit
    path."""
    from numpy.lib.stride_tricks import sliding_window_view

    def conv(x, wgt, b, stride, pads):  # pads ((t, b), (l, r))
        k = wgt.shape[2]
        (pt, pb), (pl, pr) = pads
        xp = np.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
        win = sliding_window_view(xp, (k, k), axis=(2, 3))
        win = win[:, :, ::stride, ::stride]
        out = np.einsum("nchwij,ocij->nohw", win.astype(np.int64),
                        wgt.astype(np.int64))
        return out + (0 if b is None else b[None, :, None, None])

    def bn(x, p):
        s = (w[f"{p}_scale"] / np.sqrt(w[f"{p}_var"])).astype(np.int64)
        return ((x - w[f"{p}_mean"].astype(np.int64)[None, :, None, None])
                * s[None, :, None, None]
                + w[f"{p}_B"].astype(np.int64)[None, :, None, None])

    relu = lambda v: np.maximum(v, 0)  # noqa: E731
    h = conv(x, w["c1_w"], w["c1_b"], 2, (same4(16, 3, 2), same4(16, 3, 2)))
    h = relu(bn(h, "bn1"))
    h = conv(h, w["c2_w"], None, 2, (same4(8, 3, 2), same4(8, 3, 2)))
    h = relu(bn(h, "bn2"))
    h = relu(conv(h, w["c3_w"], w["c3_b"], 2, ((0, 0), (0, 0))))
    h = h.sum(axis=(2, 3), keepdims=True) // (h.shape[2] * h.shape[3])
    h = h.reshape(1, -1)
    return h @ w["fc_w"].T.astype(np.int64) + w["fc_b"]


def mobilenet_tiny_weights(seed: int = 0) -> dict[str, np.ndarray]:
    """Float weights of :func:`mobilenet_tiny_model_bytes`."""
    rng = np.random.default_rng(seed)

    def f32(*shape, scale=0.5):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    return {
        "e_w": f32(16, 8, 1, 1), "e_b": f32(16),
        "d1_w": f32(16, 1, 3, 3), "d1_b": f32(16),
        "p_w": f32(8, 16, 1, 1),
        "d2_w": f32(8, 1, 3, 3), "d2_b": f32(8),
        "fc_w": f32(5, 8), "fc_b": f32(5),
        "zero": np.zeros((), np.float32), "six": np.full((), 6, np.float32),
    }


def mobilenet_tiny_model_bytes(seed: int = 0) -> bytes:
    """An inverted-residual block as PyTorch exports one: 1×1 expansion
    → Clip(0, 6) → depthwise 3×3 (``group`` = channels, stride 1) →
    Clip → linear 1×1 projection → Add (the skip), then a stride-2
    SAME_UPPER depthwise conv → Clip → GlobalAveragePool → Gemm, on a
    float32 (1, 8, 8, 8) NCHW input.  Clip takes its bounds as scalar
    initializer inputs (opset 13)."""
    w = mobilenet_tiny_weights(seed)
    clip = ["zero", "six"]
    nodes = [
        node("Conv", ["input", "e_w", "e_b"], ["e"], "expand",
             (attr_ints("kernel_shape", [1, 1]),)),
        node("Clip", ["e"] + clip, ["e6"], "clip_e"),
        node("Conv", ["e6", "d1_w", "d1_b"], ["d1"], "dw1",
             (attr_ints("kernel_shape", [3, 3]), attr_int("group", 16),
              attr_ints("pads", [1, 1, 1, 1]))),
        node("Clip", ["d1"] + clip, ["d16"], "clip_d1"),
        node("Conv", ["d16", "p_w"], ["p"], "project",
             (attr_ints("kernel_shape", [1, 1]),)),
        node("Add", ["p", "input"], ["res"], "residual"),
        node("Conv", ["res", "d2_w", "d2_b"], ["d2"], "dw2",
             (attr_ints("kernel_shape", [3, 3]), attr_int("group", 8),
              attr_ints("strides", [2, 2]),
              attr_string("auto_pad", "SAME_UPPER"))),
        node("Clip", ["d2"] + clip, ["d26"], "clip_d2"),
        node("GlobalAveragePool", ["d26"], ["gap"], "gap"),
        node("Flatten", ["gap"], ["flat"], "flatten", (attr_int("axis", 1),)),
        node("Gemm", ["flat", "fc_w", "fc_b"], ["logits"], "fc",
             (attr_int("transB", 1),)),
    ]
    g = graph(
        "mobilenet_tiny",
        nodes,
        [tensor(k, v) for k, v in w.items()],
        [value_info("input", (1, 8, 8, 8), FLOAT)],
        [value_info("logits", (1, 5), FLOAT)],
    )
    return model(g)


def mobilenet_tiny_numpy(x: np.ndarray, w: dict[str, np.ndarray]) -> np.ndarray:
    """Float64 NCHW forward pass of :func:`mobilenet_tiny_model_bytes`:
    depthwise convs as per-channel window sums, written out."""
    from numpy.lib.stride_tricks import sliding_window_view

    def conv1x1(x, wgt, b=None):
        out = np.einsum("nchw,oc->nohw", x, wgt[:, :, 0, 0])
        return out + (0 if b is None else b[None, :, None, None])

    def dwconv(x, wgt, b, stride, pads):  # pads ((t, b), (l, r))
        (pt, pb), (pl, pr) = pads
        xp = np.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
        win = sliding_window_view(xp, (3, 3), axis=(2, 3))
        win = win[:, :, ::stride, ::stride]
        out = np.einsum("nchwij,cij->nchw", win, wgt[:, 0])
        return out + b[None, :, None, None]

    relu6 = lambda v: np.clip(v, 0, 6)  # noqa: E731
    x = x.astype(np.float64)
    w = {k: v.astype(np.float64) for k, v in w.items()}
    h = relu6(conv1x1(x, w["e_w"], w["e_b"]))
    h = relu6(dwconv(h, w["d1_w"], w["d1_b"], 1, ((1, 1), (1, 1))))
    h = conv1x1(h, w["p_w"]) + x
    h = relu6(dwconv(h, w["d2_w"], w["d2_b"], 2,
                     (same4(8, 3, 2), same4(8, 3, 2))))
    h = h.mean(axis=(2, 3))
    return h @ w["fc_w"].T + w["fc_b"]


if __name__ == "__main__":  # pragma: no cover - fixture regeneration
    import os

    for fname, data in (("lenet5.onnx", lenet5_model_bytes()),
                        ("resnet_tiny.onnx", resnet_tiny_model_bytes())):
        path = os.path.join(os.path.dirname(__file__), "golden", fname)
        with open(path, "wb") as f:
            f.write(data)
        print(f"wrote {path} ({os.path.getsize(path)} bytes)")
