"""Line-buffer streaming depthwise conv2d — Pallas TPU kernel (VPU).

The depthwise conv (``repro.core.ir.make_depthwise_conv2d_op``) reduces
over the window only: each channel is convolved with its own K×K
filter.  There is no contraction over channels for the MXU to do, so
the kernel runs on the VPU with channels on lanes: every tap is one
float32 multiply-add of a ``(rows, W, channels)`` slab against a
``(1, channels)`` weight row.

  FPGA (emit_hls)                   TPU (this kernel)
  ----------------------------      ---------------------------------
  hls::stream row arrivals          sequential grid steps (R rows each)
  (K-1)×W×C BRAM line buffer        VMEM scratch rows (KH-s, Wp, Cb),
                                    persisted across grid steps
  K×K MACs per channel lane         KH·KW lane-wise multiply-adds
  fused bias / ReLU6 epilogue       bias + clamp on the accumulator
                                    before writeback

Grid: ``(B, C // Cb, Hp // R)``.  Channels are blocked by 128 lanes
where the count divides, else taken whole (Mosaic pads the lanes to a
multiple of 128 in its registers); the row blocks are innermost and
run in order, so the line buffer carries the last ``KH - s`` rows of
one block into the next, as in ``conv2d_stream``.  The frame arrives
pre-padded and, at stride ``s > 1``, with its columns phase-split
(``conv2d_stream._split_column_phases``): the kernel reads every tap
as a contiguous slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .conv2d_stream import (
    _acc_dtype,
    _apply_epilogue,
    _split_column_phases,
    line_buffer_rows,
)

#: channels per grid step where the count is a multiple of it
LANES = 128


def channel_block(c: int) -> int:
    """Channels per grid step: 128 lanes where ``c`` divides by 128,
    else all ``c`` (a full-extent block is always legal)."""
    return LANES if c % LANES == 0 else c


def _dw_stream_kernel(x_ref, w_ref, *rest, kh: int, kw: int, w_out: int,
                      stride: int, has_bias: bool, epilogue: str | None):
    """One row block of one channel block.  ``win_ref`` rows ``[0, C)``
    are the line buffer (the previous block's last ``C`` rows), rows
    ``[C, C + Rin)`` the current block."""
    if has_bias:
        b_ref, o_ref, win_ref = rest
    else:
        (o_ref, win_ref), b_ref = rest, None
    i = pl.program_id(2)
    acc_t = _acc_dtype(o_ref.dtype)
    carry = line_buffer_rows(kh, stride)
    rin = x_ref.shape[1]
    wq = x_ref.shape[2] // stride
    cb = x_ref.shape[3]

    if carry > 0:
        @pl.when(i == 0)
        def _init():
            win_ref[:carry] = jnp.zeros(
                (carry,) + win_ref.shape[1:], win_ref.dtype)

    win_ref[carry:carry + rin] = x_ref[0]
    r_out = rin // stride

    acc = jnp.zeros((r_out, w_out, cb), acc_t)
    for dh in range(kh):
        for dw in range(kw):
            col = (dw % stride) * wq + dw // stride
            rows = win_ref[dh:dh + r_out * stride, col:col + w_out, :]
            patch = rows.reshape(r_out, stride, w_out, cb)[:, 0]
            tap = w_ref[pl.ds(dh * kw + dw, 1), :]           # (1, Cb)
            acc = acc + patch.astype(acc_t) * tap.astype(acc_t)
    if b_ref is not None:
        acc = acc + b_ref[...].astype(acc_t)
    acc = _apply_epilogue(acc, epilogue)
    o_ref[...] = acc[None].astype(o_ref.dtype)

    if carry > 0:
        win_ref[:carry] = win_ref[rin:rin + carry]


def dwconv2d_stream_pallas(
    x_padded: jax.Array,     # (B, Hp, Wp, C) — pre-padded frame
    w: jax.Array,            # (KH, KW, C)
    bias: jax.Array | None = None,   # (C,)
    *,
    rows_per_block: int,
    w_out: int,
    stride: int = 1,
    epilogue: str | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; see ``ops.dwconv2d_stream`` for the public
    wrapper.  ``rows_per_block`` counts *input* rows per grid step, a
    multiple of ``stride`` that divides ``Hp``; each step emits
    ``rows_per_block // stride`` output rows.  ``bias`` and
    ``epilogue`` (a ``conv2d_stream.CONV_EPILOGUES`` kind) act on the
    accumulator before writeback.  Returns ``(B, Hp // stride, w_out,
    C)`` in the accumulator dtype (float32, or int32 for integers)."""
    b, hp, _, c = x_padded.shape
    kh, kw, _ = w.shape
    assert hp % rows_per_block == 0, (hp, rows_per_block)
    assert rows_per_block % stride == 0, (rows_per_block, stride)
    nb = hp // rows_per_block
    rows_out = rows_per_block // stride
    cb = channel_block(c)
    acc_t = _acc_dtype(x_padded.dtype)
    x_ph = _split_column_phases(x_padded, stride)
    wph = x_ph.shape[2]
    win_rows = line_buffer_rows(kh, stride) + rows_per_block + stride - 1

    in_specs = [
        pl.BlockSpec((1, rows_per_block, wph, cb),
                     lambda bb, cc, i: (bb, i, 0, cc)),
        pl.BlockSpec((kh * kw, cb), lambda bb, cc, i: (0, cc)),
    ]
    operands = [x_ph, w.reshape(kh * kw, c)]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, cb), lambda bb, cc, i: (0, cc)))
        operands.append(bias.reshape(1, c))
    kernel = functools.partial(
        _dw_stream_kernel, kh=kh, kw=kw, w_out=w_out, stride=stride,
        has_bias=bias is not None, epilogue=epilogue,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, c // cb, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows_out, w_out, cb),
                               lambda bb, cc, i: (bb, i, 0, cc)),
        out_shape=jax.ShapeDtypeStruct((b, hp // stride, w_out, c), acc_t),
        scratch_shapes=[pltpu.VMEM((win_rows, wph, cb), x_padded.dtype)],
        interpret=interpret,
        name="ming_dwconv2d_stream",
    )(*operands)
