"""Line-buffer streaming conv2d (+fused ReLU) — Pallas TPU kernel.

This is the TPU adaptation of MING's centerpiece (paper Sec. IV-B): a
sliding-window node that *streams* input rows instead of materializing
the input tensor on-chip.  The mapping:

  FPGA                              TPU (this kernel)
  ----------------------------      ---------------------------------
  hls::stream row arrivals          sequential grid steps (R rows each)
  (K-1)×N BRAM line buffer          VMEM scratch rows (KH-s, Wp, Cin),
                                    persisted across grid steps
  K×K window regs + DSP MAC tree    (R,W,Cin)×(Cin,Cout) MXU matmuls,
                                    one per (kh, kw) tap
  fused ReLU node (pure parallel)   fused max(acc, 0) before writeback

The kernel is *causal*: output row ``j`` of the padded frame is the conv
window ending at padded row ``j``.  ``ops.conv2d_stream`` pre-pads the
frame and slices ``[KH-1 : KH-1+H]``, recovering exact SAME-padding
semantics (validated against ``ref.conv2d``).

Grid: ``(B, Hp // rows_per_block)`` — the row-block count is chosen by
the DSE (``repro.core.dse.plan_conv_rows``) so the VMEM working set
(line buffer + weights + R output rows) fits the budget, the direct dual
of the paper's BRAM constraint.

Operands are float or integers of at most 8 bits
(:func:`kernel_accepts`): Mosaic has no matmul for wider integers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


class KernelDtypeError(TypeError):
    """Operand dtypes the compiled (non-interpret) kernel cannot take."""


def kernel_accepts(*dtypes) -> bool:
    """Whether the TPU kernel takes operands of these dtypes: floats,
    and integers of at most 8 bits (the MXU's int8 path).  Mosaic
    refuses an int16/int32 matmul, so wider integers need another
    lowering (``ops.conv2d_same_mm``)."""
    return all(
        not jnp.issubdtype(d, jnp.integer) or jnp.dtype(d).itemsize == 1
        for d in dtypes
    )


#: fused-epilogue kinds the conv path supports — mirrors the DFG-level
#: FusedEpilogue kinds the fusion passes fold into a MAC node.  Applied
#: to the int32/f32 accumulator in VMEM before writeback, so the fused
#: activation costs zero extra HBM traffic (the TPU dual of the FPGA
#: epilogue running on the stream-exit datapath).
CONV_EPILOGUES = ("relu", "squared_relu")


def _apply_epilogue(acc, epilogue: str | None):
    if epilogue is None:
        return acc
    if epilogue == "relu":
        return jnp.maximum(acc, 0)
    if epilogue == "squared_relu":
        r = jnp.maximum(acc, 0)
        return r * r
    raise ValueError(f"unsupported conv epilogue {epilogue!r}")


def line_buffer_rows(kh: int, stride: int) -> int:
    """Rows the line buffer must carry between row blocks.

    At stride ``s`` each emitted output row advances the read window by
    ``s`` input rows, so only ``max(kh - s, 0)`` rows of the previous
    block are re-read by the next one — the stride-1 case degenerates to
    the paper's ``K-1`` rows, and ``s >= kh`` needs no carry at all
    (windows never overlap vertically)."""
    return max(kh - stride, 0)


def _conv_stream_kernel(
    x_ref,      # (1, Rin, s*Wq, Cin)  current row block (the "stream")
    w_ref,      # (KH, KW, Cin, Cout)
    o_ref,      # (1, Rin//s, W, Cout)
    win_ref,    # (C + Rin + s - 1, s*Wq, Cin)  line buffer + block (VMEM)
    *,
    kh: int,
    kw: int,
    w_out: int,
    stride: int,
    epilogue: str | None,
):
    """One row block.  ``win_ref`` rows ``[0, C)`` are the line buffer
    (the last ``C`` rows of the previous block), rows ``[C, C + Rin)``
    the current block; the ``s - 1`` rows after them are never read
    into a result.  The stride is taken without strided loads, which
    Mosaic refuses for sub-32-bit data: columns arrive phase-split from
    the wrapper (column ``dw + s*j`` of the frame sits at
    ``(dw % s)*Wq + dw//s + j``), and rows are read contiguously, split
    ``(Rout, s)`` on the untiled leading axis and indexed at phase 0."""
    i = pl.program_id(1)
    acc_t = _acc_dtype(o_ref.dtype)
    carry = line_buffer_rows(kh, stride)
    rin = x_ref.shape[1]
    wq = x_ref.shape[2] // stride

    if carry > 0:
        @pl.when(i == 0)
        def _init():
            win_ref[:carry] = jnp.zeros(
                (carry,) + win_ref.shape[1:], win_ref.dtype)

    win_ref[carry:carry + rin] = x_ref[0]
    r_out = rin // stride                            # output rows per block

    acc = jnp.zeros((r_out, w_out, o_ref.shape[-1]), acc_t)
    for dh in range(kh):
        for dw in range(kw):
            col = (dw % stride) * wq + dw // stride
            rows = win_ref[dh:dh + r_out * stride, col:col + w_out, :]
            patch = rows.reshape(r_out, stride, w_out, rows.shape[-1])[:, 0]
            tap = w_ref[dh, dw]                                # (Cin, Cout)
            acc = acc + jax.lax.dot_general(
                patch,                                         # (Rout, W, Cin)
                tap,
                (((2,), (0,)), ((), ())),
                preferred_element_type=acc_t,
            )
    acc = _apply_epilogue(acc, epilogue)
    o_ref[...] = acc[None].astype(o_ref.dtype)

    if carry > 0:
        win_ref[:carry] = win_ref[rin:rin + carry]


def _split_column_phases(x: jax.Array, stride: int) -> jax.Array:
    """(B, H, Wp, C) → (B, H, s*Wq, C), ``Wq = ceil(Wp / s)``: phase
    ``p`` (columns ``p, p+s, ...``, zero-padded to ``Wq``) occupies
    columns ``[p*Wq, (p+1)*Wq)``, so the kernel reads every tap's
    stride-``s`` columns as one contiguous slice.  Identity at s=1."""
    if stride == 1:
        return x
    wp = x.shape[2]
    wq = -(-wp // stride)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, wq * stride - wp), (0, 0)))
    return jnp.concatenate([x[:, :, p::stride] for p in range(stride)],
                           axis=2)


def conv2d_stream_pallas(
    x_padded: jax.Array,     # (B, Hp, Wp, Cin) — pre-padded frame
    w: jax.Array,            # (KH, KW, Cin, Cout)
    *,
    rows_per_block: int,
    w_out: int,
    stride: int = 1,
    fuse_relu: bool = False,
    epilogue: str | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; see ``ops.conv2d_stream`` for the public wrapper.

    ``rows_per_block`` counts *input* rows per grid step and must be a
    multiple of ``stride``; each step emits ``rows_per_block // stride``
    output rows (every ``stride``-th window row — the line-buffer
    discipline at stride ``s``).  ``epilogue`` generalizes ``fuse_relu``
    to any supported fused elementwise tail (``CONV_EPILOGUES``);
    ``fuse_relu=True`` is kept as sugar for ``epilogue="relu"``.

    Compiled (``interpret=False``) operands must pass
    :func:`kernel_accepts`; others raise :class:`KernelDtypeError`.
    """
    if not interpret and not kernel_accepts(x_padded.dtype, w.dtype):
        raise KernelDtypeError(
            f"conv2d_stream: operands {x_padded.dtype}/{w.dtype} — the TPU "
            "kernel takes floats and integers of at most 8 bits; lower "
            "wider integers with ops.conv2d_same_mm"
        )
    if fuse_relu:
        if epilogue not in (None, "relu"):
            raise ValueError("fuse_relu=True conflicts with epilogue="
                             f"{epilogue!r}")
        epilogue = "relu"
    b, hp, _, cin = x_padded.shape
    kh, kw_, _, cout = w.shape
    assert hp % rows_per_block == 0, (hp, rows_per_block)
    assert rows_per_block % stride == 0, (rows_per_block, stride)
    nb = hp // rows_per_block
    rows_out = rows_per_block // stride
    acc_t = _acc_dtype(x_padded.dtype)
    x_ph = _split_column_phases(x_padded, stride)
    wph = x_ph.shape[2]
    win_rows = line_buffer_rows(kh, stride) + rows_per_block + stride - 1

    kernel = functools.partial(
        _conv_stream_kernel, kh=kh, kw=kw_, w_out=w_out, stride=stride,
        epilogue=epilogue
    )
    return pl.pallas_call(
        kernel,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec(
                (1, rows_per_block, wph, cin), lambda bb, i: (bb, i, 0, 0)
            ),
            pl.BlockSpec((kh, kw_, cin, cout), lambda bb, i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, rows_out, w_out, cout), lambda bb, i: (bb, i, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp // stride, w_out, cout), acc_t),
        scratch_shapes=[pltpu.VMEM((win_rows, wph, cin), x_padded.dtype)],
        interpret=interpret,
    )(x_ph, w)
