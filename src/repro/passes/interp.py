"""Reference DFG interpreter — the semantic oracle for pass testing.

Executes a :class:`~repro.core.ir.DFG` numerically (dense jnp math, no
tiling, no streams) so tests can assert that a rewritten graph computes
*exactly* what the original did: fusion (elementwise, conv+activation,
conv+pool), CSE, DCE, canonicalization, and the layer-group partitioner
are all checked against this executor, which in turn leans on
``repro.kernels.ref`` for the conv/pool/elementwise primitives — the
same primitives ``repro.kernels.ops.lower_group`` lowers groups with, so
the interpreter, the Pallas path, and the HLS emitter all share one
semantic definition.

Supported node shapes (everything ``cnn_graphs`` builds):

* pure-parallel elementwise ops (identity maps) for every PayloadKind;
* regular reductions whose map results are all single dims (matmul and
  friends) via einsum built from the indexing maps;
* NHWC sliding-window MAC (conv2d) via ``ref.conv2d``, and its
  depthwise form (:func:`repro.core.analysis.channelwise_dims`) via
  ``ref.depthwise_conv2d`` (the padding the maps imply: SAME or VALID);
* NHWC sliding-window MAX (max pool, non-overlapping or not) via
  ``ref.maxpool2d`` (VALID padding);
* fused epilogues, including windowed pooling entries.

Integer graphs execute in int32 (the paper's int8 PTQ regime accumulates
in int32); float graphs in float32.
"""
from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from repro.core.analysis import (
    KernelClass,
    channelwise_dims,
    classify_kernel,
    conv_spatial_pads,
    einsum_spec,
    reorder_spec,
    window_geometry,
)
from repro.core.ir import DFG, GenericOp, PayloadKind
from repro.kernels import ref


def _apply_epilogue(op: GenericOp, out, env: Mapping[str, jax.Array]):
    return ref.apply_epilogue(out, op.epilogue, env)


def _einsum_from_maps(op: GenericOp, operands):
    """Regular reduction with single-dim map results → jnp.einsum."""
    return jnp.einsum(einsum_spec(op), *operands)


def _conv2d(op: GenericOp, dfg: DFG, env: Mapping[str, jax.Array]):
    info = classify_kernel(op)
    depthwise = bool(channelwise_dims(op))
    if (op.n_dims != (6 if depthwise else 7) or len(op.inputs) != 2
            or info.dilation != 1):
        raise NotImplementedError(f"{op.name}: unsupported sliding-window shape")
    stream = [i for i in op.inputs if not dfg.values[i].is_constant]
    const = [i for i in op.inputs if dfg.values[i].is_constant]
    if len(stream) != 1 or len(const) != 1:
        raise NotImplementedError(f"{op.name}: conv needs 1 stream + 1 const input")
    x = env[stream[0]]
    pads = conv_spatial_pads(op, tuple(x.shape))
    conv = ref.depthwise_conv2d if depthwise else ref.conv2d
    return conv(x, env[const[0]], stride=info.stride,
                padding=(pads[1], pads[2]))


def _pool2d(op: GenericOp, env: Mapping[str, jax.Array]):
    info = classify_kernel(op)
    geo = window_geometry(op, info)
    if op.n_dims != 6 or len(geo.window_extents) != 2 or info.dilation != 1:
        raise NotImplementedError(f"{op.name}: unsupported pool shape")
    kh, kw = geo.window_extents
    pool = ref.maxpool2d if op.payload == PayloadKind.MAX else ref.avgpool2d
    return pool(env[op.inputs[0]], kh, kw, info.stride)


def execute_reorder(op: GenericOp, x: jax.Array) -> jax.Array:
    """Transpose / flatten data-movement ops (shared with the Pallas
    lowering so both executors agree on reorder semantics)."""
    spec = reorder_spec(op)
    assert spec is not None, op.name
    kind, arg = spec
    if kind == "transpose":
        return jnp.transpose(x, arg)
    # flatten: bring the non-batch axes into linearization order, then
    # collapse them row-major
    return jnp.transpose(x, (0,) + arg).reshape(x.shape[0], -1)


def execute_node(op: GenericOp, dfg: DFG, env: Mapping[str, jax.Array]):
    info = classify_kernel(op)
    if info.kernel_class == KernelClass.PURE_PARALLEL:
        if reorder_spec(op) is not None:
            return _apply_epilogue(op, execute_reorder(op, env[op.inputs[0]]),
                                   env)
        args = [env[i] for i in op.inputs]
        if len(args) == 1:
            out = ref.unary(op.payload, args[0])
        elif len(args) == 2:
            out = ref.binary(op.payload, args[0], args[1])
        else:
            raise NotImplementedError(f"{op.name}: {len(args)}-ary elementwise")
    elif info.kernel_class == KernelClass.REGULAR_REDUCTION:
        if op.payload != PayloadKind.MAC:
            raise NotImplementedError(f"{op.name}: non-MAC reduction")
        out = _einsum_from_maps(op, [env[i] for i in op.inputs])
    else:  # SLIDING_WINDOW
        if op.payload == PayloadKind.MAC:
            out = _conv2d(op, dfg, env)
        elif (
            op.payload in (PayloadKind.MAX, PayloadKind.AVG)
            and len(op.inputs) == 1
        ):
            out = _pool2d(op, env)
        else:
            raise NotImplementedError(f"{op.name}: unsupported sliding window")
    return _apply_epilogue(op, out, env)


def execute_dfg(
    dfg: DFG, env: Mapping[str, jax.Array]
) -> dict[str, jax.Array]:
    """Run the graph; ``env`` must bind every graph input and constant.
    Returns the full value environment (inputs + all produced values),
    so layer groups can be chained by feeding one group's result env
    into the next — exactly what the host schedule does via DRAM."""
    out_env = dict(env)
    for op in dfg.topo_order():
        out_env[op.output] = execute_node(op, dfg, out_env)
    return out_env


def graph_outputs(dfg: DFG, env: Mapping[str, jax.Array]) -> dict[str, jax.Array]:
    full = execute_dfg(dfg, env)
    return {v: full[v] for v in dfg.graph_outputs}


def random_env(dfg: DFG, seed: int = 0) -> dict[str, jax.Array]:
    """Small-integer int32 bindings for every graph input and constant —
    integer math keeps fused-vs-unfused comparisons exact."""
    key = jax.random.key(seed)
    env: dict[str, jax.Array] = {}
    names = list(dfg.graph_inputs) + [
        v for v, val in dfg.values.items() if val.is_constant
    ]
    for name in names:
        key, sub = jax.random.split(key)
        env[name] = jax.random.randint(
            sub, dfg.values[name].shape, -4, 5, jnp.int32
        )
    return env
