"""Public jit'd wrappers for the Pallas kernels + the TPU consumer of
the schedule IR.

Each wrapper:
  * derives legal tile sizes from the MING DSE (``repro.core.dse``) under
    the VMEM budget — the paper's ILP with TPU-dual constraints,
  * handles padding / reshaping so callers see clean dense semantics,
  * validates in interpret mode on CPU (``interpret=None`` → auto).

The oracles live in ``ref.py``; ``tests/test_kernels.py`` sweeps
shapes/dtypes asserting allclose between the two.

``lower_group`` / ``run_compiled`` are the TPU duals of the HLS
emitter: they consume the *same*
:class:`repro.core.compile_driver.CompiledDesign` the FPGA path emits
from — each :class:`GroupSchedule` lowers to one jit-compiled fused
executable (streaming conv kernels with fused epilogues, map-driven
einsum reductions, elementwise tails), and ``run_compiled`` chains the
groups through a value environment exactly as the emitted
``host_schedule.cpp`` threads DRAM spill buffers.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import threading
import time
from typing import Literal

import jax
import jax.numpy as jnp

import repro.instrument as instrument
from repro.instrument import metrics as _metrics

from repro.core.analysis import (
    KernelClass,
    classify_kernel,
    conv_spatial_pads,
    einsum_spec,
    reorder_spec,
    window_geometry,
)
from repro.core.dse import plan_attention_blocks, plan_conv_rows, plan_matmul_blocks
from repro.core.ir import PayloadKind
from . import conv2d_stream as _conv
from . import flash_attention as _flash
from . import fused_mlp as _mlp
from . import mamba2_ssd as _ssd
from . import ref as _ref


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_block(size: int, target: int) -> int:
    """Largest divisor of ``size`` that is ≤ target (≥ 1)."""
    best = 1
    for d in range(1, size + 1):
        if size % d == 0 and d <= target:
            best = d
    return best


# ---------------------------------------------------------------------------
# conv2d_stream
# ---------------------------------------------------------------------------


Padding = str | tuple[tuple[int, int], tuple[int, int]]


def _conv_pads(
    h: int, w: int, kh: int, kw: int, stride: int, padding: Padding
) -> tuple[tuple[int, int], tuple[int, int]]:
    """Resolve ``padding`` to explicit ((top, bottom), (left, right)).

    ``"SAME"`` splits the deficit end-heavy (``begin = total // 2`` —
    the XLA / ONNX SAME_UPPER convention; at stride 1 with odd kernels
    this is the symmetric ``(k-1)//2`` frame), ``"VALID"`` pads nothing,
    and an explicit pair-of-pairs passes through (the importer's
    asymmetric-pads path).
    """
    if isinstance(padding, str):
        if padding == "SAME":
            def same(n: int, k: int) -> tuple[int, int]:
                out = -(-n // stride)
                total = max(0, stride * (out - 1) + k - n)
                return total // 2, total - total // 2
            return same(h, kh), same(w, kw)
        if padding == "VALID":
            if kh > h or kw > w:
                raise ValueError(
                    f"VALID conv kernel ({kh}x{kw}) exceeds input ({h}x{w})"
                )
            return (0, 0), (0, 0)
        raise ValueError(f"unsupported padding {padding!r}")
    (pt, pb), (pl, pr) = padding
    return (int(pt), int(pb)), (int(pl), int(pr))


def conv2d_stream(
    x: jax.Array,            # (B, H, W, Cin)
    w: jax.Array,            # (KH, KW, Cin, Cout)
    *,
    stride: int = 1,
    padding: Padding = "SAME",
    fuse_relu: bool = False,
    epilogue: str | None = None,
    rows_per_block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """NHWC conv via the line-buffer streaming kernel (stride-s, SAME /
    VALID / explicit pads).

    Returns int32 accumulators for integer inputs (paper's int8 PTQ path),
    f32 otherwise — requantization is the caller's (graph's) concern.
    Compiled (``interpret=False``) operands must be float or integers of
    at most 8 bits; wider integers raise ``KernelDtypeError``.

    ``epilogue`` fuses an elementwise tail into the kernel's writeback
    (``"relu"`` | ``"squared_relu"``) — the TPU realization of the pass
    pipeline's conv+activation fusion (``repro.passes.fusion``);
    ``fuse_relu=True`` remains as sugar for ``epilogue="relu"``.

    Stride-s alignment: the kernel emits one output row per ``stride``
    input rows of the *aligned* frame, and output row ``g`` reads
    aligned rows ``[g*s - C, g*s - C + kh - 1]`` where ``C`` is the
    line-buffer carry (``line_buffer_rows``).  Prepending ``A = c*s - C``
    zero rows (``c = ceil(C/s)``) makes emitted row ``t + c`` read padded
    rows ``[t*s, t*s + kh - 1]`` — so the first ``c`` output rows are
    discarded and the valid output is ``out[:, c : c + h_out]``.  At
    stride 1 this degenerates to the original causal trick:
    ``C = c = kh - 1``, ``A = 0``, slice ``[kh-1 : kh-1+h]``.
    """
    interpret = _auto_interpret(interpret)
    b, h, ww, cin = x.shape
    kh, kw, _, cout = w.shape
    (pad_t, pad_b), (pad_l, pad_r) = _conv_pads(h, ww, kh, kw, stride, padding)
    h_out = (h + pad_t + pad_b - kh) // stride + 1
    w_out = (ww + pad_l + pad_r - kw) // stride + 1

    carry = _conv.line_buffer_rows(kh, stride)
    c_skip = -(-carry // stride)            # garbage leading output rows
    align = c_skip * stride - carry         # extra zero rows on top
    hp = align + pad_t + h + pad_b
    if rows_per_block is None:
        plan = plan_conv_rows(
            h=hp, w=ww + pad_l + pad_r, c_in=cin, c_out=cout, kh=kh, kw=kw,
            bytes_per_el=x.dtype.itemsize,
        )
        rows_per_block = _round_up(plan.blocks["rows"], stride)
    # rows_per_block must divide hp — pad the bottom if necessary
    hp_pad = _round_up(hp, rows_per_block)
    x_p = jnp.pad(
        x,
        ((0, 0), (align + pad_t, pad_b + (hp_pad - hp)),
         (pad_l, pad_r), (0, 0)),
    )
    out = _conv.conv2d_stream_pallas(
        x_p,
        w,
        rows_per_block=rows_per_block,
        w_out=w_out,
        stride=stride,
        fuse_relu=fuse_relu,
        epilogue=epilogue,
        interpret=interpret,
    )
    return out[:, c_skip : c_skip + h_out]


def conv2d_same_mm(
    x: jax.Array, w: jax.Array, *,
    stride: int = 1, padding: Padding = "SAME",
) -> jax.Array:
    """NHWC conv as KH·KW shifted channel matmuls.

    The lowering of integer convs whose operands are wider than 8 bits
    (on every path: the TPU kernel cannot take them) and of every
    integer conv in the *batched* executables, where it was picked by
    CPU timing: XLA's CPU path for integer ``lax.conv`` is a naive loop, an
    order of magnitude slower than its integer dot — so the conv is
    decomposed into one ``(N·H·W, Cin) @ (Cin, Cout)`` matmul per
    kernel tap, accumulated in **int32** — the same accumulator the
    streaming kernel (``conv2d_stream._acc_dtype``) and the dense
    oracle use, so sub-int32 inputs (the paper's int8 PTQ regime) get
    real int32 accumulators, not input-dtype wraparound.  Operands are
    cast to int32 *before* the matmuls: truncation mod 2³² commutes
    with integer multiply/add, so this is bit-exact with the streaming
    Pallas kernel for every integer width (including on int32
    overflow, which wraps identically everywhere).  Float inputs must
    NOT take this path — float summation order changes ulps — and keep
    the Pallas kernel.
    """
    kh, kw, cin, cout = w.shape
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
        w = w.astype(jnp.int32)
    n, h, wd, _ = x.shape
    (pad_t, pad_b), (pad_l, pad_r) = _conv_pads(h, wd, kh, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), (pad_t, pad_b), (pad_l, pad_r), (0, 0)))
    h_out = (h + pad_t + pad_b - kh) // stride + 1
    w_out = (wd + pad_l + pad_r - kw) // stride + 1
    out = None
    for dy in range(kh):
        for dx in range(kw):
            patch = xp[
                :,
                dy : dy + (h_out - 1) * stride + 1 : stride,
                dx : dx + (w_out - 1) * stride + 1 : stride,
                :,
            ]
            tap = jnp.einsum("nhwc,co->nhwo", patch, w[dy, dx])
            out = tap if out is None else out + tap
    return out


# ---------------------------------------------------------------------------
# Schedule-IR consumer: one fused executable per GroupSchedule
# ---------------------------------------------------------------------------

#: epilogue kinds the conv kernel applies *inside* the Pallas kernel
#: (on the VMEM accumulator, before writeback)
_IN_KERNEL_EPILOGUES = {
    PayloadKind.RELU: "relu",
    PayloadKind.SQUARED_RELU: "squared_relu",
}


def _split_conv_epilogue(op):
    """(in-kernel epilogue string, remaining epilogue entries) for a
    conv node: a leading unary relu/squared_relu runs on the kernel's
    accumulator; everything after (constant binops, fused pools) applies
    to the kernel's output inside the same jit unit."""
    epi = list(op.epilogue)
    if epi and epi[0].operand is None and not epi[0].window and (
        epi[0].kind in _IN_KERNEL_EPILOGUES
    ):
        return _IN_KERNEL_EPILOGUES[epi[0].kind], epi[1:]
    return None, epi


def _weight_tile_axes(op, dfg):
    """(const input name, const tensor axis, output tensor axis) for the
    *leading* weight-tileable dim of a streamed-weight node — the axis
    the DSE's ``weight_tiles`` splits the const buffer along (c_out for
    an NHWC conv, n_out for a matmul; ``NodePlan.weight_tile_dims[0]``,
    recomputed here from the maps).  ``None`` when no safe tile axis
    exists (the untiled lowering is numerically identical either way)."""
    info = classify_kernel(op)
    window = set(info.classes.window)
    cands = []  # (dim, input index, input name, const axis, output axis)
    for i, name in enumerate(op.inputs):
        if not dfg.values[name].is_constant:
            continue
        for pos, expr in enumerate(op.input_maps[i].results):
            if not expr.is_single_dim():
                continue
            (d, _), = expr.terms
            if not (op.is_parallel_dim(d) and d not in window):
                continue
            out_axis = next(
                (
                    q for q, oe in enumerate(op.output_map.results)
                    if oe.is_single_dim() and oe.terms[0][0] == d
                ),
                None,
            )
            if out_axis is not None:
                cands.append((d, i, name, pos, out_axis))
    if not cands:
        return None
    d, i, name, pos, out_axis = min(cands)  # leading dim, like plan_node
    # slicing one operand is only sound if no other input reads dim d
    for j, other in enumerate(op.inputs):
        if j == i:
            continue
        if any(d in expr.dims() for expr in op.input_maps[j].results):
            return None
    return name, pos, out_axis


def _lower_node(op, dfg, env, interpret: bool, weight_tiles: int = 1,
                fast_int_conv: bool = False):
    """Execute one GenericOp with the kernel library (jit-traceable).

    ``weight_tiles > 1`` honors the schedule's partial weight streaming:
    the const operand is processed in output-channel tiles (the TPU
    stand-in for the HLS kernel's double-buffered DRAM ``wtile`` loop)
    and the partial results concatenated — bit-exact with the resident
    lowering, but structurally the same tiled schedule the emitter
    realizes.

    Conv lowering, chosen by operand dtype:

    * an integer operand wider than 8 bits (int16/int32 activations or
      weights — e.g. the int32 accumulators an unrequantized conv hands
      the next one) takes :func:`conv2d_same_mm` on every path: the TPU
      kernel has no such matmul (``conv2d_stream.kernel_accepts``);
    * int8/uint8 and float operands take the streaming Pallas kernel,
      except that ``fast_int_conv`` (the batched-executable path) sends
      every integer conv to :func:`conv2d_same_mm`.

    Integer lowerings are bit-exact with each other (modular addition is
    order-independent); float convs always keep the Pallas kernel, so
    batched and per-sample runs stay bit-exact.
    """
    if weight_tiles > 1:
        tiled = _weight_tile_axes(op, dfg)
        if tiled is not None:
            cname, cax, oax = tiled
            w = env[cname]
            if w.shape[cax] % weight_tiles == 0:
                bare = dataclasses.replace(op, epilogue=())
                step = w.shape[cax] // weight_tiles
                parts = [
                    _lower_node(
                        bare, dfg,
                        {**env, cname: jax.lax.slice_in_dim(
                            w, t * step, (t + 1) * step, axis=cax)},
                        interpret, fast_int_conv=fast_int_conv,
                    )
                    for t in range(weight_tiles)
                ]
                out = jnp.concatenate(parts, axis=oax)
                return _ref.apply_epilogue(out, op.epilogue, env)
    info = classify_kernel(op)
    if info.kernel_class == KernelClass.SLIDING_WINDOW:
        if op.payload == PayloadKind.MAC:
            stream = [i for i in op.inputs if not dfg.values[i].is_constant]
            const = [i for i in op.inputs if dfg.values[i].is_constant]
            if (
                len(stream) == 1 and len(const) == 1
                and op.n_dims == 7 and info.dilation == 1
            ):
                x_in, w = env[stream[0]], env[const[0]]
                # the maps determine the reach; whatever exceeds the
                # actual input extent is the zero-padding frame (SAME
                # splits end-heavy, VALID reads within bounds -> (0,0))
                pads = conv_spatial_pads(op, tuple(x_in.shape))
                padding = (pads[1], pads[2])
                if not _conv.kernel_accepts(x_in.dtype, w.dtype) or (
                    fast_int_conv and jnp.issubdtype(x_in.dtype, jnp.integer)
                ):
                    out = conv2d_same_mm(x_in, w,
                                         stride=info.stride, padding=padding)
                    return _ref.apply_epilogue(out, op.epilogue, env)
                kern_epi, rest = _split_conv_epilogue(op)
                out = conv2d_stream(
                    x_in, w,
                    stride=info.stride, padding=padding,
                    epilogue=kern_epi, interpret=interpret,
                )
                return _ref.apply_epilogue(out, rest, env)
            # keep parity with the interpreter: fail loudly rather
            # than silently computing a dilation-1 conv
            raise NotImplementedError(
                f"{op.name}: unsupported conv form in lower_group"
            )
        if (
            op.payload in (PayloadKind.MAX, PayloadKind.AVG)
            and len(op.inputs) == 1
        ):
            geo = window_geometry(op, info)
            kh, kw = geo.window_extents
            pool = (
                _ref.maxpool2d if op.payload == PayloadKind.MAX
                else _ref.avgpool2d
            )
            out = pool(env[op.inputs[0]], kh, kw, info.stride)
            return _ref.apply_epilogue(out, op.epilogue, env)
        raise NotImplementedError(f"{op.name}: unsupported sliding window")
    if info.kernel_class == KernelClass.REGULAR_REDUCTION:
        if op.payload != PayloadKind.MAC:
            raise NotImplementedError(f"{op.name}: non-MAC reduction")
        out = jnp.einsum(einsum_spec(op), *(env[i] for i in op.inputs))
        return _ref.apply_epilogue(out, op.epilogue, env)
    # PURE_PARALLEL
    if reorder_spec(op) is not None:
        from repro.passes.interp import execute_reorder

        out = execute_reorder(op, env[op.inputs[0]])
        return _ref.apply_epilogue(out, op.epilogue, env)
    args = [env[i] for i in op.inputs]
    if len(args) == 1:
        out = _ref.unary(op.payload, args[0])
    elif len(args) == 2:
        out = _ref.binary(op.payload, args[0], args[1])
    else:
        raise NotImplementedError(f"{op.name}: {len(args)}-ary elementwise")
    return _ref.apply_epilogue(out, op.epilogue, env)


#: executables per group *structure* — repeated ``run_compiled`` calls
#: (batched inference, benchmark sweeps) reuse the traced/jitted unit
#: instead of re-jitting per call (ROADMAP "lower_group jits per call").
#: A true LRU (ISSUE 7): hits refresh recency, inserts beyond the cap
#: evict the least-recently-used executable — across many signatures ×
#: batch buckets the cache stays bounded instead of growing forever.
_EXEC_CACHE: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_EXEC_CACHE_CAP = 128
#: ServeEngine worker threads hit lower_group concurrently with
#: main-thread runs; the LRU mutates on every access (move_to_end /
#: popitem), so lookup+insert+stats form one critical section.
_EXEC_CACHE_LOCK = threading.Lock()
#: observability for tests and benchmarks (evictions per ISSUE 7)
exec_cache_stats = {"hits": 0, "misses": 0, "evictions": 0}


#: the batch extents batched executables are traced at: a batched run
#: pads its batch up to the nearest bucket (and chunks above the top
#: one), so at most ``len(BATCH_BUCKETS)`` compiles happen per group
#: signature no matter what batch sizes traffic brings.
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def batch_bucket(n: int) -> int:
    """The padded batch extent ``n`` executes at: the smallest bucket
    ≥ ``n``.  ``n`` must not exceed the top bucket (the runner chunks
    larger batches before bucketing)."""
    if n < 1:
        raise ValueError(f"batch extent must be >= 1, got {n}")
    for b in BATCH_BUCKETS:
        if n <= b:
            return b
    raise ValueError(
        f"batch extent {n} exceeds the top bucket {BATCH_BUCKETS[-1]} — "
        "chunk the batch first (run_compiled_batched does)"
    )


def _batch_chunks(batch: int):
    """Split ``batch`` into (start, n, bucket) chunks of at most the
    top bucket each, so any offered batch executes with a bounded set
    of traced shapes."""
    cap = BATCH_BUCKETS[-1]
    start = 0
    while start < batch:
        n = min(batch - start, cap)
        yield start, n, batch_bucket(n)
        start += n


def _group_signature(group, interpret: bool) -> tuple:
    """Hashable identity of everything the lowered executable depends
    on: node structure (maps, iterators, payloads, epilogues), value
    shapes/bits/names (env keys!), the group's streamed-weight tiling,
    and the interpret flag.  Constants arrive through ``env`` at call
    time, so they are deliberately *not* part of the key."""
    dfg = group.dfg
    sig: list = [interpret, tuple(dfg.graph_inputs), tuple(dfg.graph_outputs)]
    for op in dfg.topo_order():
        sig.append((
            op.name,
            op.inputs,
            op.output,
            tuple(str(m) for m in op.indexing_maps),
            tuple(t.value for t in op.iterator_types),
            op.dim_sizes,
            op.payload.value,
            op.elem_bits,
            tuple(
                (e.kind.value, e.operand, tuple(e.window) if e.window else ())
                for e in op.epilogue
            ),
            group.dse.weight_tiles.get(op.name, 1),
            tuple(
                (v, dfg.values[v].shape, dfg.values[v].elem_bits,
                 dfg.values[v].is_constant)
                for v in op.inputs + (op.output,)
            ),
        ))
    return tuple(sig)


def _build_group_fn(group, interpret: bool, jit: bool,
                    batch: int | None = None):
    """The uncached lowering — separable so tests can probe compile
    counts (the cache satellite of ISSUE 3; batched probes in ISSUE 7).

    ``batch`` (ISSUE 7) builds the *batched* executable: the per-sample
    group fn vmapped over a leading batch axis of extent ``batch`` on
    every non-constant value (graph inputs, spill values), constants
    broadcast unbatched.  Integer convs take the
    :func:`conv2d_same_mm` throughput lowering inside the vmapped unit.
    """
    dfg = group.dfg
    order = dfg.topo_order()
    tiles = dict(group.dse.weight_tiles)
    needed = set(dfg.graph_inputs) | {
        v for v, val in dfg.values.items() if val.is_constant
    }

    def run(env):
        env = dict(env)
        for op in order:
            env[op.output] = _lower_node(
                op, dfg, env, interpret,
                weight_tiles=tiles.get(op.name, 1),
                fast_int_conv=batch is not None,
            )
        return {v: env[v] for v in dfg.graph_outputs}

    if batch is not None:
        axes = ({
            k: (None if dfg.values[k].is_constant else 0) for k in needed
        },)
        run = jax.vmap(run, in_axes=axes)

    def pick(env):
        return {k: v for k, v in env.items() if k in needed}

    if not jit:
        return lambda env: run(pick(env))
    jitted = jax.jit(run)

    def call(env):
        return jitted(pick(env))

    call.lower = lambda env: jitted.lower(pick(env))
    return call


def lower_group(group, *, interpret: bool | None = None, jit: bool = True,
                batch: int | None = None):
    """Lower one :class:`~repro.core.compile_driver.GroupSchedule` to a
    fused executable: ``fn(env) -> {output name: array}``.

    ``env`` must bind the group's graph inputs (spill values included)
    and constants.  All nodes trace into one jit unit — the TPU analogue
    of the group's single DATAFLOW kernel: intermediates stay in
    VMEM/registers, epilogues (activations, constant binops, fused
    pools) ride the producing kernel; weight-streamed nodes run the
    tiled const-buffer schedule.  Executables are cached (LRU) per
    group signature (+ interpret flag + batch bucket), so recompiling
    or re-running the same design never re-jits.

    ``batch`` asks for the vmapped batched executable at exactly that
    (bucketed!) batch extent: non-constant env entries must carry a
    leading axis of that extent, outputs gain one.  Callers round to a
    :data:`BATCH_BUCKETS` bucket first so the cache sees a bounded key
    set (``run_compiled_batched`` handles padding/chunking).
    """
    interpret = _auto_interpret(interpret)
    if not jit:
        return _build_group_fn(group, interpret, jit=False, batch=batch)
    key = _group_signature(group, interpret) + ("batch", batch)
    with _EXEC_CACHE_LOCK:
        fn = _EXEC_CACHE.get(key)
        if fn is None:
            exec_cache_stats["misses"] += 1
            event = "miss"
            # building is cheap (jax.jit defers tracing to first call),
            # so holding the lock keeps the insert/evict atomic
            fn = _build_group_fn(group, interpret, jit=True, batch=batch)
            while len(_EXEC_CACHE) >= _EXEC_CACHE_CAP:  # LRU eviction
                _EXEC_CACHE.popitem(last=False)
                exec_cache_stats["evictions"] += 1
            _EXEC_CACHE[key] = fn
        else:
            _EXEC_CACHE.move_to_end(key)
            exec_cache_stats["hits"] += 1
            event = "hit"
        stats_snapshot = dict(exec_cache_stats)
    tracer = instrument.current()
    if tracer.enabled:
        tracer.instant("jit_cache", cat="runtime",
                       args={"group": group.name, "event": event,
                             "batch": batch})
        tracer.counter("jit_cache", stats_snapshot)
    return fn


def cached_executable(group, *, interpret: bool | None = None,
                      batch: int | None = None):
    """The executable :func:`lower_group` cached for ``group`` at
    ``batch`` (a bucket, or ``None`` for per-sample), or ``None`` if
    none was built; LRU order and stats are left alone.  Its
    ``lower(env)`` gives the lowering of what ran."""
    key = _group_signature(group, _auto_interpret(interpret)) + ("batch", batch)
    with _EXEC_CACHE_LOCK:
        return _EXEC_CACHE.get(key)


def run_compiled(design, env, *, interpret: bool | None = None,
                 jit: bool = True, stats_out: dict | None = None) -> dict:
    """Execute a :class:`~repro.core.compile_driver.CompiledDesign` on
    the Pallas path: groups run in schedule order, chained through the
    value environment (the dict entries standing in for the DRAM spill
    buffers of ``host_schedule.cpp``).  Returns the graph outputs.

    ``stats_out`` (ISSUE 6): pass a dict to collect runtime counters —
    per-group wall time + jit-cache outcome, the exec-cache hit/miss
    delta of this call, and the modeled boundary-DMA bytes per group
    transition.  Counter collection (also active whenever a tracer is
    installed) blocks on each group's outputs so per-group wall times
    measure execution, not async dispatch; the uninstrumented path is
    untouched.
    """
    tracer = instrument.current()
    reg = _metrics.current()
    collect = stats_out is not None or tracer.enabled or reg.enabled
    env = dict(env)
    if not collect:
        for g in design.groups:
            env.update(lower_group(g, interpret=interpret, jit=jit)(env))
        return {v: env[v] for v in design.source.graph_outputs}
    m_wall = reg.histogram("run_group_wall_ms",
                           "per-group execution wall time (ms)",
                           labels=("group",))
    m_dma = reg.counter("run_dma_bytes_total",
                        "modeled boundary-DMA bytes", labels=("direction",))

    before = dict(exec_cache_stats)
    transitions = design.boundary_traffic()
    rows = []
    t_run0 = time.perf_counter()
    for idx, g in enumerate(design.groups):
        g_before = dict(exec_cache_stats)
        t0 = time.perf_counter()
        with tracer.span(f"run:{g.name}", cat="runtime") as sargs:
            out = lower_group(g, interpret=interpret, jit=jit)(env)
            out = jax.block_until_ready(out)
            env.update(out)
            row = {
                "group": g.name,
                "jit_cache": (
                    "hit" if exec_cache_stats["hits"] > g_before["hits"]
                    else "miss"
                    if exec_cache_stats["misses"] > g_before["misses"]
                    else "unjitted"
                ),
            }
            if idx < len(transitions):
                w, r = transitions[idx]
                row["dma_write_bytes"] = w
                row["dma_read_bytes"] = r
                tracer.counter("dma_bytes", {"write": w, "read": r})
                if reg.enabled:
                    m_dma.inc(w, direction="write")
                    m_dma.inc(r, direction="read")
            sargs.update(row)
        row["wall_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        if reg.enabled:
            m_wall.observe(row["wall_ms"], group=g.name)
        rows.append(row)
    if stats_out is not None:
        stats_out.update({
            "groups": rows,
            "wall_ms": round((time.perf_counter() - t_run0) * 1e3, 3),
            "exec_cache": {
                "hits": exec_cache_stats["hits"] - before["hits"],
                "misses": exec_cache_stats["misses"] - before["misses"],
            },
            "dma_write_bytes": sum(w for w, _ in transitions),
            "dma_read_bytes": sum(r for _, r in transitions),
        })
    return {v: env[v] for v in design.source.graph_outputs}


def run_compiled_batched(design, env, batch: int, *,
                         interpret: bool | None = None, jit: bool = True,
                         stats_out: dict | None = None) -> dict:
    """Execute a :class:`~repro.core.compile_driver.CompiledDesign` over
    a batch in one device dispatch per group (ISSUE 7): every
    non-constant entry of ``env`` carries a leading axis of extent
    ``batch``; constants are per-design.  Groups run in schedule order
    through vmapped+jitted executables (:func:`lower_group` with
    ``batch=``): the batch is padded up to the nearest
    :data:`BATCH_BUCKETS` bucket (zero rows, sliced off the outputs
    before return, still on device) and chunked above the top bucket,
    so each group compiles at most once per bucket.  Returns the graph
    outputs as *device* arrays with a leading batch axis — the host
    conversion happens once at the caller's boundary, never per sample.

    ``interpret=False`` is the explicit device-dispatch path (real
    Pallas kernels on an accelerator); the default auto-selects
    interpret mode on CPU exactly like :func:`run_compiled`.
    """
    interpret = _auto_interpret(interpret)
    tracer = instrument.current()
    reg = _metrics.current()
    collect = stats_out is not None or tracer.enabled or reg.enabled
    if reg.enabled:
        m_wall = reg.histogram("run_group_wall_ms",
                               "per-group execution wall time (ms)",
                               labels=("group",))
        m_dma = reg.counter("run_dma_bytes_total",
                            "modeled boundary-DMA bytes",
                            labels=("direction",))
    src = design.source
    stream = [k for k in env
              if k in src.values and not src.values[k].is_constant]
    const_env = {k: v for k, v in env.items() if k not in stream}

    before = dict(exec_cache_stats)
    transitions = design.boundary_traffic()
    group_rows: dict[str, dict] = {}
    buckets: list[int] = []
    t_run0 = time.perf_counter()
    chunks_out: list[dict] = []
    for start, n, bucket in _batch_chunks(batch):
        buckets.append(bucket)
        chunk_env = dict(const_env)
        for k in stream:
            v = jnp.asarray(env[k])[start:start + n]
            if bucket != n:
                chunk_env[k] = jnp.pad(
                    v, ((0, bucket - n),) + ((0, 0),) * (v.ndim - 1)
                )
            else:
                chunk_env[k] = v
        for idx, g in enumerate(design.groups):
            fn = lower_group(g, interpret=interpret, jit=jit, batch=bucket)
            if not collect:
                chunk_env.update(fn(chunk_env))
                continue
            g_before = dict(exec_cache_stats)
            t0 = time.perf_counter()
            with tracer.span(f"run:{g.name}", cat="runtime") as sargs:
                out = jax.block_until_ready(fn(chunk_env))
                chunk_env.update(out)
                row = group_rows.setdefault(
                    g.name, {"group": g.name, "wall_ms": 0.0, "samples": 0}
                )
                row["samples"] += n
                row["jit_cache"] = (
                    "hit" if exec_cache_stats["hits"] > g_before["hits"]
                    else "miss"
                    if exec_cache_stats["misses"] > g_before["misses"]
                    else "unjitted"
                )
                sargs.update({"group": g.name, "batch": n, "bucket": bucket,
                              "jit_cache": row["jit_cache"]})
                if idx < len(transitions):
                    w, r = transitions[idx]
                    sargs.update({"dma_write_bytes": w * n,
                                  "dma_read_bytes": r * n})
                    tracer.counter("dma_bytes",
                                   {"write": w * n, "read": r * n})
                    if reg.enabled:
                        m_dma.inc(w * n, direction="write")
                        m_dma.inc(r * n, direction="read")
            step_ms = (time.perf_counter() - t0) * 1e3
            if reg.enabled:
                m_wall.observe(step_ms, group=g.name)
            row["wall_ms"] = round(row["wall_ms"] + step_ms, 3)
        outs = {v: chunk_env[v] for v in src.graph_outputs}
        if bucket != n:  # drop padding rows, still on device
            outs = {k: v[:n] for k, v in outs.items()}
        chunks_out.append(outs)
    if len(chunks_out) == 1:
        result = chunks_out[0]
    else:
        result = {
            k: jnp.concatenate([c[k] for c in chunks_out], axis=0)
            for k in src.graph_outputs
        }
    if stats_out is not None:
        stats_out.update({
            "groups": list(group_rows.values()),
            "wall_ms": round((time.perf_counter() - t_run0) * 1e3, 3),
            "exec_cache": {
                "hits": exec_cache_stats["hits"] - before["hits"],
                "misses": exec_cache_stats["misses"] - before["misses"],
            },
            "batch_buckets": buckets,
            "dma_write_bytes": sum(w for w, _ in transitions) * batch,
            "dma_read_bytes": sum(r for _, r in transitions) * batch,
        })
    return result


# ---------------------------------------------------------------------------
# flash attention (GQA, causal, decode offset)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,        # (B, Hq, Sq, D)
    k: jax.Array,        # (B, Hkv, Sk, D)
    v: jax.Array,        # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = _auto_interpret(interpret)
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    if block_q is None or block_k is None:
        plan = plan_attention_blocks(seq_q=max(sq, 8), seq_k=max(sk, 8), head_dim=d)
        block_q = block_q or _pick_block(sq, plan.blocks["block_q"])
        block_k = block_k or _pick_block(sk, plan.blocks["block_k"])

    qf = (q * scale).reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    out = _flash.flash_attention_pallas(
        qf, kf, vf,
        group=group, heads_q=hq, heads_kv=hkv,
        block_q=block_q, block_k=block_k,
        causal=causal, q_offset=q_offset, interpret=interpret,
    )
    return out.reshape(b, hq, sq, d)


# ---------------------------------------------------------------------------
# fused MLP
# ---------------------------------------------------------------------------


def fused_mlp(
    x: jax.Array,                  # (..., D)
    w_gate: jax.Array | None,      # (D, F) | None
    w_up: jax.Array,               # (D, F)
    w_down: jax.Array,             # (F, D)
    *,
    act: str = "silu",
    block_m: int | None = None,
    block_f: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = _auto_interpret(interpret)
    lead = x.shape[:-1]
    d = x.shape[-1]
    f = w_up.shape[1]
    m = math.prod(lead) if lead else 1
    x2 = x.reshape(m, d)

    if block_m is None or block_f is None:
        plan = plan_matmul_blocks(m=max(m, 8), k=d, n=max(f, 8))
        block_m = block_m or _pick_block(m, plan.blocks["bm"])
        block_f = block_f or _pick_block(f, plan.blocks["bn"])

    out = _mlp.fused_mlp_pallas(
        x2, w_gate, w_up, w_down,
        block_m=block_m, block_f=block_f, act=act, interpret=interpret,
    )
    return out.reshape(*lead, d)


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------


def mamba2_ssd(
    x: jax.Array,          # (B, L, H, P)
    dt: jax.Array,         # (B, L, H)
    a: jax.Array,          # (H,)
    b_mat: jax.Array,      # (B, L, N)
    c_mat: jax.Array,      # (B, L, N)
    *,
    init_state: jax.Array | None = None,
    chunk: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    interpret = _auto_interpret(interpret)
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]
    if chunk is None:
        chunk = _pick_block(l, 128)
    assert l % chunk == 0, (l, chunk)
    s0 = (
        init_state
        if init_state is not None
        else jnp.zeros((bsz, h, p, n), jnp.float32)
    )
    return _ssd.mamba2_ssd_pallas(
        x, dt, a, b_mat, c_mat, s0, chunk=chunk, interpret=interpret
    )
