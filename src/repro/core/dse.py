"""MING lightweight DSE (paper Sec. IV-C, Eq. (1)).

The ILP::

    min   Σ_v Cycles(v)                       (objective: sum of node latencies)
    s.t.  u_ℓ | trip(ℓ)                       (unroll divisibility)
          Σ u_ℓ η_ℓd ≤ D_total                (DSP budget)
          Σ u_ℓ η_ℓb ≤ B_total                (BRAM budget)
          κ_src(s),s = κ_dst(s),s             (stream width consistency)

is solved *exactly* with branch-and-bound over divisor lattices — the
paper's point is that streaming collapses the design space enough that a
lightweight solver suffices; we lean on the same property (candidate sets
are divisor lists, typically a few dozen entries per node).  A search
that outgrows :data:`ILP_EXACT_BUDGET` nodes (deep groups of small
nodes) keeps the best assignment a fastest-first search finds in as
many again, and says so (``DseResult.exact``).

The decision variable is one unroll factor per dataflow node.  Reduction
loops unroll first (they add MACs/cycle without widening streams); once a
node's reduction trips are fully unrolled, further factors widen the
parallel (stream) loops.  The resulting *stream width* ``κ`` must agree
across every producer/consumer pair — Eq. (1)'s stream constraint.

``plan_tpu_blocks`` is the TPU dual: identical problem shape with
VMEM-bytes standing in for BRAM and MXU lane occupancy for DSPs
(DESIGN.md §2); its output drives the Pallas kernels' BlockSpecs.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

from .resource_model import (
    ExecMode,
    FpgaResourceModel,
    GraphEstimate,
    KV260_BRAM18K,
    KV260_DSP,
    TPU_V5E,
    TpuResourceModel,
    TpuSpec,
)
from .streaming import NodePlan, StreamingPlan


def divisors(n: int, cap: int | None = None) -> list[int]:
    out = []
    i = 1
    while i * i <= n:
        if n % i == 0:
            out.append(i)
            if i != n // i:
                out.append(n // i)
        i += 1
    out.sort()
    if cap is not None:
        out = [d for d in out if d <= cap]
    return out


# ---------------------------------------------------------------------------
# Node-level unroll semantics
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class UnrollChoice:
    """One candidate unroll factor for a node, with derived quantities.

    ``weight_tiles > 1`` marks a partial-weight-streaming variant: the
    const buffer is split into that many output-channel tiles, double
    buffered from DRAM — less BRAM, more cycles (the DRAM round trip)."""

    unroll: int
    stream_width: int     # κ: parallel lanes on this node's streams
    dsp: int
    bram: int
    cycles: int
    weight_tiles: int = 1


def _reduction_trip(plan: NodePlan) -> int:
    op = plan.op
    r = 1
    for d in plan.info.classes.reduction:
        r *= op.dim_extent(d)
    return max(r, 1)


def _parallel_trip(plan: NodePlan) -> int:
    """Product of the *unrollable* parallel dims only.

    Sliding spatial (window) dims are never unrolled — replicating the
    sliding loops would break the streaming arrival order (Sec. IV-B's
    point about polyhedral reordering) — so the widening budget is the
    channel-like parallel dims (e.g. c_out), matching the paper's DSP
    ladder (Table II: conv unroll ≈ K·K·C_in · C_out)."""
    op = plan.op
    window = set(plan.info.classes.window)
    p = 1
    for d in op.parallel_dims:
        if d not in window:
            p *= op.dim_extent(d)
    return max(p, 1)


def node_candidates(
    plan: NodePlan,
    model: FpgaResourceModel,
    d_total: int,
    max_unroll: int = 4096,
    *,
    weight_streaming: bool = False,
) -> list[UnrollChoice]:
    """Enumerate legal unroll factors for one node (Unroll Constr.),
    STREAMING mode (II=1, line-buffer BRAM only).

    Factors are products r*p with r | reduction_trip and p | parallel_trip;
    the stream width is p (reduction unrolling does not widen streams).

    ``weight_streaming=True`` additionally enumerates partial-weight-
    streaming variants (weight_tiles > 1 along the const-indexed output
    channels, stream width pinned to 1): strictly slower than their
    resident-weight twins, but the only shapes that fit when the weights
    alone approach the BRAM budget.
    """
    red = _reduction_trip(plan)
    par = _parallel_trip(plan)
    tileable = plan.weight_tileable_extent
    tile_opts = [1]
    if weight_streaming and tileable > 1 and plan.const_buffer_bits > 0:
        tile_opts += [t for t in divisors(tileable) if t > 1]
    choices: dict[tuple[int, int], UnrollChoice] = {}
    for t in tile_opts:
        for r in divisors(red, cap=max_unroll):
            for p in divisors(par, cap=max(max_unroll // r, 1)):
                u = r * p
                if u > max_unroll:
                    continue
                # widening streams before exhausting the reduction wastes
                # DSPs feeding idle lanes — prune dominated shapes
                if p > 1 and r != red:
                    continue
                # a streamed weight tile feeds one lane; widening the
                # stream would demand concurrent tiles (defeats the point)
                if t > 1 and p > 1:
                    continue
                cyc = model.node_cycles(plan, u, ii=1, weight_tiles=t)
                dsp = model.node_dsp(plan, u)
                if dsp > d_total:
                    continue
                bram = model.node_bram_streaming(plan, u, width=p, weight_tiles=t)
                prev = choices.get((u, t))
                cand = UnrollChoice(u, p, dsp, bram, cyc, weight_tiles=t)
                if prev is None or cand.cycles < prev.cycles:
                    choices[(u, t)] = cand
    return sorted(choices.values(), key=lambda c: (c.unroll, c.weight_tiles))


# ---------------------------------------------------------------------------
# Exact branch-and-bound ILP solver
# ---------------------------------------------------------------------------


@dataclass
class DseResult:
    unrolls: dict[str, int]
    stream_widths: dict[str, int]
    estimate: GraphEstimate
    objective_cycles: int
    dsp_used: int
    bram_used: int
    feasible: bool
    explored: int = 0
    #: nodes mapped with partial weight streaming (node -> tile count > 1)
    weight_tiles: dict[str, int] = field(default_factory=dict)
    #: the search proved this assignment optimal; ``False`` when it ran
    #: past :data:`ILP_EXACT_BUDGET` and kept the best one it found
    exact: bool = True


#: branch-and-bound nodes :func:`solve_ilp` visits in its exact order
#: before it falls back to a fastest-first search of the same size.
#: The paper suite, the zoo and VGG-16 stay far below it; a deep group
#: of small nodes (MobileNetV2's 1×1 and depthwise convs, each with a
#: dozen or more unroll factors) does not.
ILP_EXACT_BUDGET = 20_000


class _OverBudget(Exception):
    pass


def solve_ilp(
    plan: StreamingPlan,
    *,
    options=None,
    d_total: int | None = None,
    b_total: int | None = None,
    model: FpgaResourceModel | None = None,
    max_unroll: int | None = None,
    weight_streaming: bool = False,
) -> DseResult:
    """Solve Eq. (1) exactly for the STREAMING (MING) mode.

    ``options`` (a :class:`repro.core.CompileOptions`, duck-typed here
    to keep ``core.dse`` import-light) supplies the budgets, resource
    model, and unroll cap from its target — the same bundle the driver
    and the partition DP consume, so a caller never has to unpack the
    knobs positionally.  ``weight_streaming`` stays a per-solve flag:
    the partitioner flips it per slice (see below), independent of the
    bundle's policy.

    Inter-process FIFO BRAM (see
    :meth:`FpgaResourceModel.stream_fifo_blocks`) is assignment-independent
    and charged as a fixed overhead against ``b_total`` — fusing nodes
    (``repro.passes``) shrinks it before the solver ever runs.

    ``weight_streaming=True`` lets the candidate sets include partial
    weight streaming (see :func:`node_candidates`).  Off by default:
    streamed designs are strictly slower than their resident twins, so
    admitting them unconditionally would make *every* graph "feasible"
    and erase the partitioning signal.  The partitioner re-solves with
    it for any slice whose resident plan is over budget — that makes
    streamed groups a first-class choice its DP prices against cutting
    (ISSUE 3), while graphs that fit resident never pick up tiles.
    """
    if options is not None:
        if any(v is not None for v in (d_total, b_total, model, max_unroll)):
            raise ValueError(
                "pass either options=CompileOptions(...) or the loose "
                "d_total/b_total/model/max_unroll kwargs, not both"
            )
        tgt = options.target
        d_total, b_total = tgt.d_total, tgt.b_total
        model = tgt.model()
        max_unroll = options.resolved_max_unroll
    d_total = KV260_DSP if d_total is None else d_total
    b_total = KV260_BRAM18K if b_total is None else b_total
    max_unroll = 4096 if max_unroll is None else max_unroll
    model = model or FpgaResourceModel()
    nodes = plan.node_order()
    fifo_bram = model.stream_fifo_blocks(plan)
    b_nodes = b_total - fifo_bram
    cand: dict[str, list[UnrollChoice]] = {
        n.name: node_candidates(
            n, model, d_total, max_unroll, weight_streaming=weight_streaming
        )
        for n in nodes
    }

    def _infeasible(explored: int = 0) -> DseResult:
        unrolls = {n.name: 1 for n in nodes}
        est = model.estimate(plan, ExecMode.STREAMING, unrolls)
        return DseResult(unrolls, dict(unrolls), est, est.cycles,
                         est.dsp, est.bram, feasible=False, explored=explored)

    if any(not cs for cs in cand.values()) or b_nodes < 0:
        return _infeasible()

    # stream adjacency: consumer -> producers already placed (topo order)
    producers_of: dict[str, list[str]] = {n.name: [] for n in nodes}
    for s in plan.streams.values():
        if s.producer and s.consumer:
            producers_of[s.consumer].append(s.producer)

    order = [n.name for n in nodes]
    best: dict = {"cycles": math.inf, "assign": None, "explored": 0,
                  "limit": ILP_EXACT_BUDGET}
    # optimistic per-node lower bounds for pruning: cycles drive the
    # branch-and-bound incumbent check, bram/dsp prove infeasibility of a
    # partial assignment without enumerating its subtree (this is what
    # makes "the whole graph provably does not fit" cheap enough for the
    # layer-group partitioner to probe prefixes with).
    suffix_cycles = [0] * (len(order) + 1)
    suffix_bram = [0] * (len(order) + 1)
    suffix_dsp = [0] * (len(order) + 1)
    for i in range(len(order) - 1, -1, -1):
        cs = cand[order[i]]
        suffix_cycles[i] = suffix_cycles[i + 1] + min(c.cycles for c in cs)
        suffix_bram[i] = suffix_bram[i + 1] + min(c.bram for c in cs)
        suffix_dsp[i] = suffix_dsp[i + 1] + min(c.dsp for c in cs)

    if suffix_bram[0] > b_nodes or suffix_dsp[0] > d_total:
        return _infeasible()

    def recurse(
        i: int, assign: dict[str, UnrollChoice], dsp: int, bram: int, cycles: int
    ) -> None:
        best["explored"] += 1
        if best["explored"] > best["limit"]:
            raise _OverBudget
        if cycles + suffix_cycles[i] >= best["cycles"]:
            return
        if i == len(order):
            best["cycles"] = cycles
            best["assign"] = dict(assign)
            return
        name = order[i]
        # stream constraint: κ must equal every already-placed producer's κ
        widths = {assign[p].stream_width for p in producers_of[name] if p in assign}
        for choice in cand[name]:
            if widths and choice.stream_width not in widths:
                continue
            if dsp + choice.dsp + suffix_dsp[i + 1] > d_total:
                continue
            if bram + choice.bram + suffix_bram[i + 1] > b_nodes:
                continue
            assign[name] = choice
            recurse(i + 1, assign, dsp + choice.dsp, bram + choice.bram,
                    cycles + choice.cycles)
            del assign[name]

    exact = True
    try:
        recurse(0, {}, 0, 0, 0)
    except _OverBudget:
        # too many shapes to prove the optimum: search again with each
        # node's fastest candidates first, so that the first leaves are
        # good incumbents and the cycle bound prunes hard, for as many
        # nodes again, keeping the best assignment either search found
        exact = False
        for name in order:
            cand[name] = sorted(cand[name], key=lambda c: c.cycles)
        best["limit"] += ILP_EXACT_BUDGET
        try:
            recurse(0, {}, 0, 0, 0)
        except _OverBudget:
            pass

    if best["assign"] is None:
        # infeasible under the budgets — report unroll=1 estimate
        return _infeasible(best["explored"])

    assign: dict[str, UnrollChoice] = best["assign"]
    unrolls = {n: c.unroll for n, c in assign.items()}
    tiles = {n: c.weight_tiles for n, c in assign.items() if c.weight_tiles > 1}
    est = model.estimate(
        plan, ExecMode.STREAMING, unrolls,
        widths={n: c.stream_width for n, c in assign.items()},
        weight_tiles=tiles,
    )
    return DseResult(
        unrolls=unrolls,
        stream_widths={n: c.stream_width for n, c in assign.items()},
        estimate=est,
        objective_cycles=sum(c.cycles for c in assign.values()),
        dsp_used=sum(c.dsp for c in assign.values()),
        bram_used=sum(c.bram for c in assign.values()) + fifo_bram,
        feasible=True,
        explored=best["explored"],
        weight_tiles=tiles,
        exact=exact,
    )


def solve_materialized(
    plan: StreamingPlan,
    *,
    d_total: int = KV260_DSP,
    b_total: int | None = None,
    model: FpgaResourceModel | None = None,
) -> DseResult:
    """StreamHLS-like DSE: unroll under the DSP budget only (the paper's
    observation: StreamHLS's DSE tracks DSPs but not BRAM, which is what
    lets its designs blow past edge BRAM limits)."""
    model = model or FpgaResourceModel()
    unrolls: dict[str, int] = {}
    widths: dict[str, int] = {}
    budget = d_total
    for np_ in plan.node_order():
        red = _reduction_trip(np_)
        # greedy: largest reduction-unroll fitting the remaining DSP budget
        u = 1
        for cand_u in divisors(red):
            dsp = model.node_dsp(np_, cand_u)
            if dsp <= max(budget, 0):
                u = cand_u
        budget -= model.node_dsp(np_, u)
        unrolls[np_.name] = u
        widths[np_.name] = 1
    est = model.estimate(plan, ExecMode.MATERIALIZED_DATAFLOW, unrolls)
    feasible = b_total is None or est.bram <= b_total
    return DseResult(unrolls, widths, est, est.cycles, est.dsp, est.bram,
                     feasible=feasible)


# ---------------------------------------------------------------------------
# TPU dual: Pallas block-shape selection under (VMEM, MXU) budgets
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TpuBlockPlan:
    """Chosen BlockSpec tile sizes for one fused kernel."""

    kind: str
    blocks: dict
    est_cycles: float
    vmem_bytes: int
    mxu_util: float


def _pow2_multiples(base: int, limit: int) -> list[int]:
    out = []
    v = base
    while v <= limit:
        out.append(v)
        v *= 2
    return out or [base]


def plan_attention_blocks(
    *,
    seq_q: int,
    seq_k: int,
    head_dim: int,
    vmem_budget: int | None = None,
    spec: TpuSpec = TPU_V5E,
    bytes_per_el: int = 2,
) -> TpuBlockPlan:
    """Pick (block_q, block_k) for KV-streaming flash attention.

    BRAM constraint → resident q/k/v tiles + accumulators ≤ VMEM;
    DSP constraint → tiles 128-aligned so MXU lanes are fully claimed;
    objective → minimize estimated cycles (favors the largest feasible
    k-tile: fewer stream iterations, better pipelining)."""
    model = TpuResourceModel(spec)
    budget = vmem_budget or spec.vmem_bytes
    best: Optional[TpuBlockPlan] = None
    for bq in _pow2_multiples(min(128, seq_q), min(seq_q, 1024)):
        for bk in _pow2_multiples(min(128, seq_k), min(seq_k, 2048)):
            if seq_q % bq or seq_k % bk:
                continue
            e = model.attention_blocks(
                block_q=bq, block_k=bk, head_dim=head_dim, bytes_per_el=bytes_per_el
            )
            if e.vmem_bytes > budget:
                continue
            steps = (seq_q // bq) * (seq_k // bk)
            total = e.cycles * steps
            if best is None or total < best.est_cycles or (
                total == best.est_cycles and e.vmem_bytes < best.vmem_bytes
            ):
                best = TpuBlockPlan(
                    "attention", {"block_q": bq, "block_k": bk},
                    total, e.vmem_bytes, e.mxu_util,
                )
    assert best is not None, "no feasible attention tiling"
    return best


def plan_matmul_blocks(
    *,
    m: int,
    k: int,
    n: int,
    vmem_budget: int | None = None,
    spec: TpuSpec = TPU_V5E,
    bytes_per_el: int = 2,
) -> TpuBlockPlan:
    """Pick (bm, bk, bn) for a streamed matmul (fused-MLP building block)."""
    model = TpuResourceModel(spec)
    budget = vmem_budget or spec.vmem_bytes
    best: Optional[TpuBlockPlan] = None
    for bm in _pow2_multiples(min(128, m), min(m, 1024)):
        for bn in _pow2_multiples(min(128, n), min(n, 1024)):
            for bk in _pow2_multiples(min(128, k), min(k, 2048)):
                if m % bm or n % bn or k % bk:
                    continue
                e = model.matmul_block(bm, bk, bn, bytes_per_el)
                if e.vmem_bytes > budget:
                    continue
                steps = (m // bm) * (n // bn) * (k // bk)
                total = e.cycles * steps
                key = (total, -e.mxu_util, e.vmem_bytes)
                if best is None or key < (best.est_cycles, -best.mxu_util,
                                          best.vmem_bytes):
                    best = TpuBlockPlan(
                        "matmul", {"bm": bm, "bk": bk, "bn": bn},
                        total, e.vmem_bytes, e.mxu_util,
                    )
    assert best is not None, "no feasible matmul tiling"
    return best


def plan_conv_rows(
    *,
    h: int,
    w: int,
    c_in: int,
    c_out: int,
    kh: int,
    kw: int,
    vmem_budget: int | None = None,
    spec: TpuSpec = TPU_V5E,
    bytes_per_el: int = 1,
) -> TpuBlockPlan:
    """Rows-per-block for the line-buffer streaming conv kernel.

    The VMEM working set is the TPU line buffer: (rows + kh - 1) input
    rows + weights + rows of output — directly mirroring the paper's
    (K-1)×N BRAM line buffer."""
    budget = vmem_budget or spec.vmem_bytes
    best: Optional[TpuBlockPlan] = None
    rows = 1
    while rows <= h:
        if h % rows == 0:
            in_rows = (rows + kh - 1) * w * c_in * bytes_per_el * 2
            w_bytes = kh * kw * c_in * c_out * bytes_per_el
            out_rows = rows * w * c_out * 4  # int32/fp32 accumulators
            vmem = in_rows + w_bytes + out_rows
            if vmem <= budget:
                macs = rows * w * c_out * kh * kw * c_in
                cycles = macs / (spec.mxu_dim * spec.mxu_dim)
                steps = h // rows
                cand = TpuBlockPlan(
                    "conv_rows", {"rows": rows}, cycles * steps, vmem, 1.0
                )
                # prefer more rows (fewer grid steps, better DMA pipelining)
                if best is None or cand.blocks["rows"] > best.blocks["rows"]:
                    best = cand
        rows *= 2
    assert best is not None, "no feasible conv row tiling"
    return best


#: VMEM the depthwise kernel's working set may take: half the scoped
#: budget, so the pipeline's double buffers never crowd it out
DW_VMEM_BUDGET = TPU_V5E.vmem_bytes // 2


def plan_dwconv_rows(
    *,
    h: int,
    w: int,
    c: int,
    kh: int,
    stride: int = 1,
    vmem_budget: int | None = None,
    spec: TpuSpec = TPU_V5E,
    bytes_per_el: int = 4,
) -> TpuBlockPlan:
    """Rows-per-block for the depthwise streaming kernel
    (``kernels/depthwise_stream.py``) over a padded frame of ``h`` rows
    and ``w`` columns.

    The VMEM working set of one grid step, every array lane-padded to
    128 channels: the input block double-buffered, the line buffer
    (``kh - s`` carried rows, the block, ``s - 1`` spare rows), the
    output block double-buffered and the accumulator.  The fewest row
    blocks whose working set fits ``vmem_budget``
    (:data:`DW_VMEM_BUDGET` by default) wins, and the rows are then
    spread evenly over them (a multiple of ``stride``), so the frame
    pads by less than one stride-rounded block.  The work is
    ``K·K`` lane-wise MACs per output point, on the VPU."""
    budget = vmem_budget or DW_VMEM_BUDGET
    # the kernel's channel block: 128 lanes where they divide, else all
    block = spec.mxu_dim if c % spec.mxu_dim == 0 else c
    lanes = -(-block // spec.mxu_dim) * spec.mxu_dim
    w_out = -(-(w - kh + 1) // stride)
    carry = max(kh - stride, 0)
    row_in = w * lanes * bytes_per_el
    row_out = w_out * lanes * 4  # float32 / int32 accumulators

    def vmem(rows: int) -> int:
        r_out = rows // stride
        return (2 * rows * row_in + (carry + rows + stride - 1) * row_in
                + 3 * r_out * row_out)

    top = -(-h // stride) * stride
    rows = stride
    while rows + stride <= top and vmem(rows + stride) <= budget:
        rows += stride
    blocks = -(-h // rows)
    rows = -(-(-(-h // blocks)) // stride) * stride
    macs = -(-h // stride) * w_out * c * kh * kh
    cycles = macs / spec.vpu_lanes
    return TpuBlockPlan("dwconv_rows", {"rows": rows, "blocks": blocks},
                        cycles, vmem(rows), 0.0)
