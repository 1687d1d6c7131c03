"""Device time of one kernel, read from a loaded trace by the kernel's
own name.

A device operation's text in the trace is its HLO instruction,
``%<name> = <type> <opcode>(<operands>)``, and the operands are named
too: a slice or fusion that reads a kernel's output carries the
kernel's name as well.  So a kernel's time is the time of the
operations whose *own* name, left of `` = ``, holds the kernel's
``pallas_call`` name (``%vmap_ming_dwconv2d_stream_.3`` under the
batched executables' ``vmap``), clipped to the measured window and
averaged over the devices traced, as ``trace_reduce.reduce`` sums a
marker's time.
"""
from __future__ import annotations

from bench import trace_reduce

#: the depthwise kernel's ``pallas_call`` name
#: (``repro.kernels.depthwise_stream``); the stream conv kernel's,
#: ``ming_conv2d_stream``, is not a part of it
DWCONV_KERNEL = "ming_dwconv2d_stream"


def own_name(op_text: str) -> str:
    """The instruction's own name: what precedes `` = ``."""
    return op_text.split(" = ", 1)[0]


def kernel_seconds(trace: dict, kernel: str) -> float:
    """Seconds of the window the operations named ``kernel`` took."""
    lo, hi = trace["window"]
    total = 0.0
    for ops in trace["devices"]:
        spans = [(s, s + d) for name, s, d in ops
                 if kernel in own_name(name)]
        total += sum(e - s for s, e in trace_reduce.clip(spans, lo, hi))
    return total / max(len(trace["devices"]), 1) / 1e9
