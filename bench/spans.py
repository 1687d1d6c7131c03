"""Helpers of the readers that take their numbers from the program's
own spans and counters: idle gaps named by a ``ming:*`` span, and
counter families in the engine's registry snapshot.

A program without those counters (an earlier commit) leaves nothing to
find: the counter readers then read ``None``.  An idle-gap reader reads
0.0 for a span that no gap carries only where every gap is listed;
where the reduction lists its ten longest and the span is not among
them, its idle time is unknown, and the reader reads ``None``.
"""
from __future__ import annotations

#: innermost host events that say no more than "inside the benchmark's
#: call": the benchmark's own spans (``bench:*``), the runner's
#: outermost span, and no host event at all
UNATTRIBUTED = ("ming:run", "idle host")

#: how many idle-gap names a reduction lists (``trace_reduce.reduce``'s
#: ``top``, as the harness calls it)
LISTED = 10


def idle_in_pct(run, span: str):
    """100 × the traced window's idle seconds whose innermost host event
    is ``span``, over the window; 0.0 where fewer than :data:`LISTED`
    gaps are listed and none is ``span``'s; ``None`` where the list is
    full without it (its share is unknown), or where the trace holds no
    device operation."""
    red = run.reduced
    if not red or not red["window_s"] or red["busy_s"] <= 0:
        return None
    mine = [s for name, s in red["idle_gaps"] if name == span]
    if not mine and len(red["idle_gaps"]) >= LISTED:
        return None
    return 100.0 * sum(mine) / red["window_s"]


def idle_unattributed_pct(run):
    """100 × the idle seconds no program span names: gaps whose
    innermost host event is a ``bench:*`` span, ``ming:run`` or no host
    event, plus the idle time outside the listed gaps, over the
    window; ``None`` where the trace holds no device operation."""
    red = run.reduced
    if not red or not red["window_s"] or red["busy_s"] <= 0:
        return None
    listed = sum(s for _, s in red["idle_gaps"])
    vague = sum(s for name, s in red["idle_gaps"]
                if name.startswith("bench:") or name in UNATTRIBUTED)
    idle = red["window_s"] - red["busy_s"]
    return 100.0 * (vague + max(idle - listed, 0.0)) / red["window_s"]


def counter_total(snapshot: dict | None, family: str, **labels):
    """The sum of a counter family's rows in a registry snapshot that
    carry ``labels``, or ``None`` where the family is absent."""
    if not snapshot:
        return None
    fam = snapshot.get("counters", {}).get(family)
    if fam is None:
        return None
    return sum(r["value"] for r in fam["values"]
               if all(r["labels"].get(k) == v for k, v in labels.items()))
