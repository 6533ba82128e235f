"""The program's own host spans on the profiler's clock, reduced to
per-round host time and to the part of the window in which the chip idles
while the host is inside them.

The round puts named spans on the host plane (``fl.cohort``,
``fl.inputs``, ``fl.data-pool.insert``, ...: PERF.md, "Spans and
counters").  A span is matched by its exact name; a trace without it, as
of a program that does not have it, gives ``None``, not an error.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import devtrace

Interval = Tuple[int, int]

#: the round's host prep: selection, clients and their data (``fl.cohort``);
#: batch indices, pool gather and inserts, weights, program lookup (``fl.inputs``)
PREP = ("fl.cohort", "fl.inputs")


def host_spans(events: Iterable[devtrace.Event], names: Sequence[str], lo: int,
               hi: int) -> List[Interval]:
    """``[start, end)`` of the host spans named in ``names`` that start in
    ``[lo, hi)``."""
    names = set(names)
    return [(e[3], e[3] + e[4]) for e in events
            if e[0] == devtrace.HOST_PLANE and e[2] in names and lo <= e[3] < hi]


def _length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Time covered by both of two merged, sorted interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, e - s)
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def per_round_ms(ctx: Dict, names: Sequence[str]) -> Optional[float]:
    """Summed duration of the spans named in ``names`` that start in the
    window, per window round, in milliseconds."""
    found = host_spans(ctx["events"], names, ctx["lo"], ctx["hi"])
    if not found or not ctx["rounds"]:
        return None
    return _length(found) / 1e6 / ctx["rounds"]


def idle_inside_share(ctx: Dict, names: Sequence[str]) -> Optional[float]:
    """Percent of the window in which no operation runs on the chip while
    the host is inside one of the spans named in ``names``, averaged over
    the cell's chips as ``device_idle_share`` is; never above it."""
    lo, hi = ctx["lo"], ctx["hi"]
    found = host_spans(ctx["events"], names, lo, hi)
    if not found or hi <= lo or not ctx["trace_chips"]:
        return None
    inside = devtrace.clip(devtrace.union(found), lo, hi)
    idle = []
    for d in ctx["trace_chips"]:
        ops = [(e[3], e[3] + e[4]) for e in devtrace.device_ops(ctx["events"], d)]
        busy = devtrace.clip(devtrace.union(ops), lo, hi)
        idle.append(_length(inside) - overlap_ns(inside, busy))
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
