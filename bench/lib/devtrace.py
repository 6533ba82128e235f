"""From a profiler trace to device time: busy, idle, per op, per kernel.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a flat
list of events ``(plane, line, name, start_ns, dur_ns)``; everything else
works on such lists, so the reduction is checked on a small recorded
trace (``tests/fixtures``) without a chip.

Device planes are named ``/device:TPU:<n>``; the operations that ran on the
device are the events of their ``XLA Ops`` line.  Host planes hold the
host threads' spans (JAX's own and the benchmark's ``TraceAnnotation``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, int, int]   # plane, line, name, start_ns, dur_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def load(trace_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out: List[Event] = []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, int(ev.start_ns),
                            int(ev.duration_ns)))
    return out


def device_ops(events: Iterable[Event], device: Optional[int] = None) -> List[Event]:
    """Operations that ran on the device(s): one device, or all."""
    out = []
    for e in events:
        m = DEVICE_PLANE.match(e[0])
        if m and e[1] == OPS_LINE and (device is None or int(m.group(1)) == device):
            out.append(e)
    return out


def devices(events: Iterable[Event]) -> List[int]:
    return sorted({int(m.group(1)) for e in events if (m := DEVICE_PLANE.match(e[0]))})


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged ``[start, end)`` intervals."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(events: Iterable[Event], device: int, lo: int, hi: int) -> int:
    """Time in ``[lo, hi)`` in which some operation ran on ``device``."""
    ops = [(e[3], e[3] + e[4]) for e in device_ops(events, device)]
    return sum(e - s for s, e in clip(union(ops), lo, hi))


def op_time_ns(events: Iterable[Event], pattern: str, lo: int, hi: int,
               device: Optional[int] = None) -> int:
    """Device time of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(min(e[3] + e[4], hi) - max(e[3], lo)
               for e in device_ops(events, device)
               if rx.search(e[2]) and e[3] + e[4] > lo and e[3] < hi)


CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* =")


def op_label(name: str) -> str:
    """An op's trace name (its whole HLO line) cut to the instruction and
    its result type: ``%fusion.12 = bf16[16,32,32,32,64]``."""
    return name.split("{", 1)[0].split(" fusion(", 1)[0][:120]


def top_ops(events: Iterable[Event], device: int, lo: int, hi: int, n: int = 10):
    """The ``n`` ops with the most device time, as ``[label, seconds]``.
    Loops and calls are left out: the ops inside them are counted."""
    tot: Dict[str, int] = defaultdict(int)
    for e in device_ops(events, device):
        if e[3] + e[4] > lo and e[3] < hi and not CONTAINER.match(e[2]):
            tot[op_label(e[2])] += min(e[3] + e[4], hi) - max(e[3], lo)
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Iterable[Event], device: int, lo: int, hi: int, n: int = 10,
              min_gap_ns: int = 50_000):
    """Idle time on ``device`` in ``[lo, hi)``, put down to what the host
    was doing in the middle of each gap (the shortest host span covering
    it), summed by that span's name: ``[name, seconds]``, longest first.
    Gaps under ``min_gap_ns`` (between back-to-back ops) are summed apart."""
    import numpy as np

    events = list(events)
    busy = clip(union([(e[3], e[3] + e[4]) for e in device_ops(events, device)]), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    host = [e for e in events if e[0] == HOST_PLANE]
    starts = np.asarray([h[3] for h in host], np.int64)
    ends = starts + np.asarray([h[4] for h in host], np.int64)
    tot: Dict[str, int] = defaultdict(int)
    for s, e in gaps:
        if e - s < min_gap_ns:
            tot[f"(gaps under {min_gap_ns // 1000} us)"] += e - s
            continue
        mid = (s + e) // 2
        cover = np.nonzero((starts <= mid) & (ends > mid))[0]
        name = (host[cover[np.argmin(ends[cover] - starts[cover])]][2]
                if cover.size else "(no host span)")
        tot[name] += e - s
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def span(events: Iterable[Event], name: str) -> Tuple[int, int]:
    """``[start, end)`` of the host span ``name`` (the benchmark's window)."""
    hits = [e for e in events if e[0] == HOST_PLANE and e[2] == name]
    if len(hits) != 1:
        raise ValueError(f"expected one host span {name!r}, found {len(hits)}")
    return hits[0][3], hits[0][3] + hits[0][4]
