"""Reduction of a profiler trace to device busy time, idle gaps and top ops.

The reduction works on plain tuples so that it can be checked on a small
synthetic trace; ``load`` is the only part that reads JAX's ``.xplane.pb``.

- Device ops: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane.
- Host spans: events named ``bench.<what>`` on any host thread, written by
  ``jax.profiler.TraceAnnotation`` around each call the benchmark makes
  into the program; ``bench.window`` spans the measured window.
- Busy time of a device is the union of its op intervals inside the
  window; its idle share is 1 minus busy over the window.
- Each idle gap is cut at host span boundaries, and every piece is named
  by the bench spans open during it (``+``-joined, ``window`` left out),
  or ``none`` when the host was in no bench span.
- Top ops are summed by op and cut to ``OP_NAME`` characters of their HLO
  text.  A ``while`` op's time includes the ops of its body, which are
  listed too.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]                 # seconds on the trace clock
Op = Tuple[float, float, str]                  # start, end, name
Span = Tuple[float, float, str]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
OP_NAME = 100


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]                   # device id -> ops
    spans: List[Span]                          # host bench spans


@dataclasses.dataclass
class Summary:
    window: Interval
    busy_s: Dict[int, float]                   # per device
    idle_share: Dict[int, float]
    top_ops: List[Tuple[str, float]]
    idle_by_host: List[Tuple[str, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def mean_idle_share(self) -> float:
        return sum(self.idle_share.values()) / len(self.idle_share)


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    ops: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.extend((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9, e.name)
                             for e in line.events
                             if e.name.startswith("bench."))
    return Trace(ops=ops, spans=spans)


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Disjoint, sorted union of the intervals, clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a disjoint sorted union inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gaps(idle: Sequence[Interval], spans: Sequence[Span]
              ) -> Dict[str, float]:
    """Idle seconds by the bench spans open during them: one sweep over the
    spans' edges and the sorted, disjoint gaps."""
    edges = []
    for s, e, n in spans:
        if n != WINDOW and e > s:
            edges += [(s, 1, n[len("bench."):]), (e, -1, n[len("bench."):])]
    edges.sort(key=lambda edge: edge[0])
    active: Dict[str, int] = defaultdict(int)
    out: Dict[str, float] = defaultdict(float)

    def add(a: float, b: float) -> None:
        if b > a:
            names = sorted(n for n, c in active.items() if c > 0)
            out["+".join(names) if names else "none"] += b - a

    i = 0
    for g0, g1 in idle:
        while i < len(edges) and edges[i][0] <= g0:
            active[edges[i][2]] += edges[i][1]
            i += 1
        t = g0
        while i < len(edges) and edges[i][0] < g1:
            add(t, edges[i][0])
            t = edges[i][0]
            active[edges[i][2]] += edges[i][1]
            i += 1
        add(t, g1)
    return dict(out)


def summarize(trace: Trace, devices: Sequence[int],
              window: Optional[Interval] = None, top: int = 10) -> Summary:
    """Busy time and idle share of each of ``devices`` over the window
    (the ``bench.window`` span unless given), the ``top`` device ops by
    total time, and the idle time by what the host was doing."""
    if window is None:
        marks = [(s, e) for s, e, n in trace.spans if n == WINDOW]
        if not marks:
            raise ValueError("the trace has no bench.window span")
        window = marks[-1]
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty window {window}")
    busy_s, idle_share = {}, {}
    op_time: Dict[str, float] = defaultdict(float)
    idle_named: Dict[str, float] = defaultdict(float)
    for d in devices:
        ops = trace.ops.get(d, [])
        busy = union([(s, e) for s, e, _ in ops], lo, hi)
        busy_s[d] = sum(e - s for s, e in busy)
        idle_share[d] = 1.0 - busy_s[d] / (hi - lo)
        for s, e, name in ops:
            clipped = min(e, hi) - max(s, lo)
            if clipped > 0:
                op_time[name] += clipped
        for name, sec in name_gaps(gaps(busy, lo, hi), trace.spans).items():
            idle_named[name] += sec / len(devices)
    rank = [(name[:OP_NAME], sec) for name, sec in
            sorted(op_time.items(), key=lambda kv: -kv[1])[:top]]
    idle = sorted(idle_named.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window=window, busy_s=busy_s, idle_share=idle_share,
                   top_ops=rank, idle_by_host=idle)
