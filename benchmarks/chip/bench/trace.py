"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time (the union of op intervals),
device time by op name, collective time with no concurrent compute, and
idle gaps attributed to the harness's host spans.

Device planes are the ``/device:TPU:<n>`` planes; their ops are the
events of the ``XLA Ops`` line.  On a TPU an op event is named by its whole
HLO instruction (``%topk_logits_tiles.1 = (f32[...]) custom-call(...)``);
the reduction keeps the instruction's own name (``topk_logits_tiles.1``),
so that a pattern never matches an operand.  Host spans are events whose name starts
with ``SPAN_PREFIX`` on any line of the ``/host:CPU`` plane (the
``jax.profiler.TraceAnnotation``s the harness opens).  All times are in
nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HLO_NAME = re.compile(r"^%?([^\s=]+)")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|^send|^recv")
# control-flow ops span the ops of their bodies: they are no compute of
# their own
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction's own name, without its operands."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


class Trace:
    """One profiler session, read once.

    ``ops[dev]``: [(start, end, name)] of device ``dev``'s ops;
    ``spans``: [(start, end, name)] of the harness's host spans.
    """

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.ops: Dict[int, List[Tuple[float, float, str]]] = {}
        self.spans: List[Tuple[float, float, str]] = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                evs = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs += [(e.start_ns, e.end_ns, op_name(e.name))
                                for e in line.events]
                self.ops[int(m.group(1))] = sorted(evs)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.spans += [(e.start_ns, e.end_ns, e.name)
                                   for e in line.events
                                   if e.name.startswith(SPAN_PREFIX)]
        self.spans.sort()

    # ------------------------------------------------------------ window

    def window(self) -> Interval:
        """The harness's measured-window span (the first, if several)."""
        w = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if not w:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        return w[0]

    def devices(self) -> List[int]:
        """Devices that ran at least one op in the window."""
        lo, hi = self.window()
        return [d for d, evs in sorted(self.ops.items())
                if clip([(s, e) for s, e, _ in evs], lo, hi)]

    def busy(self, dev: int) -> List[Interval]:
        lo, hi = self.window()
        return clip(union([(s, e) for s, e, _ in self.ops[dev]]), lo, hi)

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices used."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(total(self.busy(d)) for d in devs) / len(devs) / 1e9

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    # --------------------------------------------------------------- ops

    def op_time_s(self, pattern: str) -> float:
        """Device seconds of ops whose name matches ``pattern`` (a regular
        expression searched in the name), summed over devices, window
        clipped."""
        rx = re.compile(pattern)
        lo, hi = self.window()
        return sum(total(clip([(s, e)], lo, hi))
                   for evs in self.ops.values() for s, e, n in evs
                   if rx.search(n)) / 1e9

    def op_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        lo, hi = self.window()
        return sum(1 for evs in self.ops.values() for s, e, n in evs
                   if rx.search(n) and e > lo and s < hi)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """[(op name, device seconds averaged over devices)] by time.  A
        control-flow op's time includes that of the ops in its body."""
        lo, hi = self.window()
        by: Dict[str, float] = defaultdict(float)
        devs = self.devices()
        for d in devs:
            for s, e, name in self.ops[d]:
                by[name] += total(clip([(s, e)], lo, hi))
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [(name, t / 1e9 / max(len(devs), 1)) for name, t in top]

    def exposed_collective_s(self) -> float:
        """Seconds, averaged over devices, in which a collective op ran
        and no other op did (a control-flow op that spans it does not
        count)."""
        lo, hi = self.window()
        devs = self.devices()
        out = 0.0
        for d in devs:
            coll = union([(s, e) for s, e, n in self.ops[d]
                          if COLLECTIVE.search(n)])
            comp = union([(s, e) for s, e, n in self.ops[d]
                          if not COLLECTIVE.search(n)
                          and not CONTAINER.match(n)])
            out += total(clip(subtract(coll, comp), lo, hi))
        return out / max(len(devs), 1) / 1e9

    # -------------------------------------------------------------- gaps

    def idle_gaps(self, dev: Optional[int] = None) -> List[Interval]:
        if dev is None:
            devs = self.devices()
            if not devs:
                return []
            dev = devs[0]
        lo, hi = self.window()
        return subtract([(lo, hi)], self.busy(dev))

    def gap_owner(self, s: float, e: float) -> str:
        """The innermost harness span (other than the window) that covers
        most of the gap [s, e); "none" when no span overlaps it."""
        best, best_key = "none", None
        for ss, se, name in self.spans:
            if name == WINDOW_SPAN or se <= s or ss >= e:
                continue
            ov = min(se, e) - max(ss, s)
            key = (ov, -(se - ss))          # most overlap, then innermost
            if best_key is None or key > best_key:
                best, best_key = name, key
        return best

    def idle_by_span(self, n: int = 10) -> List[Tuple[str, float]]:
        """[(host span name, idle device seconds)] over the first device's
        idle gaps, each gap attributed to the span that covers most of
        it; the largest first."""
        by: Dict[str, float] = defaultdict(float)
        for s, e in self.idle_gaps():
            by[self.gap_owner(s, e)] += (e - s)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [(name, t / 1e9) for name, t in top]
