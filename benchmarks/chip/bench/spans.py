"""The program's own host spans (``repro.*``, ``repro/utils/tracing.py``)
read from the run's ``.xplane.pb``, and the readers of the per-layer
metrics built on them.

Spans are read from the one host line (thread) that ran the harness's
``bench.window`` span, with their args (the event's stats).  Idle is
charged by instant: every instant of device idle in the window
(``Trace.idle_gaps``) goes to the innermost ``repro.*`` span open at that
instant on that thread, the latest-started one still open, or to
``outside`` when none is.  One exception: a ``gen.forward`` returns
before its batch's inputs are on the device, and the idle from its end to
the first device op that starts after it began is the device's wait for
those inputs, whatever the host does meanwhile; it goes to ``input``.
The charges so sum to the window's whole device idle.  All times are in
nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import os
import statistics
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness import log
from bench.trace import (WINDOW_SPAN, Interval, clip, find_xplane, subtract,
                         total, union)

PREFIX = "repro."
OUTSIDE = "outside"
INPUT = "input"
STORE = PREFIX + "store."
FORWARD = PREFIX + "gen.forward"
APPEND = STORE + "append_shard"
FETCH = STORE + "fetch"
SOURCE = PREFIX + "train.source"
UPDATE = PREFIX + "train.update"
# the harness's own span around its draw, inside ``train.source``
HARNESS_DRAW = "bench.source_next"
# teacher-gen's owners of idle, in the order the split is logged
GEN_OWNERS = [PREFIX + o for o in (
    "store.fetch", "store.write", "store.checksum", "store.manifest",
    "store.append_shard", "gen.ledger", "gen.forward")] + [INPUT, OUTSIDE]


@dataclass
class Span:
    start: float
    end: float
    name: str
    args: Dict = field(default_factory=dict)


@dataclass
class Commit:
    """One shard's ``store.append_shard``, its ``store.fetch`` (nested in
    it) and the ``gen.forward`` of the same ``shard`` that began last
    before it (None where none was recorded)."""
    forward: Optional[Span]
    append: Span
    fetch: Optional[Span]


class HostSpans:
    """``window``: the ``bench.window`` interval; ``spans``: the
    ``repro.*`` spans of the window's thread, by start."""

    def __init__(self, window: Interval, spans: List[Span]):
        self.window = window
        self.spans = sorted(spans, key=lambda s: (s.start, -s.end))

    @classmethod
    def from_file(cls, path: str) -> "HostSpans":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                evs = list(line.events)
                win = [e for e in evs if e.name == WINDOW_SPAN]
                if not win:
                    continue
                return cls((win[0].start_ns, win[0].end_ns),
                           [Span(e.start_ns, e.end_ns, e.name,
                                 dict(e.stats))
                            for e in evs if e.name.startswith(PREFIX)])
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def ending_in_window(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [s for s in self.named(name) if lo < s.end <= hi]

    def time_in_window(self, name: str) -> float:
        lo, hi = self.window
        return sum(total(clip([(s.start, s.end)], lo, hi))
                   for s in self.named(name))

    def less_children(self, parent: str, child: str) -> List[float]:
        """For each ``parent`` span ending in the window: its duration
        less that of the ``child`` spans nested in it."""
        kids = self.named(child)
        out = []
        for p in self.ending_in_window(parent):
            inner = sum(k.end - k.start for k in kids
                        if k.start >= p.start and k.end <= p.end)
            out.append(p.end - p.start - inner)
        return out

    def commits(self) -> List[Commit]:
        """The shards whose ``store.append_shard`` ends in the window."""
        fetches = self.named(FETCH)
        out = []
        for a in self.ending_in_window(APPEND):
            fwd = [f for f in self.named(FORWARD)
                   if f.args.get("shard") == a.args.get("shard")
                   and f.start <= a.start]
            inner = [f for f in fetches
                     if a.start <= f.start and f.end <= a.end]
            out.append(Commit(fwd[-1] if fwd else None, a,
                              inner[0] if inner else None))
        return out

    def input_waits(self, op_starts: Sequence[float]) -> List[Interval]:
        """For each ``gen.forward``: from its end to the first device op
        (of the sorted ``op_starts``) that starts after it began, where
        that op starts after the forward returned.  The generation loop
        dispatches a batch only once the one before is committed, so that
        op is the batch's first."""
        out = []
        for f in self.named(FORWARD):
            i = bisect.bisect_left(op_starts, f.start)
            if i < len(op_starts) and op_starts[i] > f.end:
                out.append((f.end, op_starts[i]))
        return out

    def owners(self) -> List[Tuple[float, float, str]]:
        """The window cut at every span boundary, each piece named by
        its innermost open span (``outside`` where none is open)."""
        lo, hi = self.window
        cuts = sorted({lo, hi} | {t for s in self.spans
                                  for t in (s.start, s.end) if lo < t < hi})
        out, open_, i = [], [], 0
        for a, b in zip(cuts, cuts[1:]):
            # spans are sorted by start, the outer first on a tie, so the
            # last one open is the innermost
            while i < len(self.spans) and self.spans[i].start <= a:
                open_.append(self.spans[i])
                i += 1
            open_ = [s for s in open_ if s.end >= b]
            name = open_[-1].name if open_ else OUTSIDE
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
        return out

    def charge(self, gaps: List[Interval],
               inputs: List[Interval] = ()) -> Dict[str, float]:
        """{owner: idle ns}: idle inside the merged ``inputs`` goes to
        ``input``, each other instant to the innermost open span."""
        by: Dict[str, float] = defaultdict(float)
        gaps = union(gaps)
        rest = subtract(gaps, union(list(inputs)))
        if total(rest) < total(gaps):
            by[INPUT] = total(gaps) - total(rest)
        owners, j = self.owners(), 0
        for s, e in rest:
            while j < len(owners) and owners[j][1] <= s:
                j += 1
            k = j
            while k < len(owners) and owners[k][0] < e:
                a, b, name = owners[k]
                by[name] += min(b, e) - max(a, s)
                k += 1
        return dict(by)


def host_spans(run) -> HostSpans:
    """The run's spans, read once and kept in ``run.extra``."""
    if "host_spans" not in run.extra:
        run.extra["host_spans"] = HostSpans.from_file(
            find_xplane(os.path.join(run.out_dir, "trace")))
    return run.extra["host_spans"]


def idle_charges(run, res, tr) -> Optional[Dict[str, float]]:
    """{owner: idle ns} of the window, or None without a device plane.
    Logs teacher-gen's split per shard once."""
    if not tr.devices():
        return None
    if "idle_charges" not in run.extra:
        hs = host_spans(run)
        ops = tr.ops[tr.devices()[0]]
        by = hs.charge(tr.idle_gaps(),
                       hs.input_waits([s for s, _, _ in ops]))
        run.extra["idle_charges"] = by
        _log_gen_split(hs, by, tr)
    return run.extra["idle_charges"]


def _log_gen_split(hs: HostSpans, by: Dict[str, float], tr):
    commits = hs.commits()
    if not commits:
        return
    n, win = len(commits), hs.window[1] - hs.window[0]
    names = GEN_OWNERS + sorted(set(by) - set(GEN_OWNERS))
    log("idle by owner, per shard (ms) / % of window: " + ", ".join(
        f"{nm.replace(PREFIX, '')} {by.get(nm, 0.0) / n / 1e6:.3f} / "
        f"{100.0 * by.get(nm, 0.0) / win:.3f}%" for nm in names))
    idle = 100.0 * sum(by.values()) / win
    log(f"idle charged {idle:.4f}% of window, device idle "
        f"{100.0 * (1.0 - tr.busy_s() / tr.window_s()):.4f}%")
    # per shard, its batch's device ops are those that start from its
    # forward's start to its fetch's end: one clock means the fetch
    # returns only after the last of them has ended
    ops = tr.ops[tr.devices()[0]]
    starts = [s for s, _, _ in ops]
    lag, slack = [], []
    for c in commits:
        if c.forward is None or c.fetch is None:
            continue
        i = bisect.bisect_left(starts, c.forward.start)
        j = bisect.bisect_left(starts, c.fetch.end)
        if i < j:
            lag.append(starts[i] - c.forward.end)
            slack.append(c.fetch.end - max(e for _, e, _ in ops[i:j]))
    if slack:
        log(f"fetch end less its batch's last device op end (us): min "
            f"{min(slack) / 1e3:.1f}, median "
            f"{statistics.median(slack) / 1e3:.1f} over {len(slack)} "
            f"shards")
        log(f"batch's first device op after its forward returned (ms): "
            f"min {min(lag) / 1e6:.3f}, median "
            f"{statistics.median(lag) / 1e6:.3f}")


# ----------------------------------------------------------- readers

def idle_share(run, res, tr, owner) -> Optional[float]:
    """Device idle charged to owners that ``owner(name)`` accepts, over
    the window, in %; None where no such span was recorded (a program
    without these spans)."""
    hs = host_spans(run)
    by = idle_charges(run, res, tr)
    if by is None or not any(owner(s.name) for s in hs.spans):
        return None
    lo, hi = hs.window
    return 100.0 * sum(v for k, v in by.items() if owner(k)) / (hi - lo)


def idle_ledger(run, res, tr):
    """Device idle charged to ``repro.gen.ledger``, over the window."""
    return idle_share(run, res, tr, lambda k: k == PREFIX + "gen.ledger")


def idle_store(run, res, tr):
    """Device idle charged to any ``repro.store.*`` span, over the
    window."""
    return idle_share(run, res, tr, lambda k: k.startswith(STORE))


def store_write_ms(run, res, tr):
    """Median over the window's shards of ``repro.store.append_shard``
    less its ``repro.store.fetch`` child, in ms: the store's host time
    per shard once its data is on the host."""
    hs = host_spans(run)
    shards = hs.ending_in_window(APPEND)
    if not shards:
        return None
    frames = sum(int(s.args.get("frames", 0)) for s in shards)
    log(f"store spans: {len(shards)} shards, {frames} frames committed "
        f"(window count: {res.get('frames')} frames)")
    names = [o for o in GEN_OWNERS if o.startswith(PREFIX)]
    log("host time per shard (ms): " + ", ".join(
        f"{o.replace(PREFIX, '')} "
        f"{hs.time_in_window(o) / len(shards) / 1e6:.3f}" for o in names))
    return statistics.median(hs.less_children(APPEND, FETCH)) / 1e6


def input_wait(run, res, tr):
    """Device idle charged to ``repro.train.source``, the Trainer's wait
    for its next batch, over the window, in %.  Logs the wait's host
    time beside it, and how much of it the harness's own draw takes."""
    share = idle_share(run, res, tr, lambda k: k == SOURCE)
    hs = host_spans(run)
    updates = hs.ending_in_window(UPDATE)
    draws = hs.ending_in_window(SOURCE)
    if share is None or not updates or not draws:
        return share
    harness = [(s, e) for s, e, n in tr.spans if n == HARNESS_DRAW]
    inner = [sum(e - s for s, e in harness
                 if d.start <= s and e <= d.end) for d in draws]
    lo, hi = hs.window

    def med(xs):
        return statistics.median(xs) / 1e6

    log(f"train spans: {len(updates)} updates in the window, median "
        f"update dispatch {med([u.end - u.start for u in updates]):.3f} "
        f"ms; source waits: host time "
        f"{100.0 * hs.time_in_window(SOURCE) / (hi - lo):.3f}% of window, "
        f"median {med([d.end - d.start for d in draws]):.3f} ms, max "
        f"{max(d.end - d.start for d in draws) / 1e6:.3f} ms, of which "
        f"{HARNESS_DRAW} median {med(inner):.3f} ms")
    return share
