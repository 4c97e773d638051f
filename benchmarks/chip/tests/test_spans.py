"""The reduction of the program's own ``repro.*`` host spans
(``bench/spans.py``): on hand-made intervals, and on a trace recorded here
with the CPU profiler."""
from types import SimpleNamespace

import pytest

from bench import spans, trace
from bench.spans import HostSpans, Span


def S(start, end, name, **args):
    return Span(start, end, "repro." + name, args)


# a window (0, 100) with two shards' commits and a ledger transition:
#   0-10 gen.forward | 10-40 append_shard(fetch 10-25, write 25-30,
#   checksum 30-33, manifest 35-40) | 40-45 gen.forward |
#   45-70 append_shard(fetch 45-55, manifest 65-70) | 80-90 gen.ledger
SPANS = [S(0, 10, "gen.forward", shard=0),
         S(10, 40, "store.append_shard", shard=0, frames=7),
         S(10, 25, "store.fetch"), S(25, 30, "store.write"),
         S(30, 33, "store.checksum"), S(35, 40, "store.manifest"),
         S(40, 45, "gen.forward", shard=1),
         S(45, 70, "store.append_shard", shard=1, frames=5),
         S(45, 55, "store.fetch"), S(65, 70, "store.manifest"),
         S(80, 90, "gen.ledger")]


@pytest.fixture
def hs():
    return HostSpans((0, 100), SPANS)


def test_gap_across_nested_spans_goes_to_innermost_open(hs):
    """One gap from 20 to 50 crosses fetch, write, checksum, the
    append_shard's own time between children, manifest, a forward and the
    next fetch."""
    assert hs.charge([(20, 50)]) == {
        "repro.store.fetch": 5 + 5, "repro.store.write": 5,
        "repro.store.checksum": 3, "repro.store.append_shard": 2,
        "repro.store.manifest": 5, "repro.gen.forward": 5}


def test_charges_with_outside_sum_to_the_idle(hs):
    gaps = [(5, 12), (60, 95), (98, 100)]
    by = hs.charge(gaps)
    assert by["outside"] == (80 - 70) + (95 - 90) + (100 - 98)
    assert by["repro.gen.ledger"] == 10
    assert sum(by.values()) == trace.total(gaps)


def test_latest_started_open_span_wins_over_an_unclosed_outer():
    """Spans that overlap without nesting (a generator's span open across
    a yield): the latest-started span still open owns the instant."""
    h = HostSpans((0, 30), [S(0, 20, "train.update"),
                            S(10, 30, "train.source")])
    assert h.charge([(0, 30)]) == {"repro.train.update": 10,
                                   "repro.train.source": 20}


def test_window_clipping():
    """A span open before the window counts only inside it; a span that
    ends after the window does not count as a shard of the window."""
    h = HostSpans((10, 50), [S(0, 20, "train.source"),
                             S(30, 40, "train.source"),
                             S(45, 60, "store.append_shard", frames=9),
                             S(12, 18, "store.append_shard", frames=4)])
    assert h.time_in_window("repro.train.source") == 10 + 10
    assert [s.args["frames"] for s in
            h.ending_in_window("repro.store.append_shard")] == [4]
    # 10-12 source, 12-18 append_shard, 18-20 source, 20-30 outside,
    # 30-40 source, 40-45 outside, 45-50 append_shard
    assert h.charge([(0, 100)]) == {"repro.train.source": 2 + 2 + 10,
                                    "repro.store.append_shard": 6 + 5,
                                    "outside": 10 + 5}
    assert sum(h.charge([(10, 50)]).values()) == 40


def test_append_shard_less_its_fetch_is_paired_per_shard(hs):
    assert hs.less_children("repro.store.append_shard",
                            "repro.store.fetch") == [30 - 15, 25 - 10]


def test_commits_pair_forward_by_shard_and_fetch_by_nesting():
    """Shard ids repeat from one generation call to the next: a commit
    takes the latest forward of its shard that began before it."""
    h = HostSpans((0, 100), [
        S(0, 5, "gen.forward", shard=0), S(5, 20, "store.append_shard",
                                           shard=0, frames=1),
        S(5, 12, "store.fetch"),
        S(20, 25, "gen.forward", shard=1), S(25, 40, "store.append_shard",
                                             shard=1, frames=1),
        S(25, 30, "store.fetch"),
        S(50, 55, "gen.forward", shard=0), S(55, 70, "store.append_shard",
                                             shard=0, frames=1),
        S(55, 61, "store.fetch"),
        S(80, 90, "store.append_shard", shard=7, frames=1)])
    got = [(c.forward and c.forward.start, c.append.start,
            c.fetch and c.fetch.start) for c in h.commits()]
    assert got == [(0, 5, 5), (20, 25, 25), (50, 55, 55), (None, 80, None)]


def test_idle_before_a_forwards_first_device_op_goes_to_input(hs):
    """The dispatch returns before the batch's inputs are on the device:
    idle from a forward's end to the first op that starts after the
    forward began is ``input``, though the host has moved on into the
    store; a forward whose first op starts during its dispatch has no
    such wait."""
    # shard 0's first op starts at 2, inside its forward (0-10); shard
    # 1's at 52, after its forward (40-45) returned
    starts = [2, 20, 52, 60]
    inputs = hs.input_waits(starts)
    assert inputs == [(45, 52)]
    by = hs.charge([(40, 55)], inputs)
    assert by == {"repro.gen.forward": 5, "input": 7,
                  "repro.store.fetch": 3}
    assert sum(by.values()) == 15


class FakeTrace(trace.Trace):
    def __init__(self, ops, spans_):
        self.ops, self.spans = ops, sorted(spans_)


def test_readers_on_a_device_plane(hs):
    """The idle readers share one charge; the store's take every
    ``store.*`` owner, the ledger's its own; both are over the window."""
    tr = FakeTrace({0: [(0, 8, "fusion.1"), (42, 44, "while.2"),
                        (70, 75, "topk_logits_tiles.1")]},
                   [(0, 100, "bench.window")])
    run = SimpleNamespace(extra={"host_spans": hs})
    res = {"frames": 12}
    gaps = tr.idle_gaps()
    assert trace.total(gaps) == 100 - 15
    store = spans.idle_store(run, res, tr)
    ledger = spans.idle_ledger(run, res, tr)
    by = run.extra["idle_charges"]
    assert sum(by.values()) == trace.total(gaps)
    assert store == pytest.approx(30 + 25)      # both commits, all idle
    assert ledger == pytest.approx(10)
    assert spans.store_write_ms(run, res, tr) == pytest.approx(
        (15 + 15) / 2 / 1e6)


def test_input_wait_reads_device_idle_under_the_source():
    """Host time in ``train.source`` that the device does not wait for
    reads nothing; idle while the Trainer waits for its batch does."""
    h = HostSpans((0, 100), [S(0, 10, "train.source"),
                             S(10, 50, "train.update"),
                             S(50, 60, "train.source"),
                             S(60, 100, "train.update")])
    # the device runs 5-57 and 58-100: idle 0-5 and 57-58 in the draws
    tr = FakeTrace({0: [(5, 57, "while.1"), (58, 100, "while.2")]},
                   [(0, 100, "bench.window"), (2, 6, "bench.source_next"),
                    (51, 59, "bench.source_next")])
    run = SimpleNamespace(extra={"host_spans": h})
    assert spans.input_wait(run, {}, tr) == pytest.approx(5 + 1)
    assert run.extra["idle_charges"] == {"repro.train.source": 6}


def test_idle_readers_without_a_device_plane(hs):
    tr = FakeTrace({}, [(0, 100, "bench.window")])
    run = SimpleNamespace(extra={"host_spans": hs})
    assert spans.idle_store(run, {}, tr) is None
    assert spans.idle_ledger(run, {}, tr) is None
    assert spans.input_wait(run, {}, tr) is None


def test_recorded_cpu_trace_reads_back_names_nesting_and_args(tmp_path):
    """Spans opened through the program's helper under a CPU profiler
    session are read back from the window's thread with their args; spans
    on another thread are not."""
    import threading

    import jax

    from repro.utils.tracing import span

    def other_thread():
        with span("store.append_shard", shard=99):
            pass

    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with span("gen.ledger"):
                pass
            for i in range(3):
                with span("gen.forward", shard=i):
                    pass
                with span("store.append_shard", shard=i, frames=10 + i):
                    with span("store.fetch"):
                        pass
                    with span("store.manifest"):
                        pass
            with span("train.update"):
                pass
            t = threading.Thread(target=other_thread)
            t.start()
            t.join(timeout=10)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    run = SimpleNamespace(out_dir=str(tmp_path), extra={})
    h = spans.host_spans(run)
    assert spans.host_spans(run) is h                  # read once
    shards = h.ending_in_window("repro.store.append_shard")
    assert [s.args for s in shards] == [
        {"shard": i, "frames": 10 + i} for i in range(3)]
    fetches = h.ending_in_window("repro.store.fetch")
    assert len(fetches) == 3
    for p, f in zip(shards, fetches):
        assert p.start <= f.start and f.end <= p.end
    assert h.ending_in_window("repro.gen.ledger")[0].args == {}
    assert len(h.ending_in_window("repro.train.update")) == 1
    commits = h.commits()
    assert [c.forward.args["shard"] for c in commits] == [0, 1, 2]
    assert all(c.fetch is not None for c in commits)
    lo, hi = h.window
    assert all(lo <= s.start and s.end <= hi for s in h.spans)
    assert len(h.less_children("repro.store.append_shard",
                               "repro.store.fetch")) == 3


def test_readers_return_nothing_for_a_program_without_spans():
    """The program before these spans: every reader is silent."""
    tr = FakeTrace({0: [(0, 8, "fusion.1")]}, [(0, 100, "bench.window")])
    run = SimpleNamespace(extra={"host_spans": HostSpans((0, 100), [])})
    for read in (spans.idle_store, spans.idle_ledger, spans.store_write_ms,
                 spans.input_wait):
        assert read(run, {"frames": 1}, tr) is None
