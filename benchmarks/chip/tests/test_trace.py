"""The trace reduction, on a small trace recorded on one TPU v5e chip by
``record_trace.py`` (``data/one_chip.xplane.pb``: a matmul, the
``topk_logits`` kernel and a ``psum``, twice, under the harness's host
spans, with a 3 ms host sleep in a span before, between and after the
calls) and on hand-made intervals."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def one_chip():
    return trace.Trace(os.path.join(DATA, "one_chip.xplane.pb"))


def test_recorded_device_plane_and_op_names(one_chip):
    """The TPU plane is found, and its ops are named by their HLO
    instruction (``%name = ...`` cut to ``name``)."""
    assert one_chip.devices() == [0]
    names = {n for _, _, n in one_chip.ops[0]}
    assert "topk_logits_tiles.1" in names
    assert not any(" " in n or n.startswith("%") for n in names)


def test_recorded_busy_union_and_window(one_chip):
    assert one_chip.window_s() == pytest.approx(0.01177716)
    # the union of the 46 op intervals, all inside the window
    assert one_chip.busy_s() == pytest.approx(0.000256839)
    assert one_chip.busy_s() < one_chip.window_s()


def test_recorded_kernel_time_by_name(one_chip):
    assert one_chip.op_count("topk_logits_tiles") == 2
    assert one_chip.op_time_s("topk_logits_tiles") == pytest.approx(
        0.000153717)
    top = dict(one_chip.top_ops())
    assert max(top, key=top.get) == "topk_logits_tiles.1"


def test_recorded_gaps_and_collectives(one_chip):
    """Idle time is the window less busy time, all charged to a host span;
    one chip's psum is no collective op, so nothing is exposed.  The
    device's ops are stamped up to a millisecond before the host span that
    dispatched them, so both calls' ops fall in host-wait time."""
    idle = dict(one_chip.idle_by_span())
    assert sum(idle.values()) == pytest.approx(
        one_chip.window_s() - one_chip.busy_s())
    assert set(idle) == {"bench.host_wait"}
    assert one_chip.exposed_collective_s() == 0.0


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (4, 4), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_subtract_and_clip():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert trace.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert trace.clip(a, 5, 25) == [(5, 10), (20, 25)]
    assert trace.total(trace.subtract(a, b)) == 2 + 4 + 3 + 4


class FakeTrace(trace.Trace):
    """A Trace built from lists instead of a file."""

    def __init__(self, ops, spans):
        self.ops, self.spans = ops, sorted(spans)


def test_exposed_collective_and_gap_owner_by_hand():
    ops = {0: [(0, 10, "fusion.1"), (8, 14, "all-reduce.3"),
               (20, 30, "topk_logits_tiles.2"), (9, 13, "while.7")],
           1: [(0, 12, "fusion.1"), (12, 16, "all-reduce.3")]}
    spans = [(0, 40, "bench.window"), (0, 15, "bench.compute"),
             (14, 21, "bench.host_wait"), (13, 40, "bench.outer")]
    tr = FakeTrace(ops, spans)
    assert tr.window() == (0, 40)
    assert tr.busy(0) == [(0, 14), (20, 30)]
    assert tr.busy_s() == pytest.approx((24 + 16) / 2 / 1e9)
    # device 0: all-reduce alone in (10, 14), the while spanning it being
    # no compute of its own; device 1: (12, 16)
    assert tr.exposed_collective_s() == pytest.approx((4 + 4) / 2 / 1e9)
    assert tr.op_time_s("topk_logits_tiles") == pytest.approx(10 / 1e9)
    # device 0 idles (14, 20) and (30, 40): the first is most covered by
    # host_wait (6 of 6 vs outer 6 of 6: the innermost wins), the second
    # only by outer
    assert tr.idle_gaps() == [(14, 20), (30, 40)]
    assert dict(tr.idle_by_span()) == pytest.approx(
        {"bench.host_wait": 6e-9, "bench.outer": 10e-9})
