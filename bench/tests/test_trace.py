"""The reduction from a profiler trace to device time, on a hand-made trace
whose answers are known."""
import pytest

from bench import trace


def _op(name, s, e, meta=""):
    return trace.Op(name, s, e, meta)


HAND = trace.Trace(
    ops={"/device:TPU:0": trace.nest([
             _op("fusion.1", 1.0, 2.0),
             _op("all-reduce.3", 1.5, 3.0),
             _op("while.5", 4.0, 5.0),
             _op("custom-call.7", 4.0, 4.5, "compress_rng"),
             _op("fusion.2", 4.5, 5.0)]),
         "/device:TPU:1": trace.nest([_op("fusion.1", 0.5, 1.0),
                                      _op("all-reduce.3", 2.0, 2.5)])},
    host=[("bench.window", 1.0, 6.0), ("bench.prepare", 3.0, 3.9),
          ("bench.wait", 5.0, 6.0)])


def test_window_and_busy_union():
    win = trace.window(HAND)
    assert win == (1.0, 6.0)
    busy = trace.busy(HAND, win)
    # [1, 3] and [4, 5] on chip 0 (the while op holds two others); chip 1's
    # first op lies before the window
    assert busy == {"/device:TPU:0": 3.0, "/device:TPU:1": 0.5}


def test_time_by_name():
    t = trace.op_seconds(HAND, lambda o: "compress_rng" in o.meta,
                         (1.0, 6.0))
    assert t == {"/device:TPU:0": 0.5, "/device:TPU:1": 0.0}


def test_exposed_collective_time():
    exp = trace.exposed(HAND, lambda o: o.name.startswith("all-reduce"),
                        (1.0, 6.0))
    # chip 0: all-reduce [1.5, 3] under fusion [1, 2] -> [2, 3] exposed
    assert exp == {"/device:TPU:0": 1.0, "/device:TPU:1": 0.5}


def test_idle_gaps_named_by_host_span():
    gaps = trace.idle_gaps(HAND, (1.0, 6.0))
    assert gaps == [["bench.prepare", 1.0], ["bench.wait", 1.0]]


def test_top_ops_average_over_chips():
    top = dict((n, t) for n, t in trace.top_ops(HAND, (1.0, 6.0)))
    assert top["all-reduce.3"] == pytest.approx((1.5 + 0.5) / 2)
    assert top["fusion.1"] == pytest.approx(0.5)
    assert "while.5" not in top


def test_nesting():
    ops = HAND.ops["/device:TPU:0"]
    # fusion.1 and all-reduce.3 overlap without nesting: both are leaves
    assert [o.name for o in ops if not o.leaf] == ["while.5"]


def test_union_and_subtract():
    u = trace.union([(0, 2), (1, 3), (5, 6), (6, 7)], 0.5, 6.5)
    assert u == [(0.5, 3), (5, 6.5)]
    assert trace.subtract([(0, 10)], [(1, 2), (3, 4)]) == \
        [(0, 1), (2, 3), (4, 10)]
    assert trace.length(u) == 4.0

