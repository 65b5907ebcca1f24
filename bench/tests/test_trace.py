"""The trace reduction on a small synthetic trace."""
import pytest

from benchlib import trace
from benchlib.trace import Trace

MS = 1e6   # ns


def sample():
    ops = [("fusion.1", 0 * MS, 2 * MS), ("paged_attention.9", 1 * MS, 2 * MS),
           ("fusion.1", 5 * MS, 1 * MS), ("paged_prefill_attention.2", 9 * MS, 1 * MS)]
    modules = [("jit_decode_step(1)", 0, 3 * MS),
               ("jit_decode_step(1)", 5 * MS, 1 * MS)]
    host = [("bench.step", 0, 7 * MS), ("bench.wait", 7 * MS, 1.8 * MS),
            ("bench.step", 8.8 * MS, 1.7 * MS)]
    return Trace(ops, modules, host)


def test_busy_union_and_window():
    t = sample()
    assert trace.union(t.ops) == [(0, 3 * MS), (5 * MS, 6 * MS),
                                  (9 * MS, 10 * MS)]
    assert trace.busy_ns(t) == pytest.approx(5 * MS)
    assert trace.window(t) == (0, 10.5 * MS)


def test_busy_share_inside_step_spans():
    t = sample()
    steps = trace.host_spans(t, "step")
    # steps cover 8.7 ms, of which the device is busy 3 + 1 + 1 ms
    assert trace.busy_share_of(t, steps) == pytest.approx(5 / 8.7)


def test_idle_gaps_named_by_host_span():
    gaps = trace.idle_gaps(sample())
    assert [g[0] for g in gaps] == ["wait", "step", "step"]
    assert [g[1] for g in gaps] == pytest.approx([3e-3, 2e-3, 0.5e-3])


def test_loop_ops_give_way_to_their_body():
    t = Trace([("while.5", 0, 10 * MS), ("fusion.2", 1 * MS, 3 * MS),
               ("fusion.3", 5 * MS, 4 * MS), ("copy.1", 12 * MS, 1 * MS)],
              [], [])
    assert [e[0] for e in trace.leaves(t.ops)] == ["fusion.2", "fusion.3",
                                                  "copy.1"]
    assert trace.top_ops(t, 1) == [["fusion.3", pytest.approx(4e-3)]]
    assert trace.busy_ns(t) == pytest.approx(11 * MS)


def test_top_ops_and_matching():
    t = sample()
    top = trace.top_ops(t, 2)
    assert top[0] == ["fusion.1", pytest.approx(3e-3)]
    assert len(top) == 2
    assert trace.matching(t.ops, ("paged_attention.9",)) == [t.ops[1]]
