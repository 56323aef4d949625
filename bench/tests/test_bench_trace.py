"""The trace reduction (bench/trace.py): interval arithmetic on hand-made
intervals, and the whole reduction on a small trace recorded on the CPU
(fixtures/cpu_trace.xplane.pb: a window span holding three call spans,
each two jitted calls around a 20 ms sleep)."""
import os

import pytest

import tinycell  # noqa: F401  (puts the checkout on sys.path)
from bench import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "cpu_trace.xplane.pb")


def _trace(ops, spans=(), host=()):
    return trace.Trace(
        ops={d: [trace.Op(n, s, e, "") for n, s, e in v]
             for d, v in ops.items()},
        spans=list(spans), host=list(host))


def test_merge_covered_subtract():
    merged = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert trace.covered(merged, 1, 6) == 3
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_idle_and_span_gaps():
    tr = _trace({"d0": [("a", 0, 40), ("b", 60, 100)],
                 "d1": [("a", 0, 100)]},
                spans=[("bench.window", 0, 100), ("bench.call", 0, 50),
                       ("bench.call", 50, 100)])
    assert trace.busy_seconds(tr, 0, 100) == pytest.approx(90 / 1e9)
    assert trace.idle_pct(tr, 0, 100) == pytest.approx(10.0)
    # d0 idles 10 ns in each call, d1 never: 5 ns a call on average
    assert trace.span_idle_ms(tr, "bench.call") == pytest.approx(5e-6)
    assert trace.span_idle_ms(tr, "bench.other") is None


def test_window_needs_its_span():
    tr = _trace({"d0": [("a", 0, 10)]}, spans=[("bench.call", 0, 10)])
    with pytest.raises(ValueError):
        trace.window(tr)
    assert trace.window(_trace({}, spans=[("bench.window", 3, 9)])) == (3, 9)


def test_exposed_collective_and_top_ops():
    tr = _trace({"d0": [("fusion", 0, 50), ("all-gather-start", 40, 70),
                        ("fusion", 70, 100)]})
    # 20 of the collective's 30 ns run alone, over 100 ns busy
    assert trace.exposed_collective_pct(tr, 0, 100) == pytest.approx(20.0)
    assert trace.top_ops(tr, 0, 100)[0] == ["fusion", 80 / 1e9]
    assert trace.exposed_collective_pct(
        _trace({"d0": [("fusion", 0, 10)]}), 0, 10) is None


def test_idle_gaps_named_by_host_work():
    tr = _trace({"d0": [("a", 0, 10), ("b", 40, 50)]},
                spans=[("bench.window", 0, 60), ("bench.call", 0, 60)],
                host=[("gather", 12, 38), ("outer", 5, 59)])
    gaps = trace.idle_gaps(tr, 0, 60)
    assert gaps[0] == ["bench.call > gather", 30 / 1e9]
    assert gaps[1] == ["bench.call > outer", 10 / 1e9]


def test_recorded_cpu_trace():
    tr = trace.load(FIXTURE, device_plane="^/host:CPU$", op_line="^tf_XLA",
                    op_stat="hlo_op")
    assert [s[0] for s in tr.spans] == ["bench.window"] + ["bench.call"] * 3
    lo, hi = trace.window(tr)
    busy = trace.busy_seconds(tr, lo, hi)
    assert 0 < busy < (hi - lo) / 1e9
    assert 0 < trace.idle_pct(tr, lo, hi) < 100
    # each call sleeps 20 ms with the device idle
    assert trace.span_idle_ms(tr, "bench.call") >= 20.0
    names = [n for n, _ in trace.top_ops(tr, lo, hi)]
    assert any(n.startswith("dot_general") for n in names)
    gaps = trace.idle_gaps(tr, lo, hi, n=3)
    assert all(label == "bench.call > $time sleep" and s >= 0.02
               for label, s in gaps)
    assert trace.op_seconds(tr, "dot_general", lo, hi) > 0
    assert trace.exposed_collective_pct(tr, lo, hi) is None
