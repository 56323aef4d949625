"""The readers of the program's own spans (bench/spans.py and
bench/metrics/{build,gather,table}_gap_ms_per_call.py) on hand-made traces:
nested spans, several calls, two devices, and None where the program wrote
no span for them."""
import types

import pytest

import tinycell  # noqa: F401  (puts the checkout on sys.path)
from bench import harness, spans, trace


def _trace(ops, spans_=(), host=()):
    return trace.Trace(
        ops={d: [trace.Op(n, s, e, "") for n, s, e in v]
             for d, v in ops.items()},
        spans=list(spans_), host=list(host))


def _reader(name):
    return harness.load_reader(types.SimpleNamespace(root=harness.ROOT),
                               name)


def _ctx(tr, lo=0, hi=1000):
    return types.SimpleNamespace(trace=tr, lo=lo, hi=hi)


# one call 0..100: gather 0..10, upload 10..20, lower 20..30, compile
# 30..40, epochs 40..45, embed 50..80 holding a build 55..70, fetch 80..90,
# pool 90..100; the device runs 42..60 and 75..85
CALL = [("bench.window", 0, 100), ("bench.call", 0, 100)]
HOST = [("train.call", 0, 100), ("train.gather", 0, 10),
        ("train.upload", 10, 20), ("train.lower", 20, 30),
        ("train.compile", 30, 40), ("train.epoch", 40, 45),
        ("train.embed", 50, 80), ("train.lower", 55, 62),
        ("train.compile", 62, 70), ("train.fetch", 80, 90),
        ("train.pool", 90, 100)]
OPS = {"d0": [("fusion", 42, 60), ("fusion", 75, 85)]}


def test_gaps_of_one_call():
    tr = _trace(OPS, CALL, HOST)
    ms = 1e-6          # the trace's clock is in ns
    # build: 20..40 idle (20) + 60..70 idle of 55..70 (10)
    assert _reader("build_gap_ms_per_call")(_ctx(tr)) == pytest.approx(30 * ms)
    # gather + upload: 0..20, all idle
    assert _reader("gather_gap_ms_per_call")(_ctx(tr)) == pytest.approx(
        20 * ms)
    # table: 50..80 less 55..70 is 50..55 (busy) + 70..80 (idle 70..75),
    # fetch 80..90 (idle 85..90), pool 90..100 (idle)
    assert _reader("table_gap_ms_per_call")(_ctx(tr)) == pytest.approx(
        20 * ms)


def test_self_time_leaves_out_nested_build():
    tr = _trace({"d0": []}, CALL, [("train.embed", 0, 100),
                                   ("train.compile", 20, 90)])
    assert spans.idle_ms_per_call(tr, 0, 100, ["train.embed"]) == \
        pytest.approx(100e-6)
    assert spans.idle_ms_per_call(tr, 0, 100, ["train.embed"],
                                  ["train.compile"]) == pytest.approx(30e-6)


def test_several_calls_and_two_devices():
    calls = [("bench.window", 0, 200), ("bench.call", 0, 100),
             ("bench.call", 100, 200)]
    host = [("train.gather", 0, 40), ("train.gather", 100, 120)]
    # d0 idles in all 60 ns of gather, d1 runs through 0..30 of it
    tr = _trace({"d0": [("a", 150, 160)], "d1": [("a", 0, 30)]}, calls,
                host)
    got = _reader("gather_gap_ms_per_call")(_ctx(tr, 0, 200))
    # (60 + 30) ns over two devices, over two calls
    assert got == pytest.approx(90 / 2 / 2 * 1e-6)
    # spans outside the window do not count: 100..120 idles on both
    assert spans.idle_ms_per_call(tr, 100, 200, ["train.gather"]) == \
        pytest.approx(20e-6)


def test_gaps_are_none_without_the_programs_spans():
    tr = _trace(OPS, CALL, [("$time sleep", 0, 50)])
    for name in ("build_gap_ms_per_call", "gather_gap_ms_per_call",
                 "table_gap_ms_per_call"):
        assert _reader(name)(_ctx(tr)) is None
    # and without a call to divide by
    tr = _trace(OPS, [("bench.window", 0, 100)], HOST)
    assert _reader("build_gap_ms_per_call")(_ctx(tr)) is None
