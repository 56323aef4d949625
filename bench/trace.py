"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
per-layer numbers.

A trace is read once into plain intervals: the operations each device ran
(``ops``), the benchmark's own host spans (``bench.*`` annotations) and the
other host events on the thread that opened them (for attributing idle
gaps). Every function below works on those intervals alone, so the
arithmetic is checked on a small trace recorded on the CPU.

Times are nanoseconds on the profiler's clock; the host and the device
planes share it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

TPU_PLANE = r"^/device:TPU:\d+$"
TPU_OP_LINE = r"^XLA Ops$"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv", re.IGNORECASE)


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float
    detail: str        # the event's string stats, for matching kernel names


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]                   # device -> ops by start
    spans: List[Tuple[str, float, float]]      # bench.* host spans
    host: List[Tuple[str, float, float]]       # that thread's other events

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, device_plane: str = TPU_PLANE,
         op_line: str = TPU_OP_LINE, op_stat: Optional[str] = None) -> Trace:
    """Read ``path``. Device ops are the events of lines matching
    ``op_line`` on planes matching ``device_plane``; with ``op_stat`` only
    events carrying that stat count (a CPU trace mixes ops with thread-pool
    markers on one line)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    spans, host = [], []
    plane_re, line_re = re.compile(device_plane), re.compile(op_line)
    for plane in data.planes:
        if plane_re.match(plane.name):
            got = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if not line_re.match(line.name):
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    if op_stat is not None and op_stat not in stats:
                        continue
                    if ev.duration_ns <= 0:
                        continue
                    detail = " ".join(str(v) for v in stats.values()
                                      if isinstance(v, str))
                    # a TPU op's name is its whole HLO instruction
                    name, _, rest = ev.name.partition(" = ")
                    got.append(Op(name.lstrip("%"), ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  " ".join((rest, detail))))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events]
                mine = [e for e in events if e[0].startswith(SPAN_PREFIX)]
                if mine:
                    spans.extend(mine)
                    host.extend(e for e in events
                                if not e[0].startswith(SPAN_PREFIX))
    for v in ops.values():
        v.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s[1])
    host.sort(key=lambda s: s[1])
    return Trace(ops=ops, spans=spans, host=host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint union."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` covers."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``, both disjoint and sorted."""
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


def busy(trace: Trace, device: str) -> List[Interval]:
    return merge((o.start, o.end) for o in trace.ops[device])


def window(trace: Trace, name: str = "bench.window") -> Interval:
    """The span named ``name``; a trace without it has no window."""
    for n, s, e in trace.spans:
        if n == name:
            return s, e
    raise ValueError(f"the trace has no span {name!r}")


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------
def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in ``[lo, hi]`` in which an op ran, averaged over devices."""
    devs = trace.devices
    return sum(covered(busy(trace, d), lo, hi) for d in devs) / len(devs) / 1e9


def idle_pct(trace: Trace, lo: float, hi: float) -> float:
    return 100.0 * (1.0 - busy_seconds(trace, lo, hi) / ((hi - lo) / 1e9))


def span_idle_ms(trace: Trace, span: str) -> Optional[float]:
    """Device-idle milliseconds inside each ``span``, per span, averaged
    over devices."""
    mine = [(s, e) for n, s, e in trace.spans if n == span]
    if not mine:
        return None
    idle = 0.0
    for d in trace.devices:
        merged = busy(trace, d)
        idle += sum((e - s) - covered(merged, s, e) for s, e in mine)
    return idle / len(trace.devices) / len(mine) / 1e6


def op_seconds(trace: Trace, pattern: str, lo: float, hi: float) -> float:
    """Total device seconds, summed over devices, of the ops in
    ``[lo, hi]`` whose name or detail matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(o.end - o.start for d in trace.devices for o in trace.ops[d]
               if lo <= o.start < hi and (rx.search(o.name)
                                          or rx.search(o.detail))) / 1e9


def exposed_collective_pct(trace: Trace, lo: float, hi: float
                           ) -> Optional[float]:
    """Time a collective runs with no other op on its device, over device
    busy time, averaged over devices. None when no collective ran."""
    shares, seen = [], False
    for d in trace.devices:
        ops = [o for o in trace.ops[d] if lo <= o.start < hi]
        coll = merge((o.start, o.end) for o in ops
                     if COLLECTIVE.search(o.name))
        seen = seen or bool(coll)
        comp = merge((o.start, o.end) for o in ops
                     if not COLLECTIVE.search(o.name))
        total = covered(merge(coll + comp), lo, hi)
        alone = sum(e - s for s, e in subtract(coll, comp))
        shares.append(100.0 * alone / total if total else 0.0)
    return sum(shares) / len(shares) if seen else None


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The ``n`` op names with the most device seconds in the window,
    averaged over devices."""
    tot: Dict[str, float] = {}
    for d in trace.devices:
        for o in trace.ops[d]:
            if lo <= o.start < hi:
                tot[o.name] = tot.get(o.name, 0.0) + (o.end - o.start)
    k = len(trace.devices)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def _host_label(trace: Trace, t: float) -> str:
    """The benchmark span and the innermost host event that hold ``t``."""
    span = [n for n, s, e in trace.spans if s <= t < e]
    ev = [(e - s, n) for n, s, e in trace.host if s <= t < e]
    parts = span[-1:] + ([min(ev)[1]] if ev else [])
    return " > ".join(parts) if parts else "outside spans"


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10
              ) -> List[List]:
    """The ``n`` longest idle gaps of the first device in ``[lo, hi]``,
    each named by what the host was doing at its middle."""
    d = trace.devices[0]
    gaps = subtract([(lo, hi)], busy(trace, d))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(trace, (s + e) / 2), (e - s) / 1e9]
            for s, e in gaps[:n]]
