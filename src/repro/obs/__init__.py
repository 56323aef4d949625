"""repro.obs — unified tracing + metrics for the whole stack.

Usage (DESIGN.md §16)::

    from repro import obs

    with obs.span("partition.local_move", level=lvl, arcs=int(n_arcs)):
        ...                                   # timed + attributed region

    obs.counter("graphstore.chunks").inc()    # always-on metrics
    obs.gauge("train.loss.p0").set(0.31)
    obs.histogram("serving.batch_size").record(24)

Tracing is **disabled by default**. ``obs.span(...)`` in disabled mode
returns a shared no-op context manager — no allocation, no lock, no
timestamp — so instrumented hot loops cost one function call and one
attribute check (<1% of pipeline wall, gated by
``tools/obs_overhead_smoke.py``). Call sites that would compute expensive
attributes to feed a span (e.g. ``float(loss)``, which forces a JAX
device sync) must guard on :func:`enabled` first.

Metrics are **always live** — a counter increment is one locked integer
add — so subsystems use registry counters as primary storage (serving's
cache/compile books) and snapshots stay deterministic across processes.

``obs.enable()`` turns span collection on; ``obs.export_trace(path)``
writes Chrome trace-event JSON (open in Perfetto / ``chrome://tracing``);
``python -m repro.obs summarize out.json`` aggregates it per span name.

**The profiler sink.** While a JAX profiler session runs
(``jax.profiler.start_trace``), every span is also written to the
profiler's trace as a ``TraceMe`` of the same name carrying the span's
scalar attributes, whether or not ``obs.enable()`` was called, so the
spans sit on the same clock as the device's ops. :func:`step_span` writes
its span as a profiler step (what ``jax.profiler.StepTraceAnnotation``
writes). The sink never syncs the device, and jaxlib's profiler is
imported at the first span, not here: this package stays importable
without JAX.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .sampler import jax_profiler_session, peak_rss_bytes, sample_memory
from .trace import Span, Tracer

__all__ = [
    "SCHEMA_VERSION", "enabled", "enable", "disable", "span", "step_span",
    "counter", "gauge", "histogram", "registry", "tracer", "export_trace",
    "trace_document", "sample_memory_now", "profiler_session", "reset",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Span", "Tracer",
    "peak_rss_bytes",
]

# Bumped when the exported trace document's shape changes; stamped into
# traces and benchmark rows so trajectories stay attributable.
SCHEMA_VERSION = 1

_enabled = False
_tracer = Tracer()
_registry = MetricsRegistry()


class _NoopSpan:
    """Shared do-nothing span for the disabled fast path."""

    __slots__ = ()
    duration: Optional[float] = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass


_NOOP_SPAN = _NoopSpan()

# jaxlib's TraceMe, looked up by the first span (see _profiling)
_TraceMe: Any = None


def _profiling() -> bool:
    """Whether a JAX profiler session is running. The first call looks up
    jaxlib's ``TraceMe`` and rebinds this name to its ``is_enabled`` (or to
    a constant False where jaxlib is absent), so later spans pay one C
    call."""
    global _TraceMe, _profiling
    try:
        from jaxlib._profiler import TraceMe
    except ImportError:
        _profiling = lambda: False  # noqa: E731
    else:
        _TraceMe = TraceMe
        _profiling = TraceMe.is_enabled
    return _profiling()


class _ProfiledSpan:
    """A span written to the profiler's trace, and to the tracer when span
    collection is on; ``with`` yields the tracer's span (or the no-op)."""

    __slots__ = ("_me", "_inner")

    def __init__(self, me, inner):
        self._me = me
        self._inner = inner

    def __enter__(self):
        self._me.__enter__()
        return self._inner.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            return self._inner.__exit__(exc_type, exc, tb)
        finally:
            self._me.__exit__(exc_type, exc, tb)


def _profiled(name: str, attrs: Dict[str, Any], **marks: Any):
    scalars = {k: v for k, v in attrs.items()
               if isinstance(v, (bool, int, float, str))}
    inner = _tracer.span(name, **attrs) if _enabled else _NOOP_SPAN
    return _ProfiledSpan(_TraceMe(name, **scalars, **marks), inner)


def enabled() -> bool:
    """Whether span collection is on (metrics are always on)."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def span(name: str, **attrs: Any):
    """Open a nested span; no-op (shared singleton) when disabled and no
    profiler runs."""
    if _profiling():
        return _profiled(name, attrs)
    if not _enabled:
        return _NOOP_SPAN
    return _tracer.span(name, **attrs)


def step_span(name: str, step_num: int, **attrs: Any):
    """:func:`span` that the profiler records as step ``step_num`` (as
    ``jax.profiler.StepTraceAnnotation`` does)."""
    if _profiling():
        return _profiled(name, attrs, _r=1, step_num=step_num)
    if not _enabled:
        return _NOOP_SPAN
    return _tracer.span(name, **attrs)


def counter(name: str) -> Counter:
    return _registry.counter(name)


def gauge(name: str) -> Gauge:
    return _registry.gauge(name)


def histogram(name: str) -> Histogram:
    return _registry.histogram(name)


def registry() -> MetricsRegistry:
    return _registry


def tracer() -> Tracer:
    return _tracer


def trace_document() -> Dict[str, Any]:
    """The Chrome trace-event JSON object for everything recorded so far."""
    return _tracer.to_chrome(metrics=_registry.snapshot(),
                             schema_version=SCHEMA_VERSION)


def export_trace(path: str) -> str:
    """Write the trace (+ metrics snapshot) to ``path``; returns ``path``."""
    return _tracer.export(path, metrics=_registry.snapshot(),
                          schema_version=SCHEMA_VERSION)


def sample_memory_now() -> None:
    """Sample peak RSS / JAX device memory into the registry gauges."""
    sample_memory(_registry)


def profiler_session(out_dir: Optional[str]) -> jax_profiler_session:
    """``jax.profiler`` hook for the training stage (no-op if dir is None)."""
    return jax_profiler_session(out_dir, registry=_registry)


def reset() -> None:
    """Clear spans and metrics and disable tracing (test isolation)."""
    global _enabled
    _enabled = False
    _tracer.reset()
    _registry.reset()
